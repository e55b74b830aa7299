"""Pallas fused paged-decode attention: one VMEM pass per live block.

The XLA paged decode path (models/transformer.Attention.
_paged_decode_attention) reads the KV pool by materializing each row's
gathered logical window — `k_full = kf[gidx]` re-writes (B, S, KV, D)
(and its int8 scale rows) through HBM every decode step before the
score matmul reads it back. This kernel removes that round trip: the
grid walks each row's block table IN KERNEL (the tables ride in SMEM as
scalar-prefetched operands and drive the K/V BlockSpec index maps), and
every (row, logical-block) grid cell fuses

    int8 dequant  →  QK score  →  streaming softmax  →  weighted V-sum

for every kv head over one (block_size, kv_heads * head_dim) tile
resident in VMEM. Each live block is read from HBM exactly once per
step and no gathered-K/V intermediate ever exists.

Tile shapes (what Mosaic accepts — a block's last two dims must be
multiples of (8, 128) or the array's own): the pool leaves
(blocks, block, kv_heads, head_dim) are viewed as
(blocks, block, kv_heads * head_dim) — a free row-major reshape — so a
tile is (block, kv_heads * head_dim) and one kv head is a static,
lane-aligned column slice of it. The scale leaves become
(blocks, block, kv_heads) the same way. Queries regroup kv-head-major
and their (T * n_rep) rows pad up to a sublane multiple; per-row query
positions ride as a VMEM column (a vector cannot be read out of SMEM),
and each row's largest position rides in SMEM for the block skip.

Streaming softmax is the flash-attention recurrence
(ops/flash_attention.py, arxiv 2205.14135) carried across the
sequential block-walk grid dimension in VMEM scratch: running max `m`,
running normalizer `l`, unnormalized accumulator `acc`, initialized at
block 0 (`pl.when(i == 0)`) and finalized after the last block
(`pl.when(i == bps - 1)`).

int8 KV op-order contract (the `_int8_quantize` consumer side —
models/transformer._attend_window is the single XLA definition):
  - K/V payloads convert int8 → compute dtype on the VMEM read
    (the `astype` fuses into the load, as on the XLA path);
  - the per-token K scale applies to the fp32-accumulated scores AFTER
    the matmul (it factors out of the contracted head_dim);
  - the per-token V scale folds into the probabilities (it cannot
    factor out of the summed sequence dim), which then cast to the
    compute dtype before the V matmul.
Streaming softmax reorders the reduction relative to the one-shot XLA
softmax, so fp equality with the XLA twin is tolerance-level, not
bit-level; greedy-token equivalence on real prompts is the behavioural
pin (tests/test_composition_matrix.py), with the tolerance itself
pinned by tests/test_paged_attention.py.

Masking matches the XLA twin exactly: causal `k_pos <= q_pos` plus the
optional sliding window, applied as -1e30 before the streaming-softmax
update. Stale pool blocks (scratch block 0, freed blocks still named by
a row's table tail) land entirely in the masked region, and a
fully-masked block's contribution washes out of the recurrence as soon
as any visible block follows (alpha multiplies the bogus partial sums
by exp(-1e30 - m_real) = 0); every row's own position is always
visible, so a visible block always follows.

Blocks entirely in the future of every query in the row
(`i * block_size > max(q_pos)`) skip their compute under `pl.when` —
the paged analogue of flash attention's causal block skipping. Their
table entries all name the scratch block, and the pipeline does not
fetch a tile again whose block index did not change.

`interpret=True` threads into `pl.pallas_call` exactly like
ops/flash_attention.py: the same kernel runs on CPU under the Pallas
interpreter, which is what lets tier-1 pin the fused path against the
XLA twin without a chip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_SUBLANES = 8


def _col_to_row(col: jax.Array) -> jax.Array:
    """(n, 1) → (1, n) without a transpose: mask the lane-broadcast
    column with the identity and sum over sublanes. Exact (it adds
    zeros), and every op is one Mosaic lowers at any n."""
    n = col.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decode_kernel(tables_ref, maxpos_ref,       # scalar prefetch (SMEM)
                   q_ref, qpos_ref, k_ref, v_ref,  # VMEM tiles
                   *rest,
                   block_size: int, blocks_per_seq: int, kv_heads: int,
                   head_dim: int, sm_scale: float, window: int,
                   kv_quant: bool):
    """Grid cell (b, i): row b's queries, every kv head, against the
    row's i-th logical block. The block walk (grid dim 1) is sequential,
    so acc/m/l scratch carries the softmax recurrence across blocks.
    `rest` is (ks_ref, vs_ref, o_ref, acc, m, l) for an int8 pool and
    (o_ref, acc, m, l) for a float one."""
    del tables_ref  # consumed by the index maps
    if kv_quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block_size <= maxpos_ref[b])
    def _attend():
        qpos = qpos_ref[0]                          # (rows, 1) int32
        cols = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (qpos.shape[0], block_size), 1)
        keep = cols <= qpos
        if window:
            keep &= qpos - cols < window
        for h in range(kv_heads):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, h]                         # (rows, D)
            k_blk = k_ref[0, :, lanes]              # (bs, D)
            v_blk = v_ref[0, :, lanes]
            if kv_quant:
                k_blk = k_blk.astype(q.dtype)
                v_blk = v_blk.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (rows, bs)
            if kv_quant:
                s = s * _col_to_row(ks_ref[0, :, h:h + 1])
            s = s * sm_scale
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_ref[h]                       # (rows, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            m_ref[h] = m_new
            if kv_quant:
                p = p * _col_to_row(vs_ref[0, :, h:h + 1])
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == blocks_per_seq - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array,
                           k_pool: jax.Array,
                           v_pool: jax.Array,
                           block_tables: jax.Array,
                           positions: jax.Array,
                           *,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           sm_scale: Optional[float] = None,
                           window: int = 0,
                           logit_softcap: float = 0.0,
                           interpret: bool = False) -> jax.Array:
    """Fused paged-decode attention over a block pool.

    Args:
      q: (B, T, H, D) queries — T is the current chunk (1 for plain
        decode, K+1 for a speculative verify span, the chunk length for
        chunked prefill).
      k_pool / v_pool: (num_blocks, block_size, KV, D) shared pool
        (int8 payload when scales are given).
      block_tables: (B, blocks_per_seq) logical→physical block ids —
        the table WITHOUT the engine's extra clip column (callers slice
        `tables[:, :max_seq_len // block_size]`).
      positions: (B, T) per-row query positions.
      k_scale / v_scale: (num_blocks, block_size, KV, 1) fp32
        per-token-per-kv-head scale rows (both or neither).
      window: sliding window in keys (0 = full causal).
      logit_softcap: rejected (XLA-only, matching ops/flash_attention).
      interpret: run under the Pallas interpreter (CPU tier-1 pinning).

    Returns (B, T, H, D) in q.dtype.
    """
    if logit_softcap:
        raise NotImplementedError(
            'paged_decode_attention does not support logit softcap; '
            'use the XLA path (decode_kernel="xla") for softcapped '
            'models — same policy as ops/flash_attention.py')
    if (k_scale is None) != (v_scale is None):
        raise ValueError('k_scale and v_scale must be given together')
    batch, cur_len, num_heads, head_dim = q.shape
    num_blocks, block_size, kv_heads, _ = k_pool.shape
    if num_heads % kv_heads:
        raise ValueError(
            f'num_heads {num_heads} not divisible by kv_heads '
            f'{kv_heads}')
    n_rep = num_heads // kv_heads
    blocks_per_seq = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    kv_quant = k_scale is not None
    rows = cur_len * n_rep
    # Plain decode has 2 (llama3-1b) or 4 (mistral-7b) rows a kv head:
    # pad to whole sublane tiles. Pad rows repeat the last real row's
    # position, compute finite garbage, and are sliced away below.
    pad = -rows % _SUBLANES
    rows_p = rows + pad
    positions = positions.astype(jnp.int32)

    # Queries regroup kv-head-major so each head's (T*rep, D) tile
    # contracts against its lane slice of the block tile.
    qg = q.reshape(batch, cur_len, kv_heads, n_rep, head_dim).transpose(
        0, 2, 1, 3, 4).reshape(batch, kv_heads, rows, head_dim)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qpos = jnp.pad(jnp.repeat(positions, n_rep, axis=1),
                   ((0, 0), (0, pad)), mode='edge')[..., None]

    # Index maps receive the scalar-prefetched operands after the grid
    # indices: the K/V (and scale) tiles are addressed THROUGH the
    # block table — this is the in-kernel table walk.
    q_spec = pl.BlockSpec((1, kv_heads, rows_p, head_dim),
                          lambda b, i, tables, maxpos: (b, 0, 0, 0))
    qpos_spec = pl.BlockSpec((1, rows_p, 1),
                             lambda b, i, tables, maxpos: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, block_size, kv_heads * head_dim),
                           lambda b, i, tables, maxpos:
                           (tables[b, i], 0, 0))
    scale_spec = pl.BlockSpec((1, block_size, kv_heads),
                              lambda b, i, tables, maxpos:
                              (tables[b, i], 0, 0))

    flat = (num_blocks, block_size, kv_heads * head_dim)
    in_specs = [q_spec, qpos_spec, kv_spec, kv_spec]
    operands = [qg, qpos, k_pool.reshape(flat), v_pool.reshape(flat)]
    if kv_quant:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.reshape(flat[:2] + (kv_heads,)),
                     v_scale.reshape(flat[:2] + (kv_heads,))]

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_size=block_size,
            blocks_per_seq=blocks_per_seq, kv_heads=kv_heads,
            head_dim=head_dim, sm_scale=sm_scale, window=window,
            kv_quant=kv_quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, blocks_per_seq),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kv_heads, rows_p, head_dim), jnp.float32),
                pltpu.VMEM((kv_heads, rows_p, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows_p, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, kv_heads, rows_p, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), jnp.max(positions, axis=1),
      *operands)
    return out[:, :, :rows].reshape(
        batch, kv_heads, cur_len, n_rep, head_dim).transpose(
            0, 2, 1, 3, 4).reshape(batch, cur_len, num_heads, head_dim)


def fused_hbm_bytes_per_step(live_blocks: int, block_size: int,
                             kv_heads: int, head_dim: int,
                             num_layers: int, payload_itemsize: int,
                             kv_quant: bool) -> int:
    """HBM bytes ONE fused decode step streams through the kernel:
    every live block's K and V payload read once per layer (plus the
    fp32 scale rows under int8). The XLA gather path pays this same
    read PLUS a write+read of the materialized (B, S, KV, D) gathered
    window — see docs/performance.md "Fused decode kernel" for the
    full accounting this helper anchors."""
    per_block = 2 * block_size * kv_heads * head_dim * payload_itemsize
    if kv_quant:
        per_block += 2 * block_size * kv_heads * 4   # fp32 scale rows
    return live_blocks * per_block * num_layers
