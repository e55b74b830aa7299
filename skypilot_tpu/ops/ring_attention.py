"""Ring attention: exact attention over sequence shards on the `sp` axis.

Long-context is first-class in this framework (SURVEY §5: the reference
has NO sequence/context parallelism anywhere — it delegates to the engines
it launches). Here it is a core op: sequences shard across devices on the
`sp` mesh axis, and attention runs as a ring over ICI.

Algorithm (Ring Attention, Liu et al. 2023 — blockwise parallel
transformers on a device ring):
- Every device holds Q/K/V shards of its sequence chunk.
- For `sp` steps: compute blockwise attention of the local Q chunk against
  the currently-held K/V chunk with *online softmax* accumulation (the
  flash-attention recurrence across devices), then rotate K/V one hop
  around the ring with `jax.lax.ppermute`.
- ICI makes the rotation latency hide under the chunk matmul: the permute
  of step i+1 overlaps the compute of step i (XLA schedules the
  collective-permute async on TPU).

Causality is handled at the chunk level:
- kv_chunk > q_chunk (strictly future): the whole step is skipped with
  `lax.cond` — half the FLOPs, like block-skipping in the pallas kernel.
- kv_chunk == q_chunk: intra-chunk causal mask.
- kv_chunk < q_chunk: full (unmasked) chunk attention.

This op composes with the mesh: `tp` shards heads inside each step's
matmuls; `fsdp/dp` shard batch. Called under `shard_map` (see
`ring_attention_sharded`) or any SPMD context where `axis_name` exists.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from skypilot_tpu.parallel import sharding as sharding_lib

_NEG_INF = -1e30

_IMPLS = ('xla', 'pallas', 'pallas_interpret')


def _chunk_update(q, k, v, o, m, l, *, sm_scale, mask_mode, q_offset,
                  k_offset):
    """One online-softmax accumulation step of local Q against one K/V
    chunk. Shapes: q (B,Sq,H,D); k/v (B,Sk,H,D); o (B,Sq,H,D) f32;
    m/l (B,H,Sq) f32. mask_mode: 0=full attend, 1=causal within chunk."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask_mode == 1:
        s_q, s_k = s.shape[-2], s.shape[-1]
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1)                      # (B,H,Sq)
    m_new = jnp.maximum(m, m_cur)
    # Guard fully-masked rows: exp(-inf - -inf) → use stable max.
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)                       # (B,H,Sq)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = (o * alpha.transpose(0, 2, 1)[..., None] +
             jnp.einsum('bhqk,bkhd->bqhd', p.astype(v.dtype), v
                        ).astype(jnp.float32))
    return o_new, m_new, l_new


def _chunk_update_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                         l_ref, o_out, m_out, l_out, *, sm_scale,
                         mask_mode):
    """Pallas body for one (batch, head) tile of `_chunk_update`: the
    score matmul, online-softmax rescale and weighted V-sum run in one
    VMEM pass instead of XLA materializing the (B,H,Sq,Sk) score tensor
    in HBM between ring hops. Op order mirrors `_chunk_update` exactly
    (fp32 score accumulation; probs cast to v.dtype for the V matmul,
    then widened back) so the two impls stay numerically twinned.
    offs_ref is scalar-prefetched [q_offset, k_offset] — traced values
    inside the fori_loop ring step, so they ride in SMEM rather than
    being baked into the kernel."""
    q = q_ref[0]                                      # (Sq, D)
    k = k_ref[0]                                      # (Sk, D)
    v = v_ref[0]
    m = m_ref[0, 0, 0]                                # (Sq,)
    l = l_ref[0, 0, 0]
    o = o_ref[0]                                      # (Sq, D) f32
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if mask_mode == 1:
        s_q, s_k = s.shape
        rows = offs_ref[0] + jax.lax.broadcasted_iota(
            jnp.int32, (s_q, s_k), 0)
        cols = offs_ref[1] + jax.lax.broadcasted_iota(
            jnp.int32, (s_q, s_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m - m_new)
    l_out[0, 0, 0] = l * alpha + jnp.sum(p, axis=-1)
    m_out[0, 0, 0] = m_new
    # The MXU accumulates in 32 bits and Mosaic wants that said; the
    # round trip through v.dtype is the twin's einsum output rounding.
    o_out[0] = (
        o * alpha[:, None] +
        jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        ).astype(v.dtype).astype(jnp.float32))


def _chunk_update_pallas(q, k, v, o, m, l, *, sm_scale, mask_mode,
                         q_offset, k_offset, interpret):
    """`_chunk_update` with the per-(batch, head) tile running as a
    pallas kernel. Same signature/semantics; `interpret` threads through
    to `pl.pallas_call` the way ops/flash_attention.py does, so the ring
    composes with CPU fake-device shard_map tests."""
    batch, s_q, heads, head_dim = q.shape
    s_k = k.shape[1]
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])
    # Tiles Mosaic accepts (last two block dims multiples of (8, 128)
    # or the array's own): (B, S, H, D) is viewed as (B, S, H*D) — a
    # free reshape — so one head is the lane-aligned column block
    # (S, D); m/l (B, H, Sq) become (B, H, 1, Sq) rows.
    qo_spec = pl.BlockSpec((1, s_q, head_dim),
                           lambda b, h, offs: (b, 0, h))
    kv_spec = pl.BlockSpec((1, s_k, head_dim),
                           lambda b, h, offs: (b, 0, h))
    ml_spec = pl.BlockSpec((1, 1, 1, s_q),
                           lambda b, h, offs: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, heads),
        in_specs=[qo_spec, kv_spec, kv_spec, qo_spec, ml_spec, ml_spec],
        out_specs=[qo_spec, ml_spec, ml_spec],
    )
    kernel = functools.partial(_chunk_update_kernel, sm_scale=sm_scale,
                               mask_mode=mask_mode)
    fold = lambda x: x.reshape(x.shape[0], x.shape[1], -1)
    row = lambda x: x[:, :, None, :]
    o_new, m_new, l_new = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(fold(o).shape, jnp.float32),
            jax.ShapeDtypeStruct(row(m).shape, jnp.float32),
            jax.ShapeDtypeStruct(row(l).shape, jnp.float32),
        ],
        interpret=interpret,
    )(offs, fold(q), fold(k), fold(v), fold(o), row(m), row(l))
    # Tuple, not list: the lax.cond skip branch in the ring step passes
    # its carry through unchanged, and branch pytrees must match.
    return (o_new.reshape(o.shape), m_new[:, :, 0, :],
            l_new[:, :, 0, :])


def ring_attention(q: jax.Array,
                   k: jax.Array,
                   v: jax.Array,
                   *,
                   axis_name: str = 'sp',
                   causal: bool = True,
                   sm_scale: Optional[float] = None,
                   impl: str = 'xla') -> jax.Array:
    """Exact attention over a sequence-sharded ring. Call inside
    shard_map/SPMD with `axis_name` bound.

    Args: q/k/v (B, S_local, H, D) — the local sequence chunk, kv heads
    already folded to match q heads (GQA folding happens in the caller,
    like ops/flash_attention.py). `impl` selects the per-hop chunk
    update: 'xla' (default, einsum), 'pallas' (fused VMEM kernel) or
    'pallas_interpret' (same kernel, interpreter mode — CPU tests).
    Returns (B, S_local, H, D) in q.dtype.
    """
    if impl not in _IMPLS:
        raise ValueError(
            f'ring_attention impl={impl!r}; expected one of {_IMPLS}')
    if sm_scale is None:
        sm_scale = q.shape[-1]**-0.5
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, s_local, heads, head_dim = q.shape

    o0 = jnp.zeros((batch, s_local, heads, head_dim), jnp.float32)
    m0 = jnp.full((batch, heads, s_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, s_local), jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    if impl == 'xla':
        update = _chunk_update
    else:
        update = functools.partial(_chunk_update_pallas,
                                   interpret=impl == 'pallas_interpret')

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        # After i rotations, this device holds the K/V chunk originally on
        # device (my_idx - i) mod sp.
        src_idx = (my_idx - i) % axis_size
        q_offset = my_idx * s_local
        k_offset = src_idx * s_local

        def attend_full(args):
            o, m, l = args
            return update(q, k_cur, v_cur, o, m, l,
                          sm_scale=sm_scale, mask_mode=0,
                          q_offset=q_offset, k_offset=k_offset)

        def attend_causal(args):
            o, m, l = args
            return update(q, k_cur, v_cur, o, m, l,
                          sm_scale=sm_scale, mask_mode=1,
                          q_offset=q_offset, k_offset=k_offset)

        def skip(args):
            return args

        if causal:
            # Future chunk → skip compute entirely; same chunk → masked;
            # past chunk → full. Nested cond keeps all branches
            # collective-free (the permute below runs unconditionally, so
            # the SPMD program stays uniform across devices).
            o, m, l = jax.lax.cond(
                src_idx > my_idx, skip,
                lambda args: jax.lax.cond(src_idx == my_idx, attend_causal,
                                          attend_full, args), (o, m, l))
        else:
            o, m, l = attend_full((o, m, l))

        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = jax.lax.fori_loop(0, axis_size, step,
                                      (o0, m0, l0, k, v))
    del m
    # Normalize; fully-masked rows (can't happen with causal self-attn on
    # aligned chunks, but guard anyway) produce 0.
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention_ambient(q: jax.Array,
                           k: jax.Array,
                           v: jax.Array,
                           *,
                           causal: bool = True,
                           sm_scale: Optional[float] = None,
                           impl: str = 'xla') -> jax.Array:
    """Ring attention over the ambient mesh (callers enter it with
    `jax.set_mesh(mesh)`): the form model code uses, so Flax modules don't
    thread Mesh objects. Specs follow the canonical activation layout."""
    # The canonical (B, S, H, D) activation layout from the shared rule
    # table (parallel/sharding.py) — no local copy of the mapping.
    spec = sharding_lib.spec_for('batch', 'seq', 'act_heads', None)
    fn = functools.partial(ring_attention, axis_name='sp', causal=causal,
                           sm_scale=sm_scale, impl=impl)
    return sharding_lib.shard_map(fn, in_specs=(spec, spec, spec),
                                  out_specs=spec)(q, k, v)


def ring_attention_sharded(mesh: Mesh,
                           q: jax.Array,
                           k: jax.Array,
                           v: jax.Array,
                           *,
                           causal: bool = True,
                           sm_scale: Optional[float] = None,
                           impl: str = 'xla') -> jax.Array:
    """Convenience wrapper: shard_map over the framework mesh with the
    canonical activation layout (batch on dp/fsdp, sequence on sp, heads
    on tp). Inputs are global arrays; XLA inserts the resharding."""
    spec = sharding_lib.spec_for('batch', 'seq', 'act_heads', None)

    @functools.partial(
        sharding_lib.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec)
    def _sharded(q, k, v):
        return ring_attention(q, k, v, axis_name='sp', causal=causal,
                              sm_scale=sm_scale, impl=impl)

    return _sharded(q, k, v)
