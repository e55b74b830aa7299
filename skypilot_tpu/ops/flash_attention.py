"""Fused causal attention for TPU (pallas), with an XLA reference path.

This is one of the "hot ops" the framework owns natively (the reference
framework delegates all compute to the engines it launches — vLLM/torch —
per SURVEY §2.9; this framework ships its own model stack, so attention is
in-tree).

Design (per the pallas TPU playbook):
- Online-softmax tiling: the (S,S) score matrix never materializes in HBM.
  Grid = (batch*heads, S/block_q); K/V rows for one (batch, head) stay
  resident in VMEM while q-blocks stream through the MXU.
- Causal blocks are *skipped*, not masked: the k-loop upper bound is
  derived from the q-block index, so the kernel does ~half the FLOPs of
  dense attention.
- MXU dtype discipline: every dot's OPERANDS stay in the input dtype
  (bf16 for model runs — the MXU's native mode; emulated fp32 matmul is
  ~6x slower) with fp32 ACCUMULATION via preferred_element_type; softmax
  statistics, lse/delta, and all gradient accumulators are fp32. fp32
  inputs keep fp32 operands (tests stay exact). This matters most at
  long sequence, where attention's FLOP share dominates the step.
- Backward is the standard flash-attention backward pair of pallas
  kernels (dq kernel gridded over q-blocks; dk/dv kernel gridded over
  k-blocks), recomputing p from the saved logsumexp instead of an S×S
  residual. Causal block-skipping applies on both sides, so the O(S²)
  recompute-through-XLA cost of the old VJP is gone — this is what keeps
  MFU from collapsing at seq ≥ 2048.

GQA is handled by folding: kv heads are repeated to match q heads before
the kernel (cheap relative to attention FLOPs at the sizes we run).
"""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from skypilot_tpu.parallel import sharding as sharding_lib

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool, sm_scale: float,
                         logit_softcap: float = 0.0,
                         window: int = 0) -> jax.Array:
    """Plain XLA attention; fp32 softmax. Shapes: (B, S, H, D)."""
    logits = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    if logit_softcap:
        # Gemma-2 style tanh cap; XLA fuses this into the matmul epilogue.
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    if causal or window:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        rows = jnp.arange(s_q)[:, None] + (s_k - s_q)
        cols = jnp.arange(s_k)[None, :]
        mask = cols <= rows
        if window:
            mask &= rows - cols < window
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum('bhqk,bkhd->bqhd', probs, v)


def _window_lo(qi, block_q: int, block_k: int, window: int):
    """First k-block any row of q-block `qi` can see under a sliding
    window of `window` keys (query row r sees keys (r-window, r])."""
    first_visible = qi * block_q - (window - 1)
    return jnp.maximum(0, first_visible // block_k)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale: float,
                causal: bool, window: int, block_q: int, block_k: int,
                seq_len: int, head_dim: int):
    qi = pl.program_id(1)
    # MXU discipline: dot OPERANDS stay in the input dtype (bf16 for
    # model runs — the MXU's native mode, ~6x the emulated-fp32 matmul
    # rate) with fp32 ACCUMULATION via preferred_element_type. The
    # softmax statistics and the output accumulator are fp32 throughout.
    # This is the single biggest long-sequence MFU lever: attention's
    # FLOP share grows with S, so fp32-operand dots here were what
    # dragged step MFU down as sequences lengthened.
    q = q_ref[0]                                          # (bq, d) raw
    in_dtype = q.dtype

    num_kb = seq_len // block_k
    if causal:
        # Process every k-block containing keys ≤ the last query of this
        # q-block: ceil((qi+1)*block_q / block_k).
        hi = ((qi + 1) * block_q + block_k - 1) // block_k
        hi = jnp.minimum(hi, num_kb)
    else:
        hi = num_kb
    lo = _window_lo(qi, block_q, block_k, window) if window else 0

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]  # (bk, d) raw
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # fp32 scale
        if causal or window:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = cols <= rows  # window implies causal (API-enforced)
            if window:
                keep &= rows - cols < window
            s = jnp.where(keep, s, -1e30)
        m_cur = jnp.max(s, axis=-1)                       # (bq,)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                   # (bq, bk) fp32
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(in_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    init = (jnp.zeros((block_q, head_dim), jnp.float32),
            jnp.full((block_q,), -jnp.inf, jnp.float32),
            jnp.zeros((block_q,), jnp.float32))
    acc, m, l = jax.lax.fori_loop(lo, hi, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # Per-row logsumexp of the SCALED logits — the backward kernels
    # rebuild p = exp(s - lse) from this instead of an S×S residual.
    # Layout note: lse rides as (BH, 1, S) full-row blocks written via a
    # dynamic slice — a (1, block_q) block on a (BH, S) array violates the
    # TPU lowering's (8, 128)-divisibility rule for the last two dims.
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = m + jnp.log(l_safe)


def _pallas_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                    window: int, sm_scale: float, block_q: int,
                    block_k: int, interpret: bool):
    """q,k,v: (BH, S, D) — pre-folded batch*heads, kv already repeated.
    Returns (out, lse)."""
    bh, seq_len, head_dim = q.shape
    grid = (bh, seq_len // block_q)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, seq_len=seq_len,
                               head_dim=head_dim)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, head_dim), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, seq_len), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale: float, causal: bool, window: int,
                   block_q: int, block_k: int, seq_len: int,
                   head_dim: int):
    """dQ for one q-block: stream k-blocks (skipping fully-masked ones),
    rebuild p from lse, accumulate ds @ K."""
    qi = pl.program_id(1)
    q = q_ref[0]                                          # (bq, d) raw
    do = do_ref[0]                                        # (bq, d) raw
    in_dtype = q.dtype
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]     # (bq,)
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]

    num_kb = seq_len // block_k
    if causal:
        hi = ((qi + 1) * block_q + block_k - 1) // block_k
        hi = jnp.minimum(hi, num_kb)
    else:
        hi = num_kb
    lo = _window_lo(qi, block_q, block_k, window) if window else 0

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]  # (bk, d) raw
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal or window:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = cols <= rows
            if window:
                keep &= rows - cols < window
            s = jnp.where(keep, s, -1e30)
        p = jnp.exp(s - lse[:, None])                     # (bq, bk) fp32
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])                    # dlogits, fp32
        return dq + jax.lax.dot_general(
            ds.astype(in_dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_q, head_dim), jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale: float, causal: bool,
                    window: int, block_q: int, block_k: int, seq_len: int,
                    head_dim: int):
    """dK/dV for one k-block: stream q-blocks at-or-after it (causal),
    skipping q-blocks past the sliding window, rebuild p, accumulate
    pᵀ @ dO and dsᵀ @ Q."""
    kb = pl.program_id(1)
    k_blk = k_ref[0]                                      # (bk, d) raw
    v_blk = v_ref[0]
    in_dtype = k_blk.dtype

    num_qb = seq_len // block_q
    # First q-block whose LAST row can see this k-block's first key.
    lo = (kb * block_k) // block_q if causal else 0
    if window:
        # Last visible query row for ANY key here: (kb+1)*block_k - 1 +
        # window - 1; blocks beyond it contribute nothing.
        last_row = (kb + 1) * block_k + window - 2
        hi = jnp.minimum(num_qb, last_row // block_q + 1)
    else:
        hi = num_qb

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :]  # (bq, d) raw
        do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]  # (bq,)
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal or window:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = cols <= rows
            if window:
                keep &= rows - cols < window
            s = jnp.where(keep, s, -1e30)
        p = jnp.exp(s - lse[:, None])                     # (bq, bk) fp32
        p_c = p.astype(in_dtype)
        dv = dv + jax.lax.dot_general(
            p_c, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        dp = jax.lax.dot_general(do_blk, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(
            ds.astype(in_dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bk, d)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((block_k, head_dim), jnp.float32),
         jnp.zeros((block_k, head_dim), jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_backward(q, k, v, do, lse, delta, causal, window, sm_scale,
                     block_q, block_k, interpret):
    """All inputs pre-folded (BH, S, D) / (BH, S). Returns dq, dk, dv."""
    bh, seq_len, head_dim = q.shape
    full = lambda: pl.BlockSpec((1, seq_len, head_dim),
                                lambda b, i: (b, 0, 0))
    full_row = lambda: pl.BlockSpec((1, 1, seq_len), lambda b, i: (b, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_len=seq_len, head_dim=head_dim),
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            full(), full(),
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            full_row(), full_row(),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_len, head_dim), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, window=window, block_q=block_q,
                          block_k=block_k, seq_len=seq_len,
                          head_dim=head_dim),
        grid=(bh, seq_len // block_k),
        in_specs=[
            full(),
            pl.BlockSpec((1, block_k, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, i: (b, i, 0)),
            full(), full_row(), full_row(),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, head_dim), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=2)


def _fold(x: jax.Array) -> jax.Array:
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, s, d = x.shape
    del bh
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, sm_scale, block_q, block_k, interpret):
    b, s, h, d = q.shape
    del s, d
    n_rep = h // k.shape[2]
    out, _ = _pallas_forward(_fold(q), _fold(_repeat_kv(k, n_rep)),
                             _fold(_repeat_kv(v, n_rep)), causal, window,
                             sm_scale, block_q, block_k, interpret)
    return _unfold(out, b, h)


def _flash_fwd(q, k, v, causal, window, sm_scale, block_q, block_k,
               interpret):
    b, s, h, d = q.shape
    del s, d
    n_rep = h // k.shape[2]
    out_f, lse = _pallas_forward(_fold(q), _fold(_repeat_kv(k, n_rep)),
                                 _fold(_repeat_kv(v, n_rep)), causal,
                                 window, sm_scale, block_q, block_k,
                                 interpret)
    return _unfold(out_f, b, h), (q, k, v, out_f, lse)


def _flash_bwd(causal, window, sm_scale, block_q, block_k, interpret,
               residuals, g):
    q, k, v, out_f, lse = residuals
    b, s, h, d = q.shape
    del s, d
    num_kv = k.shape[2]
    n_rep = h // num_kv
    qf = _fold(q)
    kf = _fold(_repeat_kv(k, n_rep))
    vf = _fold(_repeat_kv(v, n_rep))
    gf = _fold(g)
    # delta_i = rowsum(dO_i * O_i) — the softmax-normalization term of
    # dlogits (XLA fuses this elementwise+reduce pair on its own).
    # (BH, 1, S): the lse/delta row layout the kernels expect.
    delta = jnp.sum(gf.astype(jnp.float32) * out_f.astype(jnp.float32),
                    axis=-1)[:, None, :]
    dqf, dkf, dvf = _pallas_backward(qf, kf, vf, gf, lse, delta, causal,
                                     window, sm_scale, block_q, block_k,
                                     interpret)
    dq = _unfold(dqf, b, h).astype(q.dtype)
    dk_full = _unfold(dkf, b, h)                     # (b, s, h, d)
    dv_full = _unfold(dvf, b, h)
    if n_rep > 1:
        # GQA: repeated kv heads j*n_rep..j*n_rep+n_rep-1 all came from
        # kv head j — sum their gradients back.
        bsz, seq, _, hd = dk_full.shape
        dk_full = dk_full.reshape(bsz, seq, num_kv, n_rep, hd).sum(axis=3)
        dv_full = dv_full.reshape(bsz, seq, num_kv, n_rep, hd).sum(axis=3)
    return (dq, dk_full.astype(k.dtype), dv_full.astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    impl: str = 'auto',
                    logit_softcap: float = 0.0,
                    window: int = 0) -> jax.Array:
    """Multi-head attention with GQA support.

    Args:
      q: (batch, seq, num_heads, head_dim)
      k/v: (batch, seq, num_kv_heads, head_dim); num_heads must be a
        multiple of num_kv_heads.
      impl: 'pallas' | 'xla' | 'auto' (pallas on TPU when shapes tile,
        xla otherwise).
      logit_softcap: Gemma-2-style tanh cap on attention logits (0 = off).
        Supported on the XLA path only; 'auto' routes capped attention to
        XLA, explicit 'pallas'/'ring' reject it.
      window: sliding-window size in keys, Mistral-style — query row r
        attends keys (r-window, r]. 0 = full causal. Requires causal;
        the pallas kernels skip blocks entirely outside the window, so
        compute drops from O(S²) to O(S·window) for long sequences.
    """
    b, s, h, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    if h % k.shape[2]:
        raise ValueError(f'num_heads {h} not divisible by kv heads '
                         f'{k.shape[2]}')
    if window and not causal:
        raise ValueError('window requires causal attention')
    if window < 0:
        raise ValueError(f'window must be >= 0, got {window}')
    # Blocks never exceed the sequence (the 256-default would otherwise
    # reject short sequences that tile fine at their own length).
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if impl == 'auto':
        on_tpu = any(dev.platform == 'tpu' for dev in jax.devices())
        # Mosaic requires 128-aligned slices in the lane dimension: short
        # sequences (clamped blocks < 128) fall back to XLA — they are
        # tiny anyway (e.g. the 8-token shape used to init engines).
        tiles = (s % block_q == 0 and s % block_k == 0 and
                 d in (64, 128, 256) and
                 block_q % 128 == 0 and block_k % 128 == 0)
        impl = 'pallas' if (on_tpu and tiles and
                            not logit_softcap) else 'xla'
        # Trace time, so once per compiled program: which attention a
        # run got is read from its log, not guessed from its flags.
        logger.info(
            "flash_attention: impl='auto' resolved to %r (seq=%d "
            'head_dim=%d block_q=%d block_k=%d softcap=%s tpu=%s)', impl,
            s, d, block_q, block_k, bool(logit_softcap), on_tpu)
    if impl == 'xla':
        n_rep = h // k.shape[2]
        return _reference_attention(q, _repeat_kv(k, n_rep),
                                    _repeat_kv(v, n_rep), causal, sm_scale,
                                    logit_softcap, window)
    if logit_softcap:
        raise ValueError(
            f'logit_softcap is only supported on the XLA attention path '
            f'(got impl={impl!r}); use attention_impl="xla" or "auto".')
    if impl == 'ring':
        # Context parallelism: sequence sharded on the `sp` mesh axis,
        # K/V rotating around the ring (ops/ring_attention.py). Requires
        # an ambient mesh (jax.set_mesh) with an `sp` axis.
        if window:
            raise ValueError('window is not supported on the ring path; '
                             'a sliding window makes ring rotation '
                             'unnecessary — shard the sequence instead.')
        from skypilot_tpu.ops.ring_attention import ring_attention_ambient
        n_rep = h // k.shape[2]
        return ring_attention_ambient(
            q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), causal=causal,
            sm_scale=sm_scale)
    if impl in ('pallas', 'pallas_interpret'):
        if s % block_q or s % block_k:
            raise ValueError(f'seq {s} must tile by block_q={block_q}, '
                             f'block_k={block_k}')
        kernel = lambda q, k, v: _flash(  # noqa: E731
            q, k, v, causal, window, sm_scale, block_q, block_k,
            impl == 'pallas_interpret')
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and mesh.size > 1:
            # A Mosaic call has no partitioning rule (jax refuses to
            # lower it under a sharded jit), so under an ambient mesh
            # (sharding.use_mesh) each device runs the kernel on its
            # own batch and head shard. Attention mixes neither, so no
            # collective is needed.
            spec = sharding_lib.spec_for('batch', None, 'act_heads', None)
            kernel = sharding_lib.shard_map(
                kernel, in_specs=(spec, spec, spec), out_specs=spec)
        return kernel(q, k, v)
    raise ValueError(f'Unknown impl {impl!r}')
