"""Mamba-2 (SSD) mixer: the state-space half of a hybrid block.

Falcon-H1 runs this mixer in parallel with attention on one shared
pre-norm (models/transformer.DecoderLayer). One call does, for every row
of the batch:

    p            = in_proj(ssm_in_multiplier * u) * m      m: a multiplier a segment
    z, xBC, dt   = split(p, [d_ssm, d_ssm + 2 G N, H])
    xBC          = silu(conv_bias + causal depthwise conv of xBC, `ssm_conv` taps)
    xs, B, C     = split(xBC, [d_ssm, G N, G N])
    delta        = softplus(dt + dt_bias);  a = exp(-exp(A_log) * delta)
    S[t]         = a * S[t-1] + delta * outer(xs[t], B[t])        (H, P, N)
    y[t]         = S[t] @ C[t] + D * xs[t]
    y            = grouped_rms_norm(y * silu(z)) * norm_w   (gate first)
    out          = ssm_out_multiplier * out_proj(y)

A sequence's state here is of FIXED size whatever its position: the
scan state S (float32 by default) and the convolution's last
`ssm_conv - 1` inputs (compute dtype). Both live in the flax 'cache'
collection beside K and V, indexed by SLOT, and are rewritten at every
step (docs/serving.md "Models with recurrent state"):

- a chunk whose first position is 0 starts from a zero state, so a
  reused slot is clean by construction and admission dispatches nothing;
- positions at and after a row's `valid` count (the right pads of the
  engine's fixed prefill chunk; a decode step's empty or prefilling
  slots, whose count is 0) advance neither state: their step is zero, so
  the decay is one and nothing is added, and the convolution's carried
  inputs are the last REAL ones;
- a row whose count is 0 keeps both states bit for bit.

T > 1 walks the chunk in blocks of `ssm_chunk` positions by the chunked
form (a block's outputs from one masked (Q, Q) product and the state it
started from; its end state from one more product), never materialising
a state a position. T == 1 is the one-step recurrence, elementwise in
float32. Where the float32 state enters a product (`S @ C`) the product
runs at `highest` precision: at the default a TPU would round the state
to bfloat16 on the way in, and its extra bits would be held for nothing.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.kv_cache import STATE_LEAVES

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
SCAN_STATE, CONV_STATE = STATE_LEAVES


def scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """x * multiplier, computed in float32 and rounded once (a
    multiplier rounded to bfloat16 would be a systematic 0.2% error);
    1.0 traces nothing, so models without multipliers keep their
    programs."""
    if multiplier == 1.0:
        return x
    return (x.astype(F32) * multiplier).astype(x.dtype)


def segment_multipliers(cfg: ModelConfig) -> Optional[np.ndarray]:
    """`ssm_multipliers` spread over the input projection's outputs
    [z | x | B | C | dt], or None when all are 1."""
    if all(m == 1.0 for m in cfg.ssm_multipliers):
        return None
    gn = cfg.ssm_groups * cfg.ssm_state
    sizes = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    return np.concatenate([np.full(n, m, np.float32)
                           for n, m in zip(sizes, cfg.ssm_multipliers)])


def _unbox(var):
    box = var.value
    return (box.unbox() if hasattr(box, 'unbox') else box), box


def _rebox(var, box, arr) -> None:
    var.value = (box.replace_boxed(arr) if hasattr(box, 'replace_boxed')
                 else arr)


def ssd_step(state, xs, b, c, delta, a_log, d_skip):
    """The one-step recurrence. state: (B, G, R, P, N) float32; xs:
    (B, G, R, P); b, c: (B, G, N); delta: (B, G, R), already masked.
    Returns (y (B, G, R, P) float32, new state)."""
    xs, b, c = xs.astype(F32), b.astype(F32), c.astype(F32)
    decay = jnp.exp(-jnp.exp(a_log) * delta)                 # (B, G, R)
    new = (decay[..., None, None] * state
           + (delta[..., None] * xs)[..., None]
           * b[:, :, None, None, :])
    y = jnp.sum(new * c[:, :, None, None, :], axis=-1)
    return y + d_skip[..., None] * xs, new


def ssd_chunked(state, xs, b, c, delta, a_log, d_skip, block: int,
                dtype):
    """The chunked (SSD) form over T positions, `block` at a time.
    state: (B, G, R, P, N) float32; xs: (B, T, G, R, P); b, c:
    (B, T, G, N); delta: (B, T, G, R) float32, zero where a position
    must not advance the state. Returns (y (B, T, G, R, P) float32, end
    state). Products whose inputs are activations run on `dtype` inputs
    with float32 accumulation; the one that reads the state runs in
    float32 at `highest`."""
    batch, t = xs.shape[:2]
    q = block if t >= block else t
    pad = (-t) % q
    if pad:
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        xs, b, c, delta = (jnp.pad(a, widths(a))
                           for a in (xs, b, c, delta))
    nb = (t + pad) // q
    blocks = lambda a: jnp.moveaxis(
        a.reshape((batch, nb, q) + a.shape[2:]), 1, 0)
    rate = -jnp.exp(a_log)                                   # (G, R)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one_block(s0, args):
        x_k, b_k, c_k, d_k = args        # (B, Q, ...) of one block
        cum = jnp.cumsum(rate * d_k, axis=1)                 # (B,Q,G,R)
        # inside the block: y[t] += sum_{s<=t} exp(cum t - cum s)
        #                            * delta[s] * (C[t].B[s]) * x[s]
        cb = jnp.einsum('btgn,bsgn->bgts', c_k, b_k,
                        preferred_element_type=F32)
        diff = cum[:, :, None] - cum[:, None, :]             # (B,t,s,G,R)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                                  -jnp.inf))
        m = decay * d_k[:, None] * cb.transpose(0, 2, 3, 1)[..., None]
        y = jnp.einsum('btsgr,bsgrp->btgrp', m.astype(dtype), x_k,
                       preferred_element_type=F32)
        # from the state the block started with
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            'bgrpn,btgn->btgrp', s0, c_k.astype(F32), precision=HIGHEST,
            preferred_element_type=F32)
        y = y + d_skip[..., None] * x_k.astype(F32)
        # the state the block ends with
        w = jnp.exp(cum[:, -1:] - cum) * d_k                 # (B,Q,G,R)
        xw = (x_k.astype(F32) * w[..., None]).astype(dtype)
        s1 = (jnp.exp(cum[:, -1])[..., None, None] * s0
              + jnp.einsum('bsgrp,bsgn->bgrpn', xw, b_k,
                           preferred_element_type=F32))
        return s1, y

    state, ys = jax.lax.scan(one_block, state,
                             tuple(blocks(a) for a in (xs, b, c, delta)))
    y = jnp.moveaxis(ys, 0, 1).reshape((batch, nb * q) + ys.shape[3:])
    return y[:, :t], state


class Mamba2Mixer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, u: jax.Array, positions: jax.Array,
                 state_rows: Optional[Tuple] = None) -> jax.Array:
        """u: (B, T, d_model), the block's shared pre-norm output;
        positions: (B, T). state_rows = (slots, valid), each (B,) int32
        or None: the row of the state leaves that each batch row owns
        (None: row b owns leaf row b) and how many of its T positions
        are real (None: all)."""
        # Late import: transformer.py imports this module.
        from skypilot_tpu.models.transformer import dense_general
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        batch, t = u.shape[:2]
        heads, p_dim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        g = cfg.ssm_groups
        if heads % g:
            raise ValueError(f'ssm_heads {heads} not divisible by '
                             f'ssm_groups {g}')
        r = heads // g
        d_ssm, gn, ch = cfg.d_ssm, g * n, cfg.ssm_conv_channels
        keep = cfg.ssm_conv - 1
        slots, valid = state_rows if state_rows is not None else (None,
                                                                  None)

        proj = dense_general(cfg, cfg.ssm_proj_width, ('embed', 'mlp'),
                             'in_proj', use_bias=cfg.ssm_proj_bias)(
                                 scaled(u, cfg.ssm_in_multiplier))
        proj = proj.astype(F32)
        mvec = segment_multipliers(cfg)
        if mvec is not None:
            proj = proj * mvec
        z = proj[..., :d_ssm]
        xbc = proj[..., d_ssm:d_ssm + ch].astype(dtype)
        dt = proj[..., d_ssm + ch:]                          # (B, T, H)

        a_log = self.param('A_log', nn.with_logical_partitioning(
            nn.initializers.zeros, (None,)), (heads,), F32)
        d_skip = self.param('D', nn.with_logical_partitioning(
            nn.initializers.ones, (None,)), (heads,), F32)
        dt_bias = self.param('dt_bias', nn.with_logical_partitioning(
            nn.initializers.zeros, (None,)), (heads,), F32)
        conv_w = self.param('conv_kernel', nn.with_logical_partitioning(
            nn.initializers.normal(stddev=cfg.ssm_conv ** -0.5),
            (None, 'mlp')), (cfg.ssm_conv, ch), jnp.dtype(cfg.param_dtype))
        conv_b = None
        if cfg.ssm_conv_bias:
            conv_b = self.param('conv_bias', nn.with_logical_partitioning(
                nn.initializers.zeros, ('mlp',)), (ch,),
                jnp.dtype(cfg.param_dtype))

        # ---- the state this call starts from ----
        rows = cfg.state_slots or batch
        if cfg.decode:
            state_var = self.variable(
                'cache', SCAN_STATE,
                lambda: nn.with_logical_partitioning(
                    jnp.zeros, ('batch', None, None, None))(
                        (rows, heads, p_dim, n),
                        jnp.dtype(cfg.ssm_state_dtype)))
            conv_var = self.variable(
                'cache', CONV_STATE,
                lambda: nn.with_logical_partitioning(
                    jnp.zeros, ('batch', None, None))(
                        (rows, keep, ch), dtype))
            state_leaf, state_box = _unbox(state_var)
            conv_leaf, conv_box = _unbox(conv_var)
            if slots is None and rows != batch:
                raise ValueError(
                    f'the state leaves hold {rows} rows and the call '
                    f'{batch}: pass state_rows with each row\'s slot')
            # Late import: only a decoding model loads the module. Rows
            # are read and written at (layer, slot) of the leaf itself
            # (the stacked one under the cache-carrying layer loop).
            from skypilot_tpu.models import cache_carry
            layer = cache_carry.current_layer()
            s_old = cache_carry.read_rows(state_leaf, slots, layer)
            c_old = cache_carry.read_rows(conv_leaf, slots, layer)
            fresh = positions[:, 0] == 0
            s0 = jnp.where(fresh[:, None, None, None], 0,
                           s_old).astype(F32)
            c0 = jnp.where(fresh[:, None, None], 0, c_old)
        else:
            s0 = jnp.zeros((batch, heads, p_dim, n), F32)
            c0 = jnp.zeros((batch, keep, ch), dtype)
        if valid is None:
            valid = jnp.full((batch,), t, jnp.int32)
        real = jnp.arange(t)[None, :] < valid[:, None]       # (B, T)

        # ---- causal depthwise convolution over x, B and C ----
        ext = jnp.concatenate([c0, xbc], axis=1)       # (B, keep + T, ch)
        acc = sum(conv_w[k].astype(F32) * ext[:, k:k + t].astype(F32)
                  for k in range(cfg.ssm_conv))
        if conv_b is not None:
            acc = acc + conv_b.astype(F32)
        xbc = nn.silu(acc).astype(dtype)
        # the last `keep` REAL inputs: history counts for short prompts
        c1 = jax.vmap(lambda e, v: jax.lax.dynamic_slice_in_dim(
            e, v, keep, axis=0))(ext, valid)

        xs = xbc[..., :d_ssm].reshape(batch, t, g, r, p_dim)
        b_in = xbc[..., d_ssm:d_ssm + gn].reshape(batch, t, g, n)
        c_in = xbc[..., d_ssm + gn:].reshape(batch, t, g, n)
        delta = jax.nn.softplus(dt + dt_bias)
        delta = jnp.where(real[..., None], delta, 0.0).reshape(
            batch, t, g, r)
        s0 = s0.reshape(batch, g, r, p_dim, n)
        a_gr, d_gr = a_log.reshape(g, r), d_skip.reshape(g, r)
        if t == 1:
            y, s1 = ssd_step(s0, xs[:, 0], b_in[:, 0], c_in[:, 0],
                             delta[:, 0], a_gr, d_gr)
            y = y[:, None]
        else:
            y, s1 = ssd_chunked(s0, xs, b_in, c_in, delta, a_gr, d_gr,
                                cfg.ssm_chunk, dtype)
        y = y.reshape(batch, t, d_ssm)

        if cfg.decode:
            touched = valid > 0
            s1 = jnp.where(touched[:, None, None, None],
                           s1.reshape(batch, heads, p_dim, n).astype(
                               state_leaf.dtype), s_old)
            c1 = jnp.where(touched[:, None, None], c1, c_old)
            _rebox(state_var, state_box, cache_carry.write_rows(
                state_leaf, slots, layer, s1))
            _rebox(conv_var, conv_box, cache_carry.write_rows(
                conv_leaf, slots, layer, c1))

        # ---- gate, grouped norm, output projection ----
        gate = nn.silu(z)
        if cfg.ssm_gated_norm:
            norm_w = self.param('norm_scale', nn.with_logical_partitioning(
                nn.initializers.ones, ('mlp',)), (d_ssm,),
                jnp.dtype(cfg.param_dtype)).astype(F32)
            if not cfg.ssm_norm_before_gate:
                y = y * gate
            yg = y.reshape(batch, t, g, d_ssm // g)
            var = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
            y = (yg * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(
                batch, t, d_ssm) * norm_w
            if cfg.ssm_norm_before_gate:
                y = y * gate
        else:
            y = y * gate
        out = dense_general(cfg, cfg.d_model, ('mlp', 'embed'),
                            'out_proj', use_bias=cfg.ssm_proj_bias)(
                                y.astype(dtype))
        return scaled(out, cfg.ssm_out_multiplier)
