"""Kimi delta attention (KDA): the linear-attention mixer of a hybrid
stack (Kimi Linear, arXiv:2510.26692; Ling-3.0-flash runs it in five
layers of six).

One call does, for every row of the batch, with H = num_heads heads of
D = head_dim (keys and values alike):

    q, k, v  = silu(causal depthwise conv of [q_proj x | k_proj x | v_proj x],
                    `kda_conv` taps, no bias)
    q        = l2norm(q) / sqrt(D);  k = l2norm(k)            a head each
    beta     = sigmoid(b_proj x)                              (H,)
    g        = bound * sigmoid(exp(A_log) * (f_proj x + dt_bias))   (H, D), in (bound, 0)
    S[t]     = (I - beta k k^T) Diag(exp g) S[t-1] + beta k v^T     (H, D, D), float32
    o[t]     = S[t]^T q
    out      = o_proj(rms_norm_head(o) * sigmoid(g_proj x))

The state is held KEYS-MAJOR, `S[k, v]` (keys by values, as `fla`
holds it), so that a key runs along the chip's sublanes and a value
along its lanes: both contractions of a step, `u = v - sum_k (S exp g)
k` and `o = sum_k S q`, are then sums over sublanes.

A sequence's state is of FIXED size whatever its position: the float32
state and the convolution's last `kda_conv - 1` inputs (compute dtype),
in the flax 'cache' collection under the same names as the Mamba-2
mixer's (kv_cache.STATE_LEAVES), indexed by SLOT and rewritten at every
step. The slot rules are models/ssm.py's:

- a chunk whose first position is 0 starts from a zero state, so a
  reused slot is clean by construction and admission dispatches nothing;
- positions at and after a row's `valid` count (a chunk's right pads,
  an inert decode row) advance neither state: their beta is 0 and their
  g is 0, so nothing decays and nothing is added, and the convolution's
  carried inputs are the last REAL ones;
- a row whose count is 0 keeps both states bit for bit.

T > 1 walks the chunk in blocks of `CHUNK` (16) positions by the chunked
(WY) form: inside a block, with G the cumulative g from the block's
start, A = k e^G, B = k e^-G and Q = q e^G,

    U   = (I + L)^-1 (V - A S0)     L[i, j] = beta_j A_i . B_j,  j < i
    O   = Q S0 + M U                M[i, j] = beta_j Q_i . B_j,  j <= i
    S1  = e^G_last S0 + sum_j beta_j (k_j e^(G_last - G_j)) (x) U_j

e^-G reaches e^(16 x |bound|), e^80 at the published bound of -5 (the
configuration refuses a lower one), inside float32. Every product of the block runs in float32 at `highest`
precision. T == 1 is the one-step recurrence, elementwise in float32.
The all-slots decode step under the decode kernels (any
`cfg.decode_kernel` but 'xla': `ssm.takes_state_kernel`) runs it through
ops/kda_state_step, which reads each row's state once and writes the new
state into the leaf where it lies.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.ssm import (CONV_STATE, SCAN_STATE, _rebox,
                                     _unbox, takes_state_kernel)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
# positions of a block of the chunked form
CHUNK = 16


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def l2_normalize(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                     keepdims=True) + L2_EPS)


def kda_gate(f: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
             bound: float) -> jax.Array:
    """g = bound * sigmoid(exp(A_log) * (f + dt_bias)), float32. f:
    (..., H, D); a_log: (H,); dt_bias: (H, D)."""
    rate = jnp.exp(a_log.astype(F32))[:, None]
    return bound * jax.nn.sigmoid(rate * (f.astype(F32)
                                          + dt_bias.astype(F32)))


def kda_step(state, q, k, v, g, beta):
    """The one-step recurrence. state: (B, H, K, V) float32, keys-major;
    q, k, g: (B, H, K); v: (B, H, V); beta: (B, H), all float32 and
    already masked. Returns (o (B, H, V), new state).

    Written so that nothing of the state's size but the new state is
    made: the decay folds into k and q before their products with the
    old state, and `q . new = (e^g q) . old + u (beta k . q)`."""
    decay = jnp.exp(g)
    bk = beta[..., None] * k
    u = v - jnp.sum(state * (decay * k)[..., None], axis=-2)
    o = (jnp.sum(state * (decay * q)[..., None], axis=-2)
         + u * jnp.sum(bk * q, axis=-1, keepdims=True))
    return o, state * decay[..., None] + bk[..., None] * u[:, :, None, :]


def kda_step_in_place(leaf, layer, touched, fresh, q, k, v, g, beta,
                      interpret: bool):
    """`kda_step` over every slot of the state leaf by the kernel that
    reads and writes each state tile once (ops/kda_state_step), the new
    state written into the leaf at `layer`: a row not `touched` keeps
    its state, a `fresh` one starts from zero. Returns (o (B, H, V)
    float32, the leaf)."""
    # Late import: only a decode program under the kernels loads it.
    from skypilot_tpu.ops.kda_state_step import kda_state_step
    return kda_state_step(leaf, layer, touched, fresh, jnp.exp(g), q, k,
                          beta[..., None] * k, v, interpret=interpret)


def _unit_lower_inverse(low: jax.Array) -> jax.Array:
    """(I + low)^-1 for strictly lower-triangular `low` (..., C, C):
    the series I - low + low^2 - ..., which ends at low^C = 0, as the
    product (I + P)(I + P^2)(I + P^4)... with P = -low."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    p = -low
    inv = eye + p
    span = 2
    while span < c:
        p = _mm('...ij,...jk->...ik', p, p)
        inv = _mm('...ij,...jk->...ik', inv, eye + p)
        span *= 2
    return inv


def kda_chunked(state, q, k, v, g, beta, block: int):
    """The chunked form over T positions, `block` at a time. state:
    (B, H, K, V) float32, keys-major; q, k, g: (B, T, H, K); v: (B, T,
    H, V); beta: (B, T, H); all float32, g and beta zero where a
    position must not advance the state. Returns (o (B, T, H, V)
    float32, end state)."""
    batch, t = q.shape[:2]
    c = block if t >= block else t
    pad = (-t) % c
    if pad:
        widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        q, k, v, g, beta = (jnp.pad(a, widths(a))
                            for a in (q, k, v, g, beta))
    nb = (t + pad) // c
    # (blocks, B, H, C, ...): a block's positions next to each other
    blocks = lambda a: jnp.moveaxis(
        a.reshape((batch, nb, c) + a.shape[2:]), 1, 0).swapaxes(2, 3)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    upto = jnp.tril(jnp.ones((c, c), bool))

    def one_block(s0, args):
        q_k, k_k, v_k, g_k, b_k = args     # (B, H, C, ...), b_k (B, H, C)
        cum = jnp.cumsum(g_k, axis=2)                       # (B,H,C,K)
        a = k_k * jnp.exp(cum)
        b = k_k * jnp.exp(-cum)
        qt = q_k * jnp.exp(cum)
        beta_j = b_k[:, :, None, :]                         # (B,H,1,C)
        low = jnp.where(strict, _mm('bhik,bhjk->bhij', a, b) * beta_j,
                        0.0)
        m = jnp.where(upto, _mm('bhik,bhjk->bhij', qt, b) * beta_j, 0.0)
        u = _mm('bhij,bhjv->bhiv', _unit_lower_inverse(low),
                v_k - _mm('bhck,bhkv->bhcv', a, s0))
        o = _mm('bhck,bhkv->bhcv', qt, s0) + _mm('bhij,bhjv->bhiv', m, u)
        last = cum[:, :, -1:]                               # (B,H,1,K)
        w = k_k * jnp.exp(last - cum) * b_k[..., None]
        s1 = (s0 * jnp.exp(last).swapaxes(-1, -2)
              + _mm('bhck,bhcv->bhkv', w, u))
        return s1, o

    state, os = jax.lax.scan(one_block, state,
                             tuple(blocks(a) for a in (q, k, v, g, beta)))
    # (nb, B, H, C, V) -> (B, T, H, V)
    o = os.swapaxes(2, 3).swapaxes(0, 1).reshape(
        (batch, nb * c) + os.shape[2:3] + os.shape[4:])
    return o[:, :t], state


class KimiDeltaMixer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 state_rows: Optional[Tuple] = None) -> jax.Array:
        """x: (B, T, d_model), the layer's pre-norm output; positions:
        (B, T). state_rows = (slots, valid) as models/ssm.Mamba2Mixer
        takes them."""
        # Late import: transformer.py is this module's importer's.
        from skypilot_tpu.models.transformer import dense_general
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        batch, t = x.shape[:2]
        h, d = cfg.num_heads, cfg.head_dim
        ch = 3 * h * d
        keep = cfg.kda_conv - 1
        slots, valid = state_rows if state_rows is not None else (None,
                                                                  None)
        kernel = (cfg.decode and slots is None
                  and takes_state_kernel(cfg, t))
        heads = lambda name: dense_general(
            cfg, (h, d), ('embed', 'heads', 'qkv_dim'), name)
        qkv = jnp.concatenate(
            [heads(n)(x).reshape(batch, t, h * d)
             for n in ('q_proj', 'k_proj', 'v_proj')], axis=-1)
        f = heads('f_proj')(x)
        beta = jax.nn.sigmoid(dense_general(
            cfg, h, ('embed', 'heads'), 'b_proj')(x).astype(F32))
        a_log = self.param('A_log', nn.with_logical_partitioning(
            nn.initializers.zeros, ('heads',)), (h,), F32)
        dt_bias = self.param('dt_bias', nn.with_logical_partitioning(
            nn.initializers.zeros, ('heads', None)), (h, d), F32)
        conv_w = self.param('conv_kernel', nn.with_logical_partitioning(
            nn.initializers.normal(stddev=cfg.kda_conv ** -0.5),
            (None, 'mlp')), (cfg.kda_conv, ch),
            jnp.dtype(cfg.param_dtype))

        # ---- the state this call starts from ----
        rows = cfg.state_slots or batch
        if cfg.decode:
            if cfg.ssm_state_dtype != 'float32':
                raise NotImplementedError(
                    'the KDA state is held in float32 only')
            state_var = self.variable(
                'cache', SCAN_STATE,
                lambda: nn.with_logical_partitioning(
                    jnp.zeros, ('batch', None, None, None))(
                        (rows, h, d, d), F32))
            # the carried inputs flat, (rows, keep x ch): a slot's row
            # then tiles the chip's lanes whole, where (keep, ch) would
            # be relaid on its way into every program
            conv_var = self.variable(
                'cache', CONV_STATE,
                lambda: nn.with_logical_partitioning(
                    jnp.zeros, ('batch', None))(
                        (rows, keep * ch), dtype))
            state_leaf, state_box = _unbox(state_var)
            conv_leaf, conv_box = _unbox(conv_var)
            if slots is None and rows != batch:
                raise ValueError(
                    f'the state leaves hold {rows} rows and the call '
                    f'{batch}: pass state_rows with each row\'s slot')
            # Late import: only a decoding model loads the module.
            from skypilot_tpu.models import cache_carry
            layer = cache_carry.current_layer()
            if not kernel:
                s_old = cache_carry.read_rows(state_leaf, slots, layer)
            c_old = cache_carry.read_rows(conv_leaf, slots, layer)
            fresh = positions[:, 0] == 0
            if not kernel:
                s0 = jnp.where(fresh[:, None, None, None], 0.0, s_old)
            c0 = jnp.where(fresh[:, None], 0, c_old)
        else:
            s0 = jnp.zeros((batch, h, d, d), F32)
            c0 = jnp.zeros((batch, keep * ch), dtype)
        if valid is None:
            valid = jnp.full((batch,), t, jnp.int32)
        real = jnp.arange(t)[None, :] < valid[:, None]       # (B, T)

        # ---- causal depthwise convolution over q, k and v ----
        tap = lambda i: c0[:, None, i * ch:(i + 1) * ch]
        ext = jnp.concatenate([tap(i) for i in range(keep)]
                              + [qkv.astype(dtype)], axis=1)
        acc = sum(conv_w[i].astype(F32) * ext[:, i:i + t].astype(F32)
                  for i in range(cfg.kda_conv))
        qkv = nn.silu(acc).reshape(batch, t, 3, h, d)
        # the last `keep` REAL inputs: history counts for short prompts
        # (a decode step's real row has one: the history moves by one)
        if t == 1:
            c1 = jnp.concatenate([c0[:, ch:], ext[:, keep]], axis=-1)
        else:
            c1 = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
                e, n, keep, axis=0))(ext, valid).reshape(batch, keep * ch)
        q = l2_normalize(qkv[:, :, 0]) * d ** -0.5
        k = l2_normalize(qkv[:, :, 1])
        v = qkv[:, :, 2]
        g = jnp.where(real[..., None, None],
                      kda_gate(f, a_log, dt_bias, cfg.kda_gate_bound), 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        if kernel:
            o, state_leaf = kda_step_in_place(
                state_leaf, layer, valid > 0, fresh, q[:, 0], k[:, 0],
                v[:, 0], g[:, 0], beta[:, 0],
                interpret=cfg.decode_kernel == 'pallas_interpret')
            o = o[:, None]
        elif t == 1:
            o, s1 = kda_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0])
            o = o[:, None]
        else:
            o, s1 = kda_chunked(s0, q, k, v, g, beta, CHUNK)

        if cfg.decode:
            touched = valid > 0
            if not kernel:
                s1 = jnp.where(touched[:, None, None, None], s1, s_old)
            c1 = jnp.where(touched[:, None], c1, c_old)
            _rebox(state_var, state_box, state_leaf if kernel
                   else cache_carry.write_rows(state_leaf, slots, layer,
                                               s1))
            _rebox(conv_var, conv_box, cache_carry.write_rows(
                conv_leaf, slots, layer, c1))

        # ---- a head's norm, the output gate, the output projection ----
        norm_w = self.param('o_norm', nn.with_logical_partitioning(
            nn.initializers.ones, ('qkv_dim',)), (d,),
            jnp.dtype(cfg.param_dtype)).astype(F32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                       keepdims=True) + cfg.norm_eps)
        o = o * norm_w * jax.nn.sigmoid(heads('g_proj')(x).astype(F32))
        return dense_general(cfg, cfg.d_model,
                             ('heads', 'qkv_dim', 'embed'), 'o_proj',
                             axis=(-2, -1))(o.astype(dtype))
