"""Plain reference for the Falcon-H1 decoder: in every block a Mamba-2
state-space mixer in parallel with grouped-query attention on one shared
RMSNorm, both added into the residual together, then a SiLU-gated MLP on
a norm of its own; the µP forward multipliers of the published config; a
final norm and an untied unembedding.

Straight `jax.numpy` in float32 at `highest` matmul precision. The
mixer's scan is a `lax.scan` over positions: one (heads, head, state)
state, advanced one position at a time. No chunks, no cache, no batching
tricks. It imports nothing of the program under test and is handed
weights made by the benchmark (perf/weights.py) under this file's own
names (families/falcon_h1.py maps the program's paths to them), one
layer at a time through `layer_weights(l)` and the whole leaves through
`whole(name)` ('embed', 'final_norm', 'lm_head').

With n1, n2, nf RMSNorms (w * x / rms(x)), sizes from the published
config's own keys (d = hidden_size, d_ssm = mamba_d_ssm, H = mamba_n_heads
of mamba_d_head, N = mamba_d_state, G = mamba_n_groups, K = mamba_d_conv):

    x0      = embedding_multiplier * E[token]
    u       = n1(x)
    p       = (ssm_in_multiplier * u) @ W_in            # d -> 2 d_ssm + 2 G N + H
    p       = p * m,  m = ssm_multipliers[0..4] over [z d_ssm | x d_ssm | B G N | C G N | dt H]
    z, xBC, dt = split(p, [d_ssm, d_ssm + 2 G N, H])
    xBC     = silu(conv_b + sum_{k<K} conv_w[k] * xBC[t - (K-1) + k])   # causal, depthwise
    xs, B, C = split(xBC, [d_ssm, G N, G N]);  group g serves heads g H/G .. (g+1) H/G - 1
    delta_h = softplus(dt_h + dt_bias_h);  a_h = exp(-exp(A_log_h) * delta_h)
    S_h[t]  = a_h * S_h[t-1] + delta_h * outer(xs_h[t], B_g[t]),  S[-1] = 0
    y_h[t]  = S_h[t] @ C_g[t] + D_h * xs_h[t]
    y       = y * silu(z);  y = norm_w * y / rms over each group of d_ssm / G   # gate first
    mix     = ssm_out_multiplier * (y @ W_out)
    q, k, v = (attention_in_multiplier * u) @ Wq, Wk, Wv;  k = key_multiplier * k;  rope(q), rope(k)
    att     = attention_out_multiplier * (softmax(q k^T / sqrt(head_dim), causal) v @ Wo)
    h       = x + mix + att
    out     = h + mlp_multipliers[1] * ((silu(mlp_multipliers[0] * (n2(h) @ Wg)) * (n2(h) @ Wu)) @ Wd)
    logits  = lm_head_multiplier * (nf(x_L) @ W_head)

`mamba_norm_before_gate` true norms first and gates after; with
`mamba_rms_norm` false the gate alone is applied.

Assumed (no network here; the configuration file lists them under
`assumed`): the order of the input projection's segments [z | x | B | C |
dt], the MLP's first multiplier inside the gate's activation, and the
gated norm's grouping (one RMS a B/C group), as the family's public
modelling code has them.

Departures of the PROGRAM from these lines (skypilot_tpu/models/ssm.py),
none of them in the mathematics: prefill computes the scan in blocks of
`mamba_chunk_size` positions (the chunked SSD form: the same sums in
another order); activations are in the configuration's compute type with
float32 accumulation, the scan state in float32; a multiplier is applied
in float32 and the product rounded once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024
HEAD_ROWS = 256


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (T, H, D); positions: (T,). Rotates the pair (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (float(theta) ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attend_group(q, k, v, q_pos, k_pos):
    """q: (Tq, R, D) for one kv head's R query heads; k, v: (Tk, D)."""
    scores = _mm('qrd,kd->rqk', q, k) * (q.shape[-1] ** -0.5)
    mask = k_pos[None, :] <= q_pos[:, None]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return _mm('rqk,kd->qrd', jax.nn.softmax(scores, axis=-1), v)


def attention(q, k, v, positions):
    """One sequence. q: (T, H, D); k, v: (T, KV, D). Causal. Walks kv
    heads and blocks of queries, so that the scores of one block only
    are alive."""
    t, h, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, d).transpose(1, 0, 2, 3)  # KV,T,R,D
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    nblk = -(-t // Q_BLOCK)
    pad = nblk * Q_BLOCK - t
    q_pos = jnp.pad(positions, (0, pad)).reshape(nblk, -1)

    def one_head(args):
        qh, kh, vh = args
        qh = jnp.pad(qh, ((0, pad), (0, 0), (0, 0)))
        qh = qh.reshape(nblk, -1, qh.shape[-2], d)
        out = jax.lax.map(
            lambda a: _attend_group(a[0], kh, vh, a[1], positions),
            (qh, q_pos))
        return out.reshape(nblk * Q_BLOCK, -1, d)[:t]

    out = jax.lax.map(one_head, (qg, kg, vg))            # KV,T,R,D
    return out.transpose(1, 0, 2, 3).reshape(t, h, d)


def sizes(cfg: dict) -> dict:
    heads, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    d_ssm = cfg.get('mamba_d_ssm') or cfg['mamba_expand'] * \
        cfg['hidden_size']
    assert d_ssm == heads * p, (d_ssm, heads, p)
    return {'d_ssm': d_ssm, 'heads': heads, 'p': p,
            'n': cfg['mamba_d_state'], 'g': cfg['mamba_n_groups'],
            'taps': cfg['mamba_d_conv']}


def segment_multipliers(cfg: dict):
    s = sizes(cfg)
    gn = s['g'] * s['n']
    widths = (s['d_ssm'], s['d_ssm'], gn, gn, s['heads'])
    return jnp.concatenate([jnp.full((w,), m, F32) for w, m in
                            zip(widths, cfg['ssm_multipliers'])])


def mixer(u, w, cfg):
    """The state-space mixer on one sequence. u: (T, hidden), normed."""
    s = sizes(cfg)
    d_ssm, heads, p, n, g, taps = (s['d_ssm'], s['heads'], s['p'],
                                   s['n'], s['g'], s['taps'])
    gn, t = g * n, u.shape[0]
    proj = _mm('td,df->tf', cfg['ssm_in_multiplier'] * u, w['w_in'])
    if 'b_in' in w:
        proj = proj + w['b_in']
    proj = proj * segment_multipliers(cfg)
    z, xbc, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn],
                  proj[:, 2 * d_ssm + 2 * gn:])
    # causal depthwise convolution: tap k reads position t - (taps-1) + k
    ext = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    acc = sum(w['conv_w'][k] * ext[k:k + t] for k in range(taps))
    if 'conv_b' in w:
        acc = acc + w['conv_b']
    xbc = jax.nn.silu(acc)
    xs = xbc[:, :d_ssm].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(t, g, n),
                   heads // g, axis=1)                       # (T, H, N)
    c = jnp.repeat(xbc[:, d_ssm + gn:].reshape(t, g, n), heads // g,
                   axis=1)
    delta = jax.nn.softplus(dt + w['dt_bias'])               # (T, H)
    decay = jnp.exp(-jnp.exp(w['a_log']) * delta)

    def step(state, args):
        x_t, b_t, c_t, delta_t, a_t = args
        state = (a_t[:, None, None] * state
                 + delta_t[:, None, None] * x_t[:, :, None]
                 * b_t[:, None, :])                          # (H, P, N)
        y_t = jnp.sum(state * c_t[:, None, :], axis=-1)
        return state, y_t + w['d_skip'][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), F32),
                        (xs, b, c, delta, decay))
    y = y.reshape(t, d_ssm)
    gate = jax.nn.silu(z)
    if cfg.get('mamba_rms_norm', True):
        before = cfg.get('mamba_norm_before_gate', False)
        if not before:
            y = y * gate
        yg = y.reshape(t, g, d_ssm // g)
        var = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
        y = (yg * jax.lax.rsqrt(var + cfg['rms_norm_eps'])).reshape(
            t, d_ssm) * w['ssm_norm']
        if before:
            y = y * gate
    else:
        y = y * gate
    out = _mm('tf,fd->td', y, w['w_out'])
    if 'b_out' in w:
        out = out + w['b_out']
    return cfg['ssm_out_multiplier'] * out


def layer_row(x, positions, w, cfg):
    """One decoder layer on one sequence. x: (T, hidden)."""
    eps = cfg['rms_norm_eps']
    u = rms_norm(x, w['attn_norm'], eps)
    mix = mixer(u, w, cfg)
    ua = cfg['attention_in_multiplier'] * u
    q = _mm('td,dhk->thk', ua, w['wq'])
    k = cfg['key_multiplier'] * _mm('td,dhk->thk', ua, w['wk'])
    v = _mm('td,dhk->thk', ua, w['wv'])
    q = rope(q, positions, cfg['rope_theta'])
    k = rope(k, positions, cfg['rope_theta'])
    att = cfg['attention_out_multiplier'] * _mm(
        'thk,hkd->td', attention(q, k, v, positions), w['wo'])
    h = x + mix + att
    hn = rms_norm(h, w['mlp_norm'], eps)
    gate_m, down_m = cfg['mlp_multipliers']
    gate = jax.nn.silu(gate_m * _mm('td,df->tf', hn, w['w_gate']))
    up = _mm('td,df->tf', hn, w['w_up'])
    return h + down_m * _mm('tf,fd->td', gate * up, w['w_down'])


CFG_KEYS = ('rms_norm_eps', 'rope_theta', 'hidden_size', 'mamba_n_heads',
            'mamba_d_head', 'mamba_d_ssm', 'mamba_expand',
            'mamba_d_state', 'mamba_n_groups', 'mamba_d_conv',
            'mamba_rms_norm', 'mamba_norm_before_gate',
            'ssm_in_multiplier', 'ssm_multipliers', 'ssm_out_multiplier',
            'attention_in_multiplier', 'key_multiplier',
            'attention_out_multiplier', 'mlp_multipliers')


def cfg_key(cfg: dict):
    """The keys the layer reads, hashable (a jit's static argument)."""
    val = lambda v: tuple(v) if isinstance(v, list) else v
    return tuple((k, val(cfg[k])) for k in CFG_KEYS if k in cfg)


@functools.partial(jax.jit, static_argnames=('key',))
def _layer_rows(x, w, key):
    cfg = dict(key)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return jax.lax.map(lambda r: layer_row(r, pos, w, cfg), x)


def _head_rows(rows, scale, lm_head, eps, multiplier):
    return multiplier * _mm('nd,dv->nv', rms_norm(rows, scale, eps),
                            lm_head)


_head = jax.jit(_head_rows, static_argnames=('eps', 'multiplier'))


@functools.partial(jax.jit, static_argnames=('eps', 'multiplier'),
                   donate_argnums=(0,))
def _head_into(out, start, rows, scale, lm_head, eps, multiplier):
    """Rows start .. of `out` filled in place (the buffer is donated)."""
    return jax.lax.dynamic_update_slice(
        out, _head_rows(rows, scale, lm_head, eps, multiplier),
        (start, 0))


def hidden_states(tokens, whole, layer_weights, num_layers: int,
                  cfg: dict):
    """tokens: (N, T) ids; whole('embed'): (V, hidden) float32;
    layer_weights(l) gives layer l's float32 weights. Returns (N, T,
    hidden) before the final norm. One layer's weights are alive at a
    time."""
    x = cfg['embedding_multiplier'] * jnp.take(
        whole('embed'), tokens, axis=0).astype(F32)
    for l in range(num_layers):
        x = _layer_rows(x, layer_weights(l), cfg_key(cfg))
    return x


def logits_at(hidden_rows, whole, cfg: dict):
    """hidden_rows: (M, hidden) -> (M, V) float32 logits, written a block
    of rows at a time into one buffer: 1,024 rows x 261,120 logits are
    1.07 GB beside a 5.35 GB float32 head, and a list of blocks joined at
    the end would hold the whole result twice."""
    scale, head = whole('final_norm'), whole('lm_head')
    kw = dict(eps=cfg['rms_norm_eps'],
              multiplier=cfg['lm_head_multiplier'])
    m = hidden_rows.shape[0]
    if m <= HEAD_ROWS:
        return _head(hidden_rows, scale, head, **kw)
    out = jnp.zeros((m, head.shape[-1]), F32)
    for i in range(0, m, HEAD_ROWS):
        out = _head_into(out, jnp.int32(i), hidden_rows[i:i + HEAD_ROWS],
                         scale, head, **kw)
    return out


# ---- the control: the same weights, held in the next precision down --
# (jitted: op by op, rounding a 5.35 GB head would hold it three times)

@functools.partial(jax.jit, static_argnames=('contract_axes',))
def round_int8(w, contract_axes: int):
    """Weight-only int8 with one float32 scale per output channel: the
    leading `contract_axes` axes are contracted and share a scale."""
    axes = tuple(range(contract_axes))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


@functools.partial(jax.jit, static_argnames=('contract_axes',))
def round_fp8(w, contract_axes: int):
    """Weight-only float8 (e4m3) with one scale per output channel."""
    axes = tuple(range(contract_axes))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


# The matmul weights; the decay, the step bias, the skip, the
# convolution and the norms stay as they are.
CONTRACT_AXES = {'wq': 1, 'wk': 1, 'wv': 1, 'wo': 2, 'w_gate': 1,
                 'w_up': 1, 'w_down': 1, 'w_in': 1, 'w_out': 1,
                 'lm_head': 1}


def lower_precision(weights: dict, how: str) -> dict:
    """The matmul weights of `weights` rounded as `how` ('int8' | 'fp8')
    says."""
    fn = {'int8': round_int8, 'fp8': round_fp8}[how]
    return {k: (fn(v, CONTRACT_AXES[k]) if k in CONTRACT_AXES else v)
            for k, v in weights.items()}
