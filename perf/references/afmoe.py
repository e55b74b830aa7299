"""Plain reference for the AFMoE decoder (`arcee-ai/Trinity-Large-Preview`,
`model_type` afmoe): leading dense layers and then expert layers, window
and full attention mixed by layer, a sigmoid router with a selection
bias, one shared expert, sandwich norms, q/k norms and a gated attention
output; a final norm and an untied unembedding.

Straight `jax.numpy` in float32 at `highest` matmul precision, one
sequence at a time, no cache, no batching, no kernels, no sorting: the
expert layer walks the held experts one after another, every token
through each, and keeps what the router weighed. It imports nothing of
the program under test and is handed weights made by the benchmark
(perf/weights.py) under this file's own names (families/afmoe.py maps
the program's paths to them): `layer_weights(i)` gives entry i of BOTH
stacked groups, the dense group's names prefixed `d_`, and layer l of
the model is entry l of the dense group while l < num_dense_layers and
entry l - num_dense_layers of the expert group after; `whole(name)`
gives 'embed', 'final_norm', 'lm_head'.

With n RMSNorms (w * x / rms(x)), d = hidden_size, H x D query heads, KV
key/value heads, E = router_width experts scored, k = num_experts_per_tok
chosen, and the held experts first_expert .. first_expert + experts_held - 1:

    x0     = sqrt(d) * Emb[token]                                  # mup_enabled
    u      = n_in(x)
    q, k, v = u @ Wq, u @ Wk, u @ Wv;  q, k = n_q(q), n_k(k)       # over D, a head
    q, k   = rope(q), rope(k)            on a sliding layer only; a full layer has none
    a      = softmax(q k^T / sqrt(D), causal, and on a sliding layer j > i - window) v
    att    = (a * sigmoid(u @ Wgate)) @ Wo                         # Wgate: d -> H x D
    h      = x + n_post_attn(att)
    u2     = n_pre_mlp(h)
    dense layer:   y = (silu(u2 @ Wg) * (u2 @ Wu)) @ Wd            # width intermediate_size
    expert layer:  s = sigmoid(u2 @ R)                             # float32, all E
                   C = the k experts with the largest s + b        # b: expert_bias, selection only
                   w_e = route_scale * s_e / sum_{c in C} s_c      # e in C (route_norm), else 0
                   y = shared(u2) + sum over HELD e of w_e * expert_e(u2)
    out    = h + n_post_mlp(y)
    logits = n_f(x_L) @ W_head

What the experts held on other chips would have added is left out, as it
is in the program: the partial sum is what goes on (the guide's cut).

Assumed (no network here; the configuration file lists them under
`assumed`): the gate's width and place (on the heads' output, from the
attention's own input), q/k norms over the head size before rotary,
rotary on sliding layers only, where the four norms sit, the embedding's
multiplier, the bias used for selection only.

Departures of the PROGRAM from these lines (skypilot_tpu/models/
layer_pattern.py, moe.py), none in the mathematics: activations and
weights in the configuration's compute type with float32 accumulation,
router scores in float32; the embedding's multiplier is rounded to the
compute type before it is applied (sqrt(3072) = 55.4256 is 55.5 in
bfloat16); a full layer applies rotary at angle zero, the identity; the
held (token, choice) pairs are sorted by expert and multiplied in
groups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024
HEAD_ROWS = 1024


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


def _attend_group(q, k, v, q_pos, k_pos, window):
    """q: (Tq, R, D) for one kv head's R query heads; k, v: (Tk, D)."""
    scores = _mm('qrd,kd->rqk', q, k) * (q.shape[-1] ** -0.5)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return _mm('rqk,kd->qrd', jax.nn.softmax(scores, axis=-1), v)


def attention(q, k, v, positions, window):
    """One sequence. q: (T, H, D); k, v: (T, KV, D). Causal, and a
    query sees the last `window` keys only (0: all). Walks kv heads and
    blocks of queries, so that one block's scores only are alive."""
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
            lambda a: _attend_group(a[0], kh, vh, a[1], positions,
                                    window),
            (qh, q_pos))
        return out.reshape(nblk * Q_BLOCK, -1, d)[:t]

    out = jax.lax.map(one_head, (qg, kg, vg))            # KV,T,R,D
    return out.transpose(1, 0, 2, 3).reshape(t, h, d)


def swiglu(u, w_gate, w_up, w_down):
    return _mm('tf,fd->td', jax.nn.silu(_mm('td,df->tf', u, w_gate))
               * _mm('td,df->tf', u, w_up), w_down)


def route(u, w, cfg):
    """(T, E) float32: the weight each token gives each expert the
    router scores: route_scale * s_e / (sum of the chosen s) for its k
    chosen ones (chosen by s + bias), 0 for the rest."""
    s = jax.nn.sigmoid(_mm('td,de->te', u, w['router']))
    select = s + w['expert_bias'] if 'expert_bias' in w else s
    _, chosen = jax.lax.top_k(select, cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get('route_norm', True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * cfg.get('route_scale', 1.0)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def experts(u, w, cfg):
    """shared(u) + the held experts' part of the routed sum: one expert
    after another, every token through each."""
    first, held = cfg['first_expert'], cfg['experts_held']
    mine = route(u, w, cfg)[:, first:first + held]         # (T, held)

    def one(acc, args):
        w_gate, w_up, w_down, weight = args
        return acc + weight[:, None] * swiglu(u, w_gate, w_up, w_down), \
            None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w['w_gate'], w['w_up'], w['w_down'], mine.T))
    return swiglu(u, w['s_gate'], w['s_up'], w['s_down']) + routed


def layer_row(x, positions, w, cfg, dense: bool, window: int,
              rotary: bool):
    """One decoder layer on one sequence. x: (T, hidden)."""
    eps = cfg['rms_norm_eps']
    u = rms_norm(x, w['attn_norm'], eps)
    q = rms_norm(_mm('td,dhk->thk', u, w['wq']), w['q_norm'], eps)
    k = rms_norm(_mm('td,dhk->thk', u, w['wk']), w['k_norm'], eps)
    v = _mm('td,dhk->thk', u, w['wv'])
    if rotary:
        q = rope(q, positions, cfg['rope_theta'])
        k = rope(k, positions, cfg['rope_theta'])
    att = attention(q, k, v, positions, window)
    att = att * jax.nn.sigmoid(_mm('td,dhk->thk', u, w['w_attn_gate']))
    h = x + rms_norm(_mm('thk,hkd->td', att, w['wo']),
                     w['post_attn_norm'], eps)
    u2 = rms_norm(h, w['mlp_norm'], eps)
    y = (swiglu(u2, w['w_gate'], w['w_up'], w['w_down']) if dense
         else experts(u2, w, cfg))
    return h + rms_norm(y, w['post_mlp_norm'], eps)


CFG_KEYS = ('rms_norm_eps', 'rope_theta', 'num_experts_per_tok',
            'route_norm', 'route_scale', 'first_expert', 'experts_held')


def cfg_key(cfg: dict):
    """The keys the layer reads, hashable (a jit's static argument)."""
    return tuple((k, cfg[k]) for k in CFG_KEYS if k in cfg)


@functools.partial(jax.jit, static_argnames=('key', 'dense', 'window',
                                             'rotary'))
def _layer_rows(x, w, key, dense, window, rotary):
    cfg = dict(key)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return jax.lax.map(
        lambda r: layer_row(r, pos, w, cfg, dense, window, rotary), x)


def layer_plan(cfg: dict) -> list:
    """(dense, entry in its group, window, rotary) for every layer."""
    n_dense = cfg['num_dense_layers']
    plan = []
    for l, kind in enumerate(cfg['layer_types']):
        sliding = kind == 'sliding_attention'
        plan.append((l < n_dense, l if l < n_dense else l - n_dense,
                     cfg['sliding_window'] if sliding else 0, sliding))
    return plan


def group_weights(weights: dict, dense: bool) -> dict:
    """The names of one group out of `layer_weights(i)`'s dict: the
    dense group's are prefixed `d_`."""
    if dense:
        return {n[2:]: a for n, a in weights.items() if n.startswith('d_')}
    return {n: a for n, a in weights.items() if not n.startswith('d_')}


def hidden_states(tokens, whole, layer_weights, num_layers: int,
                  cfg: dict):
    """tokens: (N, T) ids; whole('embed'): (V, hidden) float32;
    layer_weights(i): entry i of both groups, float32. Returns (N, T,
    hidden) before the final norm. One layer's weights are alive at a
    time."""
    plan = layer_plan(cfg)
    assert len(plan) == num_layers, (len(plan), num_layers)
    x = (cfg['hidden_size'] ** 0.5) * jnp.take(
        whole('embed'), tokens, axis=0).astype(F32)
    for dense, entry, window, rotary in plan:
        w = group_weights(layer_weights(entry), dense)
        x = _layer_rows(x, w, cfg_key(cfg), dense, window, rotary)
    return x


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(rows, scale, lm_head, eps):
    return _mm('nd,dv->nv', rms_norm(rows, scale, eps), lm_head)


def logits_at(hidden_rows, whole, cfg: dict):
    """hidden_rows: (M, hidden) -> (M, V) float32 logits, a block of
    rows at a time."""
    scale, head = whole('final_norm'), whole('lm_head')
    blocks = [_head(hidden_rows[i:i + HEAD_ROWS], scale, head,
                    eps=cfg['rms_norm_eps'])
              for i in range(0, hidden_rows.shape[0], HEAD_ROWS)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)


# ---- the control: the same weights, held in the next precision down --

@functools.partial(jax.jit, static_argnames=('lead', 'contract'))
def round_int8(w, lead: int, contract: int):
    """Weight-only int8 with one float32 scale per output channel: the
    `contract` axes after the `lead` leading ones (the expert axis) are
    contracted and share a scale."""
    axes = tuple(range(lead, lead + contract))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


@functools.partial(jax.jit, static_argnames=('lead', 'contract'))
def round_fp8(w, lead: int, contract: int):
    """Weight-only float8 (e4m3) with one scale per output channel."""
    axes = tuple(range(lead, lead + contract))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


# The matmul weights, as (leading axes kept, axes contracted); the
# router, its bias and the norms stay as they are.
_ATTN = {'wq': (0, 1), 'wk': (0, 1), 'wv': (0, 1), 'w_attn_gate': (0, 1),
         'wo': (0, 2)}
CONTRACT_AXES = dict(
    _ATTN, **{f'd_{n}': a for n, a in _ATTN.items()},
    **{n: (0, 1) for n in ('d_w_gate', 'd_w_up', 'd_w_down', 's_gate',
                           's_up', 's_down', 'lm_head')},
    **{n: (1, 1) for n in ('w_gate', 'w_up', 'w_down')})


def lower_precision(weights: dict, how: str) -> dict:
    """The matmul weights of `weights` rounded as `how` ('int8' | 'fp8')
    says."""
    fn = {'int8': round_int8, 'fp8': round_fp8}[how]
    return {k: (fn(v, *CONTRACT_AXES[k]) if k in CONTRACT_AXES else v)
            for k, v in weights.items()}
