"""Plain reference for the Llama-shaped decoder: RMSNorm, rotary
positions (half-split pairs, as the published checkpoints), grouped-query
attention with an optional sliding window and optional Q/K/V biases, a
SiLU-gated MLP, a final norm and an untied unembedding. Optional low-rank
adapters on the query and value projections.

Straight `jax.numpy` in float32 at `highest` matmul precision: no
kernels, no cache, no batching tricks. It imports nothing of the program
under test and is handed weights made by the benchmark (perf/weights.py),
one layer at a time, so that a 7B model fits beside nothing else.

Sizes come from a dict with the published config's own keys:
hidden_size, num_attention_heads, num_key_value_heads, head_dim,
intermediate_size, vocab_size, rms_norm_eps, rope_theta, sliding_window
(0 = none).

Departures from the published description: none in the mathematics. The
adapters follow y = W x + (alpha / r) B (A x), as LoRA (arXiv:2106.09685)
states it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (T, H, D); positions: (T,). Rotates the pair (i, i + D/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
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
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _mm('rqk,kd->qrd', probs, v)


def attention(q, k, v, positions, window):
    """One sequence. q: (T, H, D); k, v: (T, KV, D). Causal, windowed.
    Walks kv heads and blocks of queries so that the scores of only one
    block are alive, and remembers nothing of a block for the backward
    pass but its inputs."""
    t, h, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, d).transpose(1, 0, 2, 3)  # KV,T,R,D
    kg = k.transpose(1, 0, 2)
    vg = v.transpose(1, 0, 2)
    nblk = -(-t // Q_BLOCK)
    pad = nblk * Q_BLOCK - t
    q_pos = jnp.pad(positions, (0, pad), constant_values=0)
    q_pos = q_pos.reshape(nblk, -1)

    @jax.checkpoint
    def one_block(qb, pb, kh, vh):
        return _attend_group(qb, kh, vh, pb, positions, window)

    def one_head(args):
        qh, kh, vh = args
        qh = jnp.pad(qh, ((0, pad), (0, 0), (0, 0)))
        qh = qh.reshape(nblk, -1, qh.shape[-2], d)
        out = jax.lax.map(lambda a: one_block(a[0], a[1], kh, vh),
                          (qh, q_pos))
        return out.reshape(nblk * Q_BLOCK, -1, d)[:t]

    out = jax.lax.map(one_head, (qg, kg, vg))            # KV,T,R,D
    return out.transpose(1, 0, 2, 3).reshape(t, h, d)


def layer_row(x, positions, w, cfg, lora=None, lora_scale=1.0):
    """One decoder layer on one sequence. x: (T, hidden)."""
    eps = cfg['rms_norm_eps']
    h = rms_norm(x, w['attn_norm'], eps)
    q = _mm('td,dhk->thk', h, w['wq'])
    k = _mm('td,dhk->thk', h, w['wk'])
    v = _mm('td,dhk->thk', h, w['wv'])
    if lora is not None:
        q = q + lora_scale * _mm('tr,rhk->thk',
                                 _mm('td,dr->tr', h, lora['q_a']),
                                 lora['q_b'])
        v = v + lora_scale * _mm('tr,rhk->thk',
                                 _mm('td,dr->tr', h, lora['v_a']),
                                 lora['v_b'])
    if 'bq' in w:
        q, k, v = q + w['bq'], k + w['bk'], v + w['bv']
    q = rope(q, positions, cfg['rope_theta'])
    k = rope(k, positions, cfg['rope_theta'])
    a = attention(q, k, v, positions, cfg.get('sliding_window') or 0)
    x = x + _mm('thk,hkd->td', a, w['wo'])
    h = rms_norm(x, w['mlp_norm'], eps)
    gate = _mm('td,df->tf', h, w['w_gate'])
    up = _mm('td,df->tf', h, w['w_up'])
    return x + _mm('tf,fd->td', jax.nn.silu(gate) * up, w['w_down'])


@functools.partial(jax.jit, static_argnames=('cfg_key',))
def _layer_rows(x, w, cfg_key):
    cfg = dict(cfg_key)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return jax.lax.map(lambda r: layer_row(r, pos, w, cfg), x)


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(rows, scale, lm_head, eps):
    return _mm('nd,dv->nv', rms_norm(rows, scale, eps), lm_head)


def cfg_key(cfg: dict):
    keys = ('rms_norm_eps', 'rope_theta', 'sliding_window')
    return tuple((k, cfg.get(k) or 0) for k in keys)


def hidden_states(tokens, embed, layer_weights, num_layers: int,
                  cfg: dict):
    """tokens: (N, T) ids; embed: (V, hidden) float32; layer_weights(l)
    gives layer l's float32 weights. Returns (N, T, hidden) before the
    final norm. One layer's weights are alive at a time."""
    x = jnp.take(embed, tokens, axis=0).astype(F32)
    for l in range(num_layers):
        x = _layer_rows(x, layer_weights(l), cfg_key(cfg))
    return x


def logits_at(hidden_rows, final_scale, lm_head, cfg: dict):
    """hidden_rows: (M, hidden) -> (M, V) float32 logits."""
    return _head(hidden_rows, final_scale, lm_head,
                 eps=cfg['rms_norm_eps'])


# ---- the control: the same weights, held in the next precision down --

def round_int8(w, contract_axes: int):
    """Weight-only int8 with one float32 scale per output channel: the
    leading `contract_axes` axes are contracted and share a scale."""
    axes = tuple(range(contract_axes))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def round_fp8(w, contract_axes: int):
    """Weight-only float8 (e4m3) with one scale per output channel."""
    axes = tuple(range(contract_axes))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


CONTRACT_AXES = {'wq': 1, 'wk': 1, 'wv': 1, 'wo': 2, 'w_gate': 1,
                 'w_up': 1, 'w_down': 1, 'lm_head': 1}


def lower_precision(weights: dict, how: str) -> dict:
    """The matmul weights of `weights` rounded as `how` ('int8' | 'fp8')
    says; norms and biases stay."""
    fn = {'int8': round_int8, 'fp8': round_fp8}[how]
    return {k: (fn(v, CONTRACT_AXES[k]) if k in CONTRACT_AXES else v)
            for k, v in weights.items()}


# ---- training: loss, the adapters' gradients, clip and AdamW ---------

@functools.partial(jax.jit, static_argnames=('cfg_key', 'lora_scale'))
def _layer_rows_lora(x, w, lora, cfg_key, lora_scale):
    cfg = dict(cfg_key)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return jax.lax.map(
        lambda r: layer_row(r, pos, w, cfg, lora, lora_scale), x)


@functools.partial(jax.jit, static_argnames=('cfg_key', 'lora_scale'))
def _layer_rows_vjp(x, w, lora, dy, cfg_key, lora_scale):
    """Gradients of one layer with respect to its input and its adapters
    (not its frozen weights), row after row."""
    cfg = dict(cfg_key)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)

    def one(args):
        xr, dyr = args
        _, vjp = jax.vjp(
            lambda a, lo: layer_row(a, pos, w, cfg, lo, lora_scale),
            xr, lora)
        return vjp(dyr)

    dx, dlora = jax.lax.map(one, (x, dy))
    return dx, jax.tree.map(lambda g: jnp.sum(g, axis=0), dlora)


@functools.partial(jax.jit, static_argnames=('eps',))
def _head_loss_rows(x, scale, lm_head, targets, mask, eps):
    """Summed next-token cross-entropy over the tokens that `mask` keeps,
    and its gradient with respect to the hidden states, row after row."""
    def one_row(h, t, m):
        logits = _mm('td,dv->tv', rms_norm(h, scale, eps), lm_head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * m)

    def one(args):
        h, t, m = args
        return jax.value_and_grad(one_row)(h, t, m)

    losses, dx = jax.lax.map(one, (x, targets, mask))
    return jnp.sum(losses), dx


def lora_loss_and_grads(inputs, targets, embed, layer_weights, lora_of,
                        final_scale, lm_head, num_layers: int, cfg: dict,
                        lora_scale: float, mask=None):
    """Mean next-token loss over the tokens of the batch (all of them, or
    those that `mask`, (B, T) of 0 and 1, keeps) and its gradient with
    respect to every layer's adapters. inputs, targets: (B, T).
    lora_of(l) gives layer l's {'q_a','q_b','v_a','v_b'}. Returns
    (loss, {l: grads of lora_of(l)})."""
    key = cfg_key(cfg)
    if mask is None:
        mask = jnp.ones(inputs.shape, F32)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    x = jnp.take(embed, inputs, axis=0).astype(F32)
    saved = []
    for l in range(num_layers):
        saved.append(x)
        x = _layer_rows_lora(x, layer_weights(l), lora_of(l), key,
                             lora_scale)
    total, dx = _head_loss_rows(x, final_scale, lm_head, targets,
                                mask.astype(F32), eps=cfg['rms_norm_eps'])
    dx = dx / count
    grads = {}
    for l in reversed(range(num_layers)):
        dx, grads[l] = _layer_rows_vjp(saved.pop(), layer_weights(l),
                                       lora_of(l), dx, key, lora_scale)
    return total / count, grads


def learning_rate(count: int, hp: dict) -> float:
    """Linear warm-up from 0 to the peak over `warmup_steps`, then a
    cosine from the peak to a tenth of it at `total_steps`."""
    import math
    peak, warm = hp['learning_rate'], hp['warmup_steps']
    decay = max(hp['total_steps'], warm + 1) - warm
    if count < warm:
        return peak * count / warm
    frac = min(count - warm, decay) / decay
    alpha = 0.1
    return peak * ((1 - alpha) * 0.5 * (1 + math.cos(math.pi * frac))
                   + alpha)


@jax.jit
def _sq_sum(tree):
    return sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))


def clip_adamw_step(params, grads, mu, nu, count: int, hp: dict):
    """Clip the gradients to a global norm, then AdamW (decoupled weight
    decay, bias-corrected moments). `count` is the number of updates made
    before this one. Returns (params, mu, nu, clipped gradients)."""
    norm = jnp.sqrt(_sq_sum(grads))
    factor = jnp.minimum(1.0, hp['grad_clip_norm'] / jnp.maximum(norm,
                                                                1e-30))
    b1, b2, eps = hp['b1'], hp['b2'], hp.get('eps', 1e-8)
    lr = learning_rate(count, hp)
    t = count + 1

    def leaf(p, g, m, v):
        g = g * factor
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (step + hp['weight_decay'] * p), m, v, g

    out = jax.tree.map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)
