"""Plain reference for the SDAR decoder (`JetLM/SDAR-30B-A3B-Chat`,
`model_type` sdar_moe): the Qwen3-MoE layer it was continued from, under
a block-causal mask, generating by diffusion over blocks.

Straight `jax.numpy` in float32 at `highest` matmul precision, one
sequence at a time, no cache, no batching, no kernels, no sorting: the
expert layer walks the experts one after another, every token through
each, and keeps what the router weighed. It imports nothing of the
program under test and is handed weights made by the benchmark
(perf/weights.py) under this file's own names (families/sdar_moe.py maps
the program's paths to them): `layer_weights(l)` gives layer l's dict,
`whole(name)` gives 'embed', 'final_norm', 'lm_head'.

With n RMSNorms (w * x / rms(x)), H x D query heads, KV key/value heads,
E experts, k chosen a token, B the block length and blk(i) = i // B
(blocks aligned to absolute position 0):

    x0     = Emb[token]
    u      = n_in(x)
    q, k, v = u @ Wq, u @ Wk, u @ Wv;  q, k = n_q(q), n_k(k)   # over D, a head
    q, k   = rope(q), rope(k)                                  # theta 1e6, pair (i, i + D/2)
    a      = softmax(q k^T / sqrt(D), j visible to i iff blk(j) <= blk(i)) v
    h      = x + a @ Wo
    u2     = n_post(h)
    p      = softmax(u2 @ R)                                   # float32, all E
    C      = the k experts with the largest p
    w_e    = p_e / sum_{c in C} p_c                            # norm_topk_prob; e in C, else 0
    out    = h + sum_e w_e * W_down,e (silu(W_gate,e u2) * W_up,e u2)
    logits = n_f(x_L) @ W_head

The logits of position i predict position i (no shift). A masked
position is fed the mask token's id.

`block_hidden_states` runs one pass over two streams at once, [clean |
noisy] at the same positions: a clean query sees clean keys with blk(j)
<= blk(i); a noisy query sees clean keys with blk(j) < blk(i) and noisy
keys with blk(j) == blk(i). The noisy half of its output is then, block
by block, what a denoising pass over that block computes when every
earlier block is committed: the state of every block at one pass number
in one forward.

`generate` is SDAR's published loop (`generate.py`,
`block_diffusion_generate` with `low_confidence_static`) with no cache:
every pass forwards the whole sequence. The prompt's whole blocks are
clean; its tail opens the first generated block beside masks; a pass
takes x0 = argmax and p = softmax(logits)[x0] at each masked position
and unmasks the schedule's count of the most confident MASKED ones (the
published loop's top-k may also pick an unmasked position where fewer
are masked than the schedule unmasks, and overwrite it: a prompt's tail
is never overwritten here); the schedule is B / steps a pass, the
remainder to the first passes. The commit pass writes a cache and
yields nothing, so a loop with no cache has none.

Assumed (no network here; the configuration file lists them under
`assumed`): q/k norms over the head size before rotary (the family's;
the config has no key for it), block length, steps, the schedule, the
mask token's id, no logit shift, blocks aligned to position 0.

Departures of the PROGRAM from these lines (skypilot_tpu/models/
layer_pattern.py, moe.py, inference.py), none in the mathematics:
activations and weights in the configuration's compute type with float32
accumulation, router logits in float32; the chosen experts' weights as a
softmax over the chosen logits (equal to p_e over the chosen sum); the
(token, choice) pairs sorted by expert and multiplied in groups; K/V of
committed blocks read from a paged cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
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


def attention(q, k, v, visible):
    """One sequence. q: (T, H, D); k, v: (T, KV, D); visible: (T, T)
    bool, visible[i, j] iff query i attends key j. One kv head's query
    heads at a time, so that one group's scores only are alive."""
    t, h, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(t, kv, h // kv, d).transpose(1, 0, 2, 3)  # KV,T,R,D

    def one_head(args):
        qh, kh, vh = args
        scores = _mm('qrd,kd->rqk', qh, kh) * (d ** -0.5)
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return _mm('rqk,kd->qrd', jax.nn.softmax(scores, axis=-1), vh)

    out = jax.lax.map(one_head, (qg, k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))       # KV,T,R,D
    return out.transpose(1, 0, 2, 3).reshape(t, h, d)


def swiglu(u, w_gate, w_up, w_down):
    return _mm('tf,fd->td', jax.nn.silu(_mm('td,df->tf', u, w_gate))
               * _mm('td,df->tf', u, w_up), w_down)


def route(u, router, cfg):
    """(T, E) float32: the weight each token gives each expert: p_e
    over the chosen k's sum for its k chosen ones, 0 for the rest."""
    p = jax.nn.softmax(_mm('td,de->te', u, router), axis=-1)
    picked, chosen = jax.lax.top_k(p, cfg['num_experts_per_tok'])
    if cfg.get('norm_topk_prob', True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(picked)


def experts(u, w, cfg):
    """One expert after another, every token through each."""
    weights = route(u, w['router'], cfg)                   # (T, E)

    def one(acc, args):
        w_gate, w_up, w_down, weight = args
        return acc + weight[:, None] * swiglu(u, w_gate, w_up, w_down), \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w['w_gate'], w['w_up'], w['w_down'], weights.T))
    return out


def layer_row(x, positions, visible, w, cfg):
    """One decoder layer on one sequence. x: (T, hidden)."""
    eps = cfg['rms_norm_eps']
    u = rms_norm(x, w['attn_norm'], eps)
    q = rms_norm(_mm('td,dhk->thk', u, w['wq']), w['q_norm'], eps)
    k = rms_norm(_mm('td,dhk->thk', u, w['wk']), w['k_norm'], eps)
    v = _mm('td,dhk->thk', u, w['wv'])
    q = rope(q, positions, cfg['rope_theta'])
    k = rope(k, positions, cfg['rope_theta'])
    h = x + _mm('thk,hkd->td', attention(q, k, v, visible), w['wo'])
    return h + experts(rms_norm(h, w['mlp_norm'], eps), w, cfg)


CFG_KEYS = ('rms_norm_eps', 'rope_theta', 'num_experts_per_tok',
            'norm_topk_prob')


def cfg_key(cfg: dict):
    """The keys the layer reads, hashable (a jit's static argument)."""
    return tuple((k, cfg[k]) for k in CFG_KEYS if k in cfg)


@functools.partial(jax.jit, static_argnames=('key',))
def _layer_rows(x, positions, visible, w, key):
    cfg = dict(key)
    return jax.lax.map(
        lambda r: layer_row(r, positions, visible, w, cfg), x)


def blocks_of(positions, cfg: dict):
    """blk(i): the position itself where `causal` asks for the causal
    mask in the block-causal one's place (a test's fault)."""
    if cfg.get('causal'):
        return positions
    return positions // cfg['block_length']


def clean_visible(t: int, cfg: dict):
    blk = blocks_of(jnp.arange(t, dtype=jnp.int32), cfg)
    return blk[None, :] <= blk[:, None]


def two_stream_visible(t: int, cfg: dict):
    """(2T, 2T): rows and columns [clean | noisy]."""
    blk = blocks_of(jnp.arange(t, dtype=jnp.int32), cfg)
    before = blk[None, :] < blk[:, None]
    same = blk[None, :] == blk[:, None]
    none = jnp.zeros((t, t), bool)
    return jnp.block([[before | same, none], [before, same]])


def _run_layers(x, positions, visible, layer_weights, num_layers, cfg):
    for l in range(num_layers):
        x = _layer_rows(x, positions, visible, layer_weights(l),
                        cfg_key(cfg))
    return x


def hidden_states(tokens, whole, layer_weights, num_layers: int,
                  cfg: dict):
    """A clean sequence under the block-causal mask. tokens: (N, T) ids;
    whole('embed'): (V, hidden) float32; layer_weights(l): layer l's
    weights, float32. Returns (N, T, hidden) before the final norm. One
    layer's weights are alive at a time."""
    t = tokens.shape[1]
    x = jnp.take(whole('embed'), tokens, axis=0).astype(F32)
    return _run_layers(x, jnp.arange(t, dtype=jnp.int32),
                       clean_visible(t, cfg), layer_weights, num_layers,
                       cfg)


def block_hidden_states(clean, noisy, whole, layer_weights,
                        num_layers: int, cfg: dict):
    """One pass over the two streams [clean | noisy] at the same
    positions. clean, noisy: (N, T) ids (a masked position of `noisy`
    holds the mask token's id). Returns the noisy half, (N, T, hidden)
    before the final norm: at the positions of block b, what a pass over
    that block's noisy state computes with blocks before b clean."""
    t = clean.shape[1]
    both = jnp.concatenate([clean, noisy], axis=1)
    x = jnp.take(whole('embed'), both, axis=0).astype(F32)
    pos = jnp.arange(t, dtype=jnp.int32)
    x = _run_layers(x, jnp.concatenate([pos, pos]),
                    two_stream_visible(t, cfg), layer_weights, num_layers,
                    cfg)
    return x[:, t:]


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


def unmask_schedule(cfg: dict) -> list:
    """Positions a pass unmasks, by pass number: B / steps each, the
    remainder to the first passes."""
    base, extra = divmod(cfg['block_length'], cfg['denoising_steps'])
    return [base + (i < extra) for i in range(cfg['denoising_steps'])]


def generate(prompt, max_new: int, whole, layer_weights, num_layers: int,
             cfg: dict):
    """The published loop with no cache, for one prompt (a list of
    ids). Returns (tokens, unmask_pass, confidences): the max_new
    generated tokens, the pass of its block that unmasked each, and for
    every pass the confidences it chose among, as (block, pass, {position:
    p}, [positions unmasked]) for a test to read margins from."""
    b, mask_id = cfg['block_length'], cfg['mask_token_id']
    sched = unmask_schedule(cfg)
    n = len(prompt)
    blocks = -(-(n + max_new) // b)
    total = blocks * b
    x = np.full((total,), mask_id, np.int64)
    x[:n] = prompt
    masked = np.arange(total) >= n
    upass = np.full((total,), -1, np.int64)
    log = []
    for blk in range(n // b, blocks):
        lo, hi = blk * b, (blk + 1) * b
        for step in range(len(sched)):
            if not masked[lo:hi].any():
                break
            # the block's noisy state, every earlier block clean; later
            # blocks are not visible to it
            hidden = hidden_states(jnp.asarray(x[None]), whole,
                                   layer_weights, num_layers, cfg)
            logits = logits_at(hidden[0, lo:hi], whole, cfg)
            probs = np.asarray(jax.nn.softmax(logits, axis=-1), np.float64)
            x0 = probs.argmax(axis=-1)
            conf = probs[np.arange(b), x0]
            cand = [i for i in range(b) if masked[lo + i]]
            order = sorted(cand, key=lambda i: (-conf[i], i))
            take = order[:sched[step]]
            log.append((blk, step, {lo + i: float(conf[i]) for i in cand},
                        [lo + i for i in take]))
            for i in take:
                x[lo + i], masked[lo + i], upass[lo + i] = x0[i], False, \
                    step
    return ([int(t) for t in x[n:n + max_new]],
            [int(p) for p in upass[n:n + max_new]], log)


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
    """Weight-only float8 (4 exponent bits, 3 of mantissa: e4m3) with
    one scale per output channel. By `reduce_precision`, never by a
    cast there and back: inside a jit the TPU compiler may keep the
    excess precision and drop such a pair, and the control then reads
    the float32 weights themselves (gaps of exactly 0 on the v5e:
    PERF.md section 6, PR 36). The largest entry of a channel sits at
    240, the format's largest finite value."""
    axes = tuple(range(lead, lead + contract))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


# The matmul weights, as (leading axes kept, axes contracted); the
# router and the norms stay as they are.
CONTRACT_AXES = {'wq': (0, 1), 'wk': (0, 1), 'wv': (0, 1), 'wo': (0, 2),
                 'w_gate': (1, 1), 'w_up': (1, 1), 'w_down': (1, 1),
                 'lm_head': (0, 1)}


def lower_precision(weights: dict, how: str) -> dict:
    """The matmul weights of `weights` rounded as `how` ('int8' | 'fp8')
    says."""
    fn = {'int8': round_int8, 'fp8': round_fp8}[how]
    return {k: (fn(v, *CONTRACT_AXES[k]) if k in CONTRACT_AXES else v)
            for k, v in weights.items()}
