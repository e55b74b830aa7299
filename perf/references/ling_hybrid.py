"""Plain reference for the Ling-3.0-flash language model
(`inclusionAI/Ling-3.0-flash-VL`, `model_type` bailing_hybrid): Kimi
delta attention (KDA) in five layers of six, latent attention (MLA) in
the sixth, a dense SwiGLU in the leading layers and then experts routed
within groups, one shared; a final norm and an untied unembedding.

Straight `jax.numpy` in float32 at `highest` matmul precision, no cache,
no batching of requests, no kernels, no sorting: KDA is the recurrence
itself, a position at a time; MLA is expanded (each position's keys and
values up-projected from its latent); the expert layer scores all the
router's experts and walks the held ones one after another, every token
through each. It imports nothing of the program under test and is handed
weights made by the benchmark (perf/weights.py) under this file's own
names (families/ling_hybrid.py maps the program's paths to them):
`layer_weights(i)` gives entry i of every stacked group, the dense KDA
group's names prefixed `d_`, the MLA group's `m_`, the KDA expert
group's bare, and the expert stacks' (`w_gate`, `w_up`, `w_down`) at
expert layer i; `whole(name)` gives 'embed', 'final_norm', 'lm_head'.

With n(.) an RMSNorm (w * x / rms(x)), d = hidden_size, H heads of D,
bound = kda_lower_bound:

    x0     = Emb[token]
    u      = n_attn(x)
  KDA:
    q,k,v  = silu(causal depthwise conv4 of [u Wq | u Wk | u Wv])   over positions
    q      = q / |q| / sqrt(D);  k = k / |k|        a head each (|.| with eps 1e-6)
    beta   = sigmoid(u Wb);  g = bound * sigmoid(exp(A_log) * (u Wf + dt_bias))
    S_t    = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T    (D x D a head), S_{-1} = 0
    o_t    = S_t^T q_t
    att    = (n_head(o) * sigmoid(u Wg)) Wo
  MLA:
    q      = n_q(u Wq)                              (H, nope + rope)
    [c,kr] = u Wdkv;  c = n_kv(c)
    [kn,v] = c Wukv                                  (H, nope) and (H, v_dim)
    s      = (q_nope . kn + rope(q_rope) . rope(kr)) / sqrt(nope + rope), causal
    att    = ((softmax(s) v) * sigmoid(u Wgate)) Wo   one gate a head
    h      = x + att;  u2 = n_mlp(h)
  dense:   y = (silu(u2 Wg) * (u2 Wu)) Wd
  experts: s = sigmoid(u2 R), float32, all E;  b: expert_bias (selection only)
           group score = sum of the two best (s + b) of each of n_group groups;
           C = the k best (s + b) within the topk_group best groups
           w_e = route_scale * s_e / sum_{c in C} s_c        e in C, else 0
           y = shared(u2) + sum over HELD e of w_e * expert_e(u2)
           (an expert or the shared one: gate clamped from above and up to
           [-limit, limit] by its layer's limit, where it is not 0)
    out    = h + y
    logits = n_f(x_L) @ W_head

What the experts held on other chips would have added is left out, as it
is in the program: the partial sum is what goes on (the guide's cut).

Assumed (no network here; the configuration file lists them under
`assumed`): the gate's form, the full-rank f and g projections, where
the head-wise gate and the norms sit, q's per-head norm and the latent's
norm and no norm on the up-projected keys, rotary on the pair (i, i +
rope/2) of MLA's rotary part only, the clamps' form.

Departures of the PROGRAM from these lines (skypilot_tpu/models/kda.py,
mla.py, moe.py), none in the mathematics: activations and weights in the
configuration's compute type with float32 accumulation, router scores,
the KDA state and its recurrence in float32; a prefill chunk's recurrence
by the chunked (WY) form; MLA attends over cached latents in the
absorbed form; the held (token, choice) pairs are sorted by expert and
multiplied in groups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_ROWS = 1024
L2_EPS = 1e-6


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x: (T, H, R); positions: (T,). Rotates the pair (i, i + R/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (float(theta) ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def swiglu(u, w_gate, w_up, w_down, limit=0.0):
    gate, up = _mm('td,df->tf', u, w_gate), _mm('td,df->tf', u, w_up)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm('tf,fd->td', jax.nn.silu(gate) * up, w_down)


# ---- KDA ------------------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """One sequence's recurrence, a position at a time. q, k, g: (T, H,
    D); v: (T, H, D); beta: (T, H). Returns o: (T, H, D)."""
    def step(s, args):
        q_t, k_t, v_t, g_t, b_t = args
        s = jnp.exp(g_t)[:, :, None] * s                  # Diag(exp g) S
        pred = jnp.einsum('hk,hkv->hv', k_t, s, precision=HIGHEST)
        s = s + (b_t[:, None, None] * k_t[:, :, None]
                 * (v_t - pred)[:, None, :])
        return s, jnp.einsum('hk,hkv->hv', q_t, s, precision=HIGHEST)

    h, d = q.shape[1], q.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((h, d, v.shape[2]), F32),
                        (q, k, v, g, beta))
    return o


def kda_mixer(u, w, cfg):
    """u: (T, d) -> (T, d)."""
    t = u.shape[0]
    h, d = cfg['num_attention_heads'], cfg['head_dim']
    taps = cfg['short_conv_kernel_size']
    pre = jnp.concatenate([_mm('td,dhk->thk', u, w[n]).reshape(t, h * d)
                           for n in ('wq', 'wk', 'wv')], axis=-1)
    padded = jnp.pad(pre, ((taps - 1, 0), (0, 0)))
    conv = sum(w['conv_w'][i] * padded[i:i + t] for i in range(taps))
    q, k, v = jnp.split(jax.nn.silu(conv).reshape(t, 3, h, d), 3, axis=1)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1,
                                             keepdims=True) + L2_EPS)
    q, k = l2(q) * d ** -0.5, l2(k)
    beta = jax.nn.sigmoid(_mm('td,dh->th', u, w['wb']))
    g = cfg['kda_lower_bound'] * jax.nn.sigmoid(
        jnp.exp(w['a_log'])[:, None]
        * (_mm('td,dhk->thk', u, w['wf']) + w['dt_bias']))
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, w['o_norm'], cfg['rms_norm_eps'])
    o = o * jax.nn.sigmoid(_mm('td,dhk->thk', u, w['wg']))
    return _mm('thk,hkd->td', o, w['wo'])


# ---- MLA ------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, k_pos):
    """q: (Tq, H, E); k: (Tk, H, E); v: (Tk, H, V). Causal."""
    scores = _mm('qhe,khe->hqk', q, k) * (q.shape[-1] ** -0.5)
    mask = k_pos[None, :] <= q_pos[:, None]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return _mm('hqk,khv->qhv', jax.nn.softmax(scores, axis=-1), v)


def mla_mixer(u, positions, w, cfg):
    """u: (T, d) -> (T, d), expanded, queries a block at a time."""
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    nope, rank = cfg['qk_nope_head_dim'], cfg['kv_lora_rank']
    q = _mm('td,dhe->the', u, w['wq'])
    if 'q_norm' in w:
        q = rms_norm(q, w['q_norm'], eps)
    down = _mm('td,dr->tr', u, w['kv_down'])
    c = rms_norm(down[:, :rank], w['kv_norm'], eps)
    kr = rope(down[:, None, rank:], positions, theta)       # (T, 1, r)
    up = _mm('tr,rhx->thx', c, w['kv_up'])
    kn, v = up[..., :nope], up[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], positions, theta)], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(
        kr, kn.shape[:2] + (kr.shape[-1],))], axis=-1)
    t = u.shape[0]
    nblk = -(-t // Q_BLOCK)
    pad = nblk * Q_BLOCK - t
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (nblk, Q_BLOCK) + q.shape[1:])
    pb = jnp.pad(positions, (0, pad)).reshape(nblk, Q_BLOCK)
    att = jax.lax.map(lambda a: _attend_block(a[0], k, v, a[1], positions),
                      (qb, pb)).reshape((nblk * Q_BLOCK,) + v.shape[1:])[:t]
    att = att * jax.nn.sigmoid(_mm('td,dh->th', u, w['w_attn_gate'])
                               )[..., None]
    return _mm('thv,hvd->td', att, w['wo'])


# ---- experts -------------------------------------------------------------

def route(u, w, cfg):
    """(T, E) float32: the weight each token gives each expert the
    router scores: route_scale * s_e / (sum of the chosen s) for its k
    chosen ones (chosen by s + bias within its best groups), 0 for the
    rest."""
    s = jax.nn.sigmoid(_mm('td,de->te', u, w['router']))
    select = s + w['expert_bias'] if 'expert_bias' in w else s
    groups = cfg['n_group']
    if groups > 1:
        t, e = select.shape
        grouped = select.reshape(t, groups, e // groups)
        score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, kept = jax.lax.top_k(score, cfg['topk_group'])
        keep = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        select = jnp.where(keep[:, :, None], grouped,
                           -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(select, cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get('norm_topk_prob', True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * cfg.get('routed_scaling_factor', 1.0)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def experts(u, w, cfg, limits=(0.0, 0.0)):
    """shared(u) + the held experts' part of the routed sum: one expert
    after another, every token through each."""
    first, held = cfg['first_expert'], cfg['experts_held']
    mine = route(u, w, cfg)[:, first:first + held]         # (T, held)

    def one(acc, args):
        w_gate, w_up, w_down, weight = args
        return acc + weight[:, None] * swiglu(u, w_gate, w_up, w_down,
                                              limits[0]), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w['w_gate'], w['w_up'], w['w_down'], mine.T))
    return swiglu(u, w['s_gate'], w['s_up'], w['s_down'], limits[1]) \
        + routed


# ---- the stack -----------------------------------------------------------

CFG_KEYS = ('rms_norm_eps', 'rope_theta', 'num_attention_heads',
            'head_dim', 'short_conv_kernel_size', 'kda_lower_bound',
            'qk_nope_head_dim', 'kv_lora_rank', 'num_experts_per_tok',
            'norm_topk_prob', 'routed_scaling_factor', 'n_group',
            'topk_group', 'first_expert', 'experts_held')


def cfg_key(cfg: dict):
    """The keys the layer reads, hashable (a jit's static argument)."""
    return tuple((k, cfg[k]) for k in CFG_KEYS if k in cfg)


@functools.partial(jax.jit, static_argnames=('key', 'mixer', 'dense',
                                             'limits'))
def _layer(x, w, key, mixer, dense, limits):
    """One layer over every sequence. x: (N, T, d). KDA's recurrence
    runs the sequences side by side, MLA's attention one after
    another."""
    cfg = dict(key)
    n, t, d = x.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    u = rms_norm(x, w['attn_norm'], cfg['rms_norm_eps'])
    if mixer == 'kda':
        att = jax.vmap(lambda r: kda_mixer(r, w, cfg))(u)
    else:
        att = jax.lax.map(lambda r: mla_mixer(r, pos, w, cfg), u)
    h = x + att
    u2 = rms_norm(h, w['mlp_norm'], cfg['rms_norm_eps']).reshape(
        n * t, d)
    y = (swiglu(u2, w['w_gate'], w['w_up'], w['w_down']) if dense
         else experts(u2, w, cfg, limits))
    return h + y.reshape(n, t, d)


def layer_plan(cfg: dict) -> list:
    """(mixer, dense, prefix, entry in its group, expert layer) for every
    layer: groups are runs of one (dense, mixer) kind, as the program
    stacks them, one run a kind."""
    n_dense = cfg['first_k_dense_replace']
    plan, seen, entry = [], [], 0
    for l, mixer in enumerate(cfg['mixers']):
        dense = l < n_dense
        kind = 'dense_kda' if dense else mixer
        if dense and mixer != 'kda':
            raise ValueError('a dense layer here is a KDA layer')
        if not seen or seen[-1] != kind:
            if kind in seen:
                raise ValueError(f'a second run of {kind} layers: the '
                                 f'family names one group a kind')
            seen.append(kind)
            entry = 0
        prefix = {'dense_kda': 'd_', 'kda': '', 'mla': 'm_'}[kind]
        plan.append((mixer, dense, prefix, entry, l - n_dense))
        entry += 1
    return plan


def pick(weights: dict, prefix: str) -> dict:
    """The names of one group out of `layer_weights(i)`'s dict, their
    prefix taken off."""
    prefixes = ('d_', 'm_')
    if prefix:
        return {n[len(prefix):]: a for n, a in weights.items()
                if n.startswith(prefix)}
    return {n: a for n, a in weights.items()
            if not n.startswith(prefixes)}


def hidden_states(tokens, whole, layer_weights, num_layers: int,
                  cfg: dict):
    """tokens: (N, T) ids; whole('embed'): (V, hidden) float32;
    layer_weights(i): entry i of every group, float32. Returns (N, T,
    hidden) before the final norm."""
    plan = layer_plan(cfg)
    assert len(plan) == num_layers, (len(plan), num_layers)
    x = jnp.take(whole('embed'), tokens, axis=0).astype(F32)
    for (mixer, dense, prefix, entry, expert_layer), lim in zip(
            plan, cfg['limits']):
        w = pick(layer_weights(entry), prefix)
        if not dense and (prefix or expert_layer != entry):
            # the expert stacks at this layer's place among the expert
            # layers
            stacks = layer_weights(expert_layer)
            w.update({n: stacks[n] for n in ('w_gate', 'w_up', 'w_down')})
        x = _layer(x, w, cfg_key(cfg), mixer, dense, tuple(lim))
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
    """Weight-only float8 (4 exponent bits, 3 of mantissa: e4m3) with
    one scale per output channel, by `reduce_precision` and never by a
    cast there and back, which the TPU compiler may drop inside a jit
    (the control then reads the float32 weights: gaps of exactly 0 on
    the v5e, PERF.md section 6). The largest entry of a channel sits at
    240, the format's largest finite value under `reduce_precision`."""
    axes = tuple(range(lead, lead + contract))
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


# The matmul weights, as (leading axes kept, axes contracted); the
# router, its bias, the gate's terms, the convolution and the norms stay
# as they are.
_KDA = {n: (0, 1) for n in ('wq', 'wk', 'wv', 'wf', 'wg', 'wb')}
_KDA['wo'] = (0, 2)
_MLA = {'wq': (0, 1), 'kv_down': (0, 1), 'kv_up': (0, 1),
        'w_attn_gate': (0, 1), 'wo': (0, 2)}
_MLP = {n: (0, 1) for n in ('w_gate', 'w_up', 'w_down')}
_SHARED = {n: (0, 1) for n in ('s_gate', 's_up', 's_down')}
CONTRACT_AXES = dict(
    {f'd_{n}': a for n, a in {**_KDA, **_MLP}.items()},
    **_KDA, **_SHARED,
    **{f'm_{n}': a for n, a in {**_MLA, **_SHARED}.items()},
    **{n: (1, 1) for n in ('w_gate', 'w_up', 'w_down')},
    lm_head=(0, 1))


def lower_precision(weights: dict, how: str) -> dict:
    """The matmul weights of `weights` rounded as `how` ('int8' | 'fp8')
    says."""
    fn = {'int8': round_int8, 'fp8': round_fp8}[how]
    return {k: (fn(v, *CONTRACT_AXES[k]) if k in CONTRACT_AXES else v)
            for k, v in weights.items()}
