"""Falcon-H1 through the normal serving path: a Mamba-2 mixer beside
attention in every block, its per-slot recurrent state beside the paged
K/V (models/ssm.py, docs/serving.md "Models with recurrent state").

The oracle is the benchmark's plain reference
(perf/references/falcon_h1.py: float32, the scan as a `lax.scan` over
positions, no cache), loaded by path; it imports nothing of the program.
Weights here are flax's own draws with the mixer's scalars (decay, step
bias, skip, convolution bias, norm scale) redrawn so that every term of
the equations is exercised.

Tolerances. Program against reference in float32 on the CPU: 2e-4 on the
logits (unit-scale logits; the chunked form sums a block's 128 terms in
another order than the scan, and both sides keep float32 throughout, so
what is left is rounding, some 1e-5). Same request, other company:
bit for bit.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import convert, get_config
from skypilot_tpu.models.inference import (ContinuousBatchingEngine,
                                           InferenceEngine)
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 512

MULTIPLIERS = dict(
    embed_multiplier=4.0, attn_in_multiplier=1.0, key_multiplier=0.5,
    attn_out_multiplier=0.5, ssm_in_multiplier=0.5,
    ssm_multipliers=(0.7, 0.5, 0.6, 0.8, 0.7), ssm_out_multiplier=0.6,
    mlp_multipliers=(0.5, 0.5), lm_head_multiplier=0.5)

# the reference's name for each leaf of the program's layer tree
LAYER_NAMES = {
    ('attn_norm', 'scale'): 'attn_norm',
    ('mixer', 'in_proj', 'kernel'): 'w_in',
    ('mixer', 'conv_kernel'): 'conv_w', ('mixer', 'conv_bias'): 'conv_b',
    ('mixer', 'A_log'): 'a_log', ('mixer', 'D'): 'd_skip',
    ('mixer', 'dt_bias'): 'dt_bias', ('mixer', 'norm_scale'): 'ssm_norm',
    ('mixer', 'out_proj', 'kernel'): 'w_out',
    ('attn', 'q_proj', 'kernel'): 'wq', ('attn', 'k_proj', 'kernel'): 'wk',
    ('attn', 'v_proj', 'kernel'): 'wv', ('attn', 'o_proj', 'kernel'): 'wo',
    ('mlp_norm', 'scale'): 'mlp_norm',
    ('mlp', 'gate_proj', 'kernel'): 'w_gate',
    ('mlp', 'up_proj', 'kernel'): 'w_up',
    ('mlp', 'down_proj', 'kernel'): 'w_down',
}


def tiny_cfg(**overrides):
    """The registry's Falcon-H1 at test sizes: every switch as
    published, 2 layers, 4 x 8 mixer heads of 16 states in 2 groups."""
    kw = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
              head_dim_override=16, d_mlp=128, vocab_size=VOCAB,
              max_seq_len=512, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
              ssm_groups=2, dtype='float32', param_dtype='float32',
              attention_impl='xla', **MULTIPLIERS)
    kw.update(overrides)
    return get_config('falcon-h1-34b', **kw)


def reference_cfg(cfg) -> dict:
    """The published config's keys, as the reference reads them."""
    return {
        'hidden_size': cfg.d_model, 'rms_norm_eps': cfg.norm_eps,
        'rope_theta': cfg.rope_theta, 'mamba_n_heads': cfg.ssm_heads,
        'mamba_d_head': cfg.ssm_head_dim, 'mamba_d_ssm': cfg.d_ssm,
        'mamba_expand': 2, 'mamba_d_state': cfg.ssm_state,
        'mamba_n_groups': cfg.ssm_groups, 'mamba_d_conv': cfg.ssm_conv,
        'mamba_rms_norm': cfg.ssm_gated_norm,
        'mamba_norm_before_gate': cfg.ssm_norm_before_gate,
        'embedding_multiplier': cfg.embed_multiplier,
        'attention_in_multiplier': cfg.attn_in_multiplier,
        'key_multiplier': cfg.key_multiplier,
        'attention_out_multiplier': cfg.attn_out_multiplier,
        'ssm_in_multiplier': cfg.ssm_in_multiplier,
        'ssm_multipliers': list(cfg.ssm_multipliers),
        'ssm_out_multiplier': cfg.ssm_out_multiplier,
        'mlp_multipliers': list(cfg.mlp_multipliers),
        'lm_head_multiplier': cfg.lm_head_multiplier}


def make_params(cfg, seed: int = 0):
    from flax import linen as nn
    init_cfg = dataclasses.replace(cfg, decode=False)
    params = nn.unbox(jax.jit(Transformer(init_cfg).init)(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)))['params']
    mixer = dict(params['layers']['layer']['mixer'])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    draw = lambda k, leaf, mean, std: mean + std * jax.random.normal(
        k, leaf.shape, leaf.dtype)
    mixer['A_log'] = draw(keys[0], mixer['A_log'], np.log(4.0), 0.7)
    mixer['dt_bias'] = draw(keys[1], mixer['dt_bias'], -3.0, 0.8)
    mixer['D'] = draw(keys[2], mixer['D'], 1.0, 0.1)
    if 'conv_bias' in mixer:
        mixer['conv_bias'] = draw(keys[3], mixer['conv_bias'], 0.0, 0.1)
    if 'norm_scale' in mixer:
        mixer['norm_scale'] = draw(keys[4], mixer['norm_scale'], 1.0, 0.1)
    params['layers']['layer']['mixer'] = mixer
    return params


class Oracle:
    """The plain reference over a program's parameter tree."""

    def __init__(self, cfg, params):
        spec = importlib.util.spec_from_file_location(
            'falcon_h1_reference',
            os.path.join(ROOT, 'perf', 'references', 'falcon_h1.py'))
        self.ref = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(self.ref)
        self.cfg, self.rcfg = cfg, reference_cfg(cfg)
        layers = params['layers']['layer']

        def leaf(tree, path):
            for name in path:
                if name not in tree:
                    return None
                tree = tree[name]
            return tree

        self.layers = {ref: leaf(layers, path)
                       for path, ref in LAYER_NAMES.items()
                       if leaf(layers, path) is not None}
        self.whole = {'embed': params['embed']['embedding'],
                      'final_norm': params['final_norm']['scale'],
                      'lm_head': params['lm_head']['kernel']}

    def logits(self, tokens) -> np.ndarray:
        """(T,) ids -> (T, V) logits of the full forward pass. Padded
        on the right to the context (causal: what follows a position
        cannot move it), so that one program serves every length."""
        n = len(tokens)
        padded = list(tokens) + [0] * (self.cfg.max_seq_len - n)
        hidden = self.ref.hidden_states(
            jnp.asarray([padded], jnp.int32), self.whole.__getitem__,
            lambda l: {n: w[l] for n, w in self.layers.items()},
            self.cfg.num_layers, self.rcfg)[0]
        return np.asarray(self.ref.logits_at(
            hidden, self.whole.__getitem__, self.rcfg))[:n]

    def worst_gap(self, prompt, served) -> float:
        """By how much a served token's reference logit lies below the
        reference's best, at worst (0: every one is the argmax)."""
        logits = self.logits(list(prompt) + list(served))
        return max(float(logits[len(prompt) + j - 1].max()
                         - logits[len(prompt) + j - 1, t])
                   for j, t in enumerate(served))


@pytest.fixture(scope='module')
def model():
    cfg = tiny_cfg()
    params = make_params(cfg)
    return cfg, params, Oracle(cfg, params)


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng([n, seed]).integers(
        1, VOCAB, size=n).tolist()


# ---- the configuration ---------------------------------------------------

def test_the_registry_holds_the_published_config():
    """Key for key with the catalog row of Falcon-H1-34B-Instruct."""
    cfg = get_config('falcon-h1-34b')
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_mlp, cfg.vocab_size) == (
                72, 5120, 20, 4, 128, 21504, 261120)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_ssm, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk) == (
                32, 128, 4096, 256, 2, 4, 128)
    assert cfg.ssm_proj_width == 9248 and cfg.ssm_conv_channels == 5120
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq_len) == (
        1e11, 1e-5, 262144)
    assert cfg.ssm_conv_bias and not cfg.ssm_proj_bias
    assert cfg.ssm_gated_norm and not cfg.ssm_norm_before_gate
    assert not cfg.tie_embeddings and not cfg.qkv_bias
    assert cfg.embed_multiplier == 5.656854249492381
    assert cfg.key_multiplier == 0.011048543456039804
    assert (cfg.attn_in_multiplier, cfg.attn_out_multiplier) == (1, 0.0375)
    assert cfg.ssm_in_multiplier == 0.25
    assert cfg.ssm_multipliers == (0.3535533905932738, 0.25,
                                   0.1767766952966369, 0.5,
                                   0.3535533905932738)
    assert cfg.ssm_out_multiplier == 0.08838834764831845
    assert cfg.mlp_multipliers == (0.1767766952966369,
                                   0.011160714285714284)
    assert cfg.lm_head_multiplier == 0.0078125
    # a layer: attention 31,457,280 + mixer 68,351,072 + MLP 330,301,440
    # + two norms; embedding and head 2 x 1,336,934,400; a final norm
    per_layer = 31_457_280 + 68_351_072 + 330_301_440 + 10_240
    assert cfg.num_params() == 72 * per_layer + 2 * 1_336_934_400 + 5120
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        import json
        row = next(r for r in map(json.loads, open(catalog))
                   if r['name'] == 'Falcon-H1-34B-Instruct')['config']
        assert row['mamba_d_ssm'] == cfg.d_ssm
        assert tuple(row['ssm_multipliers']) == cfg.ssm_multipliers
        assert tuple(row['mlp_multipliers']) == cfg.mlp_multipliers
        assert row['intermediate_size'] == cfg.d_mlp


# ---- the mixer against the reference's scan --------------------------------

def _decode_net(cfg, batch: int):
    """The program in decode mode with a zeroed contiguous cache."""
    from flax import linen as nn
    net = Transformer(dataclasses.replace(cfg, decode=True, remat=False))
    cache = jax.tree.map(jnp.zeros_like, nn.unbox(net.init(
        jax.random.PRNGKey(0), jnp.ones((batch, 1), jnp.int32),
        jnp.zeros((batch, 1), jnp.int32))['cache']))
    return net, cache


@functools.lru_cache(maxsize=None)
def _stepper(cfg):
    net, cache = _decode_net(cfg, 1)
    return cache, jax.jit(lambda params, c, t, p: net.apply(
        {'params': params, 'cache': c}, t, p, mutable=['cache']))


def _prefill_then_decode(cfg, params, prompt, steps: int):
    """Through the contiguous cache at batch 1: the whole prompt in one
    call (the chunked form), then `steps` greedy tokens by the one-step
    recurrence. Returns the logits of every position fed after the
    prompt's last, and the tokens."""
    cache, apply = _stepper(cfg)
    step = functools.partial(apply, params)
    n = len(prompt)
    logits, mut = step(cache, jnp.asarray([prompt], jnp.int32),
                       jnp.arange(n, dtype=jnp.int32)[None])
    rows, toks = [np.asarray(logits[0, -1])], []
    for j in range(steps):
        toks.append(int(rows[-1].argmax()))
        logits, mut = step(mut['cache'], jnp.asarray([[toks[-1]]]),
                           jnp.asarray([[n + j]], jnp.int32))
        rows.append(np.asarray(logits[0, 0]))
    return np.stack(rows), toks


@pytest.mark.parametrize('length', [3, 100, 127, 128, 129, 255, 256, 257,
                                    300])
def test_chunked_prefill_and_one_step_decode_match_the_scan(model, length):
    """Prompt lengths on both sides of a 128-position block and of the
    256-token chunk, and one shorter than the convolution's taps."""
    cfg, params, oracle = model
    prompt = prompt_of(length)
    got, toks = _prefill_then_decode(cfg, params, prompt, 4)
    want = oracle.logits(prompt + toks)[length - 1:]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize('switch', ['norm_before_gate', 'no_norm',
                                    'conv_without_bias'])
def test_the_mixer_switches_follow_the_reference(switch):
    cfg = tiny_cfg(**{
        'norm_before_gate': {'ssm_norm_before_gate': True},
        'no_norm': {'ssm_gated_norm': False},
        'conv_without_bias': {'ssm_conv_bias': False}}[switch])
    params = make_params(cfg)
    oracle = Oracle(cfg, params)
    prompt = prompt_of(140)
    got = Transformer(cfg).apply({'params': params},
                                 jnp.asarray([prompt], jnp.int32))[0]
    np.testing.assert_allclose(np.asarray(got), oracle.logits(prompt),
                               atol=TOL, rtol=TOL)
    # and the switch changes the answer
    base = tiny_cfg()
    if switch != 'conv_without_bias':
        other = Transformer(base).apply(
            {'params': make_params(base)}, jnp.asarray([prompt], jnp.int32))
        assert float(jnp.abs(other[0] - got).max()) > 1e-3


def test_pads_and_inert_rows_leave_the_state_bit_for_bit(model):
    """Right pads advance neither state whatever they hold, and a decode
    row whose count is 0 keeps both states bit for bit."""
    cfg, params, _ = model
    net, cache = _decode_net(cfg, 3)
    rng = np.random.default_rng(0)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        cache)
    real = prompt_of(37)

    def chunk(pad_with):
        toks = jnp.asarray([real + pad_with] * 3, jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (3, 64))
        _, mut = net.apply(
            {'params': params, 'cache': cache}, toks, pos,
            state_rows=(None, jnp.asarray([37, 37, 37], jnp.int32)),
            mutable=['cache'])
        return mut['cache']['layers']['layer']['mixer']

    zeros, noise = chunk([0] * 27), chunk(prompt_of(27, 9))
    for name in ('ssm_state', 'conv_state'):
        assert np.array_equal(np.asarray(zeros[name]),
                              np.asarray(noise[name])), name
    # a decode step over three rows of which the middle one is inert
    _, mut = net.apply(
        {'params': params, 'cache': cache},
        jnp.asarray([[5], [6], [7]], jnp.int32),
        jnp.asarray([[9], [0], [11]], jnp.int32),
        state_rows=(None, jnp.asarray([1, 0, 1], jnp.int32)),
        mutable=['cache'])
    before = cache['layers']['layer']['mixer']
    after = mut['cache']['layers']['layer']['mixer']
    for name in ('ssm_state', 'conv_state'):
        old, new = np.asarray(before[name]), np.asarray(after[name])
        assert np.array_equal(old[:, 1], new[:, 1]), name
        assert not np.array_equal(old[:, 0], new[:, 0]), name
        assert not np.array_equal(old[:, 2], new[:, 2]), name


# ---- through the engines ---------------------------------------------------

def _engine(cfg, params, paged: bool, slots: int = 4, **kw):
    return ContinuousBatchingEngine(
        cfg, params=params, num_slots=slots,
        paged_block_size=16 if paged else 0, **kw)


def _tap_first_logits(engine, into: list):
    """Copy every prompt's last prefill logits into `into` where the
    engine seeds a first token, sampled on the host (a synchronous
    engine) or on the device (the default). Returns the untapped
    method."""
    real = engine._first_token

    def tapped(slots, slot, req, row, position):
        into.append(np.asarray(row))
        return real(slots, slot, req, row, position)

    engine._first_token = tapped
    return real


@pytest.mark.parametrize('paged', [True, False],
                         ids=['paged', 'contiguous'])
def test_the_batching_engine_serves_what_the_reference_would(model, paged):
    """Prefill (one chunk, two chunks, a chunk's edge) then decode
    through the per-slot state, more requests than slots so that slots
    are reused: every served token is the reference's best to 2e-4, and
    the first token's logits agree."""
    cfg, params, oracle = model
    engine = _engine(cfg, params, paged, slots=3)
    first_logits = []
    _tap_first_logits(engine, first_logits)
    try:
        prompts = [prompt_of(n) for n in (5, 100, 256, 257, 300, 2, 40)]
        futs = [engine.submit(p, max_new_tokens=10) for p in prompts]
        outs = [f.result(timeout=600)[0] for f in futs]
    finally:
        engine.stop()
    for p, toks in zip(prompts, outs):
        assert len(toks) == 10
        assert oracle.worst_gap(p, toks) <= TOL
    # first tokens land in the order the prompts' last chunks do: pair
    # each captured row with the prompt whose reference row it matches
    want = [oracle.logits(p)[-1] for p in prompts]
    matched = set()
    for got in first_logits:
        errs = [float(np.abs(got - w).max()) for w in want]
        assert min(errs) <= TOL, errs
        matched.add(int(np.argmin(errs)))
    assert matched == set(range(len(prompts)))


def test_the_inference_engine_takes_the_same_module(model):
    cfg, params, oracle = model
    engine = InferenceEngine(cfg, params=params, batch_size=2)
    prompts = np.asarray([prompt_of(37), prompt_of(37, 1)], np.int32)
    out, _ = engine.generate(jnp.asarray(prompts), max_new_tokens=8)
    chunked = InferenceEngine(cfg, params=params, batch_size=2,
                              decode_chunk=4)
    out4, _ = chunked.generate(jnp.asarray(prompts), max_new_tokens=8)
    for b in range(2):
        assert oracle.worst_gap(prompts[b].tolist(),
                                np.asarray(out[b]).tolist()) <= TOL
    assert np.array_equal(np.asarray(out), np.asarray(out4))


def _serve(engine, first, others=(), other_new: int = 6,
           first_new: int = 12, after_tokens: int = 0):
    """`first` goes to slot 0; `others` are sent with it, or once it has
    produced `after_tokens` tokens. Returns what decides a request's
    stream: its first token's logits, its tokens, and its slot's
    recurrent state once it is done."""
    import threading
    logits = []
    untapped = _tap_first_logits(engine, logits)
    seen, enough = [], threading.Event()

    def on_token(tok):
        seen.append(tok)
        if len(seen) >= after_tokens:
            enough.set()

    fut = engine.submit(first, max_new_tokens=first_new, on_token=on_token)
    if after_tokens:
        assert enough.wait(timeout=600)
    futs = [engine.submit(p, max_new_tokens=other_new) for p in others]
    toks = fut.result(timeout=600)[0]
    for f in futs:
        f.result(timeout=600)
    engine._first_token = untapped
    mixer = engine._cache['layers']['layer']['mixer']
    state = {n: np.asarray(mixer[n])[:, 0] for n in ('ssm_state',
                                                     'conv_state')}
    return logits[0], toks, state


def _same(a, b) -> None:
    assert np.array_equal(a[0], b[0]), 'first-token logits differ'
    assert a[1] == b[1], 'tokens differ'
    for name in a[2]:
        assert np.array_equal(a[2][name], b[2][name]), name


@pytest.mark.parametrize('paged', [True, False],
                         ids=['paged', 'contiguous'])
def test_a_request_is_bit_identical_alone_and_in_a_full_batch(model,
                                                              paged):
    """A short prompt, padded to the chunk: its first logits, its tokens
    and the state it leaves are the same bits whatever rides along."""
    cfg, params, _ = model
    prompt = prompt_of(21)
    engine = _engine(cfg, params, paged)
    try:
        alone = _serve(engine, prompt)
    finally:
        engine.stop()
    engine = _engine(cfg, params, paged)
    try:
        crowd = _serve(engine, prompt,
                       [prompt_of(n, 3) for n in (33, 70, 130)],
                       other_new=20)
    finally:
        engine.stop()
    _same(alone, crowd)


@pytest.mark.parametrize('paged', [True, False],
                         ids=['paged', 'contiguous'])
def test_the_ring_rides_through_churn_with_the_state_exact(model, paged):
    """Depth 0 against the default. Slot 0's request finishes by length
    while its neighbours decode on, so with the ring up its row rides
    inert through steps queued before its last token was read: its
    first logits, its tokens and the state it leaves are the same bits,
    more requests than slots reuse every slot (a rejoined slot's state
    is reset), and nothing is flushed."""
    cfg, params, _ = model
    prompt = prompt_of(21)
    crowd = [prompt_of(n, 3) for n in (33, 70, 300, 5, 130, 18)]
    runs, tokens = [], []
    for depth in (0, 1):
        engine = _engine(cfg, params, paged, slots=3, async_depth=depth)
        try:
            runs.append(_serve(engine, prompt, crowd[:2], other_new=20,
                               first_new=5))
            futs = [engine.submit(p, max_new_tokens=4 + 3 * i)
                    for i, p in enumerate(crowd)]
            tokens.append([f.result(timeout=600)[0] for f in futs])
            stats = dict(engine.tick_stats)
        finally:
            engine.stop()
    _same(runs[0], runs[1])
    assert tokens[0] == tokens[1]
    assert stats['flushes'] == 0, stats
    assert stats['chained'] > 0.8 * stats['dispatches'], stats


def test_a_reused_slot_never_sees_the_last_request(model):
    cfg, params, _ = model
    prompt = prompt_of(45)
    engine = _engine(cfg, params, True, slots=1)
    try:
        fresh = _serve(engine, prompt)
        engine.generate(prompt_of(200, 5), max_new_tokens=9)
        reused = _serve(engine, prompt)
    finally:
        engine.stop()
    _same(fresh, reused)


def test_a_neighbour_prefilling_in_the_same_tick_changes_nothing(model):
    """A three-chunk prompt arrives while the request decodes: its
    chunks and the request's decode steps share ticks."""
    cfg, params, _ = model
    prompt = prompt_of(30)
    engine = _engine(cfg, params, True, slots=2)
    try:
        alone = _serve(engine, prompt, first_new=24)
    finally:
        engine.stop()
    engine = _engine(cfg, params, True, slots=2)
    try:
        beside = _serve(engine, prompt, [prompt_of(480, 7)], first_new=24,
                        after_tokens=3)
        log = list(engine.step_log)
    finally:
        engine.stop()
    _same(alone, beside)
    kinds = [e[0] == 'prefill' for e in log]
    first_chunk = kinds.index(True, 1)     # the neighbour's first chunk
    assert any(not k for k in kinds[first_chunk:first_chunk + 4]), \
        'no decode step between the neighbour\'s chunks'


def test_slot_preemption_resumes_from_position_zero(model):
    """A preempted request prefills prompt and answer again, so the
    state it resumes from is rebuilt, not recalled."""
    import threading
    cfg, params, oracle = model
    long_prompt, short = prompt_of(60), prompt_of(9, 2)
    engine = _engine(cfg, params, True, slots=1)
    try:
        want = engine.generate(long_prompt, max_new_tokens=20)[0]
        started = threading.Event()
        seen = []
        fut = engine.submit(
            long_prompt, max_new_tokens=20, priority='batch',
            on_token=lambda t: (seen.append(t),
                                len(seen) >= 4 and started.set()))
        assert started.wait(timeout=600)
        urgent = engine.submit(short, max_new_tokens=4,
                               priority='interactive')
        urgent.result(timeout=600)
        got = fut.result(timeout=600)[0]
        assert engine.tenancy_stats['slot_preempts'] >= 1
    finally:
        engine.stop()
    assert got == want
    assert oracle.worst_gap(long_prompt, got) <= TOL


# ---- levers ------------------------------------------------------------------

@pytest.mark.parametrize('lever, kw', [
    ('speculative=2', {'speculative': 2}),
    ('prefix_cache=4', {'prefix_cache': 4}),
    ("tier='prefill'", {'tier': 'prefill', 'prefix_cache': 0}),
    ("tier='decode'", {'tier': 'decode'}),
])
def test_a_lever_that_takes_state_for_kv_blocks_refuses_by_name(lever, kw):
    with pytest.raises(NotImplementedError) as e:
        ContinuousBatchingEngine(tiny_cfg(), num_slots=2,
                                 paged_block_size=16, **kw)
    assert lever in str(e.value) and 'recurrent state' in str(e.value)


def test_a_tp_mesh_refuses_by_name():
    from skypilot_tpu.models.inference import infer_serving_tp
    from skypilot_tpu.parallel.mesh import decode_mesh
    with pytest.raises(NotImplementedError, match='tp=2'):
        tiny_cfg().assert_tp_compatible(2)
    with pytest.raises(NotImplementedError, match='tp=2'):
        ContinuousBatchingEngine(tiny_cfg(), num_slots=2,
                                 mesh=decode_mesh(2))
    assert infer_serving_tp(tiny_cfg(), 8) == 1


@pytest.mark.parametrize('call', [
    lambda e: e.export_prefixes('/tmp/never-written'),
    lambda e: e.import_prefixes('/tmp/never-read'),
    lambda e: e.prefill_prefix([1, 2, 3]),
    lambda e: e.export_prefix_chunks([1, 2, 3], 's'),
    lambda e: e.ingest_chunk(b''),
], ids=['export_prefixes', 'import_prefixes', 'prefill_prefix',
        'export_prefix_chunks', 'ingest_chunk'])
def test_the_block_stream_methods_refuse_when_called(model, call, request):
    cfg, params, _ = model
    engine = _engine(cfg, params, True, slots=1)
    with pytest.raises(NotImplementedError) as e:
        call(engine)
    assert request.node.callspec.id in str(e.value)


@pytest.mark.parametrize('kw', [{'decode_chunk': 4}, {'async_depth': 2},
                                {'kv_quant': 'int8'},
                                {'top_k': 8, 'top_p': 0.9}],
                         ids=['decode_chunk', 'async_depth', 'kv_quant',
                              'top_k_top_p'])
def test_the_other_levers_keep_working(model, kw):
    cfg, params, oracle = model
    plain = _engine(cfg, params, True, slots=2)
    engine = _engine(cfg, params, True, slots=2, **kw)
    prompts = [prompt_of(50), prompt_of(131, 1), prompt_of(7, 2)]
    try:
        want = [plain.generate(p, max_new_tokens=9)[0] for p in prompts]
        futs = [engine.submit(p, max_new_tokens=9) for p in prompts]
        got = [f.result(timeout=600)[0] for f in futs]
    finally:
        plain.stop()
        engine.stop()
    if 'kv_quant' in kw:
        # int8 K/V is another rounding: the served tokens stay close to
        # the reference's best, not at it
        assert all(oracle.worst_gap(p, t) < 0.5
                   for p, t in zip(prompts, got))
    else:
        assert got == want


def test_int8_weights_run_and_differ(model):
    """The mixer's two projections are quantized with the rest (the
    decay, the step bias, the skip, the convolution and the norm stay
    float), so int8 is a whole-model path."""
    cfg, params, oracle = model
    engine = _engine(cfg, params, True, slots=2, quantize='int8')
    try:
        mixer = engine.params['layers']['layer']['mixer']
        assert mixer['in_proj']['kernel_q'].dtype == jnp.int8
        assert mixer['out_proj']['kernel_q'].dtype == jnp.int8
        assert 'kernel' not in mixer['in_proj']
        for name in ('A_log', 'D', 'dt_bias', 'conv_kernel', 'conv_bias',
                     'norm_scale'):
            assert mixer[name].dtype == jnp.float32
        first = []
        _tap_first_logits(engine, first)
        prompt = prompt_of(90)
        toks = engine.generate(prompt, max_new_tokens=6)[0]
    finally:
        engine.stop()
    assert len(toks) == 6
    err = np.abs(first[0] - oracle.logits(prompt)[-1]).max()
    assert 1e-3 < err < 0.5, err       # rounded weights: near, not equal


# ---- counters, footprint, the span -------------------------------------------

def test_the_state_is_counted_and_the_span_counts_chunks(model):
    cfg, params, _ = model
    engine = _engine(cfg, params, True, slots=3)
    tracing.enable()
    try:
        with tracing.span('test.request'):
            fut = engine.submit(prompt_of(300), max_new_tokens=3)
        with tracing.span('test.request'):
            fut2 = engine.submit(prompt_of(20), max_new_tokens=3)
        fut.result(timeout=600)
        fut2.result(timeout=600)
        spans = [s for s in tracing.snapshot()
                 if s['name'] == 'engine.prefill']
        occ = engine.paged_occupancy()
        foot = engine.memory_footprint()
    finally:
        tracing.disable()
        engine.stop()
    # a slot, a layer: 4 x 8 x 16 float32 of scan state and 3 x (32 + 2 x
    # 2 x 16) float32 of convolution inputs
    per_slot_layer = 4 * 8 * 16 * 4 + 3 * 96 * 4
    assert occ['state_bytes'] == 3 * 2 * per_slot_layer
    # the pool: 3 slots x 32 blocks + scratch, K and V, 2 kv heads x 16
    assert occ['kv_pool_bytes'] == 2 * 2 * (3 * 32 + 1) * 16 * 2 * 16 * 4
    assert occ['state_slots_used'] == 0
    assert occ['scan_positions'] == 3 * 256 and occ['scan_tokens'] == 320
    assert foot['state_bytes'] == occ['state_bytes']
    assert foot['kv_bytes'] == occ['state_bytes'] + occ['kv_pool_bytes']
    assert sorted((s['attrs']['prompt_tokens'], s['attrs']['chunks'])
                  for s in spans) == [(20, 1), (300, 2)]


def test_other_models_carry_no_state_and_count_none():
    engine = ContinuousBatchingEngine('test-tiny', num_slots=2,
                                      paged_block_size=16)
    try:
        engine.generate([1, 2, 3], max_new_tokens=2)
        occ = engine.paged_occupancy()
        leaves = jax.tree_util.tree_leaves_with_path(engine._cache)
    finally:
        engine.stop()
    assert occ['state_bytes'] == 0 and occ['scan_positions'] == 0
    assert occ['kv_pool_bytes'] > 0 and occ['state_slots_used'] == 0
    assert not any('state' in str(path) for path, _ in leaves)


# ---- the checkpoint's names ---------------------------------------------------

def test_a_falcon_h1_checkpoint_maps_to_the_tree_both_ways(model):
    cfg, params, _ = model
    sd = convert.to_hf(params, cfg)
    pre = 'model.layers.1.'
    for key in ('mamba.in_proj.weight', 'mamba.conv1d.weight',
                'mamba.conv1d.bias', 'mamba.A_log', 'mamba.D',
                'mamba.dt_bias', 'mamba.norm.weight',
                'mamba.out_proj.weight', 'self_attn.q_proj.weight',
                'self_attn.k_proj.weight', 'self_attn.v_proj.weight',
                'self_attn.o_proj.weight', 'feed_forward.gate_proj.weight',
                'feed_forward.up_proj.weight',
                'feed_forward.down_proj.weight', 'input_layernorm.weight',
                'pre_ff_layernorm.weight'):
        assert pre + key in sd, key
    assert {'model.embed_tokens.weight', 'model.final_layernorm.weight',
            'lm_head.weight'} <= set(sd)
    # torch's layouts: Linear [out, in], depthwise Conv1d [ch, 1, taps]
    assert sd[pre + 'mamba.in_proj.weight'].shape == (
        cfg.ssm_proj_width, cfg.d_model)
    assert sd[pre + 'mamba.conv1d.weight'].shape == (
        cfg.ssm_conv_channels, 1, cfg.ssm_conv)
    back = convert.from_hf(sd, cfg)
    want = jax.tree_util.tree_leaves_with_path(params)
    got = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(want) == len(got)
    for path, leaf in want:
        assert np.array_equal(np.asarray(leaf), got[path]), path
    # a tensor the architecture does not consume is refused by name
    sd['model.layers.0.mamba.extra.weight'] = np.zeros(3, np.float32)
    with pytest.raises(ValueError, match='mamba.extra'):
        convert.from_hf(sd, cfg)
