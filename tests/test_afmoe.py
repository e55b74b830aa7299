"""Trinity-Large-Preview's architecture (afmoe) through the normal
serving path at a tiny size: a dropless expert layer that knows its share
(models/moe.py), window and full attention mixed by layer behind a
leading dense layer (models/layer_pattern.py, models/cache_carry.py),
routing counters that ride in with the tokens (models/inference.py).

The oracle is the benchmark's plain reference
(perf/references/afmoe.py: float32, one sequence at a time, the held
experts one after another, no cache), loaded by path; it imports nothing
of the program. Weights are flax's own draws with the selection bias
redrawn N(0, 0.05^2), so that selection and weights differ.

Tolerances. Program against reference in float32 on the CPU: 2e-4 on
unit-scale outputs (the grouped products sum in another order than the
reference's plain ones; what is left is rounding, some 1e-6). The same
request alone and in company: 1e-5 (a row of a matrix product does not
depend on the other rows; the CPU's blocking may).
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu.models import get_config
from skypilot_tpu.models.inference import ContinuousBatchingEngine
from skypilot_tpu.models.moe import MoEBlock, ROUTE_COUNTS
from skypilot_tpu.models.transformer import Transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256
WINDOW = 8
KINDS = ((WINDOW, True),) * 4 + ((0, False),)


def tiny(**kw):
    """16 experts, 4 held (the second share), 2 a token, window 8, one
    leading dense layer, then sliding x 3 and one full layer."""
    base = dict(
        vocab_size=VOCAB, d_model=64, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim_override=16, d_mlp=128, max_seq_len=64,
        num_experts=16, experts_per_token=2, experts_held=4,
        first_expert=4, d_expert=32, d_shared_expert=48,
        num_dense_layers=1, layer_kinds=KINDS, dtype='float32',
        param_dtype='float32')
    base.update(kw)
    return get_config('trinity-large-preview', **base)


@pytest.fixture(scope='module')
def ref():
    spec = importlib.util.spec_from_file_location(
        'afmoe_reference',
        os.path.join(ROOT, 'perf', 'references', 'afmoe.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ref_cfg(cfg, **kw) -> dict:
    """The reference's dict for a program config."""
    out = {'hidden_size': cfg.d_model, 'rms_norm_eps': cfg.norm_eps,
           'rope_theta': cfg.rope_theta,
           'num_experts_per_tok': cfg.experts_per_token,
           'route_norm': cfg.route_norm, 'route_scale': cfg.route_scale,
           'first_expert': cfg.first_expert,
           'experts_held': cfg.held_experts,
           'num_dense_layers': cfg.num_dense_layers,
           'sliding_window': WINDOW,
           'layer_types': ['sliding_attention' if rope else
                           'full_attention' for _, rope in
                           cfg.layer_kinds]}
    out.update(kw)
    return out


def init_params(cfg, seed: int = 0):
    """flax's draws; the selection bias redrawn (its init is zeros)."""
    model = Transformer(dataclasses.replace(cfg, decode=False))
    params = nn.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)))['params']
    moe = params['layers']['layer']['moe']
    moe['expert_bias'] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), moe['expert_bias'].shape)
    return params


ATTN = {('attn_norm', 'scale'): 'attn_norm',
        ('attn', 'q_proj', 'kernel'): 'wq',
        ('attn', 'k_proj', 'kernel'): 'wk',
        ('attn', 'v_proj', 'kernel'): 'wv',
        ('attn', 'q_norm', 'scale'): 'q_norm',
        ('attn', 'k_norm', 'scale'): 'k_norm',
        ('attn', 'gate_proj', 'kernel'): 'w_attn_gate',
        ('attn', 'o_proj', 'kernel'): 'wo',
        ('post_attn_norm', 'scale'): 'post_attn_norm',
        ('mlp_norm', 'scale'): 'mlp_norm',
        ('post_mlp_norm', 'scale'): 'post_mlp_norm'}
DENSE = {('mlp', 'gate_proj', 'kernel'): 'w_gate',
         ('mlp', 'up_proj', 'kernel'): 'w_up',
         ('mlp', 'down_proj', 'kernel'): 'w_down'}
STACKS = ('w_gate', 'w_up', 'w_down')
MOE = {('router',): 'router', ('expert_bias',): 'expert_bias',
       ('shared', 'gate_proj', 'kernel'): 's_gate',
       ('shared', 'up_proj', 'kernel'): 's_up',
       ('shared', 'down_proj', 'kernel'): 's_down'}
EXPERTS = {('moe',) + p: n for p, n in MOE.items()}


def pick(tree, path):
    for name in path:
        tree = tree[name]
    return tree


def reference_weights(params):
    """(layer_weights, whole) as the reference takes them: entry i of
    both stacked groups, the dense group's names prefixed `d_`."""
    def layer_weights(i):
        out = {}
        for group, names, prefix in (
                ('dense_layers', {**ATTN, **DENSE}, 'd_'),
                ('layers', {**ATTN, **EXPERTS}, '')):
            stacked = params[group]['layer']
            for path, name in names.items():
                leaf = pick(stacked, path)
                out[prefix + name] = leaf[min(i, leaf.shape[0] - 1)]
        # the expert layers' stacks lie outside the loop, whole
        for name in STACKS:
            leaf = params['experts'][name]
            out[name] = leaf[min(i, leaf.shape[0] - 1)]
        return out

    whole = {'embed': params['embed']['embedding'],
             'final_norm': params['final_norm']['scale'],
             'lm_head': params['lm_head']['kernel']}
    return layer_weights, whole.__getitem__


def reference_logits(ref, cfg, params, tokens, **kw):
    layer_weights, whole = reference_weights(params)
    rcfg = ref_cfg(cfg, **kw)
    hidden = ref.hidden_states(tokens, whole, layer_weights,
                               cfg.num_layers, rcfg)
    return ref.logits_at(hidden.reshape(-1, hidden.shape[-1]), whole,
                         rcfg).reshape(tokens.shape + (-1,))


# ---- the dropless layer --------------------------------------------------

def moe_setup(seed: int = 0, **kw):
    cfg = tiny(**kw)
    block = MoEBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 32, 64))
    params = nn.unbox(block.init(jax.random.PRNGKey(seed + 1),
                                 x))['params']
    params['expert_bias'] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), (cfg.num_experts,))
    return cfg, block, x, params


def ref_moe_weights(params) -> dict:
    """A layer on its own holds its experts as a stack of one layer."""
    return dict({name: pick(params, path) for path, name in MOE.items()},
                **{name: params[name][0] for name in STACKS})


def test_the_dropless_layer_matches_the_reference_layer(ref):
    """16 experts, 4 held, 2 a token: the sorted pairs and the grouped
    products against the reference's walk over the held experts."""
    cfg, block, x, params = moe_setup()
    got = block.apply({'params': params}, x)
    w = ref_moe_weights(params)
    want = jnp.stack([ref.experts(row, w, ref_cfg(cfg)) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)
    # the bias decides a choice somewhere, and is not in the weights
    no_bias = jnp.stack([ref.experts(
        row, dict(w, expert_bias=jnp.zeros(16)), ref_cfg(cfg))
        for row in x])
    assert float(jnp.abs(no_bias - want).max()) > 1e-3
    weights = ref.route(x[0], w, ref_cfg(cfg))
    np.testing.assert_allclose(np.asarray(weights.sum(-1)),
                               cfg.route_scale, rtol=1e-5)
    assert int((weights > 0).sum()) == 2 * 32


def test_a_token_with_every_choice_elsewhere_gets_the_shared_expert(ref):
    cfg, block, x, params = moe_setup()
    w = ref_moe_weights(params)
    got = block.apply({'params': params}, x)
    held = ref.route(x[1], w, ref_cfg(cfg))[:, 4:8]
    alone = np.asarray(held.sum(-1) == 0)
    assert alone.any() and not alone.all()
    shared = ref.swiglu(x[1], w['s_gate'], w['s_up'], w['s_down'])
    np.testing.assert_allclose(np.asarray(got[1])[alone],
                               np.asarray(shared)[alone], atol=TOL)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The share test: the routed parts that the four shares of four
    experts give, with the shared expert counted once, are the uncut
    reference layer."""
    cfg, _, x, params = moe_setup(experts_held=16, first_expert=0)
    w = ref_moe_weights(params)
    uncut = jnp.stack([ref.experts(row, w, ref_cfg(cfg)) for row in x])
    shared = jnp.stack([ref.swiglu(row, w['s_gate'], w['s_up'],
                                   w['s_down']) for row in x])
    total = shared
    for first in (0, 4, 8, 12):
        share = tiny(experts_held=4, first_expert=first)
        mine = dict(params, **{n: params[n][:, first:first + 4]
                               for n in STACKS})
        total = total + (MoEBlock(share).apply({'params': mine}, x)
                         - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=TOL, rtol=TOL)
    assert float(jnp.abs(uncut - shared).max()) > 0.1


def test_pads_route_nowhere_and_count_nowhere():
    cfg, block, x, params = moe_setup()
    valid = jnp.arange(32)[None, :] < jnp.asarray([32, 5, 0])[:, None]
    _, stats = block.apply({'params': params}, x, valid,
                           mutable=['moe_stats'])
    counts = dict(zip(ROUTE_COUNTS, np.asarray(stats['moe_stats']['counts'])))
    assert counts['pairs_routed'] == 2 * 37
    _, whole = block.apply({'params': params}, x[:1], mutable=['moe_stats'])
    _, part = block.apply({'params': params}, x[1:2, :5],
                          mutable=['moe_stats'])
    both = (np.asarray(whole['moe_stats']['counts'])
            + np.asarray(part['moe_stats']['counts']))
    assert counts['pairs_held'] == both[1]
    assert 0 < counts['pairs_held'] < counts['pairs_routed']
    assert 1 <= counts['experts_touched'] <= 4
    assert counts['max_expert_load'] * 4 >= counts['pairs_held']
    # without the collection mutable nothing is sown, and init sows
    # nothing
    assert 'moe_stats' not in block.init(jax.random.PRNGKey(0), x)


def test_softmax_scoring_is_the_trainers_dense_formulation():
    """Dropless with Mixtral's scoring, every expert held and no shared
    expert, is the trainer's exact `dense` formulation."""
    kw = dict(router_score='softmax', router_bias=False, route_scale=1.0,
              experts_held=0, first_expert=0, d_expert=0,
              d_shared_expert=0, num_experts=4, d_mlp=32)
    cfg = tiny(**kw)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    params = nn.unbox(MoEBlock(cfg).init(jax.random.PRNGKey(6),
                                         x))['params']
    dense = dataclasses.replace(cfg, moe_impl='dense')
    own = dict(params, **{n: params[n][0] for n in STACKS})
    np.testing.assert_allclose(
        np.asarray(MoEBlock(cfg).apply({'params': params}, x)),
        np.asarray(MoEBlock(dense).apply({'params': own}, x)),
        atol=TOL, rtol=TOL)


# ---- the stack -------------------------------------------------------------

@pytest.fixture(scope='module')
def model():
    cfg = tiny()
    return cfg, init_params(cfg)


def test_the_stack_matches_the_reference_across_the_window(ref, model):
    """Context 4 x window, both kinds of layer present: logits to 2e-4;
    and the reference notices each kind's switch."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, VOCAB, (2, 4 * WINDOW)), jnp.int32)
    got = Transformer(cfg).apply({'params': params}, tokens)
    want = reference_logits(ref, cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)
    assert 0.3 < float(jnp.std(want)) < 3.0
    for switched in (dict(sliding_window=0),
                     dict(layer_types=['sliding_attention'] * 5),
                     dict(sliding_window=2 * WINDOW)):
        other = reference_logits(ref, cfg, params, tokens, **switched)
        assert float(jnp.abs(other - want).max()) > 1e-2, switched


def test_one_program_a_group_whatever_the_pattern(model):
    """The kinds are data: another pattern of the same groups lowers to
    the same program text."""
    cfg, params = model
    tokens = jnp.zeros((1, 16), jnp.int32)

    def text(kinds):
        c = dataclasses.replace(cfg, layer_kinds=kinds)
        return jax.jit(lambda p, t: Transformer(c).apply(
            {'params': p}, t)).lower(params, tokens).as_text()

    a = text(KINDS)
    b = text(((0, False),) + ((16, True), (0, False)) * 2)
    strip = lambda s: [l for l in s.splitlines() if 'dense<' not in l]
    assert strip(a) == strip(b)
    assert a != b        # the windows differ, as constants


def cached_logits(cfg, params, tokens, chunks, batch_rows):
    """Logits of every position through the decode cache: `chunks`
    prompt chunks, then one position a step. Contiguous cache."""
    dcfg = dataclasses.replace(cfg, decode=True)
    model = Transformer(dcfg)
    b, t = tokens.shape
    cache = nn.unbox(model.init(
        jax.random.PRNGKey(0), tokens[:, :1],
        jnp.zeros((b, 1), jnp.int32)))['cache']
    step = jax.jit(lambda c, tok, pos: model.apply(
        {'params': params, 'cache': c}, tok, pos, mutable=['cache']))
    out, start = [], 0
    spans = list(chunks) + [1] * (t - sum(chunks))
    for n in spans:
        pos = jnp.broadcast_to(jnp.arange(start, start + n)[None], (b, n))
        logits, mutated = step(cache, tokens[:, start:start + n], pos)
        cache = nn.unbox(mutated['cache'])
        out.append(logits)
        start += n
    return jnp.concatenate(out, axis=1)[batch_rows]


def test_a_requests_logits_do_not_depend_on_its_company(ref, model):
    """Batch invariance: the same request alone and among 7 others,
    through two prefill chunks and then decode steps, and both as the
    reference has them. Capacity dispatch would fail this."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, VOCAB, (8, 28)), jnp.int32)
    among = cached_logits(cfg, params, tokens, (8, 8), 3)
    alone = cached_logits(cfg, params, tokens[3:4], (8, 8), 0)
    np.testing.assert_allclose(np.asarray(among), np.asarray(alone),
                               atol=1e-5, rtol=1e-5)
    want = reference_logits(ref, cfg, params, tokens[3:4])[0]
    np.testing.assert_allclose(np.asarray(alone), np.asarray(want),
                               atol=TOL, rtol=TOL)


# ---- the engine ------------------------------------------------------------

PROMPTS = [np.random.default_rng(7).integers(0, VOCAB, n).tolist()
           for n in (5, 20, 33, 17, 40, 9)]
NEW = 12


def serve(cfg, params, depth: int, **kw):
    eng = ContinuousBatchingEngine(
        cfg, params=params, num_slots=4, max_seq_len=64, rng_seed=3,
        paged_block_size=16, prefill_chunk=16, async_depth=depth, **kw)
    try:
        futs = [eng.submit(p, max_new_tokens=NEW) for p in PROMPTS]
        toks = [f.result(timeout=300)[0] for f in futs]
        return toks, eng.paged_occupancy(), dict(eng.tick_stats)
    finally:
        eng.stop()


@pytest.fixture(scope='module')
def served(model):
    cfg, params = model
    return {depth: serve(cfg, params, depth) for depth in (0, 1)}


def test_the_engine_serves_it_and_the_ring_changes_no_token(ref, model,
                                                            served):
    cfg, params = model
    assert served[0][0] == served[1][0]
    assert served[1][2]['chained'] > 0
    # every served token is the reference's own choice, or within 1e-3
    # of it
    for prompt, toks in zip(PROMPTS, served[1][0]):
        seq = jnp.asarray([prompt + toks], jnp.int32)
        logits = np.asarray(reference_logits(ref, cfg, params, seq)[0])
        for j, tok in enumerate(toks):
            row = logits[len(prompt) + j - 1]
            assert row.max() - row[tok] < 1e-3, (j, tok)


@pytest.mark.parametrize('depth', [0, 1])
def test_pads_and_inert_slots_leave_the_counters_alone(served, depth):
    """Six requests on four slots, chunks of 16: what was routed is the
    real tokens' pairs, to the last one; a chunk's pads and the slots
    that sat out a decode step are nowhere in it."""
    _, occ, _ = served[depth]
    layers, k = 4, 2
    assert (occ['expert_layers'], occ['experts_held']) == (layers, 4)
    prompt_tokens = sum(len(p) for p in PROMPTS)
    assert occ['route_chunk_pairs_routed'] == prompt_tokens * k * layers
    assert occ['route_chunk_calls'] == sum(-(-len(p) // 16)
                                           for p in PROMPTS)
    assert occ['route_decode_pairs_routed'] == \
        len(PROMPTS) * (NEW - 1) * k * layers
    for kind in ('chunk', 'decode'):
        held = occ[f'route_{kind}_pairs_held']
        assert 0 < held < occ[f'route_{kind}_pairs_routed']
        calls = occ[f'route_{kind}_calls']
        assert calls <= occ[f'route_{kind}_experts_touched'] \
            <= calls * layers * 4
        assert occ[f'route_{kind}_max_expert_load'] * 4 >= held
    assert served[0][1]['route_decode_pairs_held'] == \
        served[1][1]['route_decode_pairs_held']


def test_a_model_without_experts_returns_what_it_returned():
    """The programs of a model that routes nothing keep their outputs
    and `paged_occupancy()` its keys."""
    eng = ContinuousBatchingEngine('test-tiny', num_slots=2,
                                   paged_block_size=16)
    try:
        eng.submit([1, 2, 3], max_new_tokens=3).result(timeout=300)
        assert not any(k.startswith('route_')
                       for k in eng.paged_occupancy())
        assert eng._mutable == ['cache']  # pylint: disable=protected-access
    finally:
        eng.stop()


@pytest.mark.parametrize('lever, named', [
    (dict(quantize='int8'), "quantize='int8'"),
    (dict(speculative=2), 'speculative=2'),
    (dict(decode_kernel='pallas_interpret', paged_block_size=16),
     "decode_kernel='pallas_interpret'"),
    (dict(tp=2), 'tp=2'),
])
def test_a_lever_that_cannot_take_it_refuses_by_name(lever, named):
    """At construction, before any weight is made."""
    lever = dict(lever)
    if lever.pop('tp', None):
        from skypilot_tpu.parallel.mesh import decode_mesh
        lever['mesh'] = decode_mesh(2)
    with pytest.raises(NotImplementedError, match=named) as err:
        ContinuousBatchingEngine(tiny(), num_slots=2, max_seq_len=64,
                                 **lever)
    assert 'trinity-large-preview' in str(err.value)


def test_a_dense_model_with_a_pattern_is_served_and_refuses_alike():
    """A layer pattern without experts is one dense group: served, with
    nothing routed; the window is still the layer's and the gate still
    `gate_proj`, so the fused kernel and int8 weights refuse."""
    cfg = tiny(num_experts=0, num_dense_layers=0)
    for lever in (dict(decode_kernel='pallas_interpret'),
                  dict(quantize='int8')):
        with pytest.raises(NotImplementedError, match='a layer pattern'):
            ContinuousBatchingEngine(cfg, num_slots=2, max_seq_len=64,
                                     paged_block_size=16, **lever)
    eng = ContinuousBatchingEngine(cfg, num_slots=2, max_seq_len=64,
                                   paged_block_size=16)
    try:
        toks, _ = eng.submit([5, 6, 7, 8], max_new_tokens=4).result(
            timeout=300)
        assert len(toks) == 4
        assert set(eng.params) == {'embed', 'final_norm', 'layers',
                                   'lm_head'}     # no 'experts'
        assert 'route_decode_calls' not in eng.paged_occupancy()
    finally:
        eng.stop()


def test_the_registry_entry_is_the_published_model():
    cfg = get_config('trinity-large-preview')
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.num_experts,
            cfg.vocab_size) == (60, 6, 256, 200192)
    assert cfg.layer_kinds.count((4096, True)) == 45
    assert cfg.layer_kinds[3::4] == ((0, False),) * 15
    # attention 62,914,816; dense MLP 113,246,208; an expert layer's
    # router 786,432 + 256, shared 28,311,552, experts 256 x 28,311,552
    attn, norms = 62_914_816, 4 * 3072
    dense = attn + norms + 113_246_208
    expert = attn + norms + 786_432 + 256 + 257 * 28_311_552
    assert cfg.num_params() == (2 * 200192 * 3072 + 3072 + 6 * dense
                                + 54 * expert)
    with pytest.raises(ValueError, match='layer_kinds names 60'):
        get_config('trinity-large-preview', num_layers=5)
