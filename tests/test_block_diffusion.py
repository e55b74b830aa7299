"""Generation by diffusion over blocks (SDAR-30B-A3B-Chat's mechanism)
through the normal serving path at a tiny size: the block-causal mask
(`ModelConfig.last_key_seen`, the one helper every XLA attention site
masks by), the engine's block mode (prefill to the prompt's whole blocks
with no first token, a jitted block step that yields none or several
tokens a slot, K/V written on commit, the lookahead ring riding through
it), and every lever that refuses it by name.

The oracle is the benchmark's plain reference
(perf/references/sdar_moe.py: float32, one sequence at a time, no cache,
the published loop), loaded by path; it imports nothing of the program.
Weights are flax's own draws.

Tolerances. Program against reference in float32 on the CPU: 2e-4 on
unit-scale logits. Generation is compared exactly (tokens and the pass
that unmasked each) wherever the reference's own margins, between the
least confidence a pass unmasked and the largest it left masked, are
above 1e-4; a tie that close is rounding's to break.
"""
import dataclasses
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu.models import get_config
from skypilot_tpu.models.inference import (ContinuousBatchingEngine,
                                           InferenceEngine)
from skypilot_tpu.models.transformer import Transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256
MASK = 250
B = 4


def tiny(**kw):
    """8 experts of 32, 2 a token, two layers, blocks of 4 in 4 steps."""
    base = dict(vocab_size=VOCAB, d_model=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim_override=16, d_mlp=128,
                max_seq_len=64, num_experts=8, experts_per_token=2,
                d_expert=32, mask_token_id=MASK, dtype='float32',
                param_dtype='float32')
    base.update(kw)
    return get_config('sdar-30b-a3b-chat', **base)


def _load(kind: str):
    spec = importlib.util.spec_from_file_location(
        f'sdar_moe_{kind}',
        os.path.join(ROOT, 'perf', kind, 'sdar_moe.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def ref():
    return _load('references')


def ref_cfg(cfg, **kw) -> dict:
    out = {'hidden_size': cfg.d_model, 'rms_norm_eps': cfg.norm_eps,
           'rope_theta': cfg.rope_theta,
           'num_experts_per_tok': cfg.experts_per_token,
           'norm_topk_prob': cfg.route_norm,
           'block_length': cfg.block_length,
           'denoising_steps': cfg.denoising_steps,
           'mask_token_id': cfg.mask_token_id}
    out.update(kw)
    return out


def init_params(cfg, seed: int = 0):
    model = Transformer(dataclasses.replace(cfg, decode=False))
    return nn.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.ones((1, 8), jnp.int32)))['params']


def reference_weights(params):
    """(layer_weights, whole) under the reference's names, by the
    family's own table of the program's paths."""
    family = _load('families')
    flat = {'/'.join(str(getattr(k, 'key', k)) for k in path): leaf
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(flat) == set(family.LAYER) | set(family.WHOLE)
    layer = lambda l: {name: jnp.asarray(flat[path][l], jnp.float32)
                       for path, name in family.LAYER.items()}
    whole = lambda name: jnp.asarray(
        flat[next(p for p, n in family.WHOLE.items() if n == name)],
        jnp.float32)
    return layer, whole


@pytest.fixture(scope='module')
def model():
    cfg = tiny()
    params = init_params(cfg)
    return cfg, params, reference_weights(params)


def published_loop(ref, model, prompt, max_new, **kw):
    cfg, _, (layer, whole) = model
    return ref.generate(list(prompt), max_new, whole, layer,
                        cfg.num_layers, ref_cfg(cfg, **kw))


def margins_hold(log, least: float = 1e-4) -> bool:
    """True where every pass of the reference's loop chose clearly: the
    least confidence it unmasked against the largest it left."""
    for _blk, _step, conf, took in log:
        left = [c for p, c in conf.items() if p not in took]
        if left and min(conf[p] for p in took) - max(left) < least:
            return False
    return True


def assert_is_the_published_loop(ref, model, prompt, max_new, tokens,
                                 stats, **kw):
    want, passes, log = published_loop(ref, model, prompt, max_new, **kw)
    assert len(tokens) == max_new == len(stats['unmask_pass'])
    if margins_hold(log):
        assert tokens == want, (prompt, max_new)
        assert stats['unmask_pass'] == passes, (prompt, max_new)
    # the cut-off positions complete the last block
    assert (len(prompt) + max_new + len(stats['overshoot_tokens'])) % B == 0
    assert len(stats['overshoot_pass']) == len(stats['overshoot_tokens'])


def engine_for(cfg, params, **kw):
    base = dict(num_slots=4, paged_block_size=16, rng_seed=3)
    base.update(kw)
    return ContinuousBatchingEngine(cfg, params=params, **base)


@pytest.fixture(scope='module')
def engine(model):
    cfg, params, _ = model
    eng = engine_for(cfg, params)
    yield eng
    eng.stop()


def prompt_of(n: int, seed: int = 0) -> list:
    return np.random.default_rng([n, seed]).integers(
        0, MASK, size=n).tolist()


# ---- the mask ---------------------------------------------------------------

def test_the_helper_says_where_a_query_stops_seeing():
    cfg = tiny()
    assert [cfg.last_key_seen(p) for p in range(9)] == \
        [3, 3, 3, 3, 7, 7, 7, 7, 11]
    np.testing.assert_array_equal(
        cfg.last_key_seen(np.arange(6)), [3, 3, 3, 3, 7, 7])
    # block length 0 is the causal mask, and traces nothing: the very
    # array comes back
    q = jnp.arange(5)
    assert get_config('test-tiny').last_key_seen(q) is q
    assert cfg.unmask_schedule() == (1, 1, 1, 1)
    assert tiny(denoising_steps=3).unmask_schedule() == (2, 1, 1)
    assert tiny(denoising_steps=1).unmask_schedule() == (4,)
    with pytest.raises(ValueError, match='denoising_steps'):
        tiny(denoising_steps=5)
    with pytest.raises(ValueError, match='mask_token_id'):
        tiny(mask_token_id=-1)


def test_the_stack_matches_the_reference_under_the_block_causal_mask(
        ref, model):
    cfg, params, (layer, whole) = model
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (2, 22)), jnp.int32)
    want = Transformer(dataclasses.replace(cfg, decode=False)).apply(
        {'params': params}, tokens)
    rcfg = ref_cfg(cfg)
    hidden = ref.hidden_states(tokens, whole, layer, cfg.num_layers, rcfg)
    got = ref.logits_at(hidden.reshape(-1, hidden.shape[-1]), whole, rcfg)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=TOL, rtol=TOL)
    # the mask is really the block's: made causal, the logits differ
    causal = ref.hidden_states(tokens, whole, layer, cfg.num_layers,
                               dict(rcfg, causal=True))
    causal = ref.logits_at(causal.reshape(-1, causal.shape[-1]), whole,
                           rcfg)
    assert float(jnp.abs(causal - got).max()) > 1e-2


def test_two_streams_give_every_blocks_state_at_one_pass(ref, model):
    """`block_hidden_states` against the plain forward it stands for:
    the noisy half at block b is the clean forward of [clean blocks
    before b | noisy block b]."""
    cfg, _, (layer, whole) = model
    rcfg = ref_cfg(cfg)
    rng = np.random.default_rng(1)
    clean = rng.integers(0, MASK, (1, 16))
    noisy = np.where(rng.random((1, 16)) < 0.5, MASK, clean)
    both = ref.block_hidden_states(jnp.asarray(clean), jnp.asarray(noisy),
                                   whole, layer, cfg.num_layers, rcfg)
    for blk in range(4):
        seq = clean.copy()
        seq[:, blk * B:(blk + 1) * B] = noisy[:, blk * B:(blk + 1) * B]
        one = ref.hidden_states(jnp.asarray(seq), whole, layer,
                                cfg.num_layers, rcfg)
        np.testing.assert_allclose(
            np.asarray(both[:, blk * B:(blk + 1) * B]),
            np.asarray(one[:, blk * B:(blk + 1) * B]), atol=1e-5, rtol=1e-5)


# ---- the engine against the published loop ------------------------------------

@pytest.mark.parametrize('max_new', [1, B, B + 1, 7])
@pytest.mark.parametrize('length', [1, B - 1, B, B + 1, 16])
def test_the_engine_is_the_published_loop(ref, model, engine, length,
                                          max_new):
    """Prefill to the prompt's whole blocks, block steps through the
    paged cache, commit: for a prompt shorter than a block, one short of
    it, whole blocks, one over, and a whole KV block; for an answer of
    one token, a block, one over, and not a multiple."""
    prompt = prompt_of(length)
    tokens, stats = engine.submit(prompt, max_new_tokens=max_new).result(
        timeout=300)
    assert_is_the_published_loop(ref, model, prompt, max_new, tokens,
                                 stats)
    # the plan was exact: a block of m masks is m passes and a commit,
    # the last block needs no commit
    tail = length % B
    blocks = -(-(tail + max_new) // B)
    assert stats['passes'] == (B - tail + 1) + (blocks - 1) * (B + 1) - 1
    assert stats['new_tokens'] == max_new and stats['ttft_s'] > 0


def test_an_answer_may_fill_the_context_to_its_last_position(ref, model,
                                                            engine):
    cfg = model[0]
    for length in (40, 41, 43):
        prompt, max_new = prompt_of(length), cfg.max_seq_len - length
        tokens, stats = engine.submit(
            prompt, max_new_tokens=max_new).result(timeout=300)
        assert_is_the_published_loop(ref, model, prompt, max_new, tokens,
                                     stats)
        assert stats['overshoot_tokens'] == []
    with pytest.raises(ValueError, match='exceeds max_seq_len'):
        engine.submit(prompt_of(41), max_new_tokens=24)


@pytest.mark.parametrize('steps', [1, 2, 4])
def test_the_schedule_is_the_models_own(ref, model, steps):
    cfg, params, _ = model
    cfg = dataclasses.replace(cfg, denoising_steps=steps)
    eng = engine_for(cfg, params)
    try:
        for length, max_new in ((5, 9), (8, 4), (2, 6)):
            prompt = prompt_of(length, seed=steps)
            tokens, stats = eng.submit(
                prompt, max_new_tokens=max_new).result(timeout=300)
            assert_is_the_published_loop(ref, model, prompt, max_new,
                                         tokens, stats,
                                         denoising_steps=steps)
            assert max(stats['unmask_pass']) < steps
    finally:
        eng.stop()


def test_a_prompt_may_hold_the_mask_tokens_id(ref, model, engine):
    """Masks are a flag a position, never an id compared: a prompt with
    the mask token's id in a whole block and in its tail is a prompt."""
    prompt = prompt_of(10)
    prompt[2] = prompt[5] = prompt[9] = MASK
    tokens, stats = engine.submit(prompt, max_new_tokens=6).result(
        timeout=300)
    assert_is_the_published_loop(ref, model, prompt, 6, tokens, stats)
    # the tail's two positions were given, id or no id: the first block
    # had two masks to clear
    assert sorted(stats['unmask_pass'][:2]) == [0, 1]


REQUESTS = [(1, 3), (3, 9), (4, 5), (6, 12), (16, 7), (21, 10), (9, 4)]


def served(cfg, params, order, stagger: float = 0.0, **kw) -> dict:
    eng = engine_for(cfg, params, **kw)
    try:
        futs = {}
        for i in order:
            length, max_new = REQUESTS[i]
            futs[i] = eng.submit(prompt_of(length, seed=9),
                                 max_new_tokens=max_new)
            if stagger:
                time.sleep(stagger)
        out = {i: f.result(timeout=300) for i, f in futs.items()}
        occ = eng.paged_occupancy()
        return {i: (toks, st['unmask_pass'], st['overshoot_tokens'])
                for i, (toks, st) in out.items()}, occ
    finally:
        eng.stop()


@pytest.fixture(scope='module')
def alone(model):
    cfg, params, _ = model
    return served(cfg, params, range(len(REQUESTS)), num_slots=1,
                  async_depth=0)


@pytest.mark.parametrize('how', [
    dict(async_depth=0), dict(async_depth=1), dict(async_depth=2),
    dict(num_slots=2), dict(num_slots=3, order=[6, 5, 4, 3, 2, 1, 0]),
    dict(stagger=0.05, async_depth=2), dict(stagger=0.02, num_slots=2)])
def test_tokens_do_not_depend_on_slot_company_join_or_depth(model, alone,
                                                            how):
    """One request a time on one slot with synchronous ticks, against
    the same requests in company: other slots, other batch mates, joins
    while blocks are under way, the ring at depth 0, 1 and 2."""
    cfg, params, _ = model
    how = dict(how)
    order = how.pop('order', range(len(REQUESTS)))
    got, occ = served(cfg, params, order, **how)
    assert got == alone[0]
    if how.get('async_depth', 1) and not how.get('stagger'):
        # the ring runs in block mode: most passes were queued off the
        # device's feed while an earlier pass was unread
        assert occ['decode_chained'] > 0.5 * occ['decode_dispatches']
    assert occ['ring_flushes'] == 0


def test_the_paged_kernel_gives_the_gathers_tokens(model, alone):
    """A pass's four query rows a slot see their block's end (the
    block-causal bound) through the paged-attention kernel: the same
    tokens and unmask passes as the gather's, alone on one slot, and
    fewer blocks read than the window."""
    cfg, params, _ = model
    got, occ = served(cfg, params, range(len(REQUESTS)), num_slots=2,
                      decode_kernel='pallas_interpret')
    assert got == alone[0]
    assert 0 < occ['decode_blocks_read'] < occ['decode_window_blocks']


def test_passes_commits_and_tokens_are_counted_apart(alone):
    _, occ = alone
    tokens = sum(m for _, m in REQUESTS)
    assert occ['block_tokens'] == tokens and occ['block_length'] == B
    passes = 0
    for length, max_new in REQUESTS:
        tail = length % B
        blocks = -(-(tail + max_new) // B)
        passes += (B - tail + 1) + (blocks - 1) * (B + 1) - 1
        assert blocks >= 1
    assert occ['block_passes'] == passes == occ['decode_dispatches']
    commits = sum(-(-(l % B + m) // B) - 1 for l, m in REQUESTS)
    assert occ['block_commits'] == commits
    # one position unmasked a pass: a block of m masks forwards
    # m + (m - 1) + .. + 1 masked positions
    tri = lambda m: m * (m + 1) // 2
    masked = sum(tri(B - l % B) + (-(-(l % B + m) // B) - 1) * tri(B)
                 for l, m in REQUESTS)
    assert occ['block_masked_positions'] == masked
    assert 1.25 <= occ['block_passes'] / occ['block_tokens']


def test_inert_rows_route_nowhere_and_masked_positions_do(model):
    """One request on four slots: the router counts its B positions a
    pass, masked ones included, and nothing of the three inert rows; a
    chunk counts the prompt's whole blocks and no pad."""
    cfg, params, _ = model
    eng = engine_for(cfg, params)
    try:
        eng.submit(prompt_of(21), max_new_tokens=10).result(timeout=300)
        occ = eng.paged_occupancy()
    finally:
        eng.stop()
    k, layers = cfg.experts_per_token, cfg.num_layers
    assert occ['route_decode_calls'] == occ['block_passes'] > 0
    assert occ['route_decode_pairs_routed'] == \
        occ['block_passes'] * B * k * layers
    assert occ['route_decode_pairs_held'] == \
        occ['route_decode_pairs_routed']
    assert occ['block_masked_positions'] > 0
    assert occ['prefill_tokens'] == 20
    assert occ['route_chunk_pairs_routed'] == 20 * k * layers


def test_a_cancel_mid_block_leaves_no_block_held(ref, model):
    cfg, params, _ = model
    eng = engine_for(cfg, params, num_slots=2)
    try:
        seen = []
        fut = eng.submit(prompt_of(18), max_new_tokens=40,
                         on_token=seen.append)
        deadline = time.monotonic() + 120
        while len(seen) < 6 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert fut.cancel()
        while eng.paged_occupancy()['blocks_used'] > 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        # only the scratch block is left, and the pool adds up
        assert eng.paged_occupancy()['blocks_used'] == 1
        eng._pool.check()  # pylint: disable=protected-access
        assert 6 <= len([t for t in seen if t is not None]) < 40
        # whoever takes the blocks next reads nothing of it
        prompt = prompt_of(7, seed=5)
        tokens, stats = eng.submit(prompt, max_new_tokens=9).result(
            timeout=300)
        assert_is_the_published_loop(ref, model, prompt, 9, tokens, stats)
    finally:
        eng.stop()


def test_tokens_stream_a_block_at_a_time_in_order(model, engine):
    stamps = []
    tokens, _ = engine.submit(
        prompt_of(6), max_new_tokens=11,
        on_token=lambda t: stamps.append((t, time.monotonic()))
    ).result(timeout=300)
    assert [t for t, _ in stamps] == tokens + [None]
    # the first block had a tail of 2: 2 tokens, then 4, 4, and 1 kept
    # of the last: tokens of one block land in one emit
    lumps, last = [], None
    for _, t in stamps[:-1]:
        if last is None or t - last > 1e-4:
            lumps.append(0)
        lumps[-1] += 1
        last = t
    assert sum(lumps) == 11 and len(lumps) <= 4 and lumps[0] <= 2


def test_the_decode_span_counts_blocks_and_passes(model, engine):
    """The request's span carries the tokens it was given; its passes
    are counted where a metric reads them, in `block_stats`."""
    from skypilot_tpu.observability import tracing
    passes = engine.block_stats['block_passes']
    tracing.enable()
    try:
        with tracing.span('test.request'):
            fut = engine.submit(prompt_of(5), max_new_tokens=7)
        fut.result(timeout=300)
        spans = [s for s in tracing.snapshot()
                 if s['name'] == 'engine.decode']
    finally:
        tracing.disable()
        tracing.reset()      # the ring is the process's: leave it empty
    assert spans and spans[-1]['attrs'] == {
        'slot': spans[-1]['attrs']['slot'], 'new_tokens': 7}
    # tail 1: 3 masks + commit, then a block of 4 whose commit is not run
    assert engine.block_stats['block_passes'] - passes == 4 + 4


# ---- what refuses, by name ------------------------------------------------------

@pytest.mark.parametrize('lever, named', [
    (dict(speculative=2), 'speculative=2'),
    (dict(decode_chunk=4), 'decode_chunk=4'),
    (dict(prefix_cache=2), 'prefix_cache=2'),
    (dict(tier='prefill', prefix_cache=2), "tier='prefill'|prefix_cache"),
    (dict(quantize='int8'), "quantize='int8'"),
    (dict(kv_quant='int8'), "kv_quant='int8'"),
    (dict(max_adapters=2, adapter_rank=4), 'max_adapters=2'),
    (dict(paged_block_size=0), 'paged_block_size=0'),
    (dict(paged_block_size=6, max_seq_len=48), 'paged_block_size=6'),
])
def test_a_lever_that_cannot_take_block_mode_refuses_by_name(lever, named):
    """Before any weight is made: `params` is never touched."""
    kw = dict(num_slots=2, paged_block_size=16)
    kw.update(lever)
    with pytest.raises(NotImplementedError, match=named) as err:
        ContinuousBatchingEngine(tiny(), params=object(), **kw)
    assert 'diffusion over blocks' in str(err.value)


def test_the_rest_refuses_by_name_too(model, engine):
    cfg, params, _ = model
    with pytest.raises(NotImplementedError, match='tp=2.*diffusion over '
                                                  'blocks'):
        cfg.assert_tp_compatible(2)
    with pytest.raises(NotImplementedError, match='temperature=0.7'):
        engine.submit([1, 2, 3], max_new_tokens=4, temperature=0.7)
    with pytest.raises(NotImplementedError, match='InferenceEngine'):
        InferenceEngine(cfg, params=params)
    for method, args in (('export_prefixes', ('/nonexistent',)),
                         ('import_prefixes', ('/nonexistent',)),
                         ('prefill_prefix', ([1, 2, 3],)),
                         ('export_prefix_chunks', ([1, 2, 3], 'id')),
                         ('ingest_chunk', (b'',))):
        with pytest.raises(NotImplementedError, match=method):
            getattr(engine, method)(*args)


def test_an_autoregressive_model_is_what_it_was():
    """Block length 0: no block program is traced, no flag is carried,
    the futures' stats are the old ones."""
    eng = ContinuousBatchingEngine('test-tiny', num_slots=2,
                                   paged_block_size=16, rng_seed=1)
    try:
        tokens, stats = eng.submit([5, 6, 7, 8, 9],
                                   max_new_tokens=6).result(timeout=300)
        assert len(tokens) == 6 and 'unmask_pass' not in stats
        occ = eng.paged_occupancy()
        assert not any(k.startswith('block_') and k != 'block_size'
                       for k in occ)
        assert eng._decode_block._cache_size() == 0  # pylint: disable=protected-access
    finally:
        eng.stop()


def test_the_registry_entry_is_the_published_model():
    cfg = get_config('sdar-30b-a3b-chat')
    assert (cfg.vocab_size, cfg.d_model, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim) == (151936, 2048, 48, 32, 4,
                                                128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.d_shared_expert, cfg.num_dense_layers) == (128, 8, 768,
                                                           0, 0)
    assert (cfg.router_score, cfg.route_norm, cfg.route_scale,
            cfg.moe_impl, cfg.qk_norm) == ('softmax', True, 1.0,
                                           'dropless', True)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) \
        == (4, 4, 151669)
    assert cfg.rope_theta == 1e6 and not cfg.tie_embeddings
    assert cfg.has_layer_pattern
    layer = 18_874_624 + 262_144 + 603_979_776 + 4_096
    assert layer == 623_120_640
    assert cfg.num_params() == 48 * layer + 2 * 311_164_928 + 2048
    six = dataclasses.replace(cfg, num_layers=6)
    assert six.num_params() == 4_361_055_744        # 8.72 GB in bfloat16
