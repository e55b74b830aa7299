"""The third family in use: AFMoE (`arcee-ai/Trinity-Large-Preview`: a
dropless expert layer that knows its share behind a leading dense layer,
window and full attention mixed by layer), as files alone:
`families/afmoe.py`, `references/afmoe.py`, a configuration, a mix, a
cell, three readers. Its reference against `Transformer.apply` at a tiny
size, the family through the closed loop ending `correct` and its
lower-precision control not, what the harness refuses, and the required
work by hand."""
import copy
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import tiny
import weights as weights_lib
from drivers import serve_common

SLIDING, FULL = 'sliding_attention', 'full_attention'


def afmoe_config() -> dict:
    """`arcee-ai/Trinity-Large-Preview`'s own keys at test sizes: 16
    experts scored, 4 held from the fifth on, 2 a token, window 8; two
    published periods of which layer 0 and layers 4-7 are run."""
    kinds = [[8, True]] * 4 + [[0, False]]
    return {
        'name': 'tiny-afmoe', 'family': 'afmoe', 'hidden_size': 64,
        'intermediate_size': 128, 'moe_intermediate_size': 32,
        'num_hidden_layers': 5, 'num_dense_layers': 1,
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'head_dim': 16, 'vocab_size': 512, 'rms_norm_eps': 1e-5,
        'rope_theta': 10000, 'sliding_window': 8,
        'layer_types': [SLIDING, SLIDING, SLIDING, FULL] * 2,
        'kept_layers': {'published_index': [0, 4, 5, 6, 7]},
        'num_experts': 4, 'num_experts_per_tok': 2,
        'num_shared_experts': 1, 'score_func': 'sigmoid',
        'route_norm': True, 'route_scale': 2.0, 'mup_enabled': True,
        'tie_word_embeddings': False, 'hidden_act': 'silu',
        'published': {'num_experts': 16},
        'share': {'chips_per_layer': 4, 'rank': 1, 'first_expert': 4,
                  'decode_batch': 4},
        'program': {'registry_name': 'trinity-large-preview',
                    'overrides': {
                        'num_layers': 5, 'num_dense_layers': 1,
                        'param_dtype': 'float32', 'dtype': 'float32',
                        'd_model': 64, 'num_heads': 4, 'num_kv_heads': 2,
                        'head_dim_override': 16, 'd_mlp': 128,
                        'd_expert': 32, 'd_shared_expert': 32,
                        'vocab_size': 512, 'num_experts': 16,
                        'experts_held': 4, 'first_expert': 4,
                        'experts_per_token': 2, 'route_scale': 2.0,
                        'layer_kinds': kinds}}}


MIX = {'engine': {'max_seq_len': 128}}
SEED = 2**31 + 13


@pytest.fixture
def family():
    return common.load_family(afmoe_config())


def build(cfg_dict: dict, fam):
    cfg = serve_common.program_config(cfg_dict, MIX)
    boxed, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(fam, abstract)
    return cfg, boxed, abstract


def test_its_reference_matches_the_program(family):
    """Float32 on the CPU, 2e-4, at 5 x the window with both kinds of
    layer present: the grouped products sum in another order than the
    reference's walk over the experts, the rest is the same arithmetic."""
    from skypilot_tpu.models.transformer import Transformer
    cfg_dict = afmoe_config()
    cfg, boxed, abstract = build(cfg_dict, family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (2, 40)), jnp.int32)
    want = Transformer(cfg).apply({'params': params}, tokens)
    ref = common.load_reference(cfg_dict)
    layer, whole = weights_lib.Catalog(
        SEED, family, abstract).reference_weights()
    assert set(layer(0)) == set(family.LAYER.values())
    rcfg = family.reference_config(cfg_dict)
    assert rcfg['layer_types'] == [SLIDING] * 4 + [FULL]
    hidden = ref.hidden_states(tokens, whole, layer, cfg.num_layers, rcfg)
    got = ref.logits_at(hidden.reshape(-1, hidden.shape[-1]), whole, rcfg)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=2e-4, rtol=2e-4)
    assert 0.5 < float(jnp.std(got)) < 2.0
    # each part is really there: with it altered the logits differ
    def logits(**kw):
        h = ref.hidden_states(tokens, whole, layer, cfg.num_layers,
                              dict(rcfg, **kw))
        return ref.logits_at(h.reshape(-1, h.shape[-1]), whole, rcfg)
    for altered in (dict(sliding_window=0), dict(first_expert=8),
                    dict(route_norm=False),
                    dict(layer_types=[SLIDING] * 5)):
        assert float(jnp.abs(logits(**altered) - got).max()) > 1e-2, \
            altered


def test_the_branches_add_alike_to_the_residual(family):
    """The embedding at 1 / sqrt(hidden) under its multiplier leaves
    the residual at order 1, and every post-normed branch adds at that
    order, so that a fault in any one moves `correct`; where a token has
    an expert here, the routed part is of the shared expert's order."""
    cfg_dict = afmoe_config()
    cfg, _, abstract = build(cfg_dict, family)
    ref = common.load_reference(cfg_dict)
    layer, whole = weights_lib.Catalog(
        SEED, family, abstract).reference_weights()
    rcfg = family.reference_config(cfg_dict)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 512, 64))
    x = 8.0 * whole('embed')[tokens]
    assert 0.7 < float(jnp.std(x)) < 1.4
    w = ref.group_weights(layer(0), dense=False)
    u = ref.rms_norm(x, w['mlp_norm'], 1e-5)
    shared = ref.swiglu(u, w['s_gate'], w['s_up'], w['s_down'])
    routed = ref.experts(u, w, rcfg) - shared
    here = np.asarray(jnp.abs(routed).sum(-1) > 0)
    assert 0.2 < here.mean() < 0.8      # 2 x 4 / 16 = half a choice a token
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    assert 0.3 < rms(routed[here]) / rms(shared[here]) < 3.0


def test_the_rules_draw_what_the_family_says(family):
    cfg, boxed, abstract = build(afmoe_config(), family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    moe, stacks = params['layers']['layer']['moe'], params['experts']
    assert stacks['w_gate'].shape == (4, 4, 64, 32)
    # an expert kernel's fan-in skips the expert axis: 64, and 32 down
    assert abs(float(jnp.std(stacks['w_gate'])) * 8.0 - 1.0) < 0.1
    assert abs(float(jnp.std(stacks['w_down'])) * 32 ** 0.5 - 1.0) < 0.1
    assert abs(float(jnp.std(moe['router'])) * 8.0 - 1.0) < 0.1
    assert moe['expert_bias'].shape == (4, 16)
    assert 0.003 < float(jnp.std(moe['expert_bias'])) < 0.007
    assert abs(float(jnp.std(params['embed']['embedding'])) * 8.0
               - 1.0) < 0.1
    attn = params['dense_layers']['layer']['attn']
    assert abs(float(jnp.std(attn['o_proj']['kernel'])) * 8.0 - 1.0) < 0.1
    assert abs(float(jnp.mean(attn['q_norm']['scale'])) - 1.0) < 0.1


def test_it_goes_through_the_closed_loop_and_ends_correct(family):
    """The whole of a serving run after the look for a chip, traced, so
    that the family's counts and the three new readers are read."""
    cfg_dict = afmoe_config()
    ctx = tiny.ctx(cfg_dict, tiny.serve_mix(), tiny.SERVE_LIMITS,
                   2**31 + 5, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct'], res['checks']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert res['checks']['tokens_compared']['value'] >= 10
    rctx = res['reader_ctx']
    occ = rctx['occupancy']
    assert (occ['expert_layers'], occ['experts_held']) == (4, 4)
    steps = occ['route_decode_calls']
    assert steps > 0
    read = lambda name: common.load_module('metrics', name).read(rctx)
    assert read('experts_touched_pct') == pytest.approx(
        100.0 * occ['route_decode_experts_touched'] / (steps * 4 * 4))
    assert 0.0 < read('experts_touched_pct') <= 100.0
    assert read('expert_pairs_held_pct') == pytest.approx(
        100.0 * occ['route_decode_pairs_held']
        / occ['route_decode_pairs_routed'])
    assert 10.0 < read('expert_pairs_held_pct') < 45.0   # 4 of 16 held
    assert read('expert_load_max_over_mean') == pytest.approx(
        4.0 * occ['route_decode_max_expert_load']
        / occ['route_decode_pairs_held'])
    assert 1.0 <= read('expert_load_max_over_mean') <= 4.0
    # what the chunks routed is the prompts' own tokens, no pad's (the
    # last chunks' counts ride in with a step that had not landed)
    assert 0 < occ['route_chunk_pairs_routed'] <= \
        occ['prefill_tokens'] * 2 * 4
    assert occ['route_chunk_calls'] <= occ['prefill_chunks']
    import flops_bytes
    work = rctx['work']
    flops = sum(family.decode_flops(cfg_dict, p)
                for p in work['decode_positions'])
    flops += flops_bytes.prefilled_flops(cfg_dict, work)
    got = read('step_mfu.serve')
    assert got == pytest.approx(
        100.0 * flops / work['window_s'] / rctx['peaks']['bf16_flops_per_s'])
    assert read('dispatch_ahead_pct') > 50.0


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's engine counts no routing: the three readers return
    None and do not raise."""
    for occ in ({'blocks_capacity': 9, 'decode_dispatches': 4}, {}, None):
        for name in ('experts_touched_pct', 'expert_pairs_held_pct',
                     'expert_load_max_over_mean'):
            assert common.load_module('metrics', name).read(
                {'occupancy': occ}) is None
    # counters that are there and still zero: nothing to divide by
    zero = {'route_decode_calls': 0, 'expert_layers': 4,
            'experts_held': 4, 'route_decode_experts_touched': 0,
            'route_decode_pairs_held': 0, 'route_decode_pairs_routed': 0,
            'route_decode_max_expert_load': 0}
    for name in ('experts_touched_pct', 'expert_pairs_held_pct',
                 'expert_load_max_over_mean'):
        assert common.load_module('metrics', name).read(
            {'occupancy': zero}) is None


def test_the_control_in_float8_is_not_correct(family):
    mix = dict(tiny.serve_mix(), check_requests=16)
    ctx = tiny.ctx(afmoe_config(), mix, tiny.SERVE_LIMITS, 2**31 + 5, 2.5)
    ctx['control'] = 'fp8'
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])


def test_the_control_rounds_the_expert_stacks_an_expert_a_channel(family):
    ref = common.load_reference(afmoe_config())
    rng = np.random.default_rng(0)
    w = {'w_gate': jnp.asarray(rng.standard_normal((4, 64, 32)),
                               jnp.float32) * jnp.asarray(
                                   [1.0, 10.0, 0.1, 1.0])[:, None, None],
         'd_wo': jnp.asarray(rng.standard_normal((4, 16, 64)), jnp.float32),
         'router': jnp.ones((64, 16)), 'expert_bias': jnp.ones((16,)),
         'q_norm': jnp.ones((16,))}
    low = ref.lower_precision(w, 'int8')
    err = jnp.abs(low['w_gate'] - w['w_gate']).max(axis=(1, 2))
    # a scale an expert and channel: the small expert is not rounded on
    # the large one's grid
    assert float(err[2]) < 0.01 < float(err[1])
    assert float(jnp.abs(low['d_wo'] - w['d_wo']).max()) > 1e-4
    assert all(low[n] is w[n] for n in ('router', 'expert_bias', 'q_norm'))
    assert set(ref.CONTRACT_AXES) <= set(family.LAYER.values()) | {'lm_head'}


@pytest.mark.parametrize('key, value, named', [
    ('route_scale', 2.5, 'route_scale'),
    ('num_experts', 8, 'experts_held'),
    ('published', {'num_experts': 32}, 'router_width'),
    ('share', {'first_expert': 8}, 'first_expert'),
    ('moe_intermediate_size', 64, 'f_expert'),
    ('num_dense_layers', 2, 'dense_layers'),
    ('sliding_window', 16, 'window_0'),
    ('kept_layers', {'published_index': [0, 3, 5, 6, 7]}, 'rope_1'),
    ('score_func', 'softmax', 'score'),
    ('mup_enabled', False, 'embed_by_sqrt_dim'),
])
def test_a_size_or_switch_that_differs_is_named(family, key, value, named):
    cfg_dict = afmoe_config()
    cfg_dict[key] = value
    with pytest.raises(common.HarnessError, match=named):
        serve_common.program_config(cfg_dict, MIX)


def test_kept_layers_that_contradict_themselves_are_refused(family):
    cfg_dict = afmoe_config()
    cfg_dict['kept_layers']['layer_types'] = [SLIDING] * 5
    with pytest.raises(ValueError, match='published_index picks'):
        family.kinds(cfg_dict)


def test_a_tree_of_another_family_is_refused_by_name(family):
    cfg = serve_common.program_config(afmoe_config(), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    llama = common.load_module('families', 'llama_shaped')
    with pytest.raises(common.HarnessError, match='dense_layers/|moe/|experts/'):
        weights_lib.check_tree(llama, abstract)
    cfg = serve_common.program_config(tiny.config(False, 0), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    with pytest.raises(common.HarnessError, match='layers/layer/mlp/'):
        weights_lib.check_tree(family, abstract)


def test_a_training_mix_over_it_is_refused(family):
    assert not hasattr(family, 'lora_train_flops') and family.LORA == {}


def test_the_required_work_equals_hand_sums(family):
    c = afmoe_config()
    # attention: q, gate and o 64 x 64 each, k and v 64 x 32
    attn = 3 * 64 * 64 + 2 * 64 * 32
    assert family.attn_matmul_params(c) == attn
    dense, shared, expert, router = (3 * 64 * 128, 3 * 64 * 32,
                                     3 * 64 * 32, 64 * 16)
    # a token holds 2 x 4 / 16 = half a chosen expert here
    assert family.held_share(c) == 0.5
    token = 2 * (5 * attn + dense + 4 * (router + shared + 0.5 * expert))
    assert family.token_matmul_flops(c) == token
    # one generated token at position 19: the four sliding layers see 8
    # keys, the full one 20; 4 heads x 16 in 2 matmuls; the 64 x 512 head
    assert family.decode_flops(c, 19) == (
        token + 4 * 4 * 16 * (4 * 8 + 20) + 2 * 64 * 512)
    # prompt positions 4 .. 11, the last one's logits: a sliding layer
    # sees 5 + 6 + 7 + 8 x 5 = 58 keys, the full one 5 + .. + 12 = 68
    assert family.prefill_flops(c, 4, 12, True) == (
        8 * token + 4 * 4 * 16 * (4 * 58 + 68) + 2 * 64 * 512)
    # a step of 4 tokens touches 4 x (1 - (14/16)^4) of the 4 held
    touched = 4 * (1 - (14 / 16) ** 4)
    assert family.experts_touched(c, 4) == pytest.approx(touched)
    other = 4 * 64 + 2 * 16
    assert family.weight_bytes_per_step(c, 2) == pytest.approx(
        2 * (5 * (attn + other) + dense
             + 4 * (router + shared + touched * expert) + 64 * 512 + 64)
        + 4 * 4 * 16)
    # K and V of a position and layer: 2 kv heads x 16 x 2 bytes x 2
    assert family.kv_bytes_per_token(c) == 128
    assert family.decode_state_bytes(c, 19) == (4 * 8 + 20) * 128
    assert family.decode_state_bytes(c, 3) == 5 * 4 * 128


# ---- the configuration file, at its published widths -----------------------

def test_the_cells_files_agree_with_the_program_and_the_catalog(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, 'trinity-large-l5-ep8.chat-128')
    config = common.load_config(cell['config'])
    mix = common.load_traffic(cell['traffic'])
    cfg = serve_common.program_config(config, mix)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.max_seq_len) == \
        (5, 1, 1024)
    assert (cfg.num_experts, cfg.held_experts, cfg.first_expert,
            cfg.experts_per_token) == (256, 32, 0, 4)
    assert cfg.param_dtype == 'bfloat16' and cfg.dtype == 'bfloat16'
    assert cfg.layer_kinds == ((4096, True),) * 4 + ((0, False),)
    _, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(family, abstract)
    params = sum(math.prod(a.shape)
                 for a in jax.tree_util.tree_leaves(abstract))
    attn = 62_914_816
    dense = attn + 113_246_208 + 12_288
    expert = attn + 786_432 + 256 + 28_311_552 + 905_969_664 + 12_288
    assert expert == 997_995_008 and dense == 176_173_312
    assert params == dense + 4 * expert + 2 * 76_873_728 + 3072
    assert params == 4_321_903_872          # 8.64 GB in bfloat16
    # K and V: 5 layers x 2 x 8 x 128 x 2 bytes a position
    assert family.decode_state_bytes(config, 0) == 20_480
    assert family.decode_state_bytes(config, 1023) == 1024 * 20_480
    # a decode step: what is not an expert once, and of each layer's 32
    # experts the 27.7 that 128 tokens are expected to touch
    touched = 32 * (1 - (252 / 256) ** 128)
    assert family.experts_touched(config, 128) == pytest.approx(touched)
    assert 27.7 < touched < 27.8
    rest = (dense + 4 * (expert - 905_969_664 - 256) + 76_873_728 + 3072)
    assert family.weight_bytes_per_step(config) == pytest.approx(
        2 * (rest + 4 * touched * 28_311_552) + 4 * 4 * 256)
    assert 7.5e9 < family.weight_bytes_per_step(config) < 7.55e9
    # the mix's slots are the batch the roofline assumes
    assert mix['engine']['num_slots'] == config['share']['decode_batch']
    assert mix['clients'] == 192 and mix['pool_size'] % 192 == 0
    assert config['share']['chips_per_layer'] * config['num_experts'] == \
        config['published']['num_experts']
    assert config['share']['chips_per_layer'] * config['vocab_size'] == \
        config['published']['vocab_size']
    # the catalog's row, key for key but the four reduced
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'Trinity-Large-Preview')
        assert config['source'] == row['source_url']
        differ = [k for k, v in row['config'].items() if config[k] != v]
        assert sorted(differ) == sorted(config['reduced']) == sorted(
            next(c for c in bench['configs']
                 if c['name'] == cell['config'])['reduced'])
        for k in differ:
            assert config['published'][k] == row['config'][k]
    limits = common.load_json(os.path.join(
        common.PERF_DIR, 'cells', f'{cell["name"]}.json'))['limits']
    assert limits['min_tokens'] >= 1000


def test_the_cell_is_listed_where_its_metrics_are(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, 'trinity-large-l5-ep8.chat-128')
    e2e = {m['name'] for m in common.cell_metrics(bench, cell,
                                                  'end_to_end')}
    assert e2e == {'tokens_per_s', 'tpot_p90_ms', 'setup_s'}
    layers = {m['name'] for m in common.cell_metrics(bench, cell,
                                                     'per_layer')}
    assert {'experts_touched_pct', 'expert_pairs_held_pct',
            'expert_load_max_over_mean', 'decode_hbm_roofline',
            'prefill_mfu', 'step_mfu.serve', 'device_idle_pct.serve',
            'dispatch_ahead_pct'} <= layers
    assert not layers & {'scan_fill_pct', 'state_cache_share_pct',
                         'step_mfu.train', 'flash_attn_roofline'}
    for name in layers:
        common.load_module('metrics', name)
