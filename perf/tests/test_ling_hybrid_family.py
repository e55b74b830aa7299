"""The fifth family in use: Ling-3.0-flash's language model (Kimi delta
attention in five layers of six, latent attention in the sixth, experts
routed within groups, a chip's share of each layer), as files alone:
`families/ling_hybrid.py`, `references/ling_hybrid.py`, a configuration,
a mix, a cell, three readers. Its reference against `Transformer.apply`
at a tiny size, the family through the closed loop ending `correct` and
its lower-precision control not, the share of the expert layer against
the uncut layer, what the harness refuses, and the required work by
hand."""
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

KEPT = [0, 6, 7, 8, 9, 10, 11]
MIXERS = ['kda'] * 6 + ['mla']


def ling_config() -> dict:
    """`inclusionAI/Ling-3.0-flash-VL`'s own keys at test sizes: 16
    experts scored in 4 groups of which 2 are kept, 4 held (group 1), 2
    a token; two published periods of six of which layer 0 and layers
    6-11 are run."""
    return {
        'name': 'tiny-ling', 'family': 'ling_hybrid', 'hidden_size': 64,
        'intermediate_size': 96, 'moe_intermediate_size': 32,
        'moe_shared_expert_intermediate_size': 32,
        'num_shared_experts': 1, 'num_hidden_layers': 7,
        'first_k_dense_replace': 1, 'num_attention_heads': 4,
        'num_key_value_heads': 4, 'num_kv_heads_for_linear_attn': 0,
        'head_dim': 16, 'vocab_size': 512, 'rms_norm_eps': 1e-6,
        'rope_theta': 6000000, 'layer_group_size': 6,
        'short_conv_kernel_size': 4, 'kda_lower_bound': -5,
        'kv_lora_rank': 32, 'qk_nope_head_dim': 16,
        'qk_rope_head_dim': 8, 'v_head_dim': 16, 'use_qk_norm': True,
        'num_experts': 4, 'num_experts_per_tok': 2, 'n_group': 4,
        'topk_group': 2, 'score_function': 'sigmoid',
        'moe_router_enable_expert_bias': True, 'norm_topk_prob': True,
        'routed_scaling_factor': 2.5, 'tie_word_embeddings': False,
        'hidden_act': 'silu',
        'expert_swiglu_limit_list': [0] * 12,
        'share_expert_swiglu_limit_list': [0] * 12,
        'kept_layers': {'published_index': KEPT},
        'published': {'num_experts': 16},
        'share': {'chips_per_layer': 4, 'rank': 1, 'first_expert': 4,
                  'decode_batch': 4},
        'program': {'registry_name': 'ling-3.0-flash',
                    'overrides': {
                        'num_layers': 7, 'num_dense_layers': 1,
                        'param_dtype': 'float32', 'dtype': 'float32',
                        'd_model': 64, 'num_heads': 4, 'num_kv_heads': 4,
                        'head_dim_override': 16, 'd_mlp': 96,
                        'd_expert': 32, 'd_shared_expert': 32,
                        'vocab_size': 512, 'num_experts': 16,
                        'experts_held': 4, 'first_expert': 4,
                        'experts_per_token': 2, 'n_group': 4,
                        'topk_group': 2, 'mla_kv_rank': 32,
                        'mla_nope_dim': 16, 'mla_rope_dim': 8,
                        'mla_v_dim': 16, 'mixer_kinds': MIXERS,
                        'expert_swiglu_limits': [0.0] * 7,
                        'shared_swiglu_limits': [0.0] * 7}}}


MIX = {'engine': {'max_seq_len': 128}}
SEED = 2**31 + 17


@pytest.fixture
def family():
    return common.load_family(ling_config())


def build(cfg_dict: dict, fam):
    cfg = serve_common.program_config(cfg_dict, MIX)
    boxed, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(fam, abstract)
    return cfg, boxed, abstract


def reference_logits(cfg_dict, abstract, tokens, **altered):
    fam = common.load_family(cfg_dict)
    ref = common.load_reference(cfg_dict)
    layer, whole = weights_lib.Catalog(
        SEED, fam, abstract).reference_weights()
    rcfg = dict(fam.reference_config(cfg_dict), **altered)
    hidden = ref.hidden_states(tokens, whole, layer, 7, rcfg)
    return ref.logits_at(hidden.reshape(-1, hidden.shape[-1]), whole, rcfg)


def test_its_reference_matches_the_program(family):
    """Float32 on the CPU, 2e-4, over 40 positions (two and a half of
    the chunked form's blocks): KDA by its recurrence against the
    chunked form, MLA expanded against absorbed, the expert layer's
    walk against sorted grouped products."""
    from skypilot_tpu.models.transformer import Transformer
    cfg_dict = ling_config()
    cfg, boxed, abstract = build(cfg_dict, family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (2, 40)), jnp.int32)
    want = Transformer(cfg).apply({'params': params}, tokens)
    layer, _ = weights_lib.Catalog(SEED, family,
                                   abstract).reference_weights()
    assert set(layer(0)) == set(family.LAYER.values())
    got = reference_logits(cfg_dict, abstract, tokens)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=2e-4, rtol=2e-4)
    assert 0.5 < float(jnp.std(got)) < 3.0
    # each part is really there: with it altered the logits differ
    for altered in (dict(first_expert=8), dict(n_group=1),
                    dict(kda_lower_bound=-4.0),
                    dict(mixers=['kda'] * 7),
                    dict(norm_topk_prob=False)):
        other = reference_logits(cfg_dict, abstract, tokens, **altered)
        assert float(jnp.abs(other - got).max()) > 1e-2, altered


def test_a_clamp_the_layer_carries_is_the_references(family):
    """The published clamps are 0 on every kept layer; with limits of
    0.3 (routed) and 0.2 (shared) on the KDA expert layers, below what
    the projections reach, program and reference still agree to 2e-4
    and both differ from the unclamped logits."""
    from skypilot_tpu.models.transformer import Transformer
    cfg_dict = ling_config()
    for p in KEPT[1:6]:
        cfg_dict['expert_swiglu_limit_list'][p] = 0.3
        cfg_dict['share_expert_swiglu_limit_list'][p] = 0.2
    ov = cfg_dict['program']['overrides']
    ov['expert_swiglu_limits'] = [0.0] + [0.3] * 5 + [0.0]
    ov['shared_swiglu_limits'] = [0.0] + [0.2] * 5 + [0.0]
    cfg, boxed, abstract = build(cfg_dict, family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 512, (1, 24)), jnp.int32)
    want = Transformer(cfg).apply({'params': params}, tokens)
    got = reference_logits(cfg_dict, abstract, tokens)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=2e-4, rtol=2e-4)
    free = reference_logits(ling_config(), abstract, tokens)
    assert float(jnp.abs(free - got).max()) > 1e-2
    # a clamp that differs between file and program is named
    ov['shared_swiglu_limits'] = [0.0] * 7
    with pytest.raises(common.HarnessError, match='shared_limit_1'):
        serve_common.program_config(cfg_dict, MIX)


def test_the_shares_add_up_to_the_uncut_expert_layer(family):
    """The guide's share test: the four chips' held-expert parts, plus
    the shared expert counted once, are the uncut layer's routed sum
    under group-limited routing, a chip's group of 4 experts each."""
    ref = common.load_reference(ling_config())
    rng = np.random.default_rng(2)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    w = {'router': f32(64, 16) * 0.3, 'expert_bias': f32(16) * 0.005,
         'w_gate': f32(16, 64, 32) / 8, 'w_up': f32(16, 64, 32) / 8,
         'w_down': f32(16, 32, 64) / 6, 's_gate': f32(64, 32) / 8,
         's_up': f32(64, 32) / 8, 's_down': f32(32, 64) / 6}
    u = f32(48, 64)
    base = {'num_experts_per_tok': 2, 'n_group': 4, 'topk_group': 2,
            'norm_topk_prob': True, 'routed_scaling_factor': 2.5}
    whole = ref.experts(u, w, dict(base, first_expert=0, experts_held=16))
    shared = ref.swiglu(u, w['s_gate'], w['s_up'], w['s_down'])
    parts = []
    for rank in range(4):
        mine = dict(w, **{n: w[n][4 * rank:4 * rank + 4]
                          for n in ('w_gate', 'w_up', 'w_down')})
        parts.append(ref.experts(u, mine, dict(base, first_expert=4 * rank,
                                               experts_held=4)) - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)
    # every token's experts lie in 2 of the 4 groups: two shares see it
    # at most, and at least one share sees nothing of some tokens
    touched = np.stack([np.abs(np.asarray(p)).sum(-1) > 0 for p in parts])
    assert touched.sum(0).max() <= 2 and (touched.sum(0) < 4).all()


def test_the_rules_draw_what_the_family_says(family):
    cfg, boxed, abstract = build(ling_config(), family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    assert params['experts']['w_gate'].shape == (6, 4, 64, 32)
    mixer = params['layers']['layer']['mixer']
    assert mixer['A_log'].shape == (5, 4)
    assert mixer['dt_bias'].shape == (5, 4, 16)
    assert abs(float(jnp.mean(mixer['dt_bias'])) + 3.0) < 0.2
    assert mixer['conv_kernel'].shape == (5, 4, 3 * 64)
    assert abs(float(jnp.std(mixer['conv_kernel'])) * 2.0 - 1.0) < 0.1
    attn = params['layers_6']['layer']['attn']
    assert attn['kv_up'].shape == (1, 32, 4, 32)
    assert abs(float(jnp.std(attn['kv_up'])) * 32 ** 0.5 - 1.0) < 0.15
    assert abs(float(jnp.std(attn['o_proj']['kernel'])) * 8.0 - 1.0) < 0.1
    moe = params['layers_6']['layer']['moe']
    assert 0.003 < float(jnp.std(moe['expert_bias'])) < 0.007


def test_it_goes_through_the_closed_loop_and_ends_correct(family):
    """The whole of a serving run after the look for a chip, traced, so
    that the family's counts and the readers are read."""
    cfg_dict = ling_config()
    ctx = tiny.ctx(cfg_dict, tiny.serve_mix(), tiny.SERVE_LIMITS,
                   2**31 + 5, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct'], res['checks']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert res['checks']['tokens_compared']['value'] >= 10
    rctx = res['reader_ctx']
    occ = rctx['occupancy']
    assert (occ['expert_layers'], occ['experts_held']) == (6, 4)
    assert occ['state_bytes'] > 0 and occ['kv_pool_bytes'] > 0
    read = lambda name: common.load_module('metrics', name).read(rctx)
    hit = read('held_group_hit_pct')
    assert hit == pytest.approx(
        100.0 * 2 * occ['route_decode_tokens_group_held']
        / occ['route_decode_pairs_routed'])
    assert 20.0 < hit < 80.0            # 2 of 4 groups kept
    assert 0.0 < read('state_cache_share_pct') < 100.0
    # no kernel in a CPU trace: nothing to read, and no raise
    assert read('kda_step_roofline') is None
    assert read('kda_state_share_pct') is None
    assert read('step_mfu.serve') > 0


def test_the_control_in_float8_is_not_correct(family):
    mix = dict(tiny.serve_mix(), check_requests=16)
    ctx = tiny.ctx(ling_config(), mix, tiny.SERVE_LIMITS, 2**31 + 5, 2.5)
    ctx['control'] = 'fp8'
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])
    ref = common.load_reference(ling_config())
    assert set(ref.CONTRACT_AXES) <= set(family.LAYER.values()) | {
        'lm_head'}


class _Trace:
    """A reduced trace with one kernel's events and the decode modules'
    busy time."""

    def __init__(self, seconds, count, decode_busy):
        self.seconds, self.count, self.busy = seconds, count, decode_busy

    def op_seconds(self, pattern):
        import re
        return ((self.seconds, self.count) if re.search(
            pattern, '%kda_state_step.3 = f32[] custom-call()')
            else (0.0, 0))

    def ops_within_modules(self, pattern):
        return self.busy


def test_the_kernel_readers_divide_what_the_trace_holds(family):
    cfg_dict = ling_config()
    peaks = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
    per_call = family.kda_state_step_bytes(cfg_dict, 8)
    # 4 heads x (2 x 16 x 16 x 4 + 6 x 16 x 4) bytes a slot
    assert per_call == 8 * 4 * (2 * 1024 + 384)
    ctx = {'trace': _Trace(2e-4, 12, 1e-3), 'config': cfg_dict,
           'num_slots': 8, 'peaks': peaks, 'chips': 1}
    read = lambda name: common.load_module('metrics', name).read(ctx)
    assert read('kda_step_roofline') == pytest.approx(
        100.0 * 12 * per_call / 1e11 / 2e-4)
    assert read('kda_state_share_pct') == pytest.approx(20.0)
    for empty in (_Trace(0.0, 0, 1e-3), _Trace(1e-4, 2, 0.0)):
        ctx['trace'] = empty
        assert read('kda_step_roofline') is None or empty.busy == 0.0
        assert read('kda_state_share_pct') is None
    for occ in ({}, None, {'route_decode_pairs_routed': 0,
                           'route_decode_tokens_group_held': 0}):
        assert common.load_module('metrics', 'held_group_hit_pct').read(
            {'occupancy': occ, 'config': cfg_dict}) is None


@pytest.mark.parametrize('key, value, named', [
    ('routed_scaling_factor', 2.0, 'route_scale'),
    ('num_experts', 8, 'experts_held'),
    ('published', {'num_experts': 32}, 'router_width'),
    ('share', {'first_expert': 8}, 'first_expert'),
    ('topk_group', 3, 'topk_group'),
    ('kv_lora_rank', 16, 'rank'),
    ('layer_group_size', 4, 'mixer_'),
    ('kda_lower_bound', -4, 'gate_bound'),
    ('first_k_dense_replace', 2, 'dense_layers'),
])
def test_a_size_or_switch_that_differs_is_named(family, key, value, named):
    cfg_dict = ling_config()
    cfg_dict[key] = value
    with pytest.raises(common.HarnessError, match=named):
        serve_common.program_config(cfg_dict, MIX)


def test_a_tree_of_another_family_is_refused_by_name(family):
    cfg = serve_common.program_config(ling_config(), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    afmoe = common.load_module('families', 'afmoe')
    with pytest.raises(common.HarnessError, match='mixer/|layers_6/'):
        weights_lib.check_tree(afmoe, abstract)


def test_the_required_work_equals_hand_sums(family):
    c = ling_config()
    kda = 6 * 64 * 64 + 64 * 4
    mla = (64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 64 * 4 + 4 * 16 * 64)
    assert (family.kda_matmul_params(c), family.mla_matmul_params(c)) == \
        (kda, mla)
    dense, shared, expert, router = (3 * 64 * 96, 3 * 64 * 32,
                                     3 * 64 * 32, 64 * 16)
    # a token holds 2 x 4 / 16 = half a chosen expert here
    assert family.held_share(c) == 0.5
    token = 2 * (6 * kda + mla + dense
                 + 6 * (router + shared + 0.5 * expert))
    assert family.token_matmul_flops(c) == token
    rec = 6 * (6 * 4 * 16 * 16 + 2 * 4 * 3 * 64)
    assert family.recurrence_flops(c) == rec
    # one generated token at position 19: the MLA layer sees 20 latents,
    # 2 x 4 heads x (2 x 32 + 8) a latent; the 64 x 512 head
    assert family.decode_flops(c, 19) == (
        token + rec + 2 * 4 * 72 * 20 + 2 * 64 * 512)
    # positions 4 .. 11, the last one's logits: 5 + .. + 12 = 68 keys
    assert family.prefill_flops(c, 4, 12, True) == (
        8 * (token + rec) + 2 * 4 * 72 * 68 + 2 * 64 * 512)
    # the state: 6 KDA layers x (4 x 16 x 16 x 4 + 3 x 192 x 2) bytes
    state = 6 * (4096 + 1152)
    assert family.state_bytes_per_slot(c) == state
    assert family.decode_state_bytes(c, 19) == 2 * state + 20 * 40 * 2


# ---- the configuration file, at its published widths -----------------------

def test_the_cells_files_agree_with_the_program_and_the_catalog(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, 'ling-3.0-flash-l7-ep8.reason-256')
    assert cell['chips'] == 1
    config = common.load_config(cell['config'])
    mix = common.load_traffic(cell['traffic'])
    cfg = serve_common.program_config(config, mix)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.max_seq_len) == \
        (7, 1, 2560)
    assert (cfg.num_experts, cfg.held_experts, cfg.first_expert,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group) == \
        (512, 64, 0, 8, 8, 4)
    assert cfg.mixer_kinds == ('kda',) * 6 + ('mla',)
    assert cfg.param_dtype == 'bfloat16' and cfg.dtype == 'bfloat16'
    _, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(family, abstract)
    params = sum(math.prod(a.shape)
                 for a in jax.tree_util.tree_leaves(abstract))
    kda = (6 * 2560 * 4096 + 2560 * 32 + 4 * 3 * 4096 + 32 + 4096 + 128)
    mla = (2560 * 32 * 192 + 192 + 2560 * 576 + 512 + 512 * 32 * 256
           + 2560 * 32 + 32 * 128 * 2560)
    experts = 2560 * 512 + 512 + 3 * 2560 * 768 * 65
    assert (kda, mla, experts) == (63_049_888, 31_965_888, 384_696_832)
    assert params == (6 * kda + mla + 47_185_920 + 6 * experts
                      + 7 * 2 * 2560 + 2 * 19_648 * 2560 + 2560)
    assert params == 2_866_268_288          # 5.73 GB in bfloat16
    # the KDA state and convolution inputs a slot: 13.0 MB
    assert family.state_bytes_per_slot(config) == 6 * (
        32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert family.decode_state_bytes(config, 0) == \
        2 * family.state_bytes_per_slot(config) + 1152
    # of each layer's 64 experts, 256 tokens touch 62.8
    assert 62.8 < family.experts_touched(config, 256) < 62.9
    # the mix's slots are the batch the roofline assumes
    assert mix['engine']['num_slots'] == config['share']['decode_batch']
    assert mix['clients'] == 384 and mix['pool_size'] % 384 == 0
    assert config['share']['chips_per_layer'] * config['num_experts'] == \
        config['published']['num_experts']
    assert config['share']['chips_per_layer'] * config['vocab_size'] == \
        config['published']['vocab_size']
    assert family.mixers(config) == config['kept_layers']['mixers']
    # the four keys cut from the published config, each named with its
    # published value
    assert sorted(config['reduced']) == sorted(config['published']) == \
        sorted(next(c for c in bench['configs']
                    if c['name'] == cell['config'])['reduced'])
    assert config['published'] == {
        'num_hidden_layers': 42, 'first_k_dense_replace': 2,
        'num_experts': 512, 'vocab_size': 157184}
    limits = common.load_json(os.path.join(
        common.PERF_DIR, 'cells', f'{cell["name"]}.json'))['limits']
    assert limits['min_tokens'] >= 1000


def test_the_cell_is_listed_where_its_metrics_are(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, 'ling-3.0-flash-l7-ep8.reason-256')
    e2e = {m['name'] for m in common.cell_metrics(bench, cell,
                                                  'end_to_end')}
    assert e2e == {'tokens_per_s', 'tpot_p90_ms', 'setup_s'}
    layers = {m['name'] for m in common.cell_metrics(bench, cell,
                                                     'per_layer')}
    assert {'kda_step_roofline', 'kda_state_share_pct',
            'held_group_hit_pct', 'state_cache_share_pct',
            'experts_touched_pct', 'expert_pairs_held_pct',
            'expert_load_max_over_mean', 'decode_hbm_roofline',
            'prefill_mfu', 'step_mfu.serve', 'device_idle_pct.serve',
            'dispatch_ahead_pct', 'host_tick_ms_p50'} <= layers
    assert not layers & {'scan_fill_pct', 'moe_gmm_share_pct',
                         'step_mfu.train', 'flash_attn_roofline',
                         'block_passes_per_token'}
    for name in layers:
        common.load_module('metrics', name)
    for name in ('kda_step_roofline', 'kda_state_share_pct',
                 'held_group_hit_pct'):
        m = next(m for m in bench['per_layer'] if m['name'] == name)
        assert m['workloads'] == [cell['name']]
