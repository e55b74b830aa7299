"""The fourth family in use: SDAR (`JetLM/SDAR-30B-A3B-Chat`: generation by
diffusion over blocks under a block-causal mask, over 128 small dropless
experts), as files alone: `families/sdar_moe.py`, `references/sdar_moe.py`,
a configuration, a mix, a cell, a driver of its own
(`drivers/closed_loop_blocks.py`: what a generated token is differs, so
the counts of the traced stretch and the comparison that decides
`correct` do) and three readers. Its reference against
`Transformer.apply` at a tiny size, the family through the driver ending
`correct`, its lower-precision control and each of the four faults not,
what the harness refuses, and the required work by hand."""
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

CELL = 'sdar-30b-a3b-l6.block4-chat-128'


def sdar_config(steps: int = 4) -> dict:
    """`JetLM/SDAR-30B-A3B-Chat`'s own keys at test sizes: 8 experts of
    32, 2 a token, blocks of 4."""
    return {
        'name': 'tiny-sdar', 'family': 'sdar_moe', 'hidden_size': 64,
        'intermediate_size': 128, 'moe_intermediate_size': 32,
        'num_hidden_layers': 2, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'head_dim': 16, 'vocab_size': 512,
        'rms_norm_eps': 1e-6, 'rope_theta': 1000000, 'num_experts': 8,
        'num_experts_per_tok': 2, 'norm_topk_prob': True,
        'attention_bias': False, 'tie_word_embeddings': False,
        'hidden_act': 'silu', 'mlp_only_layers': [],
        'decoder_sparse_step': 1, 'use_sliding_window': False,
        'rope_scaling': None,
        'assumed': {'block_length': 4, 'denoising_steps': steps,
                    'mask_token_id': 500,
                    'remasking_strategy': 'low_confidence_static'},
        'stage': {'decode_batch': 4},
        'program': {'registry_name': 'sdar-30b-a3b-chat', 'overrides': {
            'num_layers': 2, 'param_dtype': 'float32', 'dtype': 'float32',
            'd_model': 64, 'num_heads': 4, 'num_kv_heads': 2,
            'head_dim_override': 16, 'd_mlp': 128, 'd_expert': 32,
            'vocab_size': 512, 'num_experts': 8, 'experts_per_token': 2,
            'mask_token_id': 500, 'denoising_steps': steps}}}


MIX = {'engine': {'max_seq_len': 128}}
SEED = 2**31 + 13


def blocks_mix(**kw) -> dict:
    return dict(tiny.serve_mix(), driver='closed_loop_blocks', **kw)


@pytest.fixture
def family():
    return common.load_family(sdar_config())


def build(cfg_dict: dict, fam):
    cfg = serve_common.program_config(cfg_dict, MIX)
    boxed, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(fam, abstract)
    return cfg, boxed, abstract


def test_its_reference_matches_the_program(family):
    """Float32 on the CPU, 2e-4, under the block-causal mask, with the
    benchmark's own weights under the family's names."""
    from skypilot_tpu.models.transformer import Transformer
    cfg_dict = sdar_config()
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
    assert (rcfg['block_length'], rcfg['denoising_steps'],
            rcfg['mask_token_id']) == (4, 4, 500)
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
    for altered in (dict(causal=True), dict(block_length=8),
                    dict(norm_topk_prob=False),
                    dict(num_experts_per_tok=1), dict(rope_theta=1e4)):
        assert float(jnp.abs(logits(**altered) - got).max()) > 1e-2, \
            altered


def test_the_rules_draw_what_the_family_says(family):
    cfg, boxed, abstract = build(sdar_config(), family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    stacks = params['experts']
    assert stacks['w_gate'].shape == (2, 8, 64, 32)
    # an expert kernel's fan-in skips the expert axis: 64, and 32 down
    assert abs(float(jnp.std(stacks['w_gate'])) * 8.0 - 1.0) < 0.1
    assert abs(float(jnp.std(stacks['w_down'])) * 32 ** 0.5 - 1.0) < 0.1
    moe = params['layers']['layer']['moe']
    assert abs(float(jnp.std(moe['router'])) * 8.0 - 1.0) < 0.1
    assert abs(float(jnp.std(params['embed']['embedding'])) - 1.0) < 0.1
    attn = params['layers']['layer']['attn']
    assert abs(float(jnp.std(attn['o_proj']['kernel'])) * 8.0 - 1.0) < 0.1
    assert abs(float(jnp.mean(attn['q_norm']['scale'])) - 1.0) < 0.1
    assert family.LORA == {} and not hasattr(family, 'lora_train_flops')


def test_it_goes_through_its_driver_and_ends_correct(family):
    """The whole of a serving run after the look for a chip, traced, so
    that the family's counts and the three new readers are read."""
    cfg_dict = sdar_config()
    ctx = tiny.ctx(cfg_dict, blocks_mix(), tiny.SERVE_LIMITS,
                   2**31 + 5, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop_blocks').run(ctx)
    assert res['correct'], res['checks']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert res['checks']['tokens_compared']['value'] >= 10
    # float32 against float32: the program unmasks what the reference
    # would, where it would
    assert res['checks']['choice_gap_max']['value'] < 1e-5
    assert res['checks']['choice_gap_max']['limit'] is None
    e2e = res['e2e']
    assert e2e['tokens_per_s'] > 0 and e2e['tpot_p90_ms'] > 0
    rctx = res['reader_ctx']
    occ, work = rctx['occupancy'], rctx['work']
    read = lambda name: common.load_module('metrics', name).read(rctx)
    assert read('block_passes_per_token') == pytest.approx(
        occ['block_passes'] / occ['block_tokens'])
    assert 1.25 <= read('block_passes_per_token') < 2.0
    assert read('commit_pass_share_pct') == pytest.approx(
        100.0 * occ['block_commits'] / occ['block_passes'])
    assert 5.0 < read('commit_pass_share_pct') <= 20.0
    assert read('masked_position_share_pct') == pytest.approx(
        100.0 * occ['block_masked_positions'] / (4 * occ['block_passes']))
    assert 40.0 < read('masked_position_share_pct') < 62.5
    # every expert is held: all the routed pairs are
    assert read('expert_pairs_held_pct') == pytest.approx(100.0)
    assert 0.0 < read('experts_touched_pct') <= 100.0
    assert read('dispatch_ahead_pct') > 50.0
    assert 0 < read('batch_occupancy_pct') <= 100
    # a decode step is a pass; a token counts at its own position
    assert work['decode_steps'] == sum(k for k, _ in rctx['dispatches'])
    assert work['decode_positions'] and min(work['decode_positions']) >= 8
    assert all(p % 4 == 0 for p in work['prompts_finished'])
    import flops_bytes
    flops = sum(family.decode_flops(cfg_dict, p)
                for p in work['decode_positions'])
    flops += flops_bytes.prefilled_flops(cfg_dict, work)
    assert read('step_mfu.serve') == pytest.approx(
        100.0 * flops / work['window_s']
        / rctx['peaks']['bf16_flops_per_s'])


def test_the_control_in_float8_is_not_correct(family):
    ctx = tiny.ctx(sdar_config(), blocks_mix(check_requests=16),
                   tiny.SERVE_LIMITS, 2**31 + 5, 2.0)
    ctx['control'] = 'fp8'
    res = common.load_module('drivers', 'closed_loop_blocks').run(ctx)
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])


@pytest.mark.parametrize('fault', ['causal', 'no_commit', 'shift_record',
                                   'alter'])
def test_each_fault_the_limits_were_set_against_is_not_correct(family,
                                                              fault):
    """The causal mask in the block-causal one's place; the commit pass
    spoiled (the next block reads noisy K/V); the unmask record shifted
    by one pass; every 5th token altered where it is emitted."""
    driver = common.load_module('drivers', 'closed_loop_blocks')
    assert fault in driver.FAULTS
    ctx = tiny.ctx(sdar_config(), blocks_mix(check_requests=16),
                   tiny.SERVE_LIMITS, 2**31 + 5, 2.0)
    ctx['fault'] = fault
    res = driver.run(ctx)
    assert res['failed'] == 0 and res['attempted'] > 0
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])
    # the fault is gone with the run
    from skypilot_tpu.models.configs import ModelConfig
    assert ModelConfig.last_key_seen(
        serve_common.program_config(sdar_config(), MIX), 5) == 7


def test_a_fault_that_is_not_there_is_refused_by_name():
    driver = common.load_module('drivers', 'closed_loop_blocks')
    with pytest.raises(common.HarnessError, match='no fault'):
        with driver.planted('nonesuch'):
            pass


def test_a_record_that_does_not_make_whole_blocks_is_refused():
    driver = common.load_module('drivers', 'closed_loop_blocks')

    class Done:
        def __init__(self, stats):
            self._stats = stats

        def result(self):
            return None, self._stats

    class Req:
        index, prompt, tokens = 3, [1, 2, 3, 4, 5], [6, 7, 8, 9]

    r = Req()
    r.future = Done({'unmask_pass': [1, 0, 2, 0], 'overshoot_tokens': [9,
                     9, 9], 'overshoot_pass': [3, 1, 2]})
    clean, upass, end = driver.replay_record(r, 4, 4)
    assert clean == [1, 2, 3, 4, 5, 6, 7, 8, 9, 9, 9, 9] and end == 9
    assert upass == [-1] * 5 + [1, 0, 2, 0, 3, 1, 2]
    assert driver.replay_record(r, 4, 4, 'shift_record')[1][5:] == \
        [2, 1, 3, 1, 0, 2, 3]
    r.future = Done({'unmask_pass': [1, 0, 2, 0], 'overshoot_tokens': [9],
                     'overshoot_pass': [3]})
    with pytest.raises(common.HarnessError, match='whole blocks'):
        driver.replay_record(r, 4, 4)


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's engine counts no passes: the three readers return
    None and do not raise."""
    names = ('block_passes_per_token', 'commit_pass_share_pct',
             'masked_position_share_pct')
    for occ in ({'blocks_capacity': 9, 'decode_dispatches': 4}, {}, None):
        for name in names:
            assert common.load_module('metrics', name).read(
                {'occupancy': occ}) is None
    zero = {'block_passes': 0, 'block_commits': 0, 'block_tokens': 0,
            'block_masked_positions': 0, 'block_length': 4}
    for name in names:
        assert common.load_module('metrics', name).read(
            {'occupancy': zero}) is None
    occ = {'block_passes': 500, 'block_commits': 96, 'block_tokens': 384,
           'block_masked_positions': 960, 'block_length': 4}
    read = lambda n: common.load_module('metrics', n).read(
        {'occupancy': occ})
    assert read('block_passes_per_token') == pytest.approx(500 / 384)
    assert read('commit_pass_share_pct') == pytest.approx(19.2)
    assert read('masked_position_share_pct') == pytest.approx(48.0)


@pytest.mark.parametrize('key, value, named', [
    ('num_experts', 16, 'experts'),
    ('num_experts_per_tok', 4, 'per_token'),
    ('moe_intermediate_size', 64, 'f_expert'),
    ('norm_topk_prob', False, 'route_norm'),
    ('rope_theta', 10000, 'rope_theta'),
    ('attention_bias', True, 'qkv_bias'),
    ('assumed', {'block_length': 8, 'denoising_steps': 4,
                 'mask_token_id': 500,
                 'remasking_strategy': 'low_confidence_static'}, 'block'),
    ('assumed', {'block_length': 4, 'denoising_steps': 2,
                 'mask_token_id': 500,
                 'remasking_strategy': 'low_confidence_static'}, 'steps'),
    ('assumed', {'block_length': 4, 'denoising_steps': 4,
                 'mask_token_id': 499,
                 'remasking_strategy': 'low_confidence_static'},
     'mask_id'),
])
def test_a_size_or_switch_that_differs_is_named(family, key, value, named):
    cfg_dict = sdar_config()
    cfg_dict[key] = value
    with pytest.raises(common.HarnessError, match=named):
        serve_common.program_config(cfg_dict, MIX)


def test_a_schedule_or_layer_the_family_has_not_is_refused(family):
    for key, value, named in (
            ('mlp_only_layers', [0], 'expert layer at every depth'),
            ('use_sliding_window', True, 'no window'),
            ('assumed', dict(sdar_config()['assumed'],
                             remasking_strategy='low_confidence_dynamic'),
             'low_confidence_static')):
        cfg_dict = sdar_config()
        cfg_dict[key] = value
        with pytest.raises(ValueError, match=named):
            family.file_sizes(cfg_dict)


def test_a_tree_of_another_family_is_refused_by_name(family):
    cfg = serve_common.program_config(sdar_config(), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    llama = common.load_module('families', 'llama_shaped')
    with pytest.raises(common.HarnessError, match='moe/|experts/|_norm'):
        weights_lib.check_tree(llama, abstract)
    cfg = serve_common.program_config(tiny.config(False, 0), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    with pytest.raises(common.HarnessError, match='layers/layer/mlp/'):
        weights_lib.check_tree(family, abstract)


def test_the_required_work_equals_hand_sums(family):
    c = sdar_config()
    # attention: q and o 64 x 64 each, k and v 64 x 32
    attn = 2 * 64 * 64 + 2 * 64 * 32
    assert family.attn_matmul_params(c) == attn
    router, expert = 64 * 8, 3 * 64 * 32
    position = 2 * 2 * (attn + router + 2 * expert)
    assert family.position_matmul_flops(c) == position
    assert family.unmask_schedule(c) == [1, 1, 1, 1]
    # masked 4, 3, 2, 1 of 4 over the four denoising passes
    assert family.masked_passes(c) == 2.5
    assert family.masked_passes(sdar_config(steps=2)) == (4 + 2) / 4
    assert family.masked_passes(sdar_config(steps=1)) == 1.0
    # position 9 is in block 2, whose end is key 12: 4 heads x 16 in 2
    # matmuls, 2 layers
    assert family.keys_to_block_end(c, 9) == 12
    attn_9 = 4 * 4 * 16 * 2 * 12
    # a generated token: 5 passes of its position's layers, the 64 x 512
    # head for the 2.5 passes in which it is masked
    assert family.decode_flops(c, 9) == (
        5 * (position + attn_9) + 2.5 * 2 * 64 * 512)
    assert family.decode_flops(sdar_config(steps=2), 9) == (
        3 * (position + attn_9) + 1.5 * 2 * 64 * 512)
    # prompt positions 4 .. 11: 8, 8, 8, 8, 12, 12, 12, 12 keys; no row
    # of logits, whatever `last` says
    assert family.prefill_flops(c, 4, 12, True) == (
        8 * position + 4 * 4 * 16 * 2 * 80)
    assert family.prefill_flops(c, 4, 12, True) == \
        family.prefill_flops(c, 4, 12, False)
    # a pass of 4 slots x 4 positions touches 8 x (1 - (6/8)^16) experts
    touched = 8 * (1 - (6 / 8) ** 16)
    assert family.experts_touched(c, 16) == pytest.approx(touched)
    other = 2 * 64 + 2 * 16
    assert family.weight_bytes_per_step(c, 2) == pytest.approx(
        2 * (2 * (attn + other + router + touched * expert)
             + 64 * 512 + 64 + 16 * 64))
    # K and V of a position and layer: 2 kv heads x 16 x 2 bytes x 2
    assert family.kv_bytes_per_token(c) == 128
    # 5 passes read the 12 keys once each for 4 positions, 2 layers
    assert family.decode_state_bytes(c, 9) == 5 / 4 * 2 * 12 * 128
    assert family.decode_state_bytes(c, 8) == \
        family.decode_state_bytes(c, 11)


# ---- the configuration file, at its published widths -----------------------

def test_the_cells_files_agree_with_the_program_and_the_catalog(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, CELL)
    assert cell['chips'] == 1
    config = common.load_config(cell['config'])
    mix = common.load_traffic(cell['traffic'])
    cfg = serve_common.program_config(config, mix)
    assert (cfg.num_layers, cfg.max_seq_len) == (6, 1024)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (2048, 32, 4, 128, 151936)
    assert (cfg.num_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.expert_width) == (128, 128, 8, 768)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == \
        (4, 4, 151669)
    assert cfg.param_dtype == 'bfloat16' and cfg.dtype == 'bfloat16'
    assert family.file_sizes(config) == family.program_sizes(cfg)
    _, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(family, abstract)
    params = sum(math.prod(a.shape)
                 for a in jax.tree_util.tree_leaves(abstract))
    layer = 18_874_624 + 262_144 + 603_979_776 + 4_096
    assert layer == 623_120_640
    assert params == 6 * layer + 2 * 311_164_928 + 2048
    assert params == 4_361_055_744          # 8.72 GB in bfloat16
    # K and V: 6 layers x 2 x 4 x 128 x 2 bytes a position; a token's
    # share is 5 / 4 of the keys up to its block's end
    assert 6 * family.kv_bytes_per_token(config) == 12_288
    assert family.decode_state_bytes(config, 0) == 5 / 4 * 4 * 12_288
    assert family.decode_state_bytes(config, 1023) == 5 / 4 * 1024 * 12_288
    # a pass: every layer's attention, router, norms and all 128
    # experts (512 rows touch every one), the head, 512 embedding rows
    assert family.experts_touched(config, 512) == pytest.approx(128.0)
    assert family.weight_bytes_per_step(config) == pytest.approx(
        2 * (6 * layer + 311_164_928 + 2048 + 512 * 2048), rel=1e-9)
    assert 8.09e9 < family.weight_bytes_per_step(config) < 8.11e9
    # a token at position 500: 5 x 6 x 2 x 56.9 M and 2.5 x 2 x 311 M
    assert family.decode_flops(config, 500) == pytest.approx(
        5 * (6 * 2 * 56_885_248 + 4 * 32 * 128 * 6 * 504)
        + 2.5 * 2 * 311_164_928)
    # the mix's slots are the batch the roofline assumes
    assert mix['engine']['num_slots'] == config['stage']['decode_batch']
    assert mix['clients'] == 192 and mix['pool_size'] % 192 == 0
    assert mix['driver'] == 'closed_loop_blocks'
    assert config['stage']['stages'] * config['num_hidden_layers'] == \
        config['published']['num_hidden_layers']
    # the levers of the engine stay at the program's defaults
    assert set(mix['engine']) == {'num_slots', 'max_seq_len',
                                  'paged_block_size'}
    # the catalog's row, key for key but the one reduced
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'SDAR-30B-A3B-Chat')
        assert config['source'] == row['source_url']
        differ = [k for k, v in row['config'].items() if config[k] != v]
        assert differ == ['num_hidden_layers'] == list(config['reduced']) \
            == next(c for c in bench['configs']
                    if c['name'] == cell['config'])['reduced']
        assert config['published']['num_hidden_layers'] == \
            row['config']['num_hidden_layers']
        assert set(row['not_given']) == {'block length', 'noise schedule'}
    for key in ('block_length', 'denoising_steps', 'mask_token_id',
                'remasking_strategy', 'logit_shift'):
        assert key in config['assumed']
    limits = common.load_json(os.path.join(
        common.PERF_DIR, 'cells', f'{cell["name"]}.json'))['limits']
    assert limits['min_tokens'] >= 1000


def test_the_cell_is_listed_where_its_metrics_are(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, CELL)
    e2e = {m['name'] for m in common.cell_metrics(bench, cell,
                                                  'end_to_end')}
    assert e2e == {'tokens_per_s', 'tpot_p90_ms', 'setup_s'}
    layers = {m['name'] for m in common.cell_metrics(bench, cell,
                                                     'per_layer')}
    assert {'block_passes_per_token', 'commit_pass_share_pct',
            'masked_position_share_pct', 'experts_touched_pct',
            'expert_pairs_held_pct', 'expert_load_max_over_mean',
            'decode_hbm_roofline', 'prefill_mfu',
            'step_mfu.serve', 'device_idle_pct.serve',
            'dispatch_ahead_pct', 'idle_in_land_pct',
            'tick_p50_ms'} <= layers
    # `moe_gmm_share_pct` is not listed: an accepted test pins its list
    # to the one expert cell (PERF.md section 7)
    assert not layers & {'scan_fill_pct', 'state_cache_share_pct',
                         'step_mfu.train', 'flash_attn_roofline',
                         'moe_gmm_share_pct'}
    for name in layers:
        common.load_module('metrics', name)
    # the three new readers are this cell's alone
    for m in bench['per_layer']:
        if m['name'] in ('block_passes_per_token', 'commit_pass_share_pct',
                         'masked_position_share_pct'):
            assert m['workloads'] == [CELL] and m['layer'] == 'scheduler'
            assert m['moves'] == 'tokens_per_s'
