"""The second family in use: Falcon-H1 (a Mamba-2 mixer beside attention
in every block, per-slot recurrent state beside the paged K/V), as files
alone: `families/falcon_h1.py`, `references/falcon_h1.py`, a
configuration, a mix, a cell, two readers. Its reference against
`Transformer.apply` at a tiny size, the family through the closed loop
ending `correct` and its float8 control not, what the harness refuses,
the weight rules that undo the µP multipliers, and the required work by
hand."""
import copy
import dataclasses
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

MULTIPLIERS = {
    'attention_in_multiplier': 1, 'attention_out_multiplier': 0.5,
    'embedding_multiplier': 4.0, 'key_multiplier': 0.25,
    'lm_head_multiplier': 0.125, 'mlp_multipliers': [0.5, 0.25],
    'ssm_in_multiplier': 0.25,
    'ssm_multipliers': [0.35, 0.25, 0.18, 0.5, 0.35],
    'ssm_out_multiplier': 0.3}


def h1_config(layers: int = 2) -> dict:
    """`tiiuae/Falcon-H1-34B-Instruct`'s own keys at test sizes."""
    return {
        'name': 'tiny-h1', 'family': 'falcon_h1', 'hidden_size': 64,
        'intermediate_size': 128, 'num_hidden_layers': layers,
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'head_dim': 16, 'vocab_size': 512, 'rms_norm_eps': 1e-5,
        'rope_theta': 100000000000, 'attention_bias': False,
        'mlp_bias': False, 'tie_word_embeddings': False,
        'hidden_act': 'silu', 'mamba_n_heads': 4, 'mamba_d_head': 8,
        'mamba_d_ssm': 32, 'mamba_expand': 2, 'mamba_d_state': 16,
        'mamba_n_groups': 2, 'mamba_d_conv': 4, 'mamba_chunk_size': 128,
        'mamba_conv_bias': True, 'mamba_proj_bias': False,
        'mamba_rms_norm': True, 'mamba_norm_before_gate': False,
        'dtype': {'weights': 'float32', 'compute': 'float32',
                  'ssm_state': 'float32', 'conv_state': 'float32'},
        **copy.deepcopy(MULTIPLIERS),
        'program': {'registry_name': 'falcon-h1-34b', 'overrides': {
            'num_layers': layers, 'param_dtype': 'float32',
            'dtype': 'float32', 'd_model': 64, 'num_heads': 4,
            'num_kv_heads': 2, 'head_dim_override': 16, 'd_mlp': 128,
            'vocab_size': 512, 'ssm_heads': 4, 'ssm_head_dim': 8,
            'ssm_state': 16, 'ssm_groups': 2, 'attention_impl': 'xla',
            'embed_multiplier': 4.0, 'key_multiplier': 0.25,
            'attn_out_multiplier': 0.5, 'lm_head_multiplier': 0.125,
            'mlp_multipliers': (0.5, 0.25), 'ssm_in_multiplier': 0.25,
            'ssm_multipliers': (0.35, 0.25, 0.18, 0.5, 0.35),
            'ssm_out_multiplier': 0.3}}}


MIX = {'engine': {'max_seq_len': 128}}
SEED = 2**31 + 11


@pytest.fixture
def family():
    """The family with the tiny configuration's multipliers noted, and
    noted again afterwards: a test that hands it another configuration
    must not leave that one's behind."""
    fam = common.load_family(h1_config())
    fam.note_multipliers(h1_config())
    yield fam
    fam.note_multipliers(h1_config())


def build(cfg_dict: dict, fam):
    cfg = serve_common.program_config(cfg_dict, MIX)
    boxed, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(fam, abstract)
    return cfg, boxed, abstract


def test_its_reference_matches_the_program(family):
    """Float32 on the CPU, 2e-4: the chunked form sums a block in
    another order than the reference's scan, the rest is the same
    arithmetic (readings: 2e-6 to 6e-6 at 5 to 300 positions)."""
    from skypilot_tpu.models.transformer import Transformer
    cfg_dict = h1_config()
    cfg, boxed, abstract = build(cfg_dict, family)
    params = serve_common.make_params(SEED, family, boxed, abstract)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (2, 100)), jnp.int32)
    want = Transformer(cfg).apply({'params': params}, tokens)
    ref = common.load_reference(cfg_dict)
    layer, whole = weights_lib.Catalog(
        SEED, family, abstract).reference_weights()
    # every name but the optional ones this tree lacks (no projection
    # biases: mamba_proj_bias is false)
    assert set(layer(0)) == set(family.LAYER.values()) - {'b_in', 'b_out'}
    hidden = ref.hidden_states(tokens, whole, layer, cfg.num_layers,
                               family.reference_config(cfg_dict))
    got = ref.logits_at(hidden.reshape(-1, hidden.shape[-1]), whole,
                        cfg_dict)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=2e-4, rtol=2e-4)
    # the logits span units, not hundredths: the rules undo the
    # multipliers (N(0, 1/fan_in) under lm_head_multiplier 0.125 would
    # read 0.125)
    assert 0.7 < float(jnp.std(got)) < 1.4
    # the mixer is really there: without its skip the logits differ
    no_skip = lambda l: dict(layer(l), d_skip=jnp.zeros_like(
        layer(l)['d_skip']))
    hidden = ref.hidden_states(tokens, whole, no_skip, cfg.num_layers,
                               cfg_dict)
    assert float(jnp.abs(ref.logits_at(
        hidden.reshape(-1, hidden.shape[-1]), whole, cfg_dict)
        - got).max()) > 1e-3


def test_the_row_blocks_of_the_head_give_the_same_logits(family):
    cfg_dict = h1_config()
    ref = common.load_reference(cfg_dict)
    rows = jnp.asarray(np.random.default_rng(1).standard_normal((600, 64)),
                       jnp.float32)
    head = jnp.asarray(np.random.default_rng(2).standard_normal((64, 512)),
                       jnp.float32)
    whole = {'final_norm': jnp.ones((64,)), 'lm_head': head}.__getitem__
    blocks = ref.logits_at(rows, whole, cfg_dict)      # 256 + 256 + 88
    once = ref._head(rows, whole('final_norm'), head, eps=1e-5,
                     multiplier=0.125)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(once),
                               atol=1e-6, rtol=1e-6)


def test_the_three_branches_add_alike_to_the_first_residual(family):
    """With the rules that undo the multipliers, the mixer, attention
    and the MLP each add to the first layer's residual within a factor
    of three of one another, so that a fault in any one of them moves
    `correct`. At 32 positions: attention's output is a mean over the
    keys it sees, so its share falls as about one over the root of
    their number (0.33 of the mixer's at 96 positions here); whether
    `correct` still sees it at the cell's lengths is read on the chip
    (PERF.md section 6, the fault with attention's branch zeroed)."""
    cfg_dict = h1_config()
    cfg, _, abstract = build(cfg_dict, family)
    ref = common.load_reference(cfg_dict)
    layer, whole = weights_lib.Catalog(
        SEED, family, abstract).reference_weights()
    w = layer(0)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 512, 32))
    pos = jnp.arange(32, dtype=jnp.int32)
    x = cfg_dict['embedding_multiplier'] * whole('embed')[tokens]
    u = ref.rms_norm(x, w['attn_norm'], 1e-5)
    mix = ref.mixer(u, w, cfg_dict)
    q = ref.rope(ref._mm('td,dhk->thk', u, w['wq']), pos, 1e11)
    k = ref.rope(cfg_dict['key_multiplier'] * ref._mm(
        'td,dhk->thk', u, w['wk']), pos, 1e11)
    v = ref._mm('td,dhk->thk', u, w['wv'])
    att = cfg_dict['attention_out_multiplier'] * ref._mm(
        'thk,hkd->td', ref.attention(q, k, v, pos), w['wo'])
    out = ref.layer_row(x, pos, w, cfg_dict)
    mlp = out - (x + mix + att)
    rms = [float(jnp.sqrt(jnp.mean(jnp.square(b))))
           for b in (mix, att, mlp)]
    assert max(rms) / min(rms) < 3.0, rms
    assert 0.2 < min(rms) and max(rms) < 3.0, rms     # and near the
    assert 0.5 < float(jnp.std(x)) < 2.0              # residual's own


def test_the_rules_draw_what_the_family_says(family):
    cfg, boxed, abstract = build(h1_config(layers=4), family)
    mixer = serve_common.make_params(
        SEED, family, boxed, abstract)['layers']['layer']['mixer']
    # a decay rate exp(A_log) of about 1 to 16, a step softplus(dt_bias)
    # of about 0.002 to 0.05
    assert abs(float(jnp.mean(mixer['A_log'])) - math.log(4.0)) < 0.5
    assert abs(float(jnp.mean(mixer['dt_bias'])) + 4.6) < 0.6
    assert abs(float(jnp.mean(mixer['D'])) - 1.0) < 0.1
    assert abs(float(jnp.mean(mixer['norm_scale'])) - 1.0) < 0.05
    # the depthwise kernel's fan-in is its 4 taps
    assert 0.4 < float(jnp.std(mixer['conv_kernel'])) < 0.6
    # the input projection by segment: 1 / (ssm_in_multiplier x the
    # segment's multiplier x sqrt(64))
    w_in = np.asarray(mixer['in_proj']['kernel'])
    for lo, hi, m in ((0, 32, 0.35), (32, 64, 0.25), (64, 96, 0.18),
                      (96, 128, 0.5), (128, 132, 0.35)):
        want = 1.0 / (0.25 * m * 8.0)
        assert abs(w_in[..., lo:hi].std() / want - 1.0) < 0.15, (lo, hi)


def test_it_goes_through_the_closed_loop_and_ends_correct(family):
    """The whole of a serving run after the look for a chip, traced, so
    that the family's counts and the two new readers are read."""
    cfg_dict = h1_config()
    ctx = tiny.ctx(cfg_dict, tiny.serve_mix(), tiny.SERVE_LIMITS,
                   2**31 + 5, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct'], res['checks']
    assert res['attempted'] > 0 and res['failed'] == 0
    assert res['checks']['tokens_compared']['value'] >= 10
    rctx = res['reader_ctx']
    occ = rctx['occupancy']
    # 4 slots x 2 layers x (4 x 8 x 16 + 3 x 96) float32
    assert occ['state_bytes'] == 4 * 2 * (512 + 288) * 4
    assert occ['scan_positions'] == occ['prefill_chunks'] * 128
    assert occ['scan_tokens'] == occ['prefill_tokens']
    share = common.load_module('metrics', 'state_cache_share_pct').read(rctx)
    assert share == pytest.approx(100.0 * occ['state_bytes'] / (
        occ['state_bytes'] + occ['kv_pool_bytes']))
    fill = common.load_module('metrics', 'scan_fill_pct').read(rctx)
    spans = [s['attrs'] for s in rctx['spans']
             if s['name'] == 'engine.prefill']
    assert spans and all(a['chunks'] == 1 for a in spans)
    assert fill == pytest.approx(100.0 * sum(
        a['prompt_tokens'] for a in spans) / (128 * len(spans)))
    assert 5.0 < fill < 50.0        # prompts of 8-64 in a 128-wide chunk
    import flops_bytes
    work = rctx['work']
    flops = sum(family.decode_flops(cfg_dict, p)
                for p in work['decode_positions'])
    flops += flops_bytes.prefilled_flops(cfg_dict, work)
    got = common.load_module('metrics', 'step_mfu.serve').read(rctx)
    assert got == pytest.approx(
        100.0 * flops / work['window_s'] / rctx['peaks']['bf16_flops_per_s'])


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's engine has no `state_bytes` and its span no
    `chunks`: both readers return None and do not raise."""
    ctx = {'occupancy': {'blocks_capacity': 9}, 'work': {'chunk': 256},
           'spans': [{'name': 'engine.prefill',
                      'attrs': {'slot': 0, 'prompt_tokens': 40}}]}
    assert common.load_module('metrics', 'scan_fill_pct').read(ctx) is None
    assert common.load_module(
        'metrics', 'state_cache_share_pct').read(ctx) is None
    assert common.load_module('metrics', 'state_cache_share_pct').read(
        {'occupancy': {}}) is None


def test_the_control_in_float8_is_not_correct(family):
    mix = dict(tiny.serve_mix(), check_requests=16)
    ctx = tiny.ctx(h1_config(), mix, tiny.SERVE_LIMITS, 2**31 + 5, 2.5)
    ctx['control'] = 'fp8'
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])


def test_the_control_rounds_the_mixers_projections_too(family):
    ref = common.load_reference(h1_config())
    w = {'w_in': jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, 132)), jnp.float32), 'a_log': jnp.ones((4,)),
        'conv_w': jnp.ones((4, 96))}
    low = ref.lower_precision(w, 'int8')
    assert float(jnp.abs(low['w_in'] - w['w_in']).max()) > 1e-4
    assert low['a_log'] is w['a_log'] and low['conv_w'] is w['conv_w']


@pytest.mark.parametrize('key, named', [
    ('ssm_out_multiplier', 'ssm_out_multiplier'),
    ('key_multiplier', 'key_multiplier'),
    ('ssm_multipliers', 'ssm_multipliers_2'),
    ('mlp_multipliers', 'mlp_multipliers_0'),
    ('mamba_d_state', 'ssm_state'),
    ('mamba_norm_before_gate', 'ssm_norm_before_gate'),
    ('dtype', 'ssm_state_dtype'),
])
def test_a_size_switch_or_multiplier_that_differs_is_named(family, key,
                                                           named):
    cfg_dict = h1_config()
    cfg_dict[key] = {
        'ssm_out_multiplier': 0.31, 'key_multiplier': 0.26,
        'ssm_multipliers': [0.35, 0.25, 0.19, 0.5, 0.35],
        'mlp_multipliers': [0.6, 0.25], 'mamba_d_state': 32,
        'mamba_norm_before_gate': True,
        'dtype': dict(cfg_dict['dtype'], ssm_state='bfloat16')}[key]
    with pytest.raises(common.HarnessError, match=named):
        serve_common.program_config(cfg_dict, MIX)


def test_a_tree_of_another_family_is_refused_by_name(family):
    cfg = serve_common.program_config(h1_config(), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    llama = common.load_module('families', 'llama_shaped')
    with pytest.raises(common.HarnessError, match='mixer/'):
        weights_lib.check_tree(llama, abstract)
    cfg = serve_common.program_config(tiny.config(False, 0), MIX)
    _, abstract = serve_common.abstract_params(cfg)
    with pytest.raises(common.HarnessError, match='mixer/in_proj/kernel'):
        weights_lib.check_tree(family, abstract)


def test_the_required_work_equals_hand_sums(family):
    c = h1_config()
    # a layer's matmul weights: q 64x64, k and v 64x32, o 64x64, the
    # mixer's in 64x132 and out 32x64, the MLP 3 x 64x128
    layer_mm = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 64 * 132 + 32 * 64 \
        + 3 * 64 * 128
    assert family.layer_matmul_params(c) == layer_mm
    # the recurrence a position and layer: 5 operations an entry of the
    # 4 x 8 x 16 state, and 4 taps over 96 channels
    scan = 5 * 4 * 8 * 16 + 2 * 4 * 96
    assert family.scan_flops_per_position(c) == scan
    # one generated token at position 9: 2 layers, 10 keys of 16 wide in
    # 2 matmuls a head and layer, and the 64 x 512 unembedding
    assert family.decode_flops(c, 9) == (
        2 * (2 * layer_mm + scan) + 4 * 2 * 4 * 16 * 10 + 2 * 64 * 512)
    # prompt positions 4 .. 11, the last one's logits: keys 5 + .. + 12
    assert family.prefill_flops(c, 4, 12, True) == (
        8 * 2 * (2 * layer_mm + scan) + 4 * 2 * 4 * 16 * 68
        + 2 * 64 * 512)
    # a layer's other weights: two norms, 4 x 96 taps + 96 biases, 3 x 4
    # head scalars, 32 norm scales
    other = 2 * 64 + 4 * 96 + 96 + 12 + 32
    assert family.weight_bytes_per_step(c, 2) == 2 * (
        2 * (layer_mm + other) + 64 * 512 + 64)
    # a slot: 2 layers x (4 x 8 x 16 x 4 bytes + 3 x 96 x 4 bytes), read
    # and written; K and V of 10 keys, 2 kv heads x 16 x 2 bytes x 2
    assert family.state_bytes_per_slot(c) == 2 * (2048 + 1152)
    assert family.decode_state_bytes(c, 9) == (
        2 * 2 * (2048 + 1152) + 10 * 2 * 2 * 2 * 16 * 2)


# ---- the configuration file, at its published widths -----------------------

def test_the_cells_files_agree_with_the_program_and_the_catalog(family):
    bench = common.load_benchmark()
    cell = common.find_cell(bench, 'falcon-h1-34b-l6.short-chat-64')
    config = common.load_config(cell['config'])
    mix = common.load_traffic(cell['traffic'])
    try:
        cfg = serve_common.program_config(config, mix)
    finally:
        family.note_multipliers(h1_config())
    assert (cfg.num_layers, cfg.max_seq_len) == (6, 1024)
    assert cfg.param_dtype == 'bfloat16' and cfg.dtype == 'bfloat16'
    assert cfg.ssm_state_dtype == 'float32'
    _, abstract = serve_common.abstract_params(cfg)
    weights_lib.check_tree(family, abstract)
    leaves = jax.tree_util.tree_leaves(abstract)
    params = sum(math.prod(a.shape) for a in leaves)
    per_layer = 31_457_280 + 68_351_072 + 330_301_440 + 10_240
    assert params == 6 * per_layer + 2 * 1_336_934_400 + 5120
    assert params == 5_254_594_112          # 10.51 GB in bfloat16
    # a slot's state: 6 x (32 x 128 x 256 x 4 + 3 x 5120 x 2)
    assert family.state_bytes_per_slot(config) == 6 * (4_194_304 + 30_720)
    assert family.kv_bytes_per_token(config) == 12_288
    # a decode step: every layer and the head once, the table left out
    assert family.weight_bytes_per_step(config) == 2 * (
        6 * per_layer + 1_336_934_400 + 5120)
    # the catalog's row, key for key but the one reduced
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == 'Falcon-H1-34B-Instruct')
        assert config['source'] == row['source_url']
        differ = [k for k, v in row['config'].items() if config[k] != v]
        assert differ == ['num_hidden_layers'] == list(config['reduced'])
        assert config['published']['num_hidden_layers'] == \
            row['config']['num_hidden_layers']
    limits = common.load_json(os.path.join(
        common.PERF_DIR, 'cells', f'{cell["name"]}.json'))['limits']
    assert limits['min_tokens'] >= 1000
    assert mix['clients'] == 96 and mix['engine']['num_slots'] == 64
    assert mix['pool_size'] % 96 == 0
