"""Ling-3.0-flash's language model (models/kda.py, models/mla.py, the
group-limited router of models/moe.py), pinned on the CPU at tiny sizes:

- Kimi delta attention: the chunked form against the recurrence at
  lengths that are not a multiple of its block, pads that advance
  nothing, a row of count 0 kept bit for bit, a reused slot that starts
  clean, and ops/kda_state_step (interpreted, at head blocks of 32 and
  16, and on a state the chunked form wrote) against the XLA step;
- latent attention: the absorbed form (whole sequences, the paged pool
  through the gather and through ops/paged_attention) against the
  expanded one;
- the router: groups that keep every expert change nothing, so the
  ungrouped models' choices are what they were; a token's experts lie in
  its best groups; the SwiGLU clamps;
- the stack: runs of one kind a group, the engine's greedy tokens equal
  the full forward's, the refusals by mechanism, the counts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu.models import get_config
from skypilot_tpu.models import kda as kda_lib
from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.models.cache_carry import layer_groups
from skypilot_tpu.models.inference import ContinuousBatchingEngine
from skypilot_tpu.models.transformer import SwiGLU, Transformer

F32 = jnp.float32


def tiny(**kw):
    """A tiny Ling: a dense KDA layer, a KDA expert layer, an MLA expert
    layer and a KDA expert layer; 16 experts in 4 groups, 2 kept, 4
    held."""
    base = dict(vocab_size=256, d_model=64, num_layers=4, num_heads=4,
                num_kv_heads=4, head_dim_override=16, d_mlp=96,
                max_seq_len=64, num_experts=16, experts_per_token=2,
                n_group=4, topk_group=2, experts_held=4, first_expert=4,
                d_expert=32, d_shared_expert=32, num_dense_layers=1,
                mixer_kinds=('kda', 'kda', 'mla', 'kda'), mla_kv_rank=32,
                mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
                expert_swiglu_limits=(),
                shared_swiglu_limits=(), dtype='float32',
                param_dtype='float32')
    base.update(kw)
    return get_config('ling-3.0-flash', **base)


def _inputs(rng, b, t, h=3, d=8):
    n = lambda *s: jnp.asarray(rng.standard_normal(s), F32)
    q = kda_lib.l2_normalize(n(b, t, h, d)) * d ** -0.5
    k = kda_lib.l2_normalize(n(b, t, h, d))
    v = n(b, t, h, d)
    g = -5.0 * jax.nn.sigmoid(n(b, t, h, d) - 2.0)
    beta = jax.nn.sigmoid(n(b, t, h))
    return q, k, v, g, beta


def _recurrence(s, q, k, v, g, beta):
    outs = []
    for i in range(q.shape[1]):
        o, s = kda_lib.kda_step(s, q[:, i], k[:, i], v[:, i], g[:, i],
                                beta[:, i])
        outs.append(o)
    return jnp.stack(outs, axis=1), s


@pytest.mark.parametrize('t, block', [(37, 16), (16, 16), (5, 16),
                                      (23, 4)])
def test_the_chunked_form_is_the_recurrence(t, block):
    rng = np.random.default_rng(t)
    args = _inputs(rng, 2, t)
    s0 = jnp.asarray(rng.standard_normal((2, 3, 8, 8)), F32) * 0.3
    want_o, want_s = _recurrence(s0, *args)
    got_o, got_s = kda_lib.kda_chunked(s0, *args, block)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5, rtol=2e-5)


def test_pads_advance_nothing_in_the_chunked_form():
    """Right pads carry g = 0 and beta = 0: the state at the end of a
    padded chunk is the state after its real positions, and the real
    positions' outputs do not see the pads."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta = _inputs(rng, 1, 29)
    real = 19
    mask = (jnp.arange(29) < real)[None, :]
    g = jnp.where(mask[..., None, None], g, 0.0)
    beta = jnp.where(mask[..., None], beta, 0.0)
    s0 = jnp.zeros((1, 3, 8, 8), F32)
    o, s = kda_lib.kda_chunked(s0, q, k, v, g, beta, 16)
    o_real, s_real = kda_lib.kda_chunked(
        s0, *(a[:, :real] for a in (q, k, v, g, beta)), 16)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_real),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o[:, :real]),
                               np.asarray(o_real), atol=1e-6, rtol=1e-6)


def _state_kernel_against_xla_step(rng, h, layers, layer, touched, fresh,
                                   d=16):
    """The interpreted kernel over a stacked leaf of `layers` at a traced
    `layer`, against `kda_step`: a touched row advances (to float32
    round-off), a fresh one starts from zero, an untouched one keeps its
    state bit for bit, and no other layer is touched."""
    from skypilot_tpu.ops.kda_state_step import kda_state_step
    slots = len(touched)
    q, k, v, g, beta = (a[:, 0] for a in _inputs(rng, slots, 1, h, d))
    leaf = jnp.asarray(rng.standard_normal((layers, slots, h, d, d)), F32)
    touched, fresh = jnp.asarray(touched), jnp.asarray(fresh)
    o, out = jax.jit(lambda *a: kda_state_step(*a, interpret=True))(
        leaf, jnp.int32(layer), touched, fresh, jnp.exp(g), q, k,
        beta[..., None] * k, v)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, leaf[layer])
    want_o, want_s = kda_lib.kda_step(s0, q, k, v, g, beta)
    live = np.asarray(touched)
    np.testing.assert_allclose(np.asarray(o)[live],
                               np.asarray(want_o)[live], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out[layer])[live],
                               np.asarray(want_s)[live], atol=1e-5,
                               rtol=1e-5)
    for slot in np.flatnonzero(~live):
        assert np.array_equal(np.asarray(out[layer, slot]),
                              np.asarray(leaf[layer, slot]))
    for other in set(range(layers)) - {layer}:
        assert np.array_equal(np.asarray(out[other]),
                              np.asarray(leaf[other]))


def test_the_state_kernel_is_the_xla_step():
    """ops/kda_state_step under the interpreter against `kda_step` on a
    stacked leaf at a traced layer: a touched row advances, a fresh one
    starts from zero, an untouched one is kept and no other layer is
    touched."""
    _state_kernel_against_xla_step(
        np.random.default_rng(4), h=4, layers=3, layer=1,
        touched=[True, True, False, True], fresh=[False, True, False, False])


@pytest.mark.parametrize('hb', [32, 16])
def test_the_state_kernel_at_a_head_block(hb):
    """The same rules at a head block of a whole slot of 32 heads and of
    16: every head of a cell (the loop's groups of eight)."""
    from skypilot_tpu.ops import kda_state_step as ops
    assert ops.head_block(hb) == hb
    _state_kernel_against_xla_step(
        np.random.default_rng(40 + hb), h=hb, layers=2, layer=0,
        touched=[True, False, True], fresh=[False, False, True])


def test_a_state_from_the_chunked_form_steps_in_the_kernel_as_in_xla():
    """The layout's round trip from prefill to decode: the state that
    `kda_chunked` writes, advanced one step by the interpreted kernel,
    equals `kda_step` on the same state."""
    from skypilot_tpu.ops.kda_state_step import kda_state_step
    rng = np.random.default_rng(6)
    slots, t, h, d = 2, 21, 4, 16
    q, k, v, g, beta = _inputs(rng, slots, t + 1, h, d)
    _, state = kda_lib.kda_chunked(
        jnp.zeros((slots, h, d, d), F32),
        *(a[:, :t] for a in (q, k, v, g, beta)), kda_lib.CHUNK)
    step = [a[:, t] for a in (q, k, v, g, beta)]
    want_o, want_s = kda_lib.kda_step(state, *step)
    q1, k1, v1, g1, b1 = step
    o, got = jax.jit(lambda *a: kda_state_step(*a, interpret=True))(
        state, None, jnp.ones((slots,), bool), jnp.zeros((slots,), bool),
        jnp.exp(g1), q1, k1, b1[..., None] * k1, v1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_s),
                               atol=1e-5, rtol=1e-5)


def _mixer_cache(cfg, params, x, positions, state_rows, cache=None):
    model = kda_lib.KimiDeltaMixer(cfg)
    variables = {'params': params}
    if cache is not None:
        variables['cache'] = cache
    out, mutated = model.apply(variables, x, positions, state_rows,
                               mutable=['cache'])
    return out, nn.unbox(mutated['cache'])


def test_a_slot_of_count_zero_keeps_its_state_and_a_reused_one_starts_clean():
    cfg = dataclasses.replace(tiny(), decode=True, state_slots=3)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 6, 64)), F32)
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    model = kda_lib.KimiDeltaMixer(cfg)
    variables = model.init(jax.random.PRNGKey(0), x, pos,
                           (jnp.zeros((1,), jnp.int32), None))
    params = variables['params']
    dirty = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        nn.unbox(variables['cache']))
    rows = lambda slot, valid: (jnp.asarray([slot], jnp.int32),
                                jnp.asarray([valid], jnp.int32))
    # a reused slot: garbage left in slot 1 is not read at position 0
    out, cache = _mixer_cache(cfg, params, x, pos, rows(1, 6), dirty)
    clean, clean_cache = _mixer_cache(
        cfg, params, x, pos, rows(1, 6),
        jax.tree.map(jnp.zeros_like, dirty))
    assert np.array_equal(np.asarray(out), np.asarray(clean))
    for name in ('ssm_state', 'conv_state'):
        assert np.array_equal(np.asarray(cache[name][1]),
                              np.asarray(clean_cache[name][1]))
        # the other slots are untouched
        for other in (0, 2):
            assert np.array_equal(np.asarray(cache[name][other]),
                                  np.asarray(dirty[name][other]))
    # a decode step over every slot, slot 2 of count 0: kept bit for bit
    step_x = jnp.asarray(rng.standard_normal((3, 1, 64)), F32)
    step_pos = jnp.asarray([[6], [6], [3]], jnp.int32)
    states = {}
    for kernel in ('xla', 'pallas_interpret'):
        kcfg = dataclasses.replace(cfg, decode_kernel=kernel)
        _, after = _mixer_cache(kcfg, params, step_x, step_pos,
                                (None, jnp.asarray([1, 1, 0], jnp.int32)),
                                cache)
        for name in ('ssm_state', 'conv_state'):
            assert np.array_equal(np.asarray(after[name][2]),
                                  np.asarray(cache[name][2])), kernel
            assert not np.array_equal(np.asarray(after[name][0]),
                                      np.asarray(cache[name][0])), kernel
        states[kernel] = np.asarray(after['ssm_state'])
    # both read and write the one keys-major leaf
    np.testing.assert_allclose(states['pallas_interpret'], states['xla'],
                               atol=1e-5, rtol=1e-5)


def test_the_absorbed_latent_attention_is_the_expanded_one():
    """The layer's whole-sequence output (absorbed over the latents)
    against the expanded form computed here from its own weights."""
    from skypilot_tpu.models.mla import LatentAttention
    from skypilot_tpu.models.transformer import apply_rope
    cfg = tiny()
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 13, 64)), F32)
    pos = jnp.broadcast_to(jnp.arange(13, dtype=jnp.int32), (2, 13))
    layer = LatentAttention(cfg)
    params = nn.unbox(layer.init(jax.random.PRNGKey(1), x, pos)['params'])
    got = layer.apply({'params': params}, x, pos)
    rms = lambda a, w: a * jax.lax.rsqrt(
        jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps) * w
    hi = jax.lax.Precision.HIGHEST
    q = jnp.einsum('btd,dhe->bthe', x, params['q_proj']['kernel'],
                   precision=hi)
    q = rms(q, params['q_norm']['scale'])
    down = jnp.einsum('btd,dr->btr', x, params['kv_down']['kernel'],
                      precision=hi)
    c = rms(down[..., :32], params['kv_norm']['scale'])
    kr = apply_rope(down[..., None, 32:], pos, cfg.rope_theta)
    up = jnp.einsum('btr,rhx->bthx', c, params['kv_up'], precision=hi)
    k = jnp.concatenate([up[..., :16], jnp.broadcast_to(
        kr, up.shape[:3] + (8,))], -1)
    qc = jnp.concatenate([q[..., :16],
                          apply_rope(q[..., 16:], pos, cfg.rope_theta)], -1)
    s = jnp.einsum('bqhe,bkhe->bhqk', qc, k, precision=hi) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((13, 13), bool)), s, -jnp.inf)
    a = jnp.einsum('bhqk,bkhv->bqhv', jax.nn.softmax(s, -1), up[..., 16:],
                   precision=hi)
    gate = jnp.einsum('btd,dh->bth', x, params['gate_proj']['kernel'],
                      precision=hi)
    a = a * jax.nn.sigmoid(gate)[..., None]
    want = jnp.einsum('bthv,hvd->btd', a, params['o_proj']['kernel'],
                      precision=hi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('kernel', ['xla', 'pallas_interpret'])
def test_the_engine_decodes_what_the_full_forward_predicts(kernel):
    """Greedy through the paged engine (chunked prefill, the latent
    pool, the KDA state by slot, slots reused) against the argmax of one
    full forward over prompt and answer; the decode kernels the engine
    takes on a TPU, under the interpreter."""
    cfg = tiny()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))['params']
    eng = ContinuousBatchingEngine(cfg, params=params, num_slots=3,
                                   max_seq_len=64, paged_block_size=8,
                                   decode_kernel=kernel)
    try:
        prompts = [list(np.random.default_rng(i).integers(0, 256, n))
                   for i, n in enumerate((5, 19, 9, 12))]
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        outs = [f.result(timeout=600)[0] for f in futs]
        occ = eng.paged_occupancy()
    finally:
        eng.stop()
    # one causal forward over every answer, right-padded to one length
    seqs = [list(p) + list(toks) for p, toks in zip(prompts, outs)]
    width = max(map(len, seqs))
    batch = jnp.asarray([s + [0] * (width - len(s)) for s in seqs],
                        jnp.int32)
    best = np.asarray(jnp.argmax(model.apply({'params': params}, batch),
                                 -1))
    for i, (p, toks) in enumerate(zip(prompts, outs)):
        assert list(toks) == list(best[i, len(p) - 1:len(p) - 1 + len(toks)])
    assert occ['state_bytes'] > 0 and occ['kv_pool_bytes'] > 0
    assert (occ['state_kernel_steps'] > 0) == (kernel != 'xla')
    held = occ['route_decode_tokens_group_held']
    assert 0 < held < occ['route_decode_pairs_routed'] // 2


def test_groups_that_keep_every_expert_change_nothing():
    """With every group kept, group-limited selection is the plain one
    bit for bit: the models without groups (n_group 1, which skips the
    grouping altogether) choose as they did."""
    cfg = tiny(mixer_kinds=(), num_layers=1, num_dense_layers=0)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 5, 64)), F32)
    block = lambda c: moe_lib.MoEBlock(c)
    params = block(cfg).init(jax.random.PRNGKey(2), x)['params']
    params = dict(params, expert_bias=jnp.asarray(
        rng.standard_normal(16) * 0.05, F32))
    outs = [block(dataclasses.replace(cfg, n_group=g, topk_group=g)).apply(
        {'params': params}, x) for g in (1, 4)]
    assert np.array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    kept = block(cfg).apply({'params': params}, x)
    assert not np.array_equal(np.asarray(kept), np.asarray(outs[0]))


def test_a_token_chooses_within_its_best_groups():
    cfg = tiny()
    # 4 groups of 4; group scores (sum of the best two): 1.1, 1.5, 0.4,
    # 1.7: groups 3 and 1 are kept, though group 0 holds the best expert
    sel = jnp.asarray([[1.0, 0.1, 0.0, 0.0, 0.8, 0.7, 0.0, 0.0,
                        0.2, 0.2, 0.1, 0.0, 0.9, 0.8, 0.0, 0.0]], F32)
    masked, keep = moe_lib.limit_groups(cfg, sel)
    assert np.asarray(keep).tolist() == [[False, True, False, True]]
    _, chosen = jax.lax.top_k(masked, 2)
    assert sorted(np.asarray(chosen)[0].tolist()) == [4, 12]


def test_the_swiglu_clamp():
    cfg = dataclasses.replace(tiny(), dtype='float32')
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((1, 4, 64)), F32) * 3
    mlp = SwiGLU(cfg, 0.5)
    params = nn.unbox(mlp.init(jax.random.PRNGKey(3), x)['params'])
    got = mlp.apply({'params': params}, x)
    gate = x @ params['gate_proj']['kernel']
    up = x @ params['up_proj']['kernel']
    want = (jax.nn.silu(jnp.minimum(gate, 0.5)) * jnp.clip(up, -0.5, 0.5)
            ) @ params['down_proj']['kernel']
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    free = SwiGLU(cfg).apply({'params': params}, x)
    assert not np.allclose(np.asarray(free), np.asarray(got))


def test_the_published_stack_runs_a_group_a_run():
    cfg = get_config('ling-3.0-flash')
    groups = layer_groups(cfg)
    # 2 dense KDA layers, then KDA x 3, MLA, (KDA x 5, MLA) x 6 with the
    # clamps splitting the last runs: 34 (shared 5), 35 (MLA; 4, 5),
    # 36-39 (4, 5), 40 (4, 7), 41 (MLA; 4, 7)
    assert [(g.name, g.mixer, g.start, g.stop) for g in groups[:4]] == [
        ('dense_layers', 'kda', 0, 2), ('layers', 'kda', 2, 5),
        ('layers_5', 'mla', 5, 6), ('layers_6', 'kda', 6, 11)]
    assert [(g.start, g.mixer, g.limits) for g in groups[-6:]] == [
        (30, 'kda', (0.0, 0.0)), (34, 'kda', (0.0, 5.0)),
        (35, 'mla', (4.0, 5.0)), (36, 'kda', (4.0, 5.0)),
        (40, 'kda', (4.0, 7.0)), (41, 'mla', (4.0, 7.0))]
    assert all(g.expert_offset == g.start - 2 for g in groups[1:])
    assert sum(g.stop - g.start for g in groups) == 42
    # a model of one mixer keeps its two groups as they were named
    trinity = layer_groups(get_config('trinity-large-preview'))
    assert [(g.name, g.dense, g.start, g.stop) for g in trinity] == [
        ('dense_layers', True, 0, 6), ('layers', False, 6, 60)]
    assert 1.2e11 < cfg.num_params() < 1.3e11
    assert cfg.has_recurrent_state and cfg.has_latent_cache


@pytest.mark.parametrize('kw, lever', [
    (dict(kv_quant='int8', paged_block_size=8), 'kv_quant'),
    (dict(), 'paged_block_size'),
    (dict(paged_block_size=8, max_adapters=2, adapter_rank=4),
     'max_adapters'),
    (dict(paged_block_size=8, speculative=2), 'speculative'),
    (dict(paged_block_size=8, prefix_cache=4), 'prefix_cache'),
])
def test_levers_it_cannot_take_are_refused_by_name(kw, lever):
    with pytest.raises(NotImplementedError, match=lever):
        ContinuousBatchingEngine(tiny(), num_slots=2, max_seq_len=64, **kw)


def test_tp_is_refused_for_a_latent_cache():
    with pytest.raises(NotImplementedError, match='latent cache'):
        tiny(mixer_kinds=('mla',) * 4).assert_tp_compatible(2)
    with pytest.raises(NotImplementedError, match='recurrent-state'):
        tiny(mixer_kinds=('kda',) * 4).assert_tp_compatible(2)
