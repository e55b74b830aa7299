"""The plain reference against models/transformer.py at a tiny size, for
both configurations' switches: a window without biases (Mistral) and
biases without a window (Qwen2); and the weights made alone against the
weights made whole."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import tiny
import weights as weights_lib
from drivers import serve_common


def _program_logits(cfg_dict, seed, tokens):
    from skypilot_tpu.models.transformer import Transformer
    mix = {'engine': {'max_seq_len': 128}}
    cfg = serve_common.program_config(cfg_dict, mix)
    boxed, abstract = serve_common.abstract_params(cfg)
    params = serve_common.make_params(seed, boxed, abstract)
    model = Transformer(dataclasses.replace(cfg, attention_impl='xla'))
    return cfg, abstract, params, model.apply({'params': params}, tokens)


@pytest.mark.parametrize('bias,window', [(False, 24), (True, 0)])
def test_reference_matches_the_program(bias, window):
    cfg_dict = tiny.config(bias, window)
    seed = 2**31 + 3
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (2, 48)), jnp.int32)
    cfg, abstract, _, want = _program_logits(cfg_dict, seed, tokens)
    ref = common.load_module('references', 'llama_shaped')
    cat = weights_lib.Catalog(seed, abstract)
    f32 = lambda a: a.astype(jnp.float32)
    layer = lambda l: {n: f32(cat.layer(p, l))
                       for p, n in serve_common.LAYER_NAMES.items()
                       if cat.has(p)}
    if bias:
        assert 'bq' in layer(0)
        assert float(jnp.abs(layer(0)['bq']).max()) > 0
    hidden = ref.hidden_states(tokens, f32(cat.whole(serve_common.EMBED)),
                               layer, cfg.num_layers,
                               dict(cfg_dict, sliding_window=window))
    got = ref.logits_at(hidden.reshape(-1, hidden.shape[-1]),
                        f32(cat.whole(serve_common.FINAL_NORM)),
                        f32(cat.whole(serve_common.LM_HEAD)), cfg_dict)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(got.shape),
                               atol=2e-4, rtol=2e-4)


def test_window_changes_the_answer():
    """The window is really applied: with it the logits differ."""
    seed = 7
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 512, (1, 48)), jnp.int32)
    _, _, _, a = _program_logits(tiny.config(False, 8), seed, tokens)
    _, _, _, b = _program_logits(tiny.config(False, 0), seed, tokens)
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_units_made_alone_equal_the_whole():
    cfg_dict = tiny.config(True, 0)
    cfg = serve_common.program_config(cfg_dict,
                                      {'engine': {'max_seq_len': 128}})
    boxed, abstract = serve_common.abstract_params(cfg)
    params = serve_common.make_params(11, boxed, abstract)
    cat = weights_lib.Catalog(11, abstract)
    flat = {'/'.join(weights_lib.path_of(kp)): leaf for kp, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for name, whole in flat.items():
        if name.startswith('layers/'):
            for l in range(cfg.num_layers):
                assert bool((cat.layer(name, l) == whole[l]).all()), name
        else:
            assert bool((cat.whole(name) == whole).all()), name
    other = serve_common.make_params(12, boxed, abstract)
    assert float(jnp.abs(other['embed']['embedding']
                         - params['embed']['embedding']).max()) > 0.1


def test_lower_precision_moves_the_weights_a_little():
    ref = common.load_module('references', 'llama_shaped')
    w = {'wq': jax.random.normal(jax.random.PRNGKey(0), (64, 4, 16)),
         'attn_norm': jnp.ones((64,))}
    for how, lo, hi in (('int8', 1e-4, 2e-2), ('fp8', 1e-3, 2e-1)):
        low = ref.lower_precision(w, how)
        err = float(jnp.abs(low['wq'] - w['wq']).max())
        assert lo < err < hi, (how, err)
        assert bool((low['attn_norm'] == w['attn_norm']).all())
