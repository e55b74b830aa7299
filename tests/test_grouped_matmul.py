"""`ops/grouped_matmul.py`: the Pallas grouped product under the
interpreter on the CPU against `jax.lax.ragged_dot`, its twin, and the
dropless expert layer on either."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu.models import get_config
from skypilot_tpu.models import moe
from skypilot_tpu.ops import grouped_matmul as gm

M, K, N = 256, 256, 256          # two row tiles of 128
LAYERS, GROUPS = 4, 8

# what each case's groups hold, of M rows in two tiles of 128
SIZES = {
    'no group touched': [0] * 8,
    'one group touched': [0, 0, 0, 5, 0, 0, 0, 0],
    'all touched, rows past the last group':
        [3, 1, 9, 2, 30, 4, 1, 7],
    'a group straddles the row tile': [60, 0, 50, 40, 0, 0, 20, 0],
    'groups of 1 and of more than a tile': [1, 1, 140, 1, 0, 0, 1, 1],
    'the groups fill every row': [32] * 8,
    'the last group ends on the tile': [100, 0, 0, 0, 0, 0, 0, 28],
}


def _operands(dtype, seed=0):
    lhs = jax.random.normal(jax.random.PRNGKey(seed), (M, K), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(seed + 1),
                            (LAYERS, GROUPS, K, N), jnp.float32) / 16
    return lhs.astype(dtype), rhs.astype(dtype)


def _want(lhs, rhs, sizes, layer, out_dtype=jnp.float32):
    """`ragged_dot` itself on the layer's own matrices."""
    return jax.lax.ragged_dot(lhs, rhs[layer], sizes,
                              preferred_element_type=out_dtype)


@pytest.mark.parametrize('layer', [1, 3], ids=['second of four layers',
                                               'last of four layers'])
@pytest.mark.parametrize('tile_bytes,tiles', [
    (3 << 20, (128, 256, 256)), (K * 128 * 4, (128, 256, 128)),
    (128 * 128 * 4, (128, 128, 128))],
    ids=['one weight tile', 'two N tiles', 'two K by two N tiles'])
@pytest.mark.parametrize('case', list(SIZES))
def test_float32_is_ragged_dot_to_1e_5(case, tile_bytes, tiles, layer,
                                       monkeypatch):
    monkeypatch.setattr(gm, '_WEIGHT_TILE_BYTES', tile_bytes)
    assert gm.tiles_for(M, K, N, 4) == tiles
    lhs, rhs = _operands(jnp.float32)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    held = int(sizes.sum())
    # what the rows past the last group hold is the caller's: poison
    # them, and the rows of the groups must not see it
    lhs = lhs.at[held:].set(jnp.nan)
    got = gm.grouped_matmul(lhs, rhs, sizes, jnp.int32(layer),
                            impl='pallas_interpret')
    assert got.shape == (M, N) and got.dtype == jnp.float32
    want = _want(lhs, rhs, sizes, layer)
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held], atol=1e-5,
                               rtol=1e-5)
    assert np.isfinite(np.asarray(got)[:held]).all()
    twin = gm.grouped_matmul(lhs, rhs, sizes, jnp.int32(layer),
                             impl='xla')
    np.testing.assert_allclose(np.asarray(twin)[:held],
                               np.asarray(want)[:held], atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize('out_dtype', [jnp.bfloat16, jnp.float32],
                         ids=['gate and up in bf16',
                              'the down product in float32'])
@pytest.mark.parametrize('case', ['all touched, rows past the last group',
                                  'a group straddles the row tile',
                                  'groups of 1 and of more than a tile'])
def test_bf16_is_as_near_float32_as_ragged_dot_is(case, out_dtype):
    lhs32, rhs32 = _operands(jnp.float32, seed=3)
    lhs, rhs = lhs32.astype(jnp.bfloat16), rhs32.astype(jnp.bfloat16)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    held = int(sizes.sum())
    exact = np.asarray(_want(lhs32, rhs32, sizes, 2))[:held]
    twin = _want(lhs, rhs, sizes, 2, out_dtype)
    got = gm.grouped_matmul(lhs, rhs, sizes, 2,
                            preferred_element_type=out_dtype,
                            impl='pallas_interpret')
    assert got.dtype == out_dtype == twin.dtype
    f32 = lambda a: np.asarray(a.astype(jnp.float32))[:held]
    its = np.abs(f32(twin) - exact).max()
    assert 1e-4 < its < 0.2
    assert np.abs(f32(got) - exact).max() <= 1.25 * its
    # and the two agree to a rounding of the output's type
    np.testing.assert_allclose(
        f32(got), f32(twin), atol=1e-5,
        rtol=2 ** -7 if out_dtype == jnp.bfloat16 else 1e-5)


def test_float32_weights_under_bf16_rows_are_cast_a_tile_at_a_time():
    """`param_dtype` float32 with a bf16 compute type: the kernel casts
    the tile it holds, the stack is never converted whole."""
    lhs32, rhs = _operands(jnp.float32, seed=5)
    lhs = lhs32.astype(jnp.bfloat16)
    sizes = jnp.asarray(SIZES['a group straddles the row tile'])
    held = int(sizes.sum())
    got = gm.grouped_matmul(lhs, rhs, sizes, 1, impl='pallas_interpret',
                            preferred_element_type=jnp.float32)
    want = _want(lhs, rhs.astype(jnp.bfloat16), sizes, 1)
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held], atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize('m,k,n,itemsize,want', [
    (512, 3072, 3072, 2, (128, 3072, 512)),     # a decode step
    (1024, 3072, 3072, 2, (128, 3072, 512)),    # a chunk
    (512, 3072, 3072, 4, (128, 3072, 256)),
    (64, 256, 128, 4, (64, 256, 128)),
    (48, 128, 128, 2, (16, 128, 128)),
    (512, 4096, 14336, 2, (128, 4096, 256)),
    (512, 14336, 4096, 2, (128, 7168, 128)),    # K cut: 128 columns
    (512, 128 * 97, 128, 2, (128, 128, 128)),   # of it are too many
    (512, 64, 128, 4, None),                    # K does not tile
    (512, 128, 96, 4, None),                    # N does not tile
    (100, 128, 128, 4, None),                   # M does not tile
])
def test_tiles_come_from_the_static_shapes(m, k, n, itemsize, want):
    assert gm.tiles_for(m, k, n, itemsize) == want


def test_auto_on_the_cpu_is_ragged_dot_and_says_so(caplog):
    lhs, rhs = _operands(jnp.float32)
    sizes = jnp.asarray(SIZES['one group touched'], jnp.int32)
    with caplog.at_level(logging.INFO, logger=gm.logger.name):
        got = gm.grouped_matmul(lhs, rhs, sizes, 1)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "resolved to 'xla'" in lines[0], lines
    assert 'm=256 k=256 n=256' in lines[0] and 'tpu=False' in lines[0]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(gm.grouped_matmul(
            lhs, rhs, sizes, 1, impl='xla')))
    # a TPU in sight and shapes that tile: the kernel, and its tiles
    caplog.clear()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, '_on_tpu', lambda: True)
        mp.setattr(gm, '_pallas', lambda *a: 'the kernel')
        with caplog.at_level(logging.INFO, logger=gm.logger.name):
            assert gm.grouped_matmul(lhs, rhs, sizes, 1) == 'the kernel'
            untiled = gm.grouped_matmul(lhs[:, :64], rhs[:, :, :64],
                                        sizes, 1)
    lines = [r.getMessage() for r in caplog.records]
    assert "resolved to 'pallas'" in lines[0], lines
    assert 'tiles=(128, 256, 256)' in lines[0]
    assert "resolved to 'xla'" in lines[1] and 'tiles=None' in lines[1]
    assert untiled.shape == (M, N)


def test_the_kernel_refuses_shapes_that_do_not_tile_by_name():
    lhs, rhs = _operands(jnp.float32)
    with pytest.raises(ValueError, match='do not tile'):
        gm.grouped_matmul(lhs[:, :64], rhs[:, :, :64],
                          jnp.zeros((GROUPS,), jnp.int32),
                          impl='pallas')
    with pytest.raises(ValueError, match='Unknown impl'):
        gm.grouped_matmul(lhs, rhs, jnp.zeros((GROUPS,), jnp.int32),
                          impl='mosaic')


@pytest.mark.parametrize('stacked', [False, True],
                         ids=['a layer on its own',
                              'third of a stack of four'])
def test_the_dropless_layer_is_the_same_on_either(stacked, monkeypatch):
    """16 experts of which 4 are held, 2 a token, widths that tile: the
    whole layer through the kernel (interpreted) against the layer
    through `ragged_dot`, pads, shared expert and counts and all."""
    cfg = get_config(
        'trinity-large-preview', vocab_size=256, d_model=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim_override=32,
        d_mlp=128, max_seq_len=64, num_experts=16, experts_per_token=2,
        experts_held=4, first_expert=4, d_expert=128,
        d_shared_expert=128, num_dense_layers=1,
        layer_kinds=((8, True), (0, False)), dtype='float32',
        param_dtype='float32')
    block = moe.MoEBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 128))
    valid = jnp.arange(16)[None, :] < jnp.array([16, 9, 1, 0])[:, None]
    params = nn.unbox(block.init(jax.random.PRNGKey(1), x))['params']
    stacks = None
    if stacked:
        whole = tuple(jax.random.normal(
            jax.random.PRNGKey(5 + i), (4,) + params[name].shape[1:]) /
            11 for i, name in enumerate(('w_gate', 'w_up', 'w_down')))
        stacks = (whole, jnp.int32(2))
    run = lambda: block.apply({'params': params}, x, valid, stacks,
                              mutable=['moe_stats'])
    want, want_counts = run()
    taken = []
    def through_the_kernel(*args, **kw):
        taken.append(args[0].shape)
        return gm.grouped_matmul(*args, impl='pallas_interpret', **kw)
    monkeypatch.setattr(moe, 'grouped_matmul', through_the_kernel)
    got, got_counts = run()
    assert taken == [(128, 128)] * 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got_counts['moe_stats']['counts']),
        np.asarray(want_counts['moe_stats']['counts']))
    assert float(jnp.abs(want).max()) > 0.1
