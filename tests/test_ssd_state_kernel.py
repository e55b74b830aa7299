"""The one-step recurrence's kernel (ops/ssd_state_step.py) against
`models/ssm.ssd_step`, under the Pallas interpreter.

The new state is `ssd_step`'s elementwise arithmetic, so it agrees to
float32 round-off (bit for bit on the chip; the CPU compiler may fuse the
reference's multiply and add); `y` sums the N products in another order.
Rows that are not touched keep their state bit for bit, and so does every
other layer of the stacked leaf: the kernel writes the layer it is given
and nothing else. The compiled decode step of a tiny Falcon-H1 under the
kernel makes no copy of the scan-state leaf: tests/test_cache_in_place.py
(layout 'falcon-h1-kernel'), beside the v5e compile of the kernel at the
published widths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models.ssm import ssd_step, ssd_step_in_place
from skypilot_tpu.ops import ssd_state_step

P, N = 16, 32
LAYERS, LAYER = 3, 1
# (touched, fresh) a slot: advancing, inert, a fresh row that advances,
# an inert one whose position is 0 (an empty slot of a decode step)
TOUCHED = (1, 0, 1, 0, 1)
FRESH = (0, 0, 1, 1, 0)


def _inputs(groups: int, per_group: int, dtype=jnp.float32, seed: int = 0):
    rng = np.random.default_rng(seed)
    slots, heads = len(TOUCHED), groups * per_group
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.float32)
    touched = jnp.asarray(TOUCHED, jnp.int32)
    delta = jnp.abs(draw(slots, groups, per_group)) * touched[:, None, None]
    return dict(
        leaf=draw(LAYERS, slots, heads, P, N).astype(dtype),
        xs=draw(slots, groups, per_group, P), b=draw(slots, groups, N),
        c=draw(slots, groups, N), delta=delta,
        a_log=draw(groups, per_group), d_skip=draw(groups, per_group),
        touched=touched > 0, fresh=jnp.asarray(FRESH, jnp.int32) > 0)


def _reference(a, layer=LAYER):
    """The mixer's XLA path: the old rows, `ssd_step`, the kept rows."""
    leaf = a['leaf'] if layer is None else a['leaf'][layer]
    slots, heads = leaf.shape[:2]
    groups, per_group = a['a_log'].shape
    s0 = jnp.where(a['fresh'][:, None, None, None], 0,
                   leaf).astype(jnp.float32)
    y, new = ssd_step(s0.reshape(slots, groups, per_group, P, N), a['xs'],
                      a['b'], a['c'], a['delta'], a['a_log'], a['d_skip'])
    kept = jnp.where(a['touched'][:, None, None, None],
                     new.reshape(leaf.shape).astype(leaf.dtype), leaf)
    return y, kept


def _kernel(a, layer=LAYER):
    return ssd_step_in_place(
        a['leaf'], None if layer is None else jnp.int32(layer), a['touched'],
        a['fresh'], a['xs'], a['b'], a['c'], a['delta'], a['a_log'],
        a['d_skip'], interpret=True)


@pytest.mark.parametrize('groups', [1, 2])
@pytest.mark.parametrize('per_group', [4, 8, 16])
def test_the_kernel_is_the_one_step_recurrence(groups, per_group):
    """Head blocks of 4, 8 and 16 (a block is a group's heads here), one
    and two B/C groups; inert rows and fresh rows in every case."""
    assert ssd_state_step.head_block(per_group) == per_group
    a = _inputs(groups, per_group)
    want_y, want_state = _reference(a)
    got_y, got_leaf = jax.jit(_kernel)(a)
    got_state = np.asarray(got_leaf[LAYER])
    np.testing.assert_allclose(got_state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    old = np.asarray(a['leaf'])
    # an inert row keeps its state bit for bit, fresh or not
    inert = [i for i, t in enumerate(TOUCHED) if not t]
    assert np.array_equal(got_state[inert], old[LAYER][inert])
    # a row that advanced changed (a fresh one, row 2, from zero: the
    # reference's s0)
    assert not np.array_equal(got_state[0], old[LAYER][0])
    # every other layer of the stacked leaf is the same bits
    others = [l for l in range(LAYERS) if l != LAYER]
    assert np.array_equal(np.asarray(got_leaf)[others], old[others])


def test_the_decode_kernels_take_it_in_the_one_step_program_alone():
    """Any decode kernel but XLA's, the TPU's default among them (which
    no caller can name, so no engine test on the CPU reaches it); a
    program of more than one position a slot, or a model without a scan
    state, never."""
    import dataclasses
    from skypilot_tpu.models import get_config, inference
    from skypilot_tpu.models.ssm import takes_state_kernel
    falcon = get_config('falcon-h1-34b')
    # pylint: disable=protected-access
    for kernel in ('pallas', 'pallas_interpret', inference._DEFAULT_KERNEL):
        cfg = dataclasses.replace(falcon, decode_kernel=kernel)
        assert takes_state_kernel(cfg, 1), kernel
        assert not takes_state_kernel(cfg, 4), kernel
        assert not takes_state_kernel(
            dataclasses.replace(get_config('test-tiny'),
                                decode_kernel=kernel), 1), kernel
    assert not takes_state_kernel(falcon, 1)          # 'xla'


def test_a_head_block_is_the_largest_that_divides_a_group():
    assert [ssd_state_step.head_block(r) for r in (32, 16, 12, 8, 6, 2, 1)
            ] == [16, 16, 4, 8, 2, 2, 1]


def test_a_blocked_group_takes_its_own_rows_of_b_and_c():
    """32 heads in 2 groups: two head blocks a group, each reading its
    group's B and C (a block mapped to the wrong group would read the
    other group's rows, which differ)."""
    a = _inputs(2, 32)
    assert ssd_state_step.head_block(32) == 16
    want_y, want_state = _reference(a)
    got_y, got_leaf = jax.jit(_kernel)(a)
    np.testing.assert_allclose(got_leaf[LAYER], want_state, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)


def test_a_layers_own_leaf_is_written_whole():
    """Outside the cache-carrying loop (no layer index) the leaf is the
    layer's own, (S, H, P, N)."""
    a = _inputs(2, 4)
    a['leaf'] = a['leaf'][0]
    want_y, want_state = _reference(a, layer=None)
    got_y, got_leaf = jax.jit(lambda a: _kernel(a, layer=None))(a)
    assert got_leaf.shape == a['leaf'].shape
    np.testing.assert_allclose(got_leaf, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)


def test_a_bfloat16_state_is_rounded_once_as_the_mixer_rounds_it():
    """`ssm_state_dtype` bfloat16: read, advanced in float32, rounded on
    the way back; an inert row keeps its bits."""
    a = _inputs(2, 8, dtype=jnp.bfloat16)
    want_y, want_state = _reference(a)
    got_y, got_leaf = jax.jit(_kernel)(a)
    assert got_leaf.dtype == jnp.bfloat16
    got = np.asarray(got_leaf[LAYER], np.float32)
    want = np.asarray(want_state, np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
    assert np.mean(got == want) > 0.99
    inert = [i for i, t in enumerate(TOUCHED) if not t]
    assert np.array_equal(got[inert],
                          np.asarray(a['leaf'][LAYER], np.float32)[inert])
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
