"""Pallas one-step recurrence of a Mamba-2 (SSD) mixer, in place.

A decode step of a recurrent-state model moves every slot's scan state
(models/ssm.py): on Falcon-H1-34B a (slots, 32, 128, 256) float32 array
a layer, 268 MB at 64 slots. XLA runs the step's recurrence as two
passes over it, each making the new state again from the old: one
reduces it to `y = new . C`, the other keeps an inert row's
`where(touched, new, old)` and writes that into the carried leaf. This
kernel reads each state tile once and writes it once:

    new = decay * s0 + (delta * xs) (x) B      s0 = 0 for a fresh row
    y   = new . C                              (float32)
    leaf[layer, slot] = new if the row is touched, else its old state

Grid (slots, heads / hb): a cell holds `hb` heads of one slot, a
`(hb, P, N)` tile read out of the stacked `(L, slots, H, P, N)` leaf at
(layer, slot, head block) and written back to the same place. The layer
and each slot's `touched` / `fresh` flags come in by scalar prefetch; the
leaf is aliased to the output (`input_output_aliases`), so the layer
loop that carries the cache updates it where it lies and no other layer
is touched. A row that is not touched writes the tile it read: its state
stays bit for bit. A head block lies inside one B/C group (hb divides
the heads a group holds), whose B and C rows the index map picks.

The elementwise arithmetic is `ssd_step`'s, operation for operation, so
the new state is its bits; `y` sums the N products in the kernel's own
order, float32 round-off from XLA's. The small per-head terms (decay,
delta * xs, the skip) are computed by the caller in XLA.

`interpret=True` runs the same kernel under the Pallas interpreter on
the CPU (tests/test_ssd_state_kernel.py pins it against `ssd_step`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# Largest head block tried first: a (hb, 128, 256) float32 tile is
# hb x 128 KB, read and written double-buffered, so 16 heads take 8 MB
# of the v5e's 16 MB of scoped VMEM. On the v5e 16 and 8 ran alike and
# 4 1% slower (PERF.md section 6).
_HEAD_BLOCKS = (16, 8, 4, 2, 1)


def head_block(heads_per_group: int) -> int:
    """Heads a grid cell holds: the largest of `_HEAD_BLOCKS` that
    divides the heads of one B/C group."""
    return next(hb for hb in _HEAD_BLOCKS if heads_per_group % hb == 0)


def _state_kernel(layer_ref, touched_ref, fresh_ref,     # SMEM
                  decay_ref, dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *,
                  hb: int, heads: int):
    del layer_ref  # read by the index maps
    slot = pl.program_id(0)
    first = slot * heads + pl.program_id(1) * hb
    fresh = fresh_ref[slot] != 0
    touched = touched_ref[slot] != 0
    b = b_ref[0, 0]                                  # (1, N)
    c = c_ref[0, 0]
    x_cols = dx_ref[0, 0].T                          # (P, hb)
    p_dim = x_cols.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (p_dim, hb), 1)
    y_cols = jnp.zeros((p_dim, hb), F32)
    for j in range(hb):
        old = s_ref[0, 0, j]                         # (P, N)
        s0 = jnp.where(fresh, 0.0, old.astype(F32))
        new = decay_ref[first + j] * s0 + x_cols[:, j:j + 1] * b
        y_j = jnp.sum(new * c, axis=-1, keepdims=True)      # (P, 1)
        y_cols = jnp.where(lane == j, y_j, y_cols)
        o_ref[0, 0, j] = jnp.where(touched, new.astype(o_ref.dtype), old)
    y_ref[0, 0] = y_cols.T                           # (hb, P)


def ssd_state_step(leaf: jax.Array, layer: Optional[jax.Array],
                   touched: jax.Array, fresh: jax.Array, decay: jax.Array,
                   dx: jax.Array, b: jax.Array, c: jax.Array, *,
                   interpret: bool = False):
    """One step of every slot's recurrence, written into the leaf.

    Args:
      leaf: the scan state, (L, S, H, P, N) stacked with `layer` its
        index in it, or one layer's (S, H, P, N) with `layer` None.
      touched: (S,) true where the row advances; an untouched row's
        state is written back as it was read.
      fresh: (S,) true where the row starts from a zero state
        (position 0).
      decay: (S, H) float32, exp(-exp(A_log) * delta).
      dx: (S, H, P) float32, delta * xs.
      b, c: (S, G, N), a group's input and output projections.

    Returns (y (S, H, P) float32 = new . C, the leaf with every row's
    new state at `layer`).
    """
    stacked = leaf if layer is not None else leaf[None]
    _, slots, heads, p_dim, n = stacked.shape
    groups = b.shape[1]
    per_group = heads // groups
    hb = head_block(per_group)
    blocks_per_group = per_group // hb
    nhb = heads // hb
    layer_arr = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                        jnp.int32), (1,))
    vec = lambda *tail: pl.BlockSpec(
        (1, 1) + tail, lambda s, h, *_: (s, h) + (0,) * len(tail))
    group_row = pl.BlockSpec(
        (1, 1, 1, n), lambda s, h, *_: (s, h // blocks_per_group, 0, 0))
    tile = pl.BlockSpec(
        (1, 1, hb, p_dim, n),
        lambda s, h, lyr, *_: (lyr[0], s, h, 0, 0))
    y, out = pl.pallas_call(
        functools.partial(_state_kernel, hb=hb, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, nhb),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      vec(hb, p_dim), group_row, group_row, tile],
            out_specs=[vec(hb, p_dim), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((slots, nhb, hb, p_dim), F32),
                   jax.ShapeDtypeStruct(stacked.shape, stacked.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')),
        interpret=interpret,
        name='ssd_state_step',
    )(layer_arr, touched.astype(jnp.int32), fresh.astype(jnp.int32),
      decay.astype(F32).reshape(-1),
      dx.astype(F32).reshape(slots, nhb, hb, p_dim),
      b.astype(F32).reshape(slots, groups, 1, n),
      c.astype(F32).reshape(slots, groups, 1, n), stacked)
    return (y.reshape(slots, heads, p_dim),
            out if layer is not None else out[0])
