"""Pallas one-step recurrence of Kimi delta attention (KDA), in place.

A decode step of a KDA model moves every slot's state (models/kda.py):
a float32 (heads, head_dim, head_dim) array a slot and layer, 537 MB a
layer at 256 slots and Ling-3.0-flash's 32 heads of 128. XLA runs the
step's recurrence as several passes over it (the decayed state for the
delta correction, again for the new state and its contraction with q,
again for an inert row's `where(touched, new, old)` and the write into
the carried leaf). This kernel reads each state tile once and writes it
once. With the state held transposed, `S[v, k]` (values along sublanes,
keys along lanes), a head's step is

    sd  = s0 * exp(g)                       s0 = 0 for a fresh row
    u   = v - sd . k                        the delta correction (V,)
    new = sd + u (x) (beta * k)
    o   = new . q                           (float32)
    leaf[layer, slot] = new if the row is touched, else its old state

Grid (slots, heads / hb): a cell holds `hb` heads of one slot, a
`(hb, V, K)` tile read out of the stacked `(L, slots, H, V, K)` leaf at
(layer, slot, head block) and written back to the same place. The layer
and each slot's `touched` / `fresh` flags come in by scalar prefetch; the
leaf is aliased to the output (`input_output_aliases`), so the layer
loop that carries the cache updates it where it lies and no other layer
is touched. A row that is not touched writes the tile it read: its state
stays bit for bit.

The arithmetic is `models/kda.kda_step`'s, operation for operation; its
two sums over the key axis run in the kernel's own order, so the state
and `o` agree with it to float32 round-off. Every product with the state
is elementwise in float32, never a bfloat16 pass of the MXU. The decay
`exp(g)` and `beta * k` are computed by the caller in XLA.

`interpret=True` runs the same kernel under the Pallas interpreter on
the CPU (tests/test_kda.py pins it against `kda_step`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# Largest head block tried first: a (hb, 128, 128) float32 tile is
# hb x 64 KB, read and written double-buffered, so 16 heads take 4 MB of
# the v5e's 16 MB of scoped VMEM.
_HEAD_BLOCKS = (16, 8, 4, 2, 1)


def head_block(heads: int) -> int:
    """Heads a grid cell holds: the largest of `_HEAD_BLOCKS` that
    divides the heads."""
    return next(hb for hb in _HEAD_BLOCKS if heads % hb == 0)


def _state_kernel(layer_ref, touched_ref, fresh_ref,     # SMEM
                  decay_ref, q_ref, k_ref, bk_ref, v_ref, s_ref, o_ref,
                  out_ref, *, hb: int):
    del layer_ref  # read by the index maps
    slot = pl.program_id(0)
    fresh = fresh_ref[slot] != 0
    touched = touched_ref[slot] != 0
    decay = decay_ref[0, 0]                          # (hb, K)
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    bk = bk_ref[0, 0]
    v_cols = v_ref[0, 0].T                           # (V, hb)
    v_dim = v_cols.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (v_dim, hb), 1)
    o_cols = jnp.zeros((v_dim, hb), F32)
    for j in range(hb):
        old = s_ref[0, 0, j]                         # (V, K)
        s0 = jnp.where(fresh, 0.0, old)
        sd = s0 * decay[j:j + 1]
        u = v_cols[:, j:j + 1] - jnp.sum(sd * k[j:j + 1], axis=-1,
                                         keepdims=True)       # (V, 1)
        new = sd + u * bk[j:j + 1]
        o_j = jnp.sum(new * q[j:j + 1], axis=-1, keepdims=True)
        o_cols = jnp.where(lane == j, o_j, o_cols)
        out_ref[0, 0, j] = jnp.where(touched, new, old)
    o_ref[0, 0] = o_cols.T                           # (hb, V)


def kda_state_step(leaf: jax.Array, layer: Optional[jax.Array],
                   touched: jax.Array, fresh: jax.Array, decay: jax.Array,
                   q: jax.Array, k: jax.Array, bk: jax.Array,
                   v: jax.Array, *, interpret: bool = False):
    """One step of every slot's KDA recurrence, written into the leaf.

    Args:
      leaf: the state, float32 (L, S, H, V, K) stacked with `layer` its
        index in it, or one layer's (S, H, V, K) with `layer` None.
      touched: (S,) true where the row advances; an untouched row's
        state is written back as it was read.
      fresh: (S,) true where the row starts from a zero state
        (position 0).
      decay: (S, H, K) float32, exp(g).
      q, k: (S, H, K) float32, the normalised query (scale folded in)
        and key; bk: (S, H, K) float32, beta * k.
      v: (S, H, V) float32.

    Returns (o (S, H, V) float32 = new . q, the leaf with every row's
    new state at `layer`).
    """
    stacked = leaf if layer is not None else leaf[None]
    _, slots, heads, v_dim, k_dim = stacked.shape
    hb = head_block(heads)
    nhb = heads // hb
    layer_arr = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                        jnp.int32), (1,))
    row = lambda width: pl.BlockSpec(
        (1, 1, hb, width), lambda s, h, *_: (s, h, 0, 0))
    tile = pl.BlockSpec(
        (1, 1, hb, v_dim, k_dim),
        lambda s, h, lyr, *_: (lyr[0], s, h, 0, 0))
    blocks = lambda a, width: a.astype(F32).reshape(slots, nhb, hb, width)
    o, out = pl.pallas_call(
        functools.partial(_state_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, nhb),
            in_specs=[row(k_dim), row(k_dim), row(k_dim), row(k_dim),
                      row(v_dim), tile],
            out_specs=[row(v_dim), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((slots, nhb, hb, v_dim), F32),
                   jax.ShapeDtypeStruct(stacked.shape, stacked.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')),
        interpret=interpret,
        name='kda_state_step',
    )(layer_arr, touched.astype(jnp.int32), fresh.astype(jnp.int32),
      blocks(decay, k_dim), blocks(q, k_dim), blocks(k, k_dim),
      blocks(bk, k_dim), blocks(v, v_dim), stacked)
    return (o.reshape(slots, heads, v_dim),
            out if layer is not None else out[0])
