"""Pallas one-step recurrence of Kimi delta attention (KDA), in place.

A decode step of a KDA model moves every slot's state (models/kda.py):
a float32 (heads, head_dim, head_dim) array a slot and layer, 537 MB a
layer at 256 slots and Ling-3.0-flash's 32 heads of 128. XLA runs the
step's recurrence as several passes over it (the decayed state for the
delta correction, again for the new state and its contraction with q,
again for an inert row's `where(touched, new, old)` and the write into
the carried leaf). This kernel reads each state tile once and writes it
once. The state is held keys-major, `S[k, v]`: keys along the sublanes,
values along the lanes. A head's step is then

    sd  = s0 * exp(g)[:, None]              s0 = 0 for a fresh row
    u   = v - sum_k sd * k[:, None]         the delta correction (1, V)
    new = sd + (beta * k)[:, None] * u
    o   = sum_k new * q[:, None]            (1, V), float32
    leaf[layer, slot] = new if the row is touched, else its old state

so both contractions over the keys are sums over sublanes: elementwise
adds of a head's vregs and one reduction across the sublanes of the
last, where keys along the lanes would cost a reduction across the lanes
of every vreg. The per-key vectors come in as (hb, K) rows, and eight
heads' rows at a time are transposed into columns.

Grid (slots, heads / hb): a cell holds `hb` heads of one slot (a whole
slot of 32 heads where the heads allow, `head_block`), a `(hb, K, V)`
tile read out of the stacked `(L, slots, H, K, V)` leaf at (layer, slot,
head block) and written back to the same place; inside, a `fori_loop`
walks the heads eight at a time, where a Python loop over the heads
would unroll the kernel's body that many times. The layer and each
slot's `touched` / `fresh` flags come in by scalar prefetch; the leaf is
aliased to the output (`input_output_aliases`), so the layer loop that
carries the cache updates it where it lies and no other layer is
touched. A row that is not touched writes the tile it read: its state
stays bit for bit.

The arithmetic is `models/kda.kda_step`'s in another order (the decay
applied to the state, not folded into k and q), so the state and `o`
agree with it to float32 round-off. Every product with the state is
elementwise in float32, never a bfloat16 pass of the MXU. The decay
`exp(g)` and `beta * k` are computed by the caller in XLA.

`interpret=True` runs the same kernel under the Pallas interpreter on
the CPU (tests/test_ling.py pins it against `kda_step`).
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
# hb x 64 KB, read and written double-buffered, so a whole slot of 32
# heads takes 8 MB of the v5e's 16 MB of scoped VMEM.
_HEAD_BLOCKS = (32, 16, 8, 4, 2, 1)
# heads a trip of the loop inside a cell: one (8, 128) vreg of each
# per-key vector, transposed into eight columns
_GROUP = 8


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
    group = min(_GROUP, hb)

    def heads(g, carry):
        decay = decay_ref[0, g].T                    # (K, group)
        q = q_ref[0, g].T
        k = k_ref[0, g].T
        bk = bk_ref[0, g].T
        v = v_ref[0, g]                              # (group, V)
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        o = jnp.zeros(v.shape, F32)
        for j in range(group):
            h = g * group + j
            col = slice(j, j + 1)
            old = s_ref[0, 0, h]                     # (K, V)
            sd = jnp.where(fresh, 0.0, old) * decay[:, col]
            u = v[col] - jnp.sum(sd * k[:, col], axis=0, keepdims=True)
            new = sd + bk[:, col] * u
            o = jnp.where(row == j,
                          jnp.sum(new * q[:, col], axis=0, keepdims=True),
                          o)
            out_ref[0, 0, h] = jnp.where(touched, new, old)
        o_ref[0, g] = o
        return carry

    jax.lax.fori_loop(0, hb // group, heads, 0)


def kda_state_step(leaf: jax.Array, layer: Optional[jax.Array],
                   touched: jax.Array, fresh: jax.Array, decay: jax.Array,
                   q: jax.Array, k: jax.Array, bk: jax.Array,
                   v: jax.Array, *, interpret: bool = False):
    """One step of every slot's KDA recurrence, written into the leaf.

    Args:
      leaf: the state, float32 (L, S, H, K, V) stacked with `layer` its
        index in it, or one layer's (S, H, K, V) with `layer` None.
      touched: (S,) true where the row advances; an untouched row's
        state is written back as it was read.
      fresh: (S,) true where the row starts from a zero state
        (position 0).
      decay: (S, H, K) float32, exp(g).
      q, k: (S, H, K) float32, the normalised query (scale folded in)
        and key; bk: (S, H, K) float32, beta * k.
      v: (S, H, V) float32.

    Returns (o (S, H, V) float32 = sum_k new * q, the leaf with every
    row's new state at `layer`).
    """
    stacked = leaf if layer is not None else leaf[None]
    _, slots, heads, k_dim, v_dim = stacked.shape
    hb = head_block(heads)
    group = min(_GROUP, hb)
    layer_arr = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                        jnp.int32), (1,))
    # the per-head vectors as (slots, groups, group, width): a trip of
    # the loop reads one whole (group, width) tile of each
    row = lambda width: pl.BlockSpec(
        (1, hb // group, group, width), lambda s, h, *_: (s, h, 0, 0))
    tile = pl.BlockSpec(
        (1, 1, hb, k_dim, v_dim),
        lambda s, h, lyr, *_: (lyr[0], s, h, 0, 0))
    blocks = lambda a, width: a.astype(F32).reshape(
        slots, heads // group, group, width)
    o, out = pl.pallas_call(
        functools.partial(_state_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, heads // hb),
            in_specs=[row(k_dim), row(k_dim), row(k_dim), row(k_dim),
                      row(v_dim), tile],
            out_specs=[row(v_dim), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct(
            (slots, heads // group, group, v_dim), F32),
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
