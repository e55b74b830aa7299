"""Grouped matrix product for the dropless expert layer (Pallas TPU),
with `jax.lax.ragged_dot` as its twin.

The contract is `ragged_dot`'s as `models/moe.py` `_dropless` uses it:
`lhs` (M, K) holds rows sorted by group, `group_sizes` (G,) says how
many rows each group holds, and group g's rows are multiplied by ITS
matrix. The sizes may add up to less than M: the rows past the last
group are the caller's (pairs held elsewhere, pads), nothing is
computed for them, and what the output holds there is undefined (the
twin leaves zeros, the kernel whatever the buffer held): the caller
selects them out and never multiplies them.

What differs from `ragged_dot` is how the matrices are handed over:
`rhs` is EVERY layer's stack whole, (L, G, K, N), and `layer` a scalar
that the kernel adds in the weights' index map, so nothing of one
layer's shape is sliced out of the stack (a 1.8 GB copy a layer at
Trinity's widths: PERF.md section 6, PR 33).

Design (PERF.md section 6, PR 34 has the numbers that asked for it):
- The product is a weight stream: a decode step gives an expert two to
  four rows, so a touched expert's (K, N) matrix is read once from HBM,
  in tiles of up to 3 MB that the Pallas pipeline double buffers (at
  Trinity's widths all of K by 512 columns, six a matrix: 88% of the
  HBM's peak an expert where XLA's `ragged-dot` reaches 37%), and an
  expert that holds no rows is never visited.
- The grid walks VISITS: (row tile, group) pairs in row order, found
  from the sizes by a few small XLA ops and scalar-prefetched. A visit
  multiplies the whole row tile by the group's matrix and stores the
  group's rows alone (a mask), so a group that straddles a row tile is
  two visits and a row tile shared by twenty groups stays in VMEM for
  twenty. The number of visits is the grid's own (dynamic) size:
  nothing is walked for a group without rows.
- bf16 (or float32) operands as they come, float32 accumulation, the
  output in `preferred_element_type`. No backward pass: the dropless
  layer is the serving formulation.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

# The name the kernel's events carry in a device trace
# (perf/metrics/moe_gmm_share_pct.py finds them by it).
KERNEL_NAME = 'moe_gmm'

# A weight tile's bytes: large enough that the stream runs at the HBM's
# pace, small enough that two of them sit in VMEM beside the rows.
_WEIGHT_TILE_BYTES = 3 << 20


def _on_tpu() -> bool:
    return any(dev.platform == 'tpu' for dev in jax.devices())


def tiles_for(m: int, k: int, n: int, itemsize: int
              ) -> Optional[Tuple[int, int, int]]:
    """(tm, tk, tn) from the static shapes alone, or None where the
    shapes do not tile (K, N not multiples of 128, M of 16)."""
    if k % 128 or n % 128:
        return None
    tm = next((t for t in (128, 64, 32, 16) if m % t == 0), None)
    if tm is None:
        return None
    # A tile spans all of K where it can (no accumulator's round trip,
    # the rows fetched once a visit: 3-4% faster on the v5e than whole
    # rows of the matrix cut along K) and as much of N as its bytes
    # allow; K is cut only where 128 columns of it are too many.
    budget = _WEIGHT_TILE_BYTES // itemsize
    fits = lambda size, other: [t for t in range(128, size + 1, 128)
                                if size % t == 0 and t * other <= budget]
    cols = fits(n, k)
    if cols:
        return tm, k, max(cols)
    return tm, max(fits(k, 128), default=128), 128


def _visits(group_sizes: jax.Array, m: int, tm: int):
    """The (row tile, group) pairs that hold rows, in row order.

    Returns (group of each visit, row tile of each visit, each group's
    first row, each group's end, number of visits); the first two are
    padded to the most visits the shapes allow, `m // tm + G - 1`."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        g - 1)
    tile = first[group] + v - (upto - tiles)[group]
    tile = jnp.clip(tile, 0, m // tm - 1)
    return group, tile, starts, ends, upto[-1]


def _kernel(group_ref, tile_ref, start_ref, end_ref, layer_ref,
            lhs_ref, rhs_ref, out_ref, *scratch, tm, k_tiles):
    del layer_ref                      # read by the weights' index map
    v, k_i = pl.program_id(1), pl.program_id(2)
    lhs = lhs_ref[...]
    part = jax.lax.dot_general(
        lhs, rhs_ref[...].astype(lhs.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(acc):
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        # the tile's other rows are other visits': theirs stay
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])

    if k_tiles == 1:
        store(part)
        return
    acc_ref, = scratch

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = part

    @pl.when(k_i > 0)
    def _():
        acc_ref[...] += part

    @pl.when(k_i == k_tiles - 1)
    def _():
        store(acc_ref[...])


def _pallas(lhs, rhs, group_sizes, layer, out_dtype, tiling, interpret):
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    tm, tk, tn = tiling
    k_tiles, n_tiles = k // tk, n // tn
    group, tile, starts, ends, count = _visits(group_sizes, m, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    out_item = jnp.dtype(out_dtype).itemsize
    # what the pipeline holds: two of each block, and the accumulator
    vmem = (2 * tk * tn * rhs.dtype.itemsize
            + 2 * tm * tk * lhs.dtype.itemsize
            + 2 * tm * tn * out_item + tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_tiles=k_tiles),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles, count, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n_i, v, k_i, grp, til, *_:
                             (til[v], k_i)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda n_i, v, k_i, grp, til, st, en, lay:
                             (lay[0], grp[v], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n_i, v, k_i, grp, til, *_:
                                   (til[v], n_i)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if k_tiles > 1 else []),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(g * k * n * rhs.dtype.itemsize
                            + m * k * lhs.dtype.itemsize
                            + m * n * out_item)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(group, tile, starts, ends, layer, lhs, rhs)


def _ragged(lhs, rhs, group_sizes, layer, out_dtype):
    """The twin: XLA's own lowering, over all the stack's groups with
    this layer's alone holding rows."""
    layers, g = rhs.shape[:2]
    in_stack = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * g,), jnp.int32),
        group_sizes.astype(jnp.int32), (layer * g,))
    return jax.lax.ragged_dot(
        lhs, rhs.reshape((layers * g,) + rhs.shape[2:]).astype(lhs.dtype),
        in_stack, preferred_element_type=out_dtype)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array, layer=0, *,
                   preferred_element_type=None,
                   impl: str = 'auto') -> jax.Array:
    """lhs[rows of group g] @ rhs[layer, g] for every group g.

    Args:
      lhs: (M, K), rows sorted by group.
      rhs: (L, G, K, N), every layer's matrices whole.
      group_sizes: (G,) int32; the sum may be less than M (the rows
        past it hold nothing that was computed).
      layer: scalar, which of the L layers' matrices these groups are.
      preferred_element_type: the output's type (default: lhs's).
      impl: 'pallas' | 'pallas_interpret' | 'xla' | 'auto' (the kernel
        on a TPU when the shapes tile, `jax.lax.ragged_dot` otherwise).
    """
    m, k = lhs.shape
    n = rhs.shape[-1]
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    tiling = tiles_for(m, k, n, rhs.dtype.itemsize)
    if impl == 'auto':
        on_tpu = _on_tpu()
        mesh = jax.sharding.get_abstract_mesh()
        # a Mosaic call has no partitioning rule: under a mesh of more
        # than one device the product stays XLA's
        one_device = mesh.empty or mesh.size == 1
        impl = ('pallas' if on_tpu and tiling is not None and one_device
                else 'xla')
        # Trace time, so once a compiled program and product: which
        # lowering a run got is read from its log, not guessed.
        logger.info(
            "grouped_matmul: impl='auto' resolved to %r (m=%d k=%d n=%d "
            'groups=%d of %d layers, tiles=%s tpu=%s)', impl, m, k, n,
            rhs.shape[1], rhs.shape[0], tiling, on_tpu)
    if impl == 'xla':
        return _ragged(lhs, rhs, group_sizes, layer, out_dtype)
    if impl in ('pallas', 'pallas_interpret'):
        if tiling is None:
            raise ValueError(
                f'shapes m={m} k={k} n={n} do not tile (K, N multiples '
                f'of 128, M of 16); use impl="xla" or "auto".')
        return _pallas(lhs, rhs, group_sizes, layer, out_dtype, tiling,
                       impl == 'pallas_interpret')
    raise ValueError(f'Unknown impl {impl!r}')
