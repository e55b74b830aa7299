"""Weights made by the benchmark from `--seed`, for the program and for
the plain reference alike.

The program is handed these arrays (it has an argument for weights), and
the reference makes the same ones again, layer by layer, once the
program's copy has been freed. Nothing the program made is read back.

One leaf is a list of units: a layer of a stacked leaf, or one of 16
row-chunks of a large top-level leaf (an embedding). Each unit has a key
of its own, folded from the seed, the leaf's path and the unit's number,
so that a unit can be made alone.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from common import seed_key

LORA_B_STD = 0.003
CHUNKS = 16
CHUNK_MIN_ELEMS = 1 << 24


def leaf_rule(path: tuple, unit_shape: tuple):
    """(mean, std) of a leaf's entries, by the role its name gives it."""
    last = path[-1]
    if last == 'embedding':
        return 0.0, 1.0
    if last == 'kernel':
        fan_in = unit_shape[0]
        if path[-2] == 'o_proj':
            fan_in = unit_shape[0] * unit_shape[1]
        return 0.0, float(fan_in) ** -0.5
    if last == 'scale':
        return 1.0, 0.1
    if last == 'bias':
        return 0.0, 0.1
    if last == 'lora_a':
        return 0.0, float(unit_shape[-1]) ** -0.5
    if last == 'lora_b':
        return 0.0, LORA_B_STD
    raise ValueError(f'no rule for leaf {"/".join(path)}')


def base_key(seed: int):
    """The key all weights are folded from. It is an argument of every
    jitted maker here, never a constant inside one: a seed baked into a
    program makes a new program, and a new compilation, of every seed."""
    return seed_key(seed, 1)


def leaf_key(base, path: tuple):
    tag = zlib.crc32('/'.join(path).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(base, tag)


def make_unit(key, index, unit_shape: tuple, mean: float, std: float,
              dtype):
    x = jax.random.normal(jax.random.fold_in(key, index), unit_shape,
                          jnp.float32)
    return (mean + std * x).astype(dtype)


def is_stacked(path: tuple) -> bool:
    return path[0] == 'layers'


def leaf_units(path: tuple, shape: tuple):
    """(number of units, shape of one unit, stacked?)."""
    if is_stacked(path):
        return shape[0], tuple(shape[1:]), True
    size = 1
    for d in shape:
        size *= d
    if size >= CHUNK_MIN_ELEMS and shape[0] % CHUNKS == 0:
        return CHUNKS, (shape[0] // CHUNKS,) + tuple(shape[1:]), False
    return 1, tuple(shape), False


def make_leaf(base, path: tuple, shape: tuple, dtype):
    """The whole leaf, unit after unit (so the float32 draw of only one
    unit is alive at a time)."""
    n, unit_shape, _ = leaf_units(path, shape)
    mean, std = leaf_rule(path, unit_shape)
    key = leaf_key(base, path)
    units = jax.lax.map(
        lambda i: make_unit(key, i, unit_shape, mean, std, dtype),
        jnp.arange(n, dtype=jnp.int32))
    return units.reshape(shape)


def make_layer_unit(base, path: tuple, shape: tuple, dtype, layer):
    """Layer `layer` of a stacked leaf, alone."""
    _, unit_shape, stacked = leaf_units(path, shape)
    if not stacked:
        raise ValueError(f'{"/".join(path)} is not stacked by layer')
    mean, std = leaf_rule(path, unit_shape)
    return make_unit(leaf_key(base, path), layer, unit_shape, mean, std,
                     dtype)


def path_of(keypath) -> tuple:
    return tuple(str(getattr(k, 'key', getattr(k, 'name', k)))
                 for k in keypath)


def leaf_dtype(path: tuple, dtype, adapter_dtype=None):
    if adapter_dtype is not None and path[-1] in ('lora_a', 'lora_b'):
        return jnp.dtype(adapter_dtype)
    return jnp.dtype(dtype)


def make_tree(base, abstract, adapter_dtype=None):
    """Arrays for every leaf of `abstract` (a tree of ShapeDtypeStructs
    keyed as the program keys its parameters), from `base_key(seed)`.
    Call under jit, with `base` an argument."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, a: make_leaf(
            base, path_of(kp), tuple(a.shape),
            leaf_dtype(path_of(kp), a.dtype, adapter_dtype)),
        abstract)


class Catalog:
    """Shapes and dtypes of every leaf by path, and units made alone:
    what the reference draws its weights from."""

    def __init__(self, seed: int, abstract, adapter_dtype=None):
        self.base = base_key(seed)
        self.leaves = {}
        for kp, a in jax.tree_util.tree_flatten_with_path(abstract)[0]:
            p = path_of(kp)
            self.leaves['/'.join(p)] = (
                p, tuple(a.shape), leaf_dtype(p, a.dtype, adapter_dtype))
        self._layer = jax.jit(make_layer_unit, static_argnums=(1, 2, 3))
        self._whole = jax.jit(make_leaf, static_argnums=(1, 2, 3))

    def has(self, name: str) -> bool:
        return name in self.leaves

    def layer(self, name: str, layer: int):
        p, shape, dtype = self.leaves[name]
        return self._layer(self.base, p, shape, dtype,
                           jnp.int32(layer))

    def whole(self, name: str):
        p, shape, dtype = self.leaves[name]
        return self._whole(self.base, p, shape, dtype)
