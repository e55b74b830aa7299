"""The layer loop of a DECODING model: it carries the cache.

Training scans the layers over stacked weights (`Transformer.__call__`,
`nn.scan` with `variable_axes={'params': 0, ...}`). A decoding model
(`cfg.decode`) also holds a flax 'cache' collection, every leaf stacked
(L, ...) the same way: paged or contiguous K/V, int8 scale rows, a
recurrent mixer's per-slot state. Scanned like the weights, each leaf is
a scanned input AND a scanned output of the loop, and XLA runs that as a
slice of the layer's whole part out of the leaf, a write of it back into
a NEW stacked buffer, and a copy of that buffer onto the donated one
after the loop: three passes over the whole cache a program, whatever
the requests hold (on a v5e 43% of `decode-heavy`'s busy time; PERF.md
section 6, PR 30).

Here the loop CARRIES the 'cache' collection whole (`variable_carry`)
and scans the layer's index beside the weights. A layer reaches its part
of a leaf by that index (`current_layer()`), never by a slice of it:

- paged K/V: the chunk's rows scattered to, and the window gathered
  from, rows [layer * nblocks * bs, (layer + 1) * nblocks * bs) of the
  leaf's flat view (`Attention._paged_decode_attention`);
- contiguous K/V and int8 scale rows: `write_window` at
  (layer, row, start), the window read at `layer`;
- recurrent state: `read_rows` / `write_rows` at (layer, slot).

A while loop whose carried operand is only ever touched by
dynamic-update-slice and scatter is updated in place: no second buffer,
no copy after the loop (pinned on the compiled programs by
tests/test_cache_in_place.py).

A model whose layers differ by position (`cfg.has_layer_pattern`:
leading dense layers before expert layers, an attention kind a layer,
a mixer kind a layer; models/layer_pattern.py) takes the same loop once
a GROUP of layers of one shape (`layer_groups`): 'dense_layers', then
'layers', then a run more at each change of mixer or clamps. Beside the
weights and the index each loop scans the layers' kinds, `(window, rope)` a layer, which
a layer reads through `current_kind()`: the pattern is data of the one
program, not a program a kind. Such a model comes here for every apply,
init and whole sequences too (`carried` false: the cache, if the apply
makes one, is scanned like the weights and a leaf is the layer's own).

The index travels in a context variable, not in an argument:
`DecoderLayer` and everything it calls are the training path's too, and
their signatures and call lines stay as they are. Only code that runs
when `cfg.decode` is true imports this module, and only inside the
functions that need it, so a trainer never loads it.

While there is no cache yet (init, or an apply that is to create one)
`Transformer.__call__` takes its `nn.scan` over `{'params': 0,
'cache': 0}`, which is what gives the leaves their (L, ...) shape and
'layers' axis; there, and in the unrolled `scan_layers=False` model,
`current_layer()` is None and a leaf is the layer's own. The tree is the
same either way.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.transformer import DecoderLayer

_LAYER: contextvars.ContextVar = contextvars.ContextVar(
    'carried_cache_layer', default=None)
_KIND: contextvars.ContextVar = contextvars.ContextVar(
    'carried_layer_kind', default=None)

# The window of a layer that has none, as the loop carries it: no
# context reaches it, so `q_pos - k_pos < window` holds for every key.
NO_WINDOW = 1 << 30


def current_layer() -> Optional[jax.Array]:
    """The index of the layer being traced inside `carry_layers`' loop,
    where every cache leaf is the stacked (L, ...) one; None anywhere
    else, where a leaf is the layer's own."""
    return _LAYER.get()


def current_kind() -> Optional[Tuple[jax.Array, jax.Array]]:
    """(window, rope) of the layer being traced inside a layer
    pattern's loop, two traced int32 scalars (the window in keys,
    `NO_WINDOW` for none; rope 1 or 0); None anywhere else."""
    return _KIND.get()


@contextlib.contextmanager
def _at_layer(layer: Optional[jax.Array], kind=None):
    tokens = _LAYER.set(layer), _KIND.set(kind)
    try:
        yield
    finally:
        _LAYER.reset(tokens[0])
        _KIND.reset(tokens[1])


def layer_view(leaf: jax.Array, layer: Optional[jax.Array]
               ) -> Tuple[jax.Array, jax.Array]:
    """(stacked leaf, this layer's index in it): the carried (L, ...)
    leaf as it is, or the layer's own leaf under a leading axis of
    one, so that one scatter or slice serves both."""
    return (leaf[None], 0) if layer is None else (leaf, layer)


def write_window(leaf: jax.Array, new: jax.Array, start: jax.Array,
                 layer: Optional[jax.Array]) -> jax.Array:
    """leaf[(layer,) row, start[row]:start[row] + cur] = new[row] for
    every row, as ONE scatter on the leaf itself. 'clip' is
    dynamic_update_slice's clamp (a window past the end moves back
    inside), which the engine's inert rows lean on. new: (B, cur, ...);
    start: (B,)."""
    full, index = layer_view(leaf, layer)
    rows = jnp.arange(new.shape[0], dtype=jnp.int32)
    at = jnp.stack([jnp.full_like(rows, index), rows,
                    start.astype(jnp.int32)], axis=-1)          # (B, 3)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, new.ndim)),
        inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2))
    full = jax.lax.scatter(full, at, new, dnums, indices_are_sorted=True,
                           unique_indices=True, mode='clip')
    return full[0] if layer is None else full


def read_layer(leaf: jax.Array, layer: Optional[jax.Array]) -> jax.Array:
    """The layer's own part of a leaf, read where it lies."""
    if layer is None:
        return leaf
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)


def read_rows(leaf: jax.Array, slots: Optional[jax.Array],
              layer: Optional[jax.Array]) -> jax.Array:
    """Each batch row's state out of a per-slot leaf, (B, ...): read at
    (layer, slot), the layer's part never sliced out whole first. One
    row (a paged prefill chunk) by a dynamic slice, several (a decode
    step) by a gather; `slots` None means row b is slot b."""
    if slots is None:
        return read_layer(leaf, layer)
    full, index = layer_view(leaf, layer)
    if slots.shape[0] == 1:
        at = (index, slots[0]) + (0,) * (full.ndim - 2)
        return jax.lax.dynamic_slice(full, at, (1, 1) + full.shape[2:])[0]
    return full[index, slots]


def write_rows(leaf: jax.Array, slots: Optional[jax.Array],
               layer: Optional[jax.Array], new: jax.Array) -> jax.Array:
    """The leaf with `new` in the rows that `read_rows` read: a dynamic
    update slice (one row, or all of them) or a scatter, on the leaf
    itself, which XLA then updates in place (read in the programs
    compiled for a v5e: no copy of a state leaf by either; with several
    named slots, which no engine passes, the small convolution leaf is
    relaid once on the way in and out, outside the loop)."""
    full, index = layer_view(leaf, layer)
    if slots is None or slots.shape[0] == 1:
        at = (index, 0 if slots is None else slots[0]) + (0,) * (
            full.ndim - 2)
        full = jax.lax.dynamic_update_slice(full, new[None], at)
    else:
        full = full.at[index, slots].set(new)
    return full[0] if layer is None else full


class CarriedLayer(nn.Module):
    """DecoderLayer under the (carry, xs) -> (carry, out) signature
    nn.scan expects, with the scanned-over layer index put where the
    layer's cache reads and writes find it."""
    cfg: ModelConfig

    @nn.compact
    def __call__(self, carry, layer):
        x, positions, block_tables, adapter_ids, state_rows = carry
        with _at_layer(layer):
            x = DecoderLayer(self.cfg, name='layer')(
                x, positions, block_tables, adapter_ids, state_rows)
        return (x, positions, block_tables, adapter_ids, state_rows), None


class CarriedPatternLayer(nn.Module):
    """`layer_pattern.PatternLayer` under nn.scan's signature: beside
    its index the loop hands a layer its kind. `carried` false: the
    cache is scanned with the weights (or there is none), so a leaf is
    the layer's own and the index says nothing. `group` is the run's
    `LayerGroup`; its expert layers read the stacks at `expert_offset`
    plus their index."""
    cfg: ModelConfig
    group: 'LayerGroup'
    carried: bool

    @nn.compact
    def __call__(self, carry, xs, stacks):
        # Late import: only a model with a pattern loads the module.
        from skypilot_tpu.models.layer_pattern import PatternLayer
        layer, window, rope = xs
        x, positions, block_tables, adapter_ids, state_rows = carry
        g = self.group
        expert_layer = layer + g.expert_offset if g.expert_offset else layer
        with _at_layer(layer if self.carried else None, (window, rope)):
            x = PatternLayer(self.cfg, g.dense, g.mixer, g.limits,
                             name='layer')(
                x, positions, block_tables, adapter_ids, state_rows,
                None if stacks is None else (stacks, expert_layer))
        return (x, positions, block_tables, adapter_ids, state_rows), None


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """A run of layers of one kind, stacked and looped over as one:
    `name` its scope in the parameter and cache trees, `dense` its MLP
    (dense, or the expert block), `mixer` its mixer ('attention', 'kda'
    or 'mla'), `limits` its (routed, shared) SwiGLU clamps, layers
    [start, stop), and `expert_offset` the expert layers before it,
    where it reads the expert stacks."""
    name: str
    dense: bool
    mixer: str
    limits: Tuple[float, float]
    start: int
    stop: int
    expert_offset: int


def layer_groups(cfg: ModelConfig) -> Tuple[LayerGroup, ...]:
    """The groups of a layer pattern: maximal runs of layers with one
    (dense or expert, mixer, clamps) kind, so that a group's layers
    share one shape and each carries only its own cache leaves. The
    leading dense layers of an expert model come first; a model without
    experts is dense throughout. The first dense run of an expert model
    is 'dense_layers', the first other run 'layers'; a later run is
    named by its first layer ('layers_6')."""
    n = cfg.num_dense_layers if cfg.is_moe else cfg.num_layers
    if cfg.is_moe and not 0 <= n < cfg.num_layers:
        raise ValueError(
            f'{cfg.name}: {n} leading dense layers leave no expert '
            f'layer of {cfg.num_layers}')
    kind = lambda l: (l < n, cfg.mixer_kind(l), cfg.swiglu_limits(l))
    groups, start, named = [], 0, set()
    for stop in range(1, cfg.num_layers + 1):
        if stop < cfg.num_layers and kind(stop) == kind(start):
            continue
        dense, mixer, limits = kind(start)
        base = 'dense_layers' if dense and cfg.is_moe else 'layers'
        groups.append(LayerGroup(
            f'{base}_{start}' if base in named else base, dense, mixer,
            limits, start, stop, 0 if dense else start - n))
        named.add(base)
        start = stop
    return tuple(groups)


def _carry_pattern(cfg: ModelConfig, carry: Tuple, carried: bool) -> Tuple:
    if not cfg.scan_layers:
        raise NotImplementedError(
            f'{cfg.name}: a layer pattern runs as stacked groups only '
            f'(scan_layers=True)')
    kinds = cfg.layer_kinds or ((cfg.sliding_window,
                                 cfg.pos_embedding == 'rope'),
                                ) * cfg.num_layers
    windows = jnp.asarray([w or NO_WINDOW for w, _ in kinds], jnp.int32)
    ropes = jnp.asarray([int(r) for _, r in kinds], jnp.int32)
    # what a dropless expert layer counted, stacked a layer (a no-op
    # unless the apply makes 'moe_stats' mutable)
    variable_axes = {'params': 0, 'moe_stats': 0}
    if cfg.serve_adapters > 0:
        variable_axes['adapters'] = 0
    if not carried:
        variable_axes['cache'] = 0
    groups = layer_groups(cfg)
    # The experts' weights are the one thing a layer is NOT handed its
    # own slice of: scanned, each (held, in, out) slice would be copied
    # out of its stack before a grouped product could read it. Every
    # expert layer's are declared here, whole, and broadcast into each
    # group's loop (models/moe.py reads a layer's out of the stack).
    stacks = None
    if cfg.is_moe and cfg.moe_impl == 'dropless':
        from skypilot_tpu.models.moe import ExpertStacks
        stacks = ExpertStacks(
            cfg, sum(g.stop - g.start for g in groups if not g.dense),
            name='experts')()
    for g in groups:
        scanned = nn.scan(
            CarriedPatternLayer,
            variable_axes=variable_axes,
            variable_carry='cache' if carried else False,
            split_rngs={'params': True},
            in_axes=(0, nn.broadcast),
            length=g.stop - g.start,
            metadata_params={nn.PARTITION_NAME: 'layers'},
        )(cfg, g, carried, name=g.name)
        carry, _ = scanned(carry, (
            jnp.arange(g.stop - g.start, dtype=jnp.int32),
            windows[g.start:g.stop], ropes[g.start:g.stop]),
            None if g.dense else stacks)
    return carry


def carry_layers(cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array],
                 adapter_ids: Optional[jax.Array],
                 state_rows: Optional[Tuple],
                 carried: bool = True) -> jax.Array:
    """All layers applied to x, the 'cache' collection carried. Called
    from inside `Transformer.__call__` (the scanned module becomes its
    child 'layers', so the parameter tree is the training loop's).
    Weights and adapter stacks stay scanned inputs (a pattern's expert
    stacks alone are handed to the loop whole). No remat: a decoding
    model keeps nothing for a backward pass. A layer pattern runs a loop
    a group, and with `carried` false (no cache yet, or none at all)
    scans what cache there is like the weights."""
    if cfg.has_layer_pattern:
        return _carry_pattern(cfg, (x, positions, block_tables,
                                    adapter_ids, state_rows), carried)[0]
    variable_axes = {'params': 0}
    if cfg.serve_adapters > 0:
        variable_axes['adapters'] = 0
    scanned = nn.scan(
        CarriedLayer,
        variable_axes=variable_axes,
        variable_carry='cache',
        split_rngs={'params': True},
        length=cfg.num_layers,
        metadata_params={nn.PARTITION_NAME: 'layers'},
    )(cfg, name='layers')
    (x, _, _, _, _), _ = scanned(
        (x, positions, block_tables, adapter_ids, state_rows),
        jnp.arange(cfg.num_layers, dtype=jnp.int32))
    return x
