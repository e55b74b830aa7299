"""Logical-axis sharding rules: the single place where model dimensions are
mapped to mesh axes.

MaxText-style (the reference framework's TPU counterpart) but reduced to the
axes this framework uses. Model code annotates arrays with *logical* names
('batch', 'seq', 'embed', ...); these rules translate them to the physical
mesh axes from parallel/mesh.py. Changing a parallelism strategy is a rule
change, not a model change.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# (logical name, physical mesh axis/axes or None=replicated). A dim
# with no name (None in a leaf's metadata: the cache's block and
# position dims) stays unsharded without a row of its own; flax looks
# every row's name up with PartitionSpec.index, which refuses None.
LOGICAL_AXIS_RULES: List[Tuple[str, object]] = [
    # Activations.
    ('batch', ('dp', 'fsdp')),      # data parallel shards the batch
    ('seq', 'sp'),                  # sequence/context parallelism
    ('act_embed', 'tp'),            # activation feature dim under TP
    ('act_heads', 'tp'),
    # Weights.
    ('embed', 'fsdp'),              # ZeRO-3 style weight sharding
    ('heads', 'tp'),                # attention heads under TP
    ('kv_heads', 'tp'),
    ('qkv_dim', None),
    ('mlp', 'tp'),                  # MLP hidden under TP
    ('lora_rank', None),            # LoRA adapter rank: tiny, replicated
    ('vocab', 'tp'),                # embedding/unembedding vocab dim
    ('expert', 'ep'),               # MoE experts under expert parallelism
    ('layers', 'pp'),               # stacked layer dim under pipeline
    ('stage', 'pp'),                # pipeline executor's stage buffers
]


def logical_axis_rules() -> List[Tuple[str, object]]:
    return list(LOGICAL_AXIS_RULES)


def shard_map(fn, *, mesh: Optional[Mesh] = None, in_specs, out_specs):
    """`jax.shard_map`, the single call site for the whole framework.
    `mesh=None` uses the ambient mesh (`use_mesh`). Replication
    checking is disabled: callers here wrap collectives whose variance
    the checker can't infer (same rationale as the check_vma note in
    collective_bench)."""
    kwargs = dict(in_specs=in_specs, out_specs=out_specs, check_vma=False)
    if mesh is not None:
        kwargs['mesh'] = mesh
    return jax.shard_map(fn, **kwargs)


def use_mesh(mesh: Mesh):
    """Ambient-mesh context manager: what `shard_map(mesh=None)`, bare
    PartitionSpec constraints and the flash kernel's per-device wrap
    read. (`with mesh:` sets the older resource env only, which
    `jax.shard_map` does not see.)"""
    return jax.set_mesh(mesh)


def spec_for(*logical_axes: Optional[str]) -> PartitionSpec:
    """PartitionSpec for a tuple of logical axis names."""
    rules = dict(LOGICAL_AXIS_RULES)
    parts = []
    for name in logical_axes:
        if name is None:
            parts.append(None)
        else:
            parts.append(rules.get(name))
    return PartitionSpec(*parts)


def sharding_for(mesh: Mesh,
                 *logical_axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, spec_for(*logical_axes))


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a mesh."""
    try:
        return jax.lax.with_sharding_constraint(x, spec_for(*logical_axes))
    except (ValueError, RuntimeError) as e:
        if 'divisible' in str(e):
            # A REAL layout error (dim smaller than / not divisible by
            # its mesh axis) must surface — swallowing it silently drops
            # the constraint and lets GSPMD pick any layout (observed:
            # grad-accum microbatches smaller than the dp extent).
            raise
        # Not under a mesh context (e.g. pure single-device eval).
        return x


def with_logical(x, *names: Optional[str]):
    """flax param metadata wrapper (nn.with_logical_partitioning sugar)."""
    return nn.with_logical_partitioning(x, names)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on `mesh` (engine feeds, block tables,
    scalar metrics — anything every device needs whole)."""
    return NamedSharding(mesh, PartitionSpec())


def tree_shardings(mesh: Mesh, abstract_tree):
    """NamedShardings for ANY flax tree whose leaves carry logical-axis
    metadata (params, KV-cache variables, whole TrainStates).

    This is THE logical→physical translation point shared by training
    (train/trainer.py's sharded state init) and inference (the engines'
    param placement and sharded KV pools in models/inference.py): both
    sides consume these rules rather than keeping a copy, so changing a
    parallelism strategy stays a one-file rule change. Returns a tree
    shaped like `abstract_tree` (still boxed if the input was boxed —
    callers nn.unbox before jax.device_put / out_shardings)."""
    logical_specs = nn.get_partition_spec(abstract_tree)
    return nn.logical_to_mesh_sharding(logical_specs, mesh,
                                       logical_axis_rules())


def _axes_of(entry) -> Tuple[str, ...]:
    """Physical mesh axes a PartitionSpec entry names ('x' | ('x','y') |
    None)."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def zero_update_shardings(mesh: Mesh, abstract_tree, base_shardings,
                          axis: str = 'dp'):
    """ZeRO-1-style weight-update sharding (arxiv 2004.13336): augment a
    tree of NamedShardings so every array leaf is ADDITIONALLY sharded
    over the data-parallel mesh axis.

    Applied to the optimizer state (the fp32 Adam moments, which mirror
    the param tree and dwarf it at 2x fp32), this is the cross-replica
    weight-update sharding of the paper: each dp replica holds and
    updates 1/dp of the moments, XLA scatters the gradients into the
    shards and all-gathers the updated params back — the trainer's math
    does not change, only these annotations do.

    Per leaf: the FIRST dimension that (a) does not already carry
    `axis` anywhere in its spec and (b) stays divisible after adding it
    (dim % (existing-axes extent x dp) == 0) gains `axis` appended to
    its entry. Leaves with no such dimension — scalars (the Adam step
    count), odd-shaped stragglers — keep their base sharding and stay
    replicated over dp; callers bound the waste with the (1/dp + eps)
    byte pin rather than a per-leaf guarantee.

    `abstract_tree` and `base_shardings` must be UNBOXED
    (ShapeDtypeStructs and NamedShardings respectively). The SHARDINGS
    tree is the structure authority: where flax's get_partition_spec
    collapsed a subtree to one prefix sharding (optax masked/empty
    nodes under a LoRA multi_transform), the whole abstract subtree
    arrives at one call and — carrying no single .shape — keeps its
    base sharding, exactly right for frozen/empty groups. With dp == 1
    (or no `axis` on the mesh) the base shardings return unchanged.
    """
    axis_sizes = dict(mesh.shape)
    dp = axis_sizes.get(axis, 1)
    if dp <= 1:
        return base_shardings

    def augment(sharding, leaf):
        shape = getattr(leaf, 'shape', None)
        if not shape:
            return sharding
        spec = list(sharding.spec) + [None] * (len(shape) -
                                               len(sharding.spec))
        if any(axis in _axes_of(e) for e in spec):
            return sharding  # already dp-sharded (nothing weight-shaped
            # maps to dp under the rules today; future-proofing)
        for i, dim in enumerate(shape):
            used = _axes_of(spec[i])
            extent = 1
            for a in used:
                extent *= axis_sizes[a]
            if dim % (extent * dp) == 0:
                combined = used + (axis,)
                spec[i] = combined if len(combined) > 1 else combined[0]
                while spec and spec[-1] is None:
                    spec.pop()  # rank padding back off the spec
                return NamedSharding(mesh, PartitionSpec(*spec))
        return sharding

    return jax.tree.map(augment, base_shardings, abstract_tree)
