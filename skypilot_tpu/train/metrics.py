"""Optimizer-state bytes and compiled-step collective counts, published
into the process-wide registry (`train.run` is the caller). Throughput
and utilization are not computed here: the benchmark under `perf/`
counts the required work and reads the chip's peak from
`perf/peaks.json`.
"""
from __future__ import annotations

from typing import Tuple

import jax

from skypilot_tpu.observability import metrics as obs

_OPT_BYTES = obs.gauge(
    'skytpu_train_opt_state_bytes',
    'Global bytes of the optimizer state (fp32 Adam moments dominate)')
_OPT_BYTES_PER_DEVICE = obs.gauge(
    'skytpu_train_opt_state_bytes_per_device',
    'Optimizer-state bytes resident on ONE mesh device; ~1/dp of the '
    'global bytes under ZeRO-1 weight-update sharding (--zero1)')
_STEP_COLLECTIVES = obs.gauge(
    'skytpu_train_step_collectives',
    'Collective ops in the compiled train step, by op '
    '(compiled-HLO probe, parallel/hlo_probe.py)', labelnames=('op',))


def opt_state_bytes(state) -> Tuple[int, int]:
    """(global_bytes, bytes_per_device) of a TrainState's optimizer
    state. Per-device sums each leaf's shard shape on ONE device, so
    under ZeRO-1 weight-update sharding it reads ~1/dp of global — the
    quantity tests/zero1_driver.py and the
    skytpu_train_opt_state_bytes_per_device gauge pin."""
    total = per_device = 0
    for leaf in jax.tree.leaves(state.opt_state):
        if not hasattr(leaf, 'sharding'):
            continue
        itemsize = leaf.dtype.itemsize
        total += leaf.size * itemsize
        shard = 1
        for dim in leaf.sharding.shard_shape(leaf.shape):
            shard *= dim
        per_device += shard * itemsize
    return total, per_device


def publish_opt_state_bytes(state) -> Tuple[int, int]:
    """Compute opt_state_bytes and land both numbers in the registry —
    the one call train.run uses so the derived and the scraped numbers
    can never disagree."""
    total, per_device = opt_state_bytes(state)
    _OPT_BYTES.set(total)
    _OPT_BYTES_PER_DEVICE.set(per_device)
    return total, per_device


def publish_step_collectives(stats) -> None:
    """Land a trainer.compiled_step_collectives() dict in the
    skytpu_train_step_collectives{op} gauge family (the counts that
    matter for the ZeRO-1 story: how gradients land and how params come
    back). Re-settable: a late-attaching exporter reads the last
    published probe (the PR-5 lesson)."""
    for op in ('all_reduce', 'all_gather', 'reduce_scatter',
               'partition_scatter', 'reduce_scatter_effective'):
        if op in stats:
            _STEP_COLLECTIVES.labels(op=op).set(stats[op])
