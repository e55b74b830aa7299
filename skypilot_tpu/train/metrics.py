"""Step-time → tokens/sec → MFU accounting.

MFU = achieved matmul FLOPs/s ÷ peak bf16 FLOPs/s of the slice, using the
standard 6·N-active + attention-term FLOPs/token model
(ModelConfig.flops_per_token). Chip peak numbers come from
topology.GENERATIONS so the same math works on any generation; a device
that is not in that table is an error, not a default.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import jax

from skypilot_tpu import topology
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.observability import metrics as obs

# Published into the process-wide registry so bench.py / dashboards
# scrape the numbers instead of re-deriving them from raw step times.
_STEP_SECONDS = obs.gauge(
    'skytpu_train_step_seconds', 'Last measured training step time')
_TOKENS_PER_SEC = obs.gauge(
    'skytpu_train_tokens_per_sec',
    'Training throughput over all chips (last published measurement)')
_MFU = obs.gauge(
    'skytpu_train_mfu',
    'Model FLOPs utilization in [0, 1] (last published measurement)')
_STEPS_TIMED = obs.counter(
    'skytpu_train_steps_timed_total', 'Steps timed past warmup')
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


def detect_chip_peak_tflops() -> float:
    """Peak bf16 TFLOPs of one local device, from its device kind and
    topology.GENERATIONS. Raises for a kind that is not in the table,
    the CPU included: a utilization against a guessed peak is not a
    measurement. Callers off the chip pass their own peak to `mfu` or
    do without one."""
    dev = jax.devices()[0]
    kind = getattr(dev, 'device_kind', '').lower()
    squashed = kind.replace(' ', '')
    # 'v5 lite' must check before bare 'v5'-prefixed generations.
    if 'lite' in squashed:
        return topology.GENERATIONS['v5e'].bf16_tflops_per_chip
    for gen in topology.GENERATIONS.values():
        for alias in gen.aliases + (gen.name,):
            if alias in squashed:
                return gen.bf16_tflops_per_chip
    raise ValueError(
        f'no published bf16 peak for device kind {kind!r} (platform '
        f'{dev.platform!r}); known generations: '
        f'{sorted(topology.GENERATIONS)}. Pass peak_tflops_per_chip.')


@dataclasses.dataclass
class StepTimer:
    """Wall-clock per-step measurement with warmup discard."""
    warmup_steps: int = 2
    times: List[float] = dataclasses.field(default_factory=list)
    _t0: Optional[float] = None
    _count: int = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        assert self._t0 is not None
        dt = time.perf_counter() - self._t0
        self._count += 1
        if self._count > self.warmup_steps:
            self.times.append(dt)
            _STEP_SECONDS.set(dt)
            _STEPS_TIMED.inc()

    def mean_step_time(self) -> float:
        assert self.times, 'no timed steps (all warmup?)'
        return sum(self.times) / len(self.times)


def tokens_per_sec(batch_size: int, seq_len: int,
                   step_time_s: float) -> float:
    return batch_size * seq_len / step_time_s


def mfu(cfg: ModelConfig, batch_size: int, seq_len: int, step_time_s: float,
        num_chips: int, peak_tflops_per_chip: Optional[float] = None
        ) -> float:
    if peak_tflops_per_chip is None:
        peak_tflops_per_chip = detect_chip_peak_tflops()
    achieved = (cfg.flops_per_token(seq_len) * batch_size * seq_len /
                step_time_s)
    peak = peak_tflops_per_chip * 1e12 * num_chips
    return achieved / peak


def opt_state_bytes(state) -> Tuple[int, int]:
    """(global_bytes, bytes_per_device) of a TrainState's optimizer
    state. Per-device sums each leaf's shard shape on ONE device, so
    under ZeRO-1 weight-update sharding it reads ~1/dp of global — the
    quantity the `--dryrun-train-zero1` row and the
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
    the one call sites (train.run, bench dryruns) use so the derived
    and the scraped numbers can never disagree."""
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


def publish_throughput(cfg: ModelConfig, batch_size: int, seq_len: int,
                       step_time_s: float, num_chips: int
                       ) -> Tuple[float, float]:
    """Compute (tokens/sec over all chips, MFU) and publish both into
    the registry — the one call sites (bench.py, trainers) use so the
    derived numbers and the scraped numbers can never disagree. MFU is
    against the local device's published peak, so this raises off the
    chip (detect_chip_peak_tflops)."""
    tps = tokens_per_sec(batch_size, seq_len, step_time_s)
    utilization = mfu(cfg, batch_size, seq_len, step_time_s, num_chips)
    _TOKENS_PER_SEC.set(tps)
    _MFU.set(utilization)
    return tps, utilization
