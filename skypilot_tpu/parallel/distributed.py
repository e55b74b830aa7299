"""Multi-host bootstrap: coordinator election + topology env contract.

This replaces the reference's rank-wiring exports
(SKYPILOT_NODE_RANK/NODE_IPS/NUM_NODES at
sky/backends/cloud_vm_ray_backend.py:570-637 + NCCL inside user scripts)
with the JAX-native contract (SURVEY §2.9, §5):

- ICI within a slice needs no wiring at all — every host of a slice runs
  the same program and libtpu discovers the torus.
- Across hosts, `jax.distributed.initialize(coordinator, num_processes,
  process_id)` wires the control plane; the agent exports the inputs as
  env vars (agent/constants.py ENV_*), with host 0 of slice 0 as the
  elected coordinator.
- Across slices (multislice/DCN), MEGASCALE_* env vars configure the DCN
  transport; mesh axis `dp` (outermost) rides DCN by construction
  (parallel/mesh.py).

`initialize()` is what user programs (and the in-tree trainer and server)
call first; under a single process it wires nothing, so the same script
runs on one chip, a CPU test mesh, or a v5p-512 pod. It is also where the
persistent compile cache is pointed (`enable_compile_cache`), because it
runs before either entry point compiles anything.
"""
from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, List, Optional

from skypilot_tpu.agent import constants as agent_constants

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ProcessTopology:
    """One process's place in the job (parsed from the agent's env)."""
    num_slices: int
    slice_index: int
    num_hosts: int          # total across slices
    host_rank: int          # global
    host_index: int         # within its slice
    chips_per_host: int
    node_ips: List[str]
    coordinator_address: Optional[str]

    @property
    def is_coordinator(self) -> bool:
        return self.host_rank == 0

    @property
    def multihost(self) -> bool:
        return self.num_hosts > 1

    @property
    def multislice(self) -> bool:
        return self.num_slices > 1


def topology_from_env(env: Optional[Dict[str, str]] = None
                      ) -> ProcessTopology:
    e = dict(os.environ if env is None else env)
    c = agent_constants
    num_hosts = int(e.get(c.ENV_NUM_NODES, '1'))
    ips = [ip for ip in e.get(c.ENV_NODE_IPS, '').split('\n') if ip]
    coordinator = e.get(c.ENV_JAX_COORDINATOR)
    if coordinator is None and ips:
        coordinator = f'{ips[0]}:{c.JAX_COORDINATOR_PORT}'
    return ProcessTopology(
        num_slices=int(e.get(c.ENV_NUM_SLICES, '1')),
        slice_index=int(e.get(c.ENV_SLICE_INDEX, '0')),
        num_hosts=num_hosts,
        host_rank=int(e.get(c.ENV_NODE_RANK, '0')),
        host_index=int(e.get(c.ENV_HOST_INDEX, '0')),
        chips_per_host=int(e.get(c.ENV_CHIPS_PER_HOST, '1')),
        node_ips=ips,
        coordinator_address=coordinator,
    )


# One fixed directory inside the checkout (gitignored), built from no
# pid, time or tempfile: a cache that moves between runs is never found
# again.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')


def enable_compile_cache() -> Optional[str]:
    """Point jax's persistent compile cache at COMPILE_CACHE_DIR, unless
    JAX_COMPILATION_CACHE_DIR is set: jax reads that variable itself,
    and then this touches nothing. The one place in the tree that sets
    the directory; call it before the first compile. Returns the
    directory it set, or None.

    A process held to the CPU (JAX_PLATFORMS=cpu: the tests, the fake-
    device dryruns, a rehearsal) is left alone too: a CPU compile costs
    seconds, not minutes, and this jaxlib logs an error-level machine-
    feature mismatch for every CPU executable it loads back."""
    if os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        return None
    import jax
    if jax.config.jax_platforms == 'cpu':
        return None
    jax.config.update('jax_compilation_cache_dir', COMPILE_CACHE_DIR)
    # Store every program, not only those that took a second to build:
    # a serving start-up is dozens of small programs (one per prefill
    # bucket, the decode step, cache inserts), and a compile that
    # straddles a threshold would be stored on one run and not the next.
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return COMPILE_CACHE_DIR


def log_device_memory(when: str) -> None:
    """One log line per local device with `bytes_in_use` from
    `memory_stats()`: what each chip holds at this point (both entry
    points call it once weights are placed). A backend that reports
    nothing (the CPU) gets one line saying so, not a row of zeros."""
    import jax
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if not stats:
            logger.info('device_memory %s: platform %r reports no '
                        'memory_stats', when, dev.platform)
            return
        logger.info('device_memory %s: device=%d kind=%r bytes_in_use=%d '
                    'peak_bytes_in_use=%d bytes_limit=%d', when, dev.id,
                    dev.device_kind, stats['bytes_in_use'],
                    stats.get('peak_bytes_in_use', 0),
                    stats.get('bytes_limit', 0))


# The export side of this contract lives in agent/driver.py (every rank's
# env is built there, including MEGASCALE_* for multislice); this module is
# the consumer.
_initialized = False


def initialize(topology: Optional[ProcessTopology] = None,
               timeout_seconds: int = 300) -> ProcessTopology:
    """Wire this process into the job's JAX distributed runtime.

    Wires nothing for single-process jobs. Idempotent. Returns the
    topology so callers can branch on rank (e.g. only rank 0 writes
    checkpoints metadata).
    """
    global _initialized
    enable_compile_cache()
    if topology is None:
        topology = topology_from_env()
    import jax
    if topology.multihost and not _initialized:
        logger.info(
            'jax.distributed.initialize(coordinator=%s, num_processes=%d, '
            'process_id=%d)', topology.coordinator_address,
            topology.num_hosts, topology.host_rank)
        jax.distributed.initialize(
            coordinator_address=topology.coordinator_address,
            num_processes=topology.num_hosts,
            process_id=topology.host_rank,
            initialization_timeout=timeout_seconds)
        _initialized = True
    # What this process runs on, as jax reports it: every log of an
    # entry point starts by naming its device (after the wiring above,
    # which must come before the first backend call).
    dev = jax.devices()[0]
    logger.info('jax devices: platform=%s device_kind=%r count=%d '
                '(local %d)', dev.platform, dev.device_kind,
                jax.device_count(), jax.local_device_count())
    return topology
