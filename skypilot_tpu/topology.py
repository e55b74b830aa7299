"""TPU pod-slice topology: the first-class scheduling unit of this framework.

In the reference, accelerators are an opaque ``{'tpu-v2-8': 1}`` dict attached
to VMs and TPU specifics leak in as special cases (reference:
sky/clouds/gcp.py:184-195 "TPU pods cannot stop", sky/clouds/utils/
gcp_utils.py:28-57 is_tpu_vm_pod/get_num_tpu_devices,
sky/backends/cloud_vm_ray_backend.py:2485-2493 num_ips_per_node>1 only for TPU
pods). Here the slice IS the unit: every Resources resolves to a ``TpuSlice``
that knows its generation, chip count, host count, physical topology, per-chip
FLOPs/HBM, and the mesh axes it naturally supports. Gang scheduling reduces to
"provision the slice"; rank wiring reduces to (slice, host) enumeration.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

from skypilot_tpu import exceptions


@dataclasses.dataclass(frozen=True)
class TpuGeneration:
    """Static facts about one TPU generation."""
    name: str                   # canonical short name, e.g. 'v5p'
    aliases: Tuple[str, ...]    # accepted spellings in accelerator strings
    counts_cores: bool          # accelerator suffix counts TensorCores (2/chip)
    chips_per_host: int
    hbm_gb_per_chip: float
    bf16_tflops_per_chip: float
    int8_tops_per_chip: float
    # ICI topology dimensionality: 2 for 2D torus (v2/v3/v5e/v6e), 3 for 3D.
    ici_dims: int
    max_chips: int              # largest single slice
    single_host_chips: Tuple[int, ...]  # allowed sub-host/single-host sizes
    supports_spot: bool = True
    # Generation is reachable via the queued-resources API (v5e/v5p/v6e).
    queued_resources: bool = False


# Peak-compute and HBM figures are public datasheet numbers, summed a
# slice by `TpuSlice.bf16_tflops` / `.hbm_gb`. The benchmark reads its
# peaks from perf/peaks.json, not from here.
GENERATIONS: Dict[str, TpuGeneration] = {
    g.name: g for g in [
        TpuGeneration('v2', ('v2',), True, 4, 8.0, 45.0, 0.0, 2, 512, (4,)),
        TpuGeneration('v3', ('v3',), True, 4, 16.0, 123.0, 0.0, 2, 2048,
                      (4,)),
        TpuGeneration('v4', ('v4',), True, 4, 32.0, 275.0, 275.0, 3, 8192,
                      (4,)),
        TpuGeneration('v5e', ('v5e', 'v5litepod'), False, 8, 16.0, 197.0,
                      394.0, 2, 256, (1, 4, 8), queued_resources=True),
        TpuGeneration('v5p', ('v5p',), True, 4, 95.0, 459.0, 918.0, 3, 12288,
                      (4,), queued_resources=True),
        TpuGeneration('v6e', ('v6e', 'trillium'), False, 8, 32.0, 918.0,
                      1836.0, 2, 256, (1, 4, 8), queued_resources=True),
    ]
}

_ALIAS_TO_GEN: Dict[str, str] = {}
for _g in GENERATIONS.values():
    for _a in _g.aliases:
        _ALIAS_TO_GEN[_a] = _g.name

_ACC_RE = re.compile(
    r'^(?:tpu-)?(?P<gen>v2|v3|v4|v5e|v5litepod|v5p|v6e|trillium)-(?P<n>\d+)$',
    re.IGNORECASE)


def _default_topology(chips: int, dims: int) -> str:
    """Pick the most-cubic factorization of `chips` into `dims` dimensions.

    The physical wiring of real slices is constrained (e.g. v5p-64 is 2x4x4);
    a balanced factorization matches the published shapes for the common sizes
    and gives the scheduler an ICI mesh to map dp/tp axes onto.
    """
    if dims == 2:
        best = (1, chips)
        for a in range(1, int(math.isqrt(chips)) + 1):
            if chips % a == 0:
                best = (a, chips // a)
        return f'{best[0]}x{best[1]}'
    # 3D: search a<=b<=c with a*b*c == chips, maximize a (most cubic).
    best3 = (1, 1, chips)
    for a in range(1, int(round(chips ** (1 / 3))) + 2):
        if chips % a:
            continue
        rest = chips // a
        for b in range(a, int(math.isqrt(rest)) + 1):
            if rest % b == 0 and b >= a:
                c = rest // b
                if c >= b:
                    best3 = max(best3, (a, b, c), key=lambda t: (t[0], t[1]))
    return f'{best3[0]}x{best3[1]}x{best3[2]}'


@dataclasses.dataclass(frozen=True)
class TpuSlice:
    """A concrete TPU pod slice: generation + size (+ physical topology)."""
    generation: str         # 'v5p'
    count: int              # the number in the accelerator name (cores/chips)
    chips: int
    hosts: int
    topology: str           # e.g. '2x4x4'

    @property
    def gen(self) -> TpuGeneration:
        return GENERATIONS[self.generation]

    @property
    def name(self) -> str:
        """Canonical accelerator string, e.g. 'tpu-v5p-64'."""
        return f'tpu-{self.generation}-{self.count}'

    @property
    def gcp_accelerator_type(self) -> str:
        """The name the TPU API expects (v5e is 'v5litepod-N' upstream)."""
        gen = 'v5litepod' if self.generation == 'v5e' else self.generation
        return f'{gen}-{self.count}'

    @property
    def is_pod(self) -> bool:
        """Multi-host slice. Pods cannot be stopped, only deleted
        (reference behavior: sky/clouds/gcp.py:184-190)."""
        return self.hosts > 1

    @property
    def chips_per_host(self) -> int:
        return min(self.gen.chips_per_host, self.chips)

    @property
    def bf16_tflops(self) -> float:
        return self.chips * self.gen.bf16_tflops_per_chip

    @property
    def hbm_gb(self) -> float:
        return self.chips * self.gen.hbm_gb_per_chip

    def mesh_shape_hint(self) -> Tuple[int, ...]:
        """Physical ICI mesh shape as a tuple, e.g. (2, 4, 4)."""
        return tuple(int(x) for x in self.topology.split('x'))

    def host_workers(self) -> List[int]:
        return list(range(self.hosts))

    def __str__(self) -> str:
        return (f'{self.name}({self.chips} chips, {self.hosts} host'
                f'{"s" if self.hosts != 1 else ""}, {self.topology})')


def parse_accelerator(acc: str,
                      topology: Optional[str] = None) -> TpuSlice:
    """Parse 'tpu-v5p-64' / 'v5e-16' / 'v5litepod-16' into a TpuSlice.

    Raises InvalidTopologyError on unknown generations, non-factorable sizes,
    or a user topology that does not multiply out to the chip count.
    """
    m = _ACC_RE.match(acc.strip())
    if m is None:
        raise exceptions.InvalidTopologyError(
            f'Unparseable TPU accelerator {acc!r}. Expected e.g. '
            f'"tpu-v5p-64", "v5e-16", "tpu-v2-8".')
    gen_name = _ALIAS_TO_GEN[m.group('gen').lower()]
    gen = GENERATIONS[gen_name]
    count = int(m.group('n'))
    if count <= 0:
        raise exceptions.InvalidTopologyError(f'Bad TPU size in {acc!r}')
    if gen.counts_cores:
        if count % 2 and count != 1:
            raise exceptions.InvalidTopologyError(
                f'{acc!r}: {gen_name} sizes count TensorCores and must be '
                f'even.')
        chips = max(1, count // 2)
    else:
        chips = count
    if chips > gen.max_chips:
        raise exceptions.InvalidTopologyError(
            f'{acc!r}: larger than the biggest {gen_name} slice '
            f'({gen.max_chips} chips).')
    hosts = max(1, math.ceil(chips / gen.chips_per_host))
    if hosts > 1 and chips % gen.chips_per_host:
        raise exceptions.InvalidTopologyError(
            f'{acc!r}: multi-host slices must be a multiple of '
            f'{gen.chips_per_host} chips per host.')
    if topology is not None:
        parts = [int(x) for x in topology.lower().split('x')]
        if math.prod(parts) != chips:
            raise exceptions.InvalidTopologyError(
                f'topology {topology!r} does not match {chips} chips '
                f'of {acc!r}')
        topo = 'x'.join(str(p) for p in parts)
    else:
        topo = _default_topology(chips, gen.ici_dims)
    return TpuSlice(generation=gen_name, count=count, chips=chips,
                    hosts=hosts, topology=topo)


def is_tpu_accelerator(acc: str) -> bool:
    return _ACC_RE.match(acc.strip()) is not None


def list_slice_sizes(generation: str) -> List[int]:
    """All valid accelerator-name sizes for a generation (single host up to
    max pod)."""
    gen = GENERATIONS[generation]
    factor = 2 if gen.counts_cores else 1
    sizes = [c * factor for c in gen.single_host_chips
             if c <= gen.chips_per_host]
    chips = gen.chips_per_host * 2
    while chips <= gen.max_chips:
        sizes.append(chips * factor)
        chips *= 2
    return sorted(set(sizes))
