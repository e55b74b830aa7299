"""From a profiler trace (.xplane.pb) to busy and idle time, time per
XLA module and per device operation, and the host's doing in the gaps.

Reads the file with `jax.profiler.ProfileData` and nothing else. A TPU
plane is named `/device:TPU:<n>`; its line `XLA Ops` holds one event per
operation that ran on the core, `XLA Modules` one per executed program.
Host planes hold the runtime's own events and this harness's
`TraceAnnotation`s, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
CONTAINER_OPS = frozenset({'while', 'conditional', 'call'})
DEVICE_RE = re.compile(r'^/device:TPU:(\d+)$')


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return paths[-1]


def union_ns(intervals) -> int:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def module_base(name: str) -> str:
    """`jit__decode_step_impl(1234567)` -> `jit__decode_step_impl`."""
    return re.sub(r'\(\d+\)$', '', name.strip())


def op_base(name: str) -> str:
    """`%fusion.123 = ...` or `fusion.123` -> `fusion`; keeps the names
    of custom calls and collectives whole but for their numbering."""
    name = name.strip().lstrip('%').split(' ')[0]
    return re.sub(r'[.\-_]\d+$', '', name)


class DeviceTrace:
    def __init__(self, index: int):
        self.index = index
        self.ops = []       # (start_ns, end_ns, name)
        self.modules = []   # (start_ns, end_ns, name)


class Trace:
    """What the metric readers get."""

    def __init__(self, devices: dict, host_events: list):
        self.devices = devices            # index -> DeviceTrace
        self.host_events = host_events    # (start_ns, end_ns, name)
        self.stretch_ns = None            # (start_ns, end_ns) if marked

    def span_ns(self):
        starts = [o[0] for d in self.devices.values() for o in d.ops]
        ends = [o[1] for d in self.devices.values() for o in d.ops]
        if not starts:
            return None
        return min(starts), max(ends)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(union_ns([(s, e) for s, e, _ in d.ops])
                    for d in self.devices.values())
        return total / len(self.devices) / 1e9

    def idle_pct(self, window_s: float):
        """Share of `window_s` seconds in which no operation ran on the
        device, in percent; None where there is no device or window."""
        if not self.devices or not window_s or window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / window_s)

    def window_s(self):
        """Length of the marked stretch, or of the span of the device's
        operations where nothing was marked."""
        span = self.stretch_ns or self.span_ns()
        return None if span is None else (span[1] - span[0]) / 1e9

    def op_seconds(self, pattern: str, device: int = None) -> tuple:
        d = self._device(device)
        if d is None:
            return 0.0, 0
        rx = re.compile(pattern)
        hits = [(s, e) for s, e, n in d.ops if rx.search(n)]
        return sum(e - s for s, e in hits) / 1e9, len(hits)

    def ops_within_modules(self, module_pattern: str,
                           device: int = None) -> float:
        """Seconds of the union of op intervals inside the matching
        modules' spans: the device time those programs really used."""
        d = self._device(device)
        if d is None:
            return 0.0
        rx = re.compile(module_pattern)
        spans = merged([(s, e) for s, e, n in d.modules if rx.search(n)])
        if not spans:
            return 0.0
        ops = merged([(s, e) for s, e, _ in d.ops])
        total, j = 0, 0
        for s, e in spans:
            while j < len(ops) and ops[j][1] <= s:
                j += 1
            k = j
            while k < len(ops) and ops[k][0] < e:
                total += min(e, ops[k][1]) - max(s, ops[k][0])
                k += 1
        return total / 1e9

    def module_totals(self, device: int = None) -> dict:
        """{program name: summed seconds of its executions} on one
        device."""
        d = self._device(device)
        acc = {}
        for s, e, name in (d.modules if d else ()):
            acc[name] = acc.get(name, 0) + (e - s)
        return {k: v / 1e9 for k, v in sorted(acc.items(),
                                              key=lambda kv: -kv[1])}

    def _device(self, device):
        if not self.devices:
            return None
        if device is None:
            device = min(self.devices)
        return self.devices[device]

    def top_ops(self, n: int = 10) -> list:
        d = self._device(None)
        if d is None:
            return []
        acc = {}
        for s, e, name in d.ops:
            key = op_base(name)
            if key in CONTAINER_OPS:
                continue    # its body's operations are events too
            acc[key] = acc.get(key, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10, min_ns: int = 20_000) -> list:
        """Idle seconds on the lowest device by what the host was doing
        in each gap: the host event that covers most of it."""
        d = self._device(None)
        if d is None:
            return []
        busy = merged([(s, e) for s, e, _ in d.ops])
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
                if b[0] - a[1] >= min_ns]
        host = sorted(self.host_events)
        acc = {}
        j = 0
        for gs, ge in gaps:
            while j < len(host) and host[j][1] <= gs and \
                    host[j][0] < gs - 5_000_000_000:
                j += 1
            # the innermost host event that covers most of the gap: the
            # shortest of those that overlap half of it or more
            best, best_len = 'nothing recorded on the host', None
            k = j
            while k < len(host) and host[k][0] < ge:
                hs, he, name = host[k]
                cover = min(ge, he) - max(gs, hs)
                if 2 * cover >= ge - gs and name not in IGNORED_HOST and \
                        (best_len is None or he - hs < best_len):
                    best, best_len = name, he - hs
                k += 1
            acc[best] = acc.get(best, 0) + (ge - gs)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]


STRETCH = 'perf.traced_stretch'
# the harness's own waiting, which covers every gap and explains none
IGNORED_HOST = frozenset({'$time sleep', STRETCH})


def _clip(events: list, t0: int, t1: int) -> list:
    return [(max(s, t0), min(e, t1), n) for s, e, n in events
            if e > t0 and s < t1]


def load(path: str) -> Trace:
    """The trace, cut to the stretch that the harness marked with its
    `perf.traced_stretch` annotation (the whole trace where there is no
    such mark)."""
    trace = _load(path)
    marks = [(s, e) for s, e, n in trace.host_events if n == STRETCH]
    if marks:
        t0, t1 = max(marks, key=lambda m: m[1] - m[0])
        trace.stretch_ns = (t0, t1)
        for d in trace.devices.values():
            d.ops = _clip(d.ops, t0, t1)
            d.modules = _clip(d.modules, t0, t1)
        trace.host_events = [ev for ev in _clip(trace.host_events, t0, t1)
                             if ev[2] != STRETCH]
    return trace


def _load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = {}
    host_events = []
    for plane in data.planes:
        m = DEVICE_RE.match(plane.name)
        if m:
            dev = DeviceTrace(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    dev.modules = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         module_base(ev.name)) for ev in line.events]
            if dev.ops or dev.modules:
                devices[dev.index] = dev
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    host_events.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name))
    return Trace(devices, host_events)
