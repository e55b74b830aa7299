"""The device's idle time cut by what the engine's thread was doing.

The program marks each stretch of its scheduler's tick with a profiler
annotation, `engine.tick` round the whole tick and one `engine.tick.<phase>`
child round each stretch inside it (`skypilot_tpu/observability/tracing.py`
`phase`, `docs/observability.md` "Tick phases"). Under the profiler those
land in the host plane on the clock the device's events are on. The names
belong to the engine's thread alone, so the thread that `trace_reduce`
drops from a host event is not needed.

Idle time here is what `device_idle_pct.serve` reports: the stretch less
the union of the lowest device's operations, the stretch's two edges
included, over `work['window_s']`. Each idle instant goes to the phase
whose event covers it, or to `other` (housekeeping, admission, the
engine asleep, a tick's self time, outside any tick), so the five shares
add up to `device_idle_pct.serve` of the same run. A trace with no
`engine.tick` event (a program without the phases) reads None.
"""
from __future__ import annotations

import functools
import statistics

import trace_reduce

TICK = 'engine.tick'
PHASES = ('prefill', 'dispatch', 'land', 'emit')


def _events(trace, name: str) -> list:
    return sorted((s, e) for s, e, n in trace.host_events if n == name)


def _has_ticks(trace) -> bool:
    return any(n == TICK for _, _, n in trace.host_events)


def _span(trace):
    return trace.stretch_ns or trace.span_ns()


def _overlap_ns(a: list, b: list) -> int:
    """Total overlap of two sorted lists of disjoint (start, end)."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def idle_intervals(trace) -> list:
    """The intervals of the stretch in which no operation ran on the
    lowest device."""
    span = _span(trace)
    if span is None or not trace.devices:
        return []
    ops = trace.devices[min(trace.devices)].ops
    out, at = [], span[0]
    for s, e in trace_reduce.merged([(s, e) for s, e, _ in ops]):
        if s > at:
            out.append((at, min(s, span[1])))
        at = max(at, e)
    if at < span[1]:
        out.append((at, span[1]))
    return out


@functools.lru_cache(maxsize=1)     # five readers ask for one run's split
def idle_split(trace, window_s: float):
    """{phase: idle % of the stretch inside it, ..., 'other': the rest};
    None where the trace has no device, no window or no tick event."""
    if not trace.devices or not window_s or window_s <= 0 or \
            not _has_ticks(trace):
        return None
    idle = idle_intervals(trace)
    out = {p: _overlap_ns(idle, trace_reduce.merged(
        _events(trace, f'{TICK}.{p}'))) for p in PHASES}
    out['other'] = sum(e - s for s, e in idle) - sum(out.values())
    return {k: 100.0 * v / 1e9 / window_s for k, v in out.items()}


def idle_in(ctx, phase: str):
    """What an `idle_in_<phase>_pct` reader returns."""
    split = idle_split(ctx['trace'], ctx['work']['window_s'])
    return None if split is None else split[phase]


def phase_share_pct(trace, phase: str):
    """Share of the stretch that the engine's thread spent inside the
    phase, in percent."""
    span = _span(trace)
    if span is None or span[1] <= span[0] or not _has_ticks(trace):
        return None
    inside = trace_reduce.union_ns(_events(trace, f'{TICK}.{phase}'))
    return 100.0 * inside / (span[1] - span[0])


def tick_p50_ms(trace):
    """Median length, in ms, of the ticks that hold an
    `engine.tick.emit` event: the ticks that gave every decoding slot a
    token. A tick cut by an edge of the stretch is left out."""
    span = _span(trace)
    inner = [s for s, _ in _events(trace, f'{TICK}.emit')]
    lengths, j = [], 0
    for s, e in _events(trace, TICK):
        while j < len(inner) and inner[j] < s:
            j += 1
        if j < len(inner) and inner[j] < e and \
                (span is None or (s > span[0] and e < span[1])):
            lengths.append(e - s)
    return statistics.median(lengths) / 1e6 if lengths else None
