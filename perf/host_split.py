"""The host's own time in a tick, and the device's idle time cut by the
tick's sub-phases and by the garbage collector's pauses.

The program cuts two of its tick phases once more (`docs/observability.md`
"Tick phases"): `engine.tick.dispatch` into `.inputs`, `.launch` and
`.queue` per decode dispatch, `engine.tick.prefill` into `.inputs` and
`.launch` per chunk and `.first` after a prompt's last chunk. With tracing
on it also marks each collection as a `process.gc` event on the thread
that triggered it, the engine's or any other. All of them are profiler
annotations, on the clock the device's events are on.

Idle time is `phase_idle`'s: the stretch less the union of the lowest
device's operations, over `work['window_s']`. An `.inputs` or `.launch`
event counts where a recorded event of its parent covers it, so its share
is a part of `idle_in_dispatch_pct` + `idle_in_prefill_pct`: the profiler
records no event that began before its session, so at the stretch's
first instant a child may have lost its parent. The collector's share
cuts across all five phase shares. A trace with no sub-phase (a program without them)
reads None; a program that marks them times the collector too, so there a
stretch with no `process.gc` event reads 0.
"""
from __future__ import annotations

import functools
import statistics

import phase_idle
import trace_reduce

TICK = phase_idle.TICK
SUB_PHASES = (f'{TICK}.dispatch.inputs', f'{TICK}.dispatch.launch',
              f'{TICK}.dispatch.queue', f'{TICK}.prefill.inputs',
              f'{TICK}.prefill.launch', f'{TICK}.prefill.first')
GC = 'process.gc'
# what each share reads: the events whose union the idle time is cut by,
# and the parents that have to cover them (None: no parent)
PARTS = {
    'inputs': ((f'{TICK}.dispatch.inputs', f'{TICK}.prefill.inputs'),
               (f'{TICK}.dispatch', f'{TICK}.prefill')),
    'launch': ((f'{TICK}.dispatch.launch', f'{TICK}.prefill.launch'),
               (f'{TICK}.dispatch', f'{TICK}.prefill')),
    'gc': ((GC,), None),
}


def _union(trace, names) -> list:
    """The merged (start, end) of every event named in `names`, on any
    thread."""
    return trace_reduce.merged(
        [(s, e) for s, e, n in trace.host_events if n in names])


def _within(a: list, b: list) -> list:
    """The parts of the sorted disjoint intervals `a` that lie inside
    those of `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def _events(trace, part: str) -> list:
    names, parents = PARTS[part]
    inside = _union(trace, names)
    return inside if parents is None else _within(
        inside, _union(trace, parents))


def _has_sub_phases(trace) -> bool:
    return any(n in SUB_PHASES for _, _, n in trace.host_events)


@functools.lru_cache(maxsize=1)     # three readers ask for one run's split
def idle_split(trace, window_s: float):
    """{'inputs', 'launch', 'gc'}: idle % of the stretch inside those
    events; None where the trace has no device, no window or no
    sub-phase."""
    if not trace.devices or not window_s or window_s <= 0 or \
            not _has_sub_phases(trace):
        return None
    idle = phase_idle.idle_intervals(trace)
    return {part: 100.0 * phase_idle._overlap_ns(idle, _events(trace, part))
            / 1e9 / window_s for part in PARTS}


def idle_in(ctx, part: str):
    """What an `idle_in_<part>_pct` reader of this file returns."""
    split = idle_split(ctx['trace'], ctx['work']['window_s'])
    return None if split is None else split[part]


def host_tick_ms_p50(trace):
    """Median, in ms, over the ticks `tick_p50_ms` takes (those that hold
    an `engine.tick.emit`, none cut by an edge of the stretch), of the
    tick's length less its `land` and `wait` children: the engine
    thread's own work a token step. None where the program marks no
    tick phase."""
    span = phase_idle._span(trace)
    emits = [s for s, _ in phase_idle._events(trace, f'{TICK}.emit')]
    waiting = _union(trace, (f'{TICK}.land', f'{TICK}.wait'))
    lengths, j = [], 0
    for s, e in phase_idle._events(trace, TICK):
        while j < len(emits) and emits[j] < s:
            j += 1
        if j < len(emits) and emits[j] < e and \
                (span is None or (s > span[0] and e < span[1])):
            lengths.append(e - s - phase_idle._overlap_ns([(s, e)],
                                                          waiting))
    return statistics.median(lengths) / 1e6 if lengths else None
