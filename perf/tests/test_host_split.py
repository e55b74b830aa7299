"""The host's own time in a tick and the device's idle time cut by the
tick's sub-phases and the collector's pauses (`perf/host_split.py`):
a drawn trace whose every number is known, traces drawn at random against
a count on a grid of milliseconds, a program without the names, and one
traced run of the tiny engine on the CPU."""
import random

import pytest

import common
import host_split
import phase_idle
import tiny
import trace_reduce

MS = 1_000_000
SPLIT_METRICS = ('idle_in_inputs_pct', 'idle_in_launch_pct',
                 'idle_in_gc_pct')
NEW_METRICS = SPLIT_METRICS + ('host_tick_ms_p50', 'uploads_per_launch')


def _trace(ops, threads, stretch=(0, 100)):
    dev = trace_reduce.DeviceTrace(0)
    dev.ops = [(s * MS, e * MS, '%fusion.1') for s, e in ops]
    tr = trace_reduce.Trace(
        {0: dev} if ops else {},
        {th: [(int(s * MS), int(e * MS), n) for s, e, n in evs]
         for th, evs in threads.items()})
    if stretch is not None:
        tr.stretch_ns = (stretch[0] * MS, stretch[1] * MS)
    return tr


def _read(name, tr, occupancy=None):
    return common.load_module('metrics', name).read(
        {'trace': tr, 'work': {'window_s': tr.window_s()},
         'occupancy': occupancy})


# A stretch of 0-100 ms, the device busy 13-21, 24-41 and 54-71. On the
# engine's thread: tick 1 (10-50) with a chunk 11-21 (inputs 11-12,
# launch 12-14, first token 19-20), a dispatch 21-23 (inputs 21-22,
# launch 22-22.5, queue 22.5-23), land 23-43, emit 43-46, and a
# collection 30-32 while the device is busy; tick 2 (50-75), a dispatch
# 51-53 (inputs 51-52, launch 52-53), land 53-63, emit 63-66; tick 3
# (78-95), an emit 79-80 and the engine asleep 80-90. On another thread
# a collection 44-48, while the device is idle.
ENGINE = [
    (10, 50, 'engine.tick'),
    (11, 21, 'engine.tick.prefill'),
    (11, 12, 'engine.tick.prefill.inputs'),
    (12, 14, 'engine.tick.prefill.launch'),
    (19, 20, 'engine.tick.prefill.first'),
    (21, 23, 'engine.tick.dispatch'),
    (21, 22, 'engine.tick.dispatch.inputs'),
    (22, 22.5, 'engine.tick.dispatch.launch'),
    (22.5, 23, 'engine.tick.dispatch.queue'),
    (23, 43, 'engine.tick.land'),
    (30, 32, 'process.gc'),
    (43, 46, 'engine.tick.emit'),
    (50, 75, 'engine.tick'),
    (51, 53, 'engine.tick.dispatch'),
    (51, 52, 'engine.tick.dispatch.inputs'),
    (52, 53, 'engine.tick.dispatch.launch'),
    (53, 63, 'engine.tick.land'),
    (63, 66, 'engine.tick.emit'),
    (78, 95, 'engine.tick'),
    (79, 80, 'engine.tick.emit'),
    (80, 90, 'engine.tick.wait'),
]
OTHER = [(44, 48, 'process.gc'), (0, 100, '$<unknown> acquire')]
OPS = [(13, 21), (24, 30), (30, 41), (54, 71)]
# idle: 0-13, 21-24, 41-54, 71-100
WANT = {
    'idle_in_inputs_pct': 1.0 + 1.0 + 1.0,     # 11-12, 21-22, 51-52
    'idle_in_launch_pct': 1.0 + 0.5 + 1.0,     # 12-13, 22-22.5, 52-53
    'idle_in_gc_pct': 4.0,                     # 44-48; 30-32 is busy
    'host_tick_ms_p50': 15.0,  # of 40 - 20, 25 - 10 and 17 - 10
}


@pytest.fixture
def case():
    return _trace(OPS, {'engine': ENGINE, 'main': OTHER})


@pytest.mark.parametrize('metric', sorted(WANT))
def test_each_reader_returns_what_was_drawn(case, metric):
    assert _read(metric, case) == pytest.approx(WANT[metric])


def test_uploads_per_launch_is_the_counts_ratio():
    occ = {'host_uploads': 90, 'decode_dispatches': 50,
           'prefill_chunks': 10}
    assert _read('uploads_per_launch', _trace([], {}), occ) == 1.5


def test_a_collection_on_any_thread_is_counted(case):
    """Moved to the engine's thread, or to a third one, the idle pause
    reads the same; taken away, only the busy one is left: 0."""
    for threads in ({'engine': ENGINE + [(44, 48, 'process.gc')]},
                    {'engine': ENGINE, 'x': [(44, 48, 'process.gc')]}):
        tr = _trace(OPS, threads)
        assert _read('idle_in_gc_pct', tr) == pytest.approx(4.0)
    assert _read('idle_in_gc_pct', _trace(OPS, {'engine': ENGINE})) == 0.0


def _drawn(seed):
    """A stretch of 0-200 ms on a grid of whole ms: ticks of a chunk or
    none, a dispatch, a land, an emit, each cut into its sub-phases as the
    program cuts them, device operations at random and collections on
    two threads."""
    rng = random.Random(seed)
    engine, other, ops, at = [], [], [], 2
    while at < 180:
        t0 = at
        if rng.random() < 0.5:
            a, b, c = sorted(rng.sample(range(at + 1, at + 12), 3))
            engine += [(at, c + 1, 'engine.tick.prefill'),
                       (at, a, 'engine.tick.prefill.inputs'),
                       (a, b, 'engine.tick.prefill.launch')]
            if rng.random() < 0.5:
                engine.append((b, c, 'engine.tick.prefill.first'))
            at = c + 1
        a, b = sorted(rng.sample(range(at + 1, at + 6), 2))
        engine += [(at, b + 1, 'engine.tick.dispatch'),
                   (at, a, 'engine.tick.dispatch.inputs'),
                   (a, b, 'engine.tick.dispatch.launch'),
                   (b, b + 1, 'engine.tick.dispatch.queue')]
        at = b + 1
        land = rng.randint(1, 8)
        engine += [(at, at + land, 'engine.tick.land'),
                   (at + land, at + land + 2, 'engine.tick.emit')]
        at += land + 2
        engine.append((t0, at + 1, 'engine.tick'))
        at += 2
    for _ in range(25):
        s = rng.randrange(0, 195)
        ops.append((s, s + rng.randint(1, 6)))
    for _ in range(6):
        s = rng.randrange(0, 196)
        (engine if rng.random() < 0.5 else other).append(
            (s, s + rng.randint(1, 3), 'process.gc'))
    return ops, {'engine': engine, 'main': other}


def _on_grid(ops, threads, names):
    """% of the 200 ms in which no operation ran and some event named in
    `names` was open, counted a millisecond at a time."""
    events = [ev for evs in threads.values() for ev in evs]
    hits = 0
    for t in range(200):
        busy = any(s <= t < e for s, e in ops)
        inside = any(s <= t < e for s, e, n in events if n in names)
        hits += (not busy) and inside
    return 100.0 * hits / 200


@pytest.mark.parametrize('seed', range(8))
def test_drawn_traces_read_as_the_grid_counts(seed):
    ops, threads = _drawn(seed)
    tr = _trace(ops, threads, stretch=(0, 200))
    for metric in SPLIT_METRICS:
        part = metric[len('idle_in_'):-len('_pct')]
        assert _read(metric, tr) == pytest.approx(
            _on_grid(ops, threads, host_split.PARTS[part][0]), abs=1e-9)


@pytest.mark.parametrize('seed', range(8))
def test_inputs_and_launch_lie_inside_dispatch_and_prefill(seed):
    ops, threads = _drawn(seed)
    tr = _trace(ops, threads, stretch=(0, 200))
    ctx = {'trace': tr, 'work': {'window_s': tr.window_s()}}
    split = phase_idle.idle_split(tr, tr.window_s())
    parts = host_split.idle_in(ctx, 'inputs') + \
        host_split.idle_in(ctx, 'launch')
    assert parts <= split['dispatch'] + split['prefill'] + 1e-9
    assert parts > 0


def test_a_sub_phase_counts_only_inside_its_parent():
    """The profiler records no event that began before its session: at
    the stretch's first instant a dispatch under way is lost and its
    inputs kept. They are not counted, so the sum stays within
    `idle_in_dispatch_pct` + `idle_in_prefill_pct`."""
    edge = [(0, 4, 'engine.tick.dispatch.inputs'),
            (4, 5, 'engine.tick.dispatch.launch')]
    tr = _trace(OPS, {'engine': edge + ENGINE, 'main': OTHER})
    assert _read('idle_in_inputs_pct', tr) == pytest.approx(3.0)
    assert _read('idle_in_launch_pct', tr) == pytest.approx(2.5)
    split = phase_idle.idle_split(tr, tr.window_s())
    assert 3.0 + 2.5 <= split['dispatch'] + split['prefill']


def test_a_program_without_the_names_reads_none(case):
    """The parent's trace: the tick phases and no sub-phase, no
    collection, no upload count. Then a trace without any phase."""
    plain = [e for e in ENGINE if e[2].count('.') < 3
             and e[2] != 'process.gc']
    tr = _trace(OPS, {'engine': plain, 'main': OTHER[1:]})
    for metric in SPLIT_METRICS:
        assert _read(metric, tr) is None
    # the tick and its land and wait are the parent's own names
    assert _read('host_tick_ms_p50', tr) == pytest.approx(15.0)
    occ = {'decode_dispatches': 50, 'prefill_chunks': 10}
    assert _read('uploads_per_launch', tr, occ) is None
    for bare in (_trace(OPS, {'main': OTHER}), _trace([], {}, None)):
        for metric in NEW_METRICS:
            assert _read(metric, bare, {}) is None


def test_traced_tiny_run_reads_the_host_split_from_the_program():
    """The harness as it stands, the program's sub-phases and collector
    marks switched on by its `tracing.enable()`: on the CPU there is no
    device plane, so the idle shares read None, and the host's tick and
    the uploads a launch read."""
    ctx = tiny.ctx(tiny.config(True, 0), tiny.serve_mix(),
                   tiny.SERVE_LIMITS, 2**31 + 13, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct']
    rctx = res['reader_ctx']
    names = {n for _, _, n in rctx['trace'].host_events}
    assert set(host_split.SUB_PHASES) - {'engine.tick.prefill.first'} <= \
        names
    assert 'process.gc' in names
    read = lambda n: common.load_module('metrics', n).read(rctx)
    assert 0 < read('host_tick_ms_p50') <= read('tick_p50_ms')
    assert 0 < read('uploads_per_launch') < 10
    for metric in SPLIT_METRICS:
        assert read(metric) is None
