"""The idle time of the device cut by the engine's tick phases: synthetic
traces for which every share is known, a trace of a program without the
phases, and one traced run of the tiny engine on the CPU."""
import pytest

import common
import phase_idle
import tiny
import trace_reduce

MS = 1_000_000
IDLE_METRICS = ('idle_in_prefill_pct', 'idle_in_dispatch_pct',
                'idle_in_land_pct', 'idle_in_emit_pct',
                'idle_in_other_pct')


def _trace(ops, host, stretch=None):
    dev = trace_reduce.DeviceTrace(0)
    dev.ops = [(s * MS, e * MS, '%fusion.1') for s, e in ops]
    tr = trace_reduce.Trace(
        {0: dev} if ops else {},
        [(s * MS, e * MS, n) for s, e, n in host])
    if stretch is not None:
        tr.stretch_ns = (stretch[0] * MS, stretch[1] * MS)
    return tr


def _tick(t0, prefill=None):
    """The host events of one 40 ms tick from `t0` (ms): an optional
    prefill chunk, then dispatch 2 ms, land 20 ms, emit 3 ms, and the
    harness's own 20 ms waits, which cover everything and are no
    phase."""
    at = t0 + 1
    out = [(t0, t0 + 40, 'engine.tick'),
           (t0, t0 + 20, '$<unknown> acquire'),
           (t0 + 20, t0 + 40, '$<unknown> acquire')]
    if prefill:
        out.append((at, at + prefill, 'engine.tick.prefill'))
        at += prefill
    out += [(at, at + 2, 'engine.tick.dispatch'),
            (at + 2, at + 22, 'engine.tick.land'),
            (at + 22, at + 25, 'engine.tick.emit')]
    return out


# Two ticks in a stretch of 0-100 ms. Tick 1 (10-50): prefill 11-21 of
# which the device is busy 13-21, dispatch 21-23, land 23-43 with the
# device busy 24-41, emit 43-46. Tick 2 (50-90), no prefill: dispatch
# 51-53, land 53-73 with the device busy 54-71, emit 73-76.
CASE_OPS = [(13, 21), (24, 30), (30, 41), (54, 71)]
CASE_HOST = _tick(10, prefill=10) + _tick(50)
CASE_WANT = {
    'prefill': 2.0,             # 11-13
    'dispatch': 2.0 + 2.0,      # 21-23, 51-53
    'land': 1.0 + 2.0 + 1.0 + 2.0,   # 23-24, 41-43, 53-54, 71-73
    'emit': 3.0 + 3.0,          # 43-46, 73-76
    # 0-11 (before the tick and its first ms), 46-51, 76-100
    'other': 11.0 + 5.0 + 24.0,
}


@pytest.fixture
def case():
    return _trace(CASE_OPS, CASE_HOST, stretch=(0, 100))


@pytest.mark.parametrize('metric', IDLE_METRICS)
def test_idle_share_of_each_phase_is_what_was_drawn(case, metric):
    phase = metric[len('idle_in_'):-len('_pct')]
    ctx = {'trace': case, 'work': {'window_s': case.window_s()}}
    got = common.load_module('metrics', metric).read(ctx)
    assert got == pytest.approx(CASE_WANT[phase])   # % of 100 ms


@pytest.mark.parametrize('stretch', [(0, 100), (12, 72), None])
def test_the_five_shares_add_up_to_the_devices_idle_share(stretch):
    """With the stretch marked, cut through a phase, or not marked at
    all (then it is the span of the device's operations)."""
    tr = _trace(CASE_OPS, CASE_HOST, stretch)
    if stretch is not None:     # what trace_reduce.load does at a mark
        t0, t1 = tr.stretch_ns
        tr.devices[0].ops = trace_reduce._clip(tr.devices[0].ops, t0, t1)
        tr.host_events = trace_reduce._clip(tr.host_events, t0, t1)
    ctx = {'trace': tr, 'work': {'window_s': tr.window_s()}}
    idle = common.load_module('metrics', 'device_idle_pct.serve').read(ctx)
    parts = [common.load_module('metrics', m).read(ctx)
             for m in IDLE_METRICS]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(idle, abs=1e-9)
    assert 0 < idle < 100


def test_tick_metrics_of_the_drawn_case(case):
    ctx = {'trace': case, 'work': {'window_s': case.window_s()}}
    share = common.load_module('metrics', 'tick_prefill_share_pct').read
    p50 = common.load_module('metrics', 'tick_p50_ms').read
    assert share(ctx) == pytest.approx(10.0)
    assert p50(ctx) == pytest.approx(40.0)
    # a tick with no emit (prefill alone, or the engine asleep) and a
    # tick cut by the stretch's edge are not a decoding slot's step
    more = CASE_HOST + [(90, 100, 'engine.tick'),
                        (91, 99, 'engine.tick.wait'),
                        (0, 10, 'engine.tick'),
                        (2, 4, 'engine.tick.emit')]
    tr = _trace(CASE_OPS, more, stretch=(0, 100))
    assert phase_idle.tick_p50_ms(tr) == pytest.approx(40.0)
    tr = _trace(CASE_OPS, CASE_HOST + [(92, 98, 'engine.tick'),
                                       (93, 94, 'engine.tick.emit')],
                stretch=(0, 100))
    assert phase_idle.tick_p50_ms(tr) == pytest.approx(40.0)  # 6 40 40


@pytest.mark.parametrize('metric', IDLE_METRICS + (
    'tick_prefill_share_pct', 'tick_p50_ms'))
def test_a_program_without_the_phases_reads_none(metric):
    """The parent commit's trace: device operations and the host's other
    events, no `engine.tick`. Also a trace with no device at all."""
    host = [e for e in CASE_HOST if not e[2].startswith('engine.tick')]
    read = common.load_module('metrics', metric).read
    for tr in (_trace(CASE_OPS, host, stretch=(0, 100)),
               _trace([], [], None)):
        assert read({'trace': tr, 'work': {'window_s': 0.1}}) is None


def test_the_recorded_chip_trace_has_no_phase_and_reads_none():
    import os
    tr = trace_reduce.load(os.path.join(
        os.path.dirname(__file__), 'data', 'chat_small.xplane.pb'))
    ctx = {'trace': tr, 'work': {'window_s': tr.window_s()}}
    assert phase_idle.idle_split(tr, tr.window_s()) is None
    assert common.load_module('metrics', 'tick_p50_ms').read(ctx) is None
    # what the split would cut: its idle intervals are the idle share
    idle_s = sum(e - s for s, e in phase_idle.idle_intervals(tr)) / 1e9
    assert 100.0 * idle_s / tr.window_s() == pytest.approx(
        tr.idle_pct(tr.window_s()), abs=1e-9)


def test_traced_tiny_run_reads_the_tick_metrics_from_the_program():
    """The harness as it stands, the engine's phases switched on by its
    `tracing.enable()`: on the CPU there is no device plane, so the idle
    shares read None, and the two host-side metrics read."""
    ctx = tiny.ctx(tiny.config(True, 0), tiny.serve_mix(),
                   tiny.SERVE_LIMITS, 2**31 + 11, 1.5, trace=True)
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct']
    rctx = res['reader_ctx']
    names = {n for _, _, n in rctx['trace'].host_events}
    for phase in ('housekeep', 'admit', 'prefill', 'dispatch', 'land',
                  'emit'):
        assert f'engine.tick.{phase}' in names
    read = lambda n: common.load_module('metrics', n).read(rctx)
    assert 0 < read('tick_prefill_share_pct') < 100
    assert read('tick_p50_ms') > 0
    assert read('idle_in_land_pct') is None
    # the phases wrote nothing into the ring the queue waits come from
    assert not [s for s in rctx['spans']
                if s['name'].startswith('engine.tick')]
    assert read('queue_wait_p50_ms') >= 0
