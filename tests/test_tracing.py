"""End-to-end request tracing + flight recorder (ISSUE 14).

Tier-1 (CPU-only, deterministic):

- Tracer core: context mint/parse round-trip (X-SkyTPU-Trace),
  bounded ring with overflow accounting, snapshot windows, Perfetto
  export with per-subsystem track names.
- THE overhead pin (acceptance): with tracing DISABLED a full
  generation — admission, chunked prefill, decode ticks, finish —
  touches neither the tracer's clock nor its record funnel (both
  poisoned to raise), and allocates no span state (`span()` returns
  the shared no-op singleton; `req.trace` stays None).
- Engine span shape: queue_wait/prefill/decode recorded per request
  under an activated context, one trace, parentage intact.
- Flight recorder: a wedged engine's watchdog recovery dumps a
  parseable postmortem (trigger, step_log tail of the wedged world,
  spans) atomically; unwritable dirs degrade to None, never raise.
- Exemplars: a traced request's TTFT observation links the histogram
  to its trace_id (worst-sample-per-window semantics).
- Timeline streaming: events flush in batches, finalize writes one
  loadable JSON with distinct timeline/spans track names.
- skylint trace-discipline: unknown/dynamic span names and stale
  KNOWN_SPANS entries surface on a fixture tree (the real-tree
  zero-findings pin lives in test_skylint).
- `/traces` endpoint + `skytpu trace` rendering helpers.
"""
import dataclasses
import json
import os
import socket
import threading
import time

import pytest
import requests

from skypilot_tpu import exceptions
from skypilot_tpu.observability import exposition
from skypilot_tpu.observability import metrics as obs
from skypilot_tpu.observability import tracing
from skypilot_tpu.utils import fault_injection


@pytest.fixture(autouse=True)
def _tracing_disabled_by_default():
    """Each test starts from the shipped default (tracing off, empty
    ring) and leaves no enablement behind for unrelated tests."""
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _cfg(**kw):
    from skypilot_tpu.models.configs import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


@pytest.fixture(scope='module')
def paged_engine():
    """One warmed paged engine shared by the span-shape tests (engine
    bring-up JIT-compiles — one per module, not per test)."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                      paged_block_size=8,
                                      prefix_cache=4)
    engine.generate([1, 2, 3], max_new_tokens=2, timeout=300)  # compile
    yield engine
    engine.stop()


# ---------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------


class TestTracerCore:

    def test_header_round_trip(self):
        tracing.enable()
        with tracing.span('lb.request') as sp:
            header = tracing.header_value(sp.ctx)
            assert header.startswith('00-') and header.endswith('-01')
            ctx = tracing.parse_header(header)
            assert ctx.trace_id == sp.ctx.trace_id
            assert ctx.span_id == sp.ctx.span_id

    @pytest.mark.parametrize('garbage', [
        None, '', 'nonsense', '00-xyz-abc-01',
        '00-' + 'a' * 31 + '-' + 'b' * 16 + '-01',   # short trace id
        '00-' + 'a' * 32 + '-' + 'g' * 16 + '-01',   # non-hex span id
        '00-' + 'a' * 32 + '-' + 'b' * 16,           # missing flags
    ])
    def test_parse_garbage_header_is_none(self, garbage):
        assert tracing.parse_header(garbage) is None

    def test_parent_resolution_explicit_ambient_minted(self):
        tracing.enable()
        root = tracing.start_span('lb.request')
        # Ambient: a span inside `with` parents to it.
        with root:
            with tracing.span('lb.route') as child:
                assert child.ctx.trace_id == root.ctx.trace_id
        root.end()
        # Explicit parent beats ambient.
        other = tracing.record_span('engine.queue_wait', 0.0, 1.0,
                                    parent=root.ctx)
        assert other.trace_id == root.ctx.trace_id
        # No parent anywhere: a fresh trace is minted.
        minted = tracing.record_span('engine.queue_wait', 0.0, 1.0)
        assert minted.trace_id != root.ctx.trace_id
        spans = {s['span_id']: s for s in tracing.snapshot()}
        assert spans[minted.span_id]['parent_id'] is None

    def test_ring_is_bounded_and_counts_drops(self, monkeypatch):
        import collections
        tracing.enable()
        obs.enable()
        monkeypatch.setattr(tracing, '_ring',
                            collections.deque(maxlen=8))
        dropped_before = tracing._SPANS_DROPPED.value()
        for i in range(20):
            tracing.record_span('engine.queue_wait', 0.0, 1.0)
        spans = tracing.snapshot()
        assert len(spans) == 8
        assert tracing._SPANS_DROPPED.value() - dropped_before == 12
        obs.disable()

    def test_snapshot_window_filters_old_spans(self):
        tracing.enable()
        now = tracing.now()
        tracing.record_span('engine.queue_wait', now - 100.0,
                            now - 99.0)
        tracing.record_span('engine.queue_wait', now - 1.0, now)
        assert len(tracing.snapshot()) == 2
        assert len(tracing.snapshot(window_s=30.0)) == 1

    def test_disabled_record_span_returns_none(self):
        assert tracing.record_span('engine.queue_wait', 0.0, 1.0) \
            is None
        assert tracing.snapshot() == []

    def test_span_exit_records_error_attr(self):
        tracing.enable()
        with pytest.raises(ValueError):
            with tracing.span('lb.request'):
                raise ValueError('boom')
        (span,) = tracing.snapshot()
        assert 'ValueError: boom' in span['attrs']['error']

    def test_perfetto_events_have_subsystem_tracks(self):
        tracing.enable()
        with tracing.span('lb.request'):
            pass
        tracing.record_span('engine.queue_wait', 0.0, 1.0)
        events = tracing.perfetto_events()
        meta = [e for e in events if e['ph'] == 'M']
        names = {e['args']['name'] for e in meta}
        assert names == {'spans:lb', 'spans:engine'}
        complete = [e for e in events if e['ph'] == 'X']
        assert len(complete) == 2
        # lb and engine spans land on DIFFERENT synthetic tracks.
        assert len({e['tid'] for e in complete}) == 2


# ---------------------------------------------------------------------
# the disabled fast path (acceptance-pinned)
# ---------------------------------------------------------------------


def _poisoned(*_a, **_k):
    raise AssertionError('disabled-path tracing touched the tracer '
                         '(clock read or span record)')


class TestDisabledOverhead:

    def test_disabled_generation_reads_no_tracer_clock(
            self, paged_engine, monkeypatch):
        """THE pin: with tracing disabled, a full generation —
        admission, chunked prefill, decode ticks, finish — never calls
        the tracer's clock or record funnel and allocates no span
        state. Every engine hook must guard BEFORE touching either."""
        assert not tracing.enabled()
        monkeypatch.setattr(tracing, '_now', _poisoned)
        monkeypatch.setattr(tracing, '_record', _poisoned)
        out, stats = paged_engine.generate([9, 10, 11, 12],
                                           max_new_tokens=4,
                                           timeout=300)
        assert len(out) == 4
        assert stats['ttft_s'] >= 0
        assert tracing.snapshot() == []

    def test_disabled_span_is_the_shared_noop_singleton(self):
        assert tracing.span('lb.request') is tracing.NULL_SPAN
        assert tracing.start_span('lb.route') is tracing.NULL_SPAN
        assert tracing.NULL_SPAN.ctx is None
        # The no-op handle absorbs the full handle surface.
        with tracing.span('lb.request') as sp:
            sp.set_attr('k', 'v')
        sp.end(outcome='ok')
        assert tracing.current() is None

    def test_disabled_submit_leaves_request_untraced(self, paged_engine):
        future = paged_engine.submit([5, 6, 7], max_new_tokens=2)
        future.result(timeout=300)
        # No header/context capture happened (one enabled-check).
        assert tracing.snapshot() == []


# ---------------------------------------------------------------------
# engine span shape
# ---------------------------------------------------------------------


class TestEngineSpans:

    def test_request_spans_one_trace_full_parentage(self, paged_engine):
        tracing.enable()
        tracing.reset()
        root = tracing.start_span('lb.request')
        with tracing.activate(root.ctx):
            out, stats = paged_engine.generate(
                list(range(20, 44)), max_new_tokens=4, timeout=300)
        root.end()
        assert len(out) == 4
        spans = tracing.snapshot()
        by_name = {}
        for s in spans:
            by_name.setdefault(s['name'], []).append(s)
        for name in ('engine.queue_wait', 'engine.prefill',
                     'engine.decode'):
            assert name in by_name, sorted(by_name)
        assert len({s['trace_id'] for s in spans}) == 1
        root_span = by_name['lb.request'][0]
        for name in ('engine.queue_wait', 'engine.prefill',
                     'engine.decode'):
            (span,) = by_name[name]
            assert span['parent_id'] == root_span['span_id']
            assert span['dur_us'] >= 0
        prefill = by_name['engine.prefill'][0]
        assert prefill['attrs']['prompt_tokens'] == 24
        assert prefill['attrs']['ttft_s'] == pytest.approx(
            stats['ttft_s'], rel=0.5)
        decode = by_name['engine.decode'][0]
        assert decode['attrs']['new_tokens'] == 4
        assert 'slot' in decode['attrs']

    def test_ttft_exemplar_links_to_trace(self, paged_engine):
        tracing.enable()
        obs.enable()
        tracing.reset()
        root = tracing.start_span('lb.request')
        with tracing.activate(root.ctx):
            paged_engine.generate([30, 31, 32], max_new_tokens=2,
                                  timeout=300)
        root.end()
        exemplars = exposition.collect_exemplars()
        assert 'skytpu_engine_ttft_seconds' in exemplars
        ex = exemplars['skytpu_engine_ttft_seconds']
        assert ex['trace_id'] == root.ctx.trace_id
        assert ex['value'] > 0
        obs.disable()

    def test_untraced_requests_record_nothing_while_enabled(
            self, paged_engine):
        """Tracing enabled but no ambient context: direct engine use
        stays span-free (the server/LB mint contexts; bare engine
        callers do not pollute the ring)."""
        tracing.enable()
        tracing.reset()
        paged_engine.generate([40, 41, 42], max_new_tokens=2,
                              timeout=300)
        assert tracing.snapshot() == []


# ---------------------------------------------------------------------
# tick phases (`tracing.phase`) and compile spans
# ---------------------------------------------------------------------

PHASE_CHILDREN = ('housekeep', 'admit', 'prefill', 'dispatch', 'land',
                  'emit', 'wait')


@pytest.fixture(scope='class')
def phase_run(paged_engine):
    """One generation on the warmed paged engine with `TraceAnnotation`
    replaced by a recorder, then the engine left to idle until it has
    slept once. Yields (events, totals, ring): events are (name,
    start_ns, end_ns, thread) in order of exit."""
    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            self.start = time.perf_counter_ns()
            return self

        def __exit__(self, *exc):
            events.append((self.name, self.start, time.perf_counter_ns(),
                           threading.get_ident()))

    # enable() first: it binds jax's own TraceAnnotation, so that what
    # this fixture restores is the real one and not "unbound".
    tracing.enable()
    tracing.reset()
    real, tracing._annotation = tracing._annotation, Recorder
    try:
        out, _ = paged_engine.generate(list(range(1, 21)),
                                       max_new_tokens=4, timeout=300)
        assert len(out) == 4
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
                e[0] == 'engine.tick.wait' for e in list(events)):
            time.sleep(0.02)
        totals, ring = tracing.phase_totals(), tracing.snapshot()
    finally:
        tracing._annotation = real
        tracing.disable()
    yield list(events), totals, ring


def _whole_ticks(events):
    """(ticks, children): the recorded `engine.tick` spans, and the
    child spans that lie between the first one's start and the last
    one's end (a tick under way when recording began or ended has
    children and no recorded parent)."""
    ticks = [e for e in events if e[0] == 'engine.tick']
    assert ticks
    first, last = min(t[1] for t in ticks), max(t[2] for t in ticks)
    children = [e for e in events if e[0].startswith('engine.tick.')
                and e[1] >= first and e[2] <= last]
    return ticks, children


class TestTickPhases:

    @pytest.mark.parametrize('child', PHASE_CHILDREN)
    def test_child_phase_sits_inside_a_tick(self, phase_run, child):
        ticks, children = _whole_ticks(phase_run[0])
        mine = [e for e in children if e[0] == f'engine.tick.{child}']
        assert mine, f'no engine.tick.{child} recorded'
        for _, start, end, thread in mine:
            assert any(t[1] <= start and end <= t[2] and t[3] == thread
                       for t in ticks)

    def test_children_tile_a_tick_without_overlap(self, phase_run):
        _, children = _whole_ticks(phase_run[0])
        spans = sorted((e[1], e[2], e[0]) for e in children)
        for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
            assert end <= start, f'{a} overlaps {b}'
        # one thread only: the names are the engine thread's own
        assert len({e[3] for e in children}) == 1

    def test_land_comes_before_emit_in_a_tick(self, phase_run):
        """Every emit follows a land of the same tick, and a tick that
        queues a step queues it before it waits on anything (the last
        tick of a request only lands: no step is queued for a slot
        known to be done)."""
        ticks, children = _whole_ticks(phase_run[0])
        decoded = dispatched = 0
        for _, t0, t1, _ in ticks:
            inside = sorted((e[1], e[0]) for e in children
                            if t0 <= e[1] and e[2] <= t1)
            names = [n.rsplit('.', 1)[1] for _, n in inside]
            if 'emit' not in names:
                continue
            decoded += 1
            assert names.index('land') < names.index('emit')
            if 'dispatch' in names:
                dispatched += 1
                assert names.index('dispatch') < names.index('land')
        assert decoded >= 3     # four tokens, the first sent by its own
        assert dispatched >= 2  # emit, behind the step queued after it

    def test_totals_hold_every_phase_and_the_ring_none(self, phase_run):
        _, totals, ring = phase_run
        for name in ('engine.tick',) + tuple(
                f'engine.tick.{c}' for c in PHASE_CHILDREN):
            assert name in tracing.KNOWN_SPANS
            assert totals[name]['count'] >= 1
            assert totals[name]['seconds'] >= 0
        # each child's seconds lie inside the ticks'
        assert sum(v['seconds'] for k, v in totals.items()
                   if k != 'engine.tick') <= \
            totals['engine.tick']['seconds'] + 0.1
        assert not [s for s in ring
                    if s['name'].startswith('engine.tick')]

    def test_disabled_phase_is_the_noop_and_totals_stay_empty(
            self, paged_engine):
        assert tracing.phase('engine.tick') is tracing.NULL_SPAN
        # a tick the idle engine entered while an earlier test had
        # tracing on still lands its totals when its 50 ms wait ends
        time.sleep(0.15)
        tracing.reset()
        paged_engine.generate([9, 8, 7], max_new_tokens=2, timeout=300)
        assert tracing.phase_totals() == {}

    def test_flight_extra_carries_the_phase_totals(self, paged_engine):
        tracing.enable()
        paged_engine.generate([3, 2, 1], max_new_tokens=2, timeout=300)
        extra = paged_engine._flight_extra('test')
        assert extra['phase_totals']['engine.tick.dispatch']['count'] >= 1
        json.dumps(extra)       # a flight record is JSON
        lines = tracing.render_flight_record(
            {'trigger': 'x', 'extra': extra, 'spans': []})
        assert any('phase_totals' in line for line in lines)

    def test_phase_lands_in_the_profilers_host_plane(self, paged_engine,
                                                     tmp_path):
        """The real TraceAnnotation under a real profiler session, on
        the CPU: the phase is an event of a host plane of the
        .xplane.pb, where the device's events would be beside it."""
        import glob
        import jax
        from jax.profiler import ProfileData
        tracing.enable()
        with jax.profiler.trace(str(tmp_path)):
            paged_engine.generate([5, 4, 3, 2], max_new_tokens=3,
                                  timeout=300)
        (path,) = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*'
                                / '*.xplane.pb'))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith('/host:'):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith('engine.tick'):
                        found[ev.name] = found.get(ev.name, 0) + 1
                        assert ev.duration_ns > 0
        assert found.get('engine.tick.dispatch', 0) >= 2, found
        assert 'engine.tick' in found and 'engine.tick.land' in found

    def test_tracing_stays_free_of_jax_until_jax_is_there(self):
        """enable() and a phase in a process that never imported jax:
        nothing imports it (the LB and the controller import this
        module), the phase still counts."""
        import subprocess
        import sys
        code = (
            'import sys\n'
            'from skypilot_tpu.observability import tracing\n'
            'tracing.enable()\n'
            'with tracing.phase("engine.tick"):\n'
            '    pass\n'
            'assert tracing.phase_totals()["engine.tick"]["count"] == 1\n'
            'assert "jax" not in sys.modules, "jax was imported"\n'
            'print("CLEAN")\n')
        out = subprocess.run(
            [sys.executable, '-c', code], capture_output=True, text=True,
            timeout=300,
            cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stderr
        assert 'CLEAN' in out.stdout


class TestCompileSpans:

    def test_a_compile_is_one_ring_span_only_while_tracing_is_on(self):
        import jax
        import jax.numpy as jnp
        arg = jax.ShapeDtypeStruct((7,), jnp.float32)
        tracing.enable()
        tracing.reset()
        jax.jit(lambda x: x * 3.25 + 1).lower(arg).compile()
        spans = [s for s in tracing.snapshot()
                 if s['name'] == 'engine.compile']
        assert len(spans) == 1
        assert spans[0]['attrs']['seconds'] > 0
        assert spans[0]['dur_us'] == pytest.approx(
            spans[0]['attrs']['seconds'] * 1e6, rel=1e-3)
        tracing.disable()
        tracing.reset()
        jax.jit(lambda x: x * 4.5 - 2).lower(arg).compile()
        assert tracing.snapshot() == []


# ---------------------------------------------------------------------
# handoff chunk context propagation (unit level; the live-HTTP 2-hop
# round trip is tests/test_chaos.py::TestDisaggHandoff)
# ---------------------------------------------------------------------


class TestChunkTracePropagation:

    @pytest.fixture(scope='class')
    def tiered_pair(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        pre = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                       paged_block_size=8,
                                       prefix_cache=4, tier='prefill')
        dec = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                       paged_block_size=8,
                                       prefix_cache=4, tier='decode')
        yield pre, dec
        pre.stop()
        dec.stop()

    def test_ingest_spans_join_the_sender_trace(self, tiered_pair):
        pre, dec = tiered_pair
        ids = list(range(50, 74))
        pre.prefill_prefix(ids, timeout=300)
        tracing.enable()
        tracing.reset()
        root = tracing.start_span('server.kv_push')
        chunks = pre.export_prefix_chunks(
            ids, 'trace-s1', chunk_blocks=1,
            trace_header=tracing.header_value(root.ctx))
        root.end()
        for chunk in chunks:
            result = dec.ingest_chunk(chunk)
        assert result['final'] and result['imported_blocks'] == 3
        # (a first-time compile on the way is a span of its own trace)
        spans = [s for s in tracing.snapshot()
                 if s['name'] != 'engine.compile']
        names = [s['name'] for s in spans]
        assert names.count('engine.ingest_chunk') == 3
        assert names.count('engine.ingest_publish') == 1
        for span in spans:
            assert span['trace_id'] == root.ctx.trace_id
            if span['name'].startswith('engine.ingest'):
                assert span['parent_id'] == root.ctx.span_id

    def test_chunk_without_trace_ingests_untraced(self, tiered_pair):
        pre, dec = tiered_pair
        ids = list(range(80, 104))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'trace-s2',
                                          chunk_blocks=1)
        tracing.enable()
        tracing.reset()
        for chunk in chunks:
            dec.ingest_chunk(chunk)
        assert tracing.snapshot() == []

    def test_corrupt_trace_header_in_chunk_is_ignored(self, tiered_pair):
        """A garbled trace id must never refuse a valid chunk — the
        context is outside the CRC and parse failures mean
        no-context."""
        pre, dec = tiered_pair
        ids = list(range(110, 134))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'trace-s3',
                                          chunk_blocks=4,
                                          trace_header='garbage!!')
        tracing.enable()
        tracing.reset()
        result = dec.ingest_chunk(chunks[0])
        assert result['final']
        assert tracing.snapshot() == []


# ---------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------


class TestFlightRecorder:

    def test_wedge_recovery_dumps_postmortem(self, tmp_path,
                                             monkeypatch):
        """Acceptance: a wedged engine's watchdog recovery leaves a
        flight record that exists, parses, and contains the wedged
        world (step_log tail, the occupied slot, the why)."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        monkeypatch.setenv('SKYTPU_FLIGHT_DIR', str(tmp_path))
        tracing.enable()
        engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                          watchdog_timeout=1.0)
        engine.generate([1, 2, 3], max_new_tokens=2,
                        timeout=300)  # compile + step_log entries
        tracing.reset()
        fault_injection.arm('engine.decode', 'wedge')
        try:
            future = engine.submit([4, 5, 6], max_new_tokens=4)
            with pytest.raises(exceptions.EngineWedgedError):
                future.result(timeout=120)
        finally:
            fault_injection.disarm_all()
        engine.stop()
        records = sorted(tmp_path.glob('flight-wedge_recovery-*.json'))
        assert records, list(tmp_path.iterdir())
        with open(records[0], encoding='utf-8') as f:
            record = json.load(f)
        assert record['schema'] == tracing.FLIGHT_SCHEMA
        assert record['trigger'] == 'wedge_recovery'
        extra = record['extra']
        assert 'no progress' in extra['why'] or 'died' in extra['why']
        assert extra['generation'] == 1
        assert extra['step_log'], 'wedged ticks missing from the dump'
        assert extra['active_slots'] == [0]  # the wedged request
        assert isinstance(record['spans'], list)
        # No torn temp files left behind (atomic publish).
        assert not list(tmp_path.glob('*.tmp'))
        # The recovery also left a span in the ring.
        names = [s['name'] for s in tracing.snapshot()]
        assert 'engine.wedge_recovery' in names
        # ... and the renderer understands the record.
        lines = tracing.render_flight_record(record)
        assert any('trigger=wedge_recovery' in line for line in lines)

    def test_flight_record_without_tracing_or_dir_is_noop(self):
        assert not tracing.enabled()
        assert os.environ.get('SKYTPU_FLIGHT_DIR') is None
        assert tracing.flight_record('tick_failure') is None

    def test_flight_record_unwritable_dir_degrades(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_FLIGHT_DIR',
                           '/proc/definitely/not/writable')
        assert tracing.flight_record('tick_failure',
                                     extra={'why': 'x'}) is None

    def test_flight_dir_only_records_engine_state_without_spans(
            self, tmp_path, monkeypatch):
        """SKYTPU_FLIGHT_DIR alone (tracing off) still captures the
        engine state — better than nothing on a wedge."""
        monkeypatch.setenv('SKYTPU_FLIGHT_DIR', str(tmp_path))
        assert not tracing.enabled()
        path = tracing.flight_record('preempt_notice',
                                     extra={'budget_s': 5})
        assert path is not None
        with open(path, encoding='utf-8') as f:
            record = json.load(f)
        assert record['spans'] == []
        assert record['extra']['budget_s'] == 5


# ---------------------------------------------------------------------
# exemplars (metrics layer)
# ---------------------------------------------------------------------


class TestExemplars:

    def test_worst_sample_per_window_wins(self):
        obs.enable()
        registry = obs.Registry()
        hist = obs.histogram('exemplar_h', 'help', registry=registry)
        hist.observe(0.2, exemplar='trace-a')
        hist.observe(0.9, exemplar='trace-b')   # worse: takes over
        hist.observe(0.5, exemplar='trace-c')   # better: ignored
        hist.observe(0.4)                       # untraced: no effect
        value, trace_id, _stamp = hist.exemplar()
        assert (value, trace_id) == (0.9, 'trace-b')
        ex = exposition.collect_exemplars(registry)
        assert ex['exemplar_h']['trace_id'] == 'trace-b'
        obs.disable()

    def test_disabled_observe_keeps_no_exemplar(self):
        obs.disable()
        registry = obs.Registry()
        hist = obs.histogram('exemplar_off', 'help', registry=registry)
        hist.observe(0.5, exemplar='trace-x')
        assert hist.exemplar() is None
        assert exposition.collect_exemplars(registry) == {}


# ---------------------------------------------------------------------
# timeline streaming (satellite)
# ---------------------------------------------------------------------


class TestTimelineStreaming:

    @pytest.fixture()
    def fresh_timeline(self, tmp_path, monkeypatch):
        from skypilot_tpu.utils import timeline
        path = str(tmp_path / 'timeline.json')
        monkeypatch.setenv('SKYTPU_TIMELINE_FILE', path)
        monkeypatch.setattr(timeline, '_enabled', True)
        monkeypatch.setattr(timeline, '_events', [])
        monkeypatch.setattr(timeline, '_tids_seen', set())
        monkeypatch.setattr(timeline, '_sink',
                            {'path': None, 'wrote_any': False,
                             'finalized': False})
        return timeline, path

    def test_streamed_append_bounds_memory(self, fresh_timeline):
        """The O(n)-per-save regression: recording N >> flush-batch
        events keeps at most one batch in memory (flushed to disk
        incrementally), and finalize produces ONE loadable JSON."""
        timeline, path = fresh_timeline
        total = timeline._FLUSH_EVERY * 2 + 100
        for i in range(total // 2):
            with timeline.Event(f'e{i}'):
                pass
        assert len(timeline._events) < timeline._FLUSH_EVERY
        assert os.path.exists(path)  # flushed mid-stream
        flushed_size = os.path.getsize(path)
        assert flushed_size > 0
        timeline.save_timeline()
        with open(path, encoding='utf-8') as f:
            data = json.load(f)
        assert len([e for e in data['traceEvents']
                    if e.get('ph') in 'BE']) == 2 * (total // 2)
        assert data['displayTimeUnit'] == 'ms'

    def test_finalize_merges_span_and_timeline_tracks(
            self, fresh_timeline):
        timeline, path = fresh_timeline
        tracing.enable()
        with tracing.span('engine.prefill'):
            pass
        with timeline.Event('t'):
            pass
        timeline.save_timeline()
        with open(path, encoding='utf-8') as f:
            data = json.load(f)
        meta_names = {e['args']['name'] for e in data['traceEvents']
                      if e.get('ph') == 'M'}
        assert any(n.startswith('timeline:') for n in meta_names)
        assert 'spans:engine' in meta_names
        spans = [e for e in data['traceEvents'] if e.get('ph') == 'X']
        assert spans and spans[0]['name'] == 'engine.prefill'

    def test_finalize_is_once(self, fresh_timeline):
        timeline, path = fresh_timeline
        with timeline.Event('t'):
            pass
        timeline.save_timeline()
        size = os.path.getsize(path)
        timeline.save_timeline()   # second call must not corrupt
        assert os.path.getsize(path) == size
        with open(path, encoding='utf-8') as f:
            json.load(f)

    def test_record_after_finalize_never_corrupts(self, fresh_timeline):
        """Events recorded after finalize are dropped, not appended
        past the closing JSON tail — even once they exceed the flush
        batch (the auto-flush path must honor the finalized flag)."""
        timeline, path = fresh_timeline
        with timeline.Event('t'):
            pass
        timeline.save_timeline()
        size = os.path.getsize(path)
        for i in range(timeline._FLUSH_EVERY + 10):
            with timeline.Event(f'late{i}'):
                pass
        assert os.path.getsize(path) == size
        with open(path, encoding='utf-8') as f:
            json.load(f)   # still ONE valid JSON document


# ---------------------------------------------------------------------
# skylint trace-discipline (fixture tree; real-tree pin: test_skylint)
# ---------------------------------------------------------------------


_FIXTURE_TRACING = '''
KNOWN_SPANS = (
    'engine.known',
    'engine.dead',
)

def span(name, parent=None, attrs=None):
    return None

def start_span(name, parent=None, attrs=None):
    return None

def record_span(name, start, end, parent=None, attrs=None):
    return None
'''

_FIXTURE_USER = '''
from fixpkg import tracing

def f(name):
    tracing.span('engine.known')
    tracing.start_span('engine.unknown')
    tracing.record_span(name, 0.0, 1.0)
'''


class TestTraceDisciplineChecker:

    def _run(self, tmp_path):
        from skypilot_tpu.analysis import drift
        from skypilot_tpu.analysis.core import ProjectTree
        root = tmp_path / 'fixpkg'
        root.mkdir()
        (root / '__init__.py').write_text('')
        (root / 'tracing.py').write_text(_FIXTURE_TRACING)
        (root / 'user.py').write_text(_FIXTURE_USER)
        tree = ProjectTree(str(root))
        return drift.TraceDisciplineChecker().run(tree)

    def test_fixture_findings(self, tmp_path):
        findings = self._run(tmp_path)
        messages = [f.message for f in findings]
        assert any('unregistered span name' in m and 'engine.unknown'
                   in m for m in messages)
        assert any('not a string literal' in m for m in messages)
        assert any('engine.dead' in m and 'no call site' in m
                   for m in messages)
        # 'engine.known' is clean: literal, registered, has a site.
        assert not any("'engine.known'" in m for m in messages)

    def test_phase_sites_and_the_tracers_own_sites_count(self, tmp_path):
        """`phase(` is a call site like `span(`, a dotted phase name
        is read from the doc catalog whole, and a span the tracer
        records itself (its compile listener) is a live site."""
        from skypilot_tpu.analysis import drift
        from skypilot_tpu.analysis.core import ProjectTree
        root = tmp_path / 'fixpkg'
        root.mkdir()
        (root / '__init__.py').write_text('')
        (root / 'tracing.py').write_text(
            "KNOWN_SPANS = ('engine.tick', 'engine.tick.emit',\n"
            "               'engine.compile')\n"
            'def phase(name):\n    return None\n'
            'def record_span(name, start, end):\n    return None\n'
            'def listener():\n'
            "    record_span('engine.compile', 0.0, 1.0)\n")
        (root / 'user.py').write_text(
            'from fixpkg import tracing\n'
            'def f():\n'
            "    with tracing.phase('engine.tick'):\n"
            "        with tracing.phase('engine.tick.emit'):\n"
            "            tracing.phase('engine.tick.nowhere')\n")
        tree = ProjectTree(str(root))
        sites = {name for name, _, _ in drift.collect_span_sites(tree)}
        assert sites == {'engine.tick', 'engine.tick.emit',
                         'engine.tick.nowhere', 'engine.compile'}
        messages = [f.message
                    for f in drift.TraceDisciplineChecker().run(tree)]
        assert len(messages) == 1 and 'engine.tick.nowhere' in messages[0]
        assert drift._DOC_SPAN_ROW_RE.match(
            '| `engine.tick.emit` | engine |').group(1) == \
            'engine.tick.emit'
        assert drift._DOC_SPAN_ROW_RE.match(
            '| `lb.route` | LB |').group(1) == 'lb.route'

    def test_no_tracing_module_skips(self, tmp_path):
        from skypilot_tpu.analysis import drift
        from skypilot_tpu.analysis.core import ProjectTree
        root = tmp_path / 'plainpkg'
        root.mkdir()
        (root / '__init__.py').write_text('')
        (root / 'mod.py').write_text('X = 1\n')
        assert drift.TraceDisciplineChecker().run(
            ProjectTree(str(root))) == []

    def test_known_spans_table_matches_doc_catalog(self):
        """Thin wrapper over the real-tree direction checks: every
        KNOWN_SPANS entry appears in the docs/observability.md span
        catalog (the full zero-findings pin is test_skylint's)."""
        import skypilot_tpu
        doc = os.path.join(
            os.path.dirname(os.path.dirname(skypilot_tpu.__file__)),
            'docs', 'observability.md')
        if not os.path.exists(doc):
            pytest.skip('docs tree not present')
        with open(doc, encoding='utf-8') as f:
            text = f.read()
        for name in tracing.KNOWN_SPANS:
            assert f'`{name}`' in text, (
                f'span {name!r} missing from the observability.md '
                f'span catalog')


# ---------------------------------------------------------------------
# /traces endpoint + rendering
# ---------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(('', 0))
        return sock.getsockname()[1]


class TestTracesEndpoint:

    @pytest.fixture(scope='class')
    def server_url(self, paged_engine):
        import asyncio
        from aiohttp import web
        from skypilot_tpu.serve.server import InferenceServer
        server = InferenceServer.__new__(InferenceServer)
        server.engine = paged_engine
        server.tokenizer_kind = 'byte'
        server._hf_tokenizer = None  # pylint: disable=protected-access
        server.ready = True
        server.request_timeout = 0.0
        server.draining = False
        port = _free_port()

        def serve():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            runner = web.AppRunner(server.make_app())
            loop.run_until_complete(runner.setup())
            loop.run_until_complete(
                web.TCPSite(runner, '127.0.0.1', port).start())
            loop.run_forever()

        threading.Thread(target=serve, daemon=True).start()
        url = f'http://127.0.0.1:{port}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                requests.get(url + '/health', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)
        return url

    def test_traces_endpoint_spans_and_schema(self, server_url):
        tracing.enable()
        tracing.reset()
        resp = requests.post(
            server_url + '/generate',
            json={'prompt_ids': [[60, 61, 62]], 'max_new_tokens': 2},
            timeout=300)
        assert resp.status_code == 200, resp.text
        data = requests.get(server_url + '/traces', timeout=30).json()
        assert data['schema'] == 'skytpu-traces/1'
        assert data['enabled'] is True
        names = {s['name'] for s in data['spans']}
        # A header-less POST minted its own trace on the server.
        assert {'server.request', 'engine.queue_wait',
                'engine.prefill', 'engine.decode'} <= names
        req_spans = [s for s in data['spans']
                     if s['name'] == 'server.request']
        assert any(s['attrs'].get('route') == '/generate'
                   for s in req_spans)

    def test_traces_endpoint_window_and_validation(self, server_url):
        tracing.enable()
        data = requests.get(server_url + '/traces?window_s=0.000001',
                            timeout=30).json()
        assert data['spans'] == [] or all(
            isinstance(s, dict) for s in data['spans'])
        resp = requests.get(server_url + '/traces?window_s=bogus',
                            timeout=30)
        assert resp.status_code == 400

    def test_untraced_get_does_not_pollute_ring(self, server_url):
        tracing.enable()
        tracing.reset()
        requests.get(server_url + '/health', timeout=30)
        requests.get(server_url + '/metrics', timeout=30)
        assert tracing.snapshot() == []


class TestRendering:

    def test_render_trace_tree_nests_and_greps(self):
        tracing.enable()
        with tracing.span('lb.request', attrs={'path': '/generate'}):
            with tracing.span('lb.route', attrs={'result': 'hit'}):
                pass
        with tracing.span('server.request', attrs={'route': '/other'}):
            pass
        lines = tracing.render_trace_tree(tracing.snapshot())
        text = '\n'.join(lines)
        assert text.count('trace ') == 2
        route_line = next(l for l in lines if 'lb.route' in l)
        request_line = next(l for l in lines if 'lb.request' in l)
        assert (len(route_line) - len(route_line.lstrip()) >
                len(request_line) - len(request_line.lstrip()))
        only = tracing.render_trace_tree(tracing.snapshot(),
                                         grep='result=hit')
        assert 'lb.route' in '\n'.join(only)
        assert '/other' not in '\n'.join(only)

    def test_orphan_parent_renders_at_root(self):
        tracing.enable()
        remote = tracing.SpanContext('ab' * 16, 'cd' * 8)
        tracing.record_span('engine.queue_wait', 0.0, 1.0,
                            parent=remote)
        lines = tracing.render_trace_tree(tracing.snapshot())
        assert any('engine.queue_wait' in line for line in lines)
