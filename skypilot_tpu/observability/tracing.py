"""End-to-end request tracing + flight recorder (docs/observability.md
"Tracing").

Aggregate histograms (``metrics.py``) answer "how is the fleet doing";
this module answers "where did THIS request's milliseconds go" across
the disaggregated serving path — LB routing decision → prefill replica
→ per-chunk KV stream → decode replica ingest → decode ticks — and
"what was the engine doing in the seconds before" a wedge recovery or
preemption (the flight recorder).

Design constraints (all pinned by tests/test_tracing.py):

- **Zero-dependency, zero-cost when disabled.** Recording is off by
  default behind ONE module-level boolean (the metrics/fault_injection
  disarmed-check pattern). With tracing disabled the decode tick pays
  no span allocation and no clock reads — per-request span state is
  ``None`` so the per-tick guard is a plain identity check; ``span()``
  returns one shared no-op handle. Every internal clock read funnels
  through ``_now`` so the overhead test can poison it.
- **Bounded memory.** Spans land in an in-process ring
  (``SKYTPU_TRACE_RING``, default 8192 spans); overflow drops the
  OLDEST span and counts ``skytpu_trace_spans_dropped_total``. A serve
  replica tracing for weeks holds a fixed-size window, which is
  exactly what the flight recorder wants anyway.
- **Context is explicit OR ambient, never guessed.** The ambient
  current span is a ``contextvars.ContextVar`` — correct across
  asyncio tasks (two interleaved aiohttp requests cannot
  cross-contaminate) and across threads (each engine/executor thread
  sees only what it ``activate()``d). Async proxy code (the LB)
  threads explicit ``SpanContext`` objects instead.

Wire format (the ``X-SkyTPU-Trace`` header, traceparent-style):

    00-<32 hex trace_id>-<16 hex span_id>-01

The LB mints a trace per proxied request and forwards the header on
every upstream call (including ``/kv/prefill``); the server middleware
continues it; ``pack_kv_chunk`` carries it inside the chunk header so
the decode replica's ingest spans join the same trace.

Span names are a CLOSED vocabulary: every ``span(...)`` /
``start_span(...)`` / ``record_span(...)`` / ``phase(...)`` call site
must use a literal name registered in ``KNOWN_SPANS`` and cataloged in
docs/observability.md — skylint's ``trace-discipline`` checker holds
both directions (the KNOWN_POINTS drift-lint pattern).
"""
from __future__ import annotations

import collections
import contextvars
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu.observability import metrics as _metrics

# ---------------------------------------------------------------------
# enable/disable (the one boolean every recording call checks first)
# ---------------------------------------------------------------------

_enabled = False

TRACE_HEADER = 'X-SkyTPU-Trace'

# The closed span-name vocabulary (skylint trace-discipline: every
# entry has a literal call site, every call site uses an entry, and
# docs/observability.md catalogs each — both directions).
KNOWN_SPANS = (
    # Load balancer (serve/load_balancer.py)
    'lb.request',          # one proxied client request, root of the trace
    'lb.route',            # policy decision (result/phase/skip reasons)
    'lb.proxy',            # one upstream attempt (replica, attempt #)
    'lb.handoff',          # whole prefill→decode KV handoff orchestration
    'lb.handoff_attempt',  # one prefill-replica attempt within a handoff
    # HTTP server (serve/server.py)
    'server.request',        # one handled request (continues the LB trace)
    'server.kv_push',        # prefill tier pushing chunks to /kv/ingest
    'server.preempt_notice',  # the notice body: drain + export window
    # Engine (models/inference.py)
    'engine.queue_wait',     # submit → admission into a decode slot
    'engine.prefill',        # admission → first token (chunked or bucketed)
    'engine.decode',         # first token → finish (coalesced, slot attr)
    'engine.ingest_chunk',   # one handoff chunk applied on the decode tier
    'engine.ingest_publish',  # final-chunk scatter + prefix-index publish
    'engine.wedge_recovery',  # watchdog recovery (flight-record trigger)
    'engine.tick_failure',   # tick exception recovery (flight-record trigger)
    'engine.preempt_export',  # preemption-notice prefix export
    'engine.adapter_load',   # adapter made resident (tick thread, slot attr)
    'engine.slot_preempt',   # batch slot yielded to an interactive arrival
    'engine.compile',        # one XLA backend compile while tracing is on
    # Tick phases of the engine's thread (`phase()`: profiler annotation
    # + per-name totals, never a ring entry). The children tile _tick:
    # what none covers is the tick's self time.
    'engine.tick',            # one whole _tick
    'engine.tick.housekeep',  # engine work, ingest expiry, deadline scans
    'engine.tick.admit',      # admission loop (blocks, CoW, prefix lookup)
    'engine.tick.prefill',    # _prefill_tick: one chunk per prefilling slot
    'engine.tick.dispatch',   # decode launch (_dispatch / _spec_tick)
    'engine.tick.land',       # host waiting for the device's answer
    'engine.tick.emit',       # tokens appended, callbacks, finishes
    'engine.tick.wait',       # idle engine sleeping on its wake event
)

# Tracing metrics (docs/observability.md).
_SPANS_RECORDED = _metrics.counter(
    'skytpu_trace_spans_recorded_total',
    'Spans recorded into the in-process trace ring')
_SPANS_DROPPED = _metrics.counter(
    'skytpu_trace_spans_dropped_total',
    'Spans evicted from the trace ring by overflow (oldest-first; '
    'size the ring with SKYTPU_TRACE_RING)')
_FLIGHT_RECORDS = _metrics.counter(
    'skytpu_trace_flight_records_total',
    'Flight records dumped, by trigger (wedge_recovery / tick_failure '
    '/ preempt_notice)', ('trigger',))


def enable() -> None:
    """Turn span recording on (anchors the wall clock once so span
    timestamps stay monotonic-derived afterwards)."""
    global _enabled, _anchor
    if _anchor is None:
        _anchor = (time.time(), time.monotonic())
    _bind_jax()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def active() -> bool:
    """True when the FLIGHT RECORDER should fire: tracing is on, or an
    operator pinned a flight directory (a recorder with no spans still
    captures step_log/tick_stats — better than nothing on a wedge)."""
    return _enabled or bool(os.environ.get('SKYTPU_FLIGHT_DIR'))


# Internal clock funnel: every span start/end reads THIS symbol, so the
# disabled-path overhead test can poison it and prove the decode tick
# never touches a clock while tracing is off.
_now = time.monotonic


def now() -> float:
    """Monotonic seconds through the tracer's clock funnel (callers
    that record after-the-fact spans share the poisoning seam)."""
    return _now()


# Wall anchor: (time.time(), time.monotonic()) captured once at
# enable(); span wall timestamps derive as anchor_wall + (mono -
# anchor_mono) so the hot path reads ONLY the monotonic clock.
_anchor: Optional[tuple] = None


def _wall_us(mono: float) -> float:
    if _anchor is None:
        return mono * 1e6
    wall0, mono0 = _anchor
    return (wall0 + (mono - mono0)) * 1e6


# ---------------------------------------------------------------------
# span ring
# ---------------------------------------------------------------------

_RING_CAP = max(64, int(os.environ.get('SKYTPU_TRACE_RING', '8192')))
_ring: 'collections.deque[dict]' = collections.deque(maxlen=_RING_CAP)
_ring_lock = threading.Lock()


def _record(span: dict) -> None:
    with _ring_lock:
        if len(_ring) == _ring.maxlen:
            _SPANS_DROPPED.inc()
        _ring.append(span)
    _SPANS_RECORDED.inc()


def snapshot(window_s: Optional[float] = None) -> List[dict]:
    """Point-in-time copy of the span ring (oldest first), optionally
    restricted to spans that STARTED within the last `window_s`
    seconds."""
    with _ring_lock:
        spans = list(_ring)
    if window_s is not None:
        cutoff = _now() - window_s
        spans = [s for s in spans if s['mono'] >= cutoff]
    return spans


def reset() -> None:
    """Drop every recorded span and phase total (tests only)."""
    with _ring_lock:
        _ring.clear()
    with _phase_lock:
        _phase_totals.clear()


# ---------------------------------------------------------------------
# context + propagation
# ---------------------------------------------------------------------


class SpanContext:
    """The (trace_id, span_id) pair a child span parents to — what
    rides the X-SkyTPU-Trace header and the KV chunk headers."""

    __slots__ = ('trace_id', 'span_id')

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f'SpanContext({self.trace_id}, {self.span_id})'


_current: 'contextvars.ContextVar[Optional[SpanContext]]' = \
    contextvars.ContextVar('skytpu_trace_current', default=None)


def current() -> Optional[SpanContext]:
    """The ambient span context (None when tracing is disabled — the
    one-boolean fast path every capture site relies on)."""
    if not _enabled:
        return None
    return _current.get()


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def header_value(ctx: Optional[SpanContext]) -> Optional[str]:
    """Render `ctx` as the X-SkyTPU-Trace header value (traceparent
    style: version 00, sampled flag 01), or None for no context."""
    if ctx is None:
        return None
    return f'00-{ctx.trace_id}-{ctx.span_id}-01'


def parse_header(value: Optional[str]) -> Optional[SpanContext]:
    """Parse an X-SkyTPU-Trace value; garbage returns None (trace
    propagation must never fail a request)."""
    if not value or not isinstance(value, str):
        return None
    parts = value.strip().split('-')
    if len(parts) != 4:
        return None
    _version, trace_id, span_id, _flags = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    return SpanContext(trace_id, span_id)


class _Activation:
    """Context manager setting the ambient context (executor threads
    adopting a request's trace); `activate(None)` is a no-op."""

    __slots__ = ('_ctx', '_token')

    def __init__(self, ctx: Optional[SpanContext]) -> None:
        self._ctx = ctx
        self._token = None

    def __enter__(self) -> '_Activation':
        if self._ctx is not None and _enabled:
            self._token = _current.set(self._ctx)
        return self

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            _current.reset(self._token)
            self._token = None


def activate(ctx: Optional[SpanContext]) -> _Activation:
    return _Activation(ctx)


# ---------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------


class _SpanHandle:
    """One live span. As a context manager it also installs itself as
    the ambient context (children created inside parent to it)."""

    __slots__ = ('ctx', 'name', '_parent_id', '_start', '_attrs',
                 '_token', '_done')

    def __init__(self, name: str, parent: Optional[SpanContext],
                 attrs: Optional[Dict[str, Any]]) -> None:
        self.name = name
        if parent is not None:
            trace_id = parent.trace_id
            self._parent_id: Optional[str] = parent.span_id
        else:
            trace_id = _new_id(16)
            self._parent_id = None
        self.ctx = SpanContext(trace_id, _new_id(8))
        self._start = _now()
        self._attrs = dict(attrs) if attrs else {}
        self._token = None
        self._done = False

    def set_attr(self, key: str, value: Any) -> None:
        self._attrs[key] = value

    def end(self, **attrs: Any) -> None:
        if self._done:
            return
        self._done = True
        if attrs:
            self._attrs.update(attrs)
        end = _now()
        _record({
            'name': self.name,
            'trace_id': self.ctx.trace_id,
            'span_id': self.ctx.span_id,
            'parent_id': self._parent_id,
            'ts_us': round(_wall_us(self._start), 3),
            'mono': self._start,
            'dur_us': round((end - self._start) * 1e6, 3),
            'pid': os.getpid(),
            'tid': threading.get_ident(),
            'attrs': self._attrs,
        })

    def __enter__(self) -> '_SpanHandle':
        self._token = _current.set(self.ctx)
        return self

    def __exit__(self, exc_type, exc, _tb) -> None:
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if exc_type is not None:
            self._attrs.setdefault('error', f'{exc_type.__name__}: {exc}')
        self.end()


class _NullSpan:
    """The shared no-op handle the disabled path returns: no
    allocation, no clocks, `ctx` is None so header propagation and
    per-request capture short-circuit on an identity check."""

    __slots__ = ()
    ctx = None
    name = ''

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def end(self, **attrs: Any) -> None:
        pass

    def __enter__(self) -> '_NullSpan':
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


def span(name: str, parent: Optional[SpanContext] = None,
         attrs: Optional[Dict[str, Any]] = None):
    """Start a span (context manager). Parent resolution: explicit
    `parent`, else the ambient context, else a fresh trace is minted.
    Disabled tracing returns the shared no-op handle."""
    if not _enabled:
        return NULL_SPAN
    return _SpanHandle(name, parent if parent is not None
                       else _current.get(), attrs)


def start_span(name: str, parent: Optional[SpanContext] = None,
               attrs: Optional[Dict[str, Any]] = None):
    """Non-lexical twin of `span()`: the caller holds the handle and
    calls `.end(**attrs)` (the LB's async proxy paths, where `with`
    blocks don't line up with the request lifecycle)."""
    if not _enabled:
        return NULL_SPAN
    return _SpanHandle(name, parent if parent is not None
                       else _current.get(), attrs)


def record_span(name: str, start_mono: float, end_mono: float,
                parent: Optional[SpanContext] = None,
                attrs: Optional[Dict[str, Any]] = None
                ) -> Optional[SpanContext]:
    """Record a span AFTER the fact from monotonic stamps the caller
    already holds (queue-wait: submit_time → admit_time). Returns the
    new span's context (for chaining) or None when disabled."""
    if not _enabled:
        return None
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        ambient = _current.get()
        if ambient is not None:
            trace_id, parent_id = ambient.trace_id, ambient.span_id
        else:
            trace_id, parent_id = _new_id(16), None
    ctx = SpanContext(trace_id, _new_id(8))
    _record({
        'name': name,
        'trace_id': trace_id,
        'span_id': ctx.span_id,
        'parent_id': parent_id,
        'ts_us': round(_wall_us(start_mono), 3),
        'mono': start_mono,
        'dur_us': round(max(0.0, end_mono - start_mono) * 1e6, 3),
        'pid': os.getpid(),
        'tid': threading.get_ident(),
        'attrs': dict(attrs) if attrs else {},
    })
    return ctx


# ---------------------------------------------------------------------
# tick phases + compile spans (what tracing takes from jax)
# ---------------------------------------------------------------------

COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'

# jax.profiler.TraceAnnotation once bound (tests put a recorder here).
_annotation = None
_jax_bound = False

_phase_totals: Dict[str, list] = {}   # name -> [count, seconds]
_phase_lock = threading.Lock()


def _bind_jax() -> None:
    """Take `TraceAnnotation` and register the compile listener, once,
    and only in a process that has imported jax already: this module
    stays importable (and enable-able) without it. Called at `enable()`
    and again from the first phase, which runs on the engine's thread
    where jax is certainly loaded."""
    global _annotation, _jax_bound
    with _phase_lock:   # enable() and the engine's thread may race here
        if _jax_bound or 'jax' not in sys.modules:
            return
        _jax_bound = True
    import jax.monitoring
    import jax.profiler
    if _annotation is None:
        _annotation = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_duration(event: str, duration: float, **_kw: Any) -> None:
    """One `engine.compile` ring span per XLA backend compile while
    tracing is on: a compile inside a serving window is a stall that
    no request span explains."""
    if _enabled and event == COMPILE_EVENT:
        end = _now()
        record_span('engine.compile', end - duration, end,
                    attrs={'seconds': round(duration, 6)})


class _Phase:
    """One live tick phase: a profiler annotation, so that under a
    profiler session the span sits in the host plane on the clock the
    device's events are on, and a duration added to the per-name
    totals. Never a ring entry: seven phases at 25 ticks/s would roll
    the ring over inside a minute and drop the request spans."""

    __slots__ = ('name', '_start', '_ann')

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> '_Phase':
        if not _jax_bound:
            _bind_jax()
        self._ann = _annotation(self.name) if _annotation else None
        if self._ann is not None:
            self._ann.__enter__()
        self._start = _now()
        return self

    def __exit__(self, *exc) -> None:
        seconds = _now() - self._start
        if self._ann is not None:
            self._ann.__exit__(*exc)
        with _phase_lock:
            total = _phase_totals.setdefault(self.name, [0, 0.0])
            total[0] += 1
            total[1] += seconds


def phase(name: str):
    """A lexical span for a stretch of the engine's tick (context
    manager; names are the `engine.tick*` entries of KNOWN_SPANS).
    Disabled tracing returns the shared no-op handle."""
    if not _enabled:
        return NULL_SPAN
    return _Phase(name)


def phase_totals() -> Dict[str, Dict[str, float]]:
    """{phase name: {'count', 'seconds'}} since the process started
    (or `reset()`): where the engine's thread spent its time."""
    with _phase_lock:
        return {name: {'count': c, 'seconds': s}
                for name, (c, s) in _phase_totals.items()}


# ---------------------------------------------------------------------
# Perfetto export (merged into utils/timeline.py's view)
# ---------------------------------------------------------------------

# Synthetic track ids: spans render on per-subsystem tracks ('spans:lb',
# 'spans:engine', ...) distinct from the timeline's real-thread B/E
# tracks and the 'C' counter tracks, so the merged view stays readable.
_SPAN_TRACK_BASE = 900000


def perfetto_events(spans: Optional[List[dict]] = None) -> List[dict]:
    """Chrome-trace events for `spans` (default: the current ring):
    one 'X' complete event per span plus 'M' thread_name metadata
    naming each subsystem track."""
    if spans is None:
        spans = snapshot()
    subsystems = sorted({s['name'].split('.', 1)[0] for s in spans})
    tids = {sub: _SPAN_TRACK_BASE + i
            for i, sub in enumerate(subsystems)}
    pid = os.getpid()
    events: List[dict] = [
        {'name': 'thread_name', 'ph': 'M', 'pid': pid, 'tid': tid,
         'args': {'name': f'spans:{sub}'}}
        for sub, tid in tids.items()
    ]
    for s in spans:
        args = {'trace_id': s['trace_id'], 'span_id': s['span_id']}
        if s.get('parent_id'):
            args['parent_id'] = s['parent_id']
        args.update(s.get('attrs') or {})
        events.append({
            'name': s['name'], 'cat': 'span', 'ph': 'X',
            'ts': s['ts_us'], 'dur': s['dur_us'],
            'pid': s['pid'], 'tid': tids[s['name'].split('.', 1)[0]],
            'args': args,
        })
    return events


# ---------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------

FLIGHT_SCHEMA = 'skytpu-flight/1'
_FLIGHT_WINDOW_S = 30.0


def flight_dir() -> str:
    return os.environ.get(
        'SKYTPU_FLIGHT_DIR',
        os.path.expanduser('~/.skytpu/flightrecords'))


def flight_record(trigger: str, extra: Optional[dict] = None,
                  window_s: float = _FLIGHT_WINDOW_S) -> Optional[str]:
    """Dump the last `window_s` seconds of spans plus caller-supplied
    engine state (step_log, tick stats) to a structured JSON file —
    the postmortem a wedge recovery, tick failure, or preemption
    notice leaves behind. Atomic publish (write-to-temp + rename, the
    PR-6 artifact discipline): a kill mid-dump never publishes a torn
    record. Best-effort by contract: a full disk must not break the
    recovery path — returns the published path, or None."""
    if not active():
        return None
    try:
        directory = flight_dir()
        os.makedirs(directory, exist_ok=True)
        payload = {
            'schema': FLIGHT_SCHEMA,
            'trigger': trigger,
            'ts': time.time(),
            'window_s': window_s,
            'pid': os.getpid(),
            'spans': snapshot(window_s=window_s),
            'extra': extra or {},
        }
        path = os.path.join(
            directory, f'flight-{trigger}-{time.time_ns()}.json')
        tmp = path + '.tmp'
        with open(tmp, 'w', encoding='utf-8') as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        _FLIGHT_RECORDS.labels(trigger=trigger).inc()
        return path
    except Exception:  # pylint: disable=broad-except
        return None


# ---------------------------------------------------------------------
# rendering (`skytpu trace` and tests share these)
# ---------------------------------------------------------------------


def _fmt_attrs(attrs: Dict[str, Any]) -> str:
    if not attrs:
        return ''
    parts = []
    for key in sorted(attrs):
        value = attrs[key]
        if isinstance(value, float):
            value = round(value, 6)
        parts.append(f'{key}={value}')
    return '  [' + ' '.join(parts) + ']'


def render_trace_tree(spans: List[dict],
                      grep: Optional[str] = None) -> List[str]:
    """Human-readable trace trees: one block per trace_id, spans
    nested by parentage (orphans — parents outside the ring — root at
    depth 0), durations in ms. `grep` keeps only traces where some
    span's name or rendered attrs contain the substring."""
    by_trace: Dict[str, List[dict]] = {}
    for s in spans:
        by_trace.setdefault(s['trace_id'], []).append(s)
    lines: List[str] = []
    for trace_id in sorted(by_trace,
                           key=lambda t: min(s['ts_us']
                                             for s in by_trace[t])):
        members = sorted(by_trace[trace_id], key=lambda s: s['ts_us'])
        if grep is not None and not any(
                grep in s['name'] or grep in _fmt_attrs(s['attrs'])
                for s in members):
            continue
        ids = {s['span_id'] for s in members}
        children: Dict[Optional[str], List[dict]] = {}
        for s in members:
            parent = s['parent_id'] if s['parent_id'] in ids else None
            children.setdefault(parent, []).append(s)
        lines.append(f'trace {trace_id} ({len(members)} spans)')

        def walk(parent_id: Optional[str], depth: int) -> None:
            for s in children.get(parent_id, []):
                lines.append(
                    f'{"  " * (depth + 1)}{s["name"]} '
                    f'{s["dur_us"] / 1000.0:.2f}ms'
                    f'{_fmt_attrs(s["attrs"])}')
                walk(s['span_id'], depth + 1)

        walk(None, 0)
    return lines


def render_flight_record(record: dict) -> List[str]:
    """Postmortem view of one flight-record dict (`skytpu trace
    --dump`)."""
    lines = [
        f'flight record: trigger={record.get("trigger")} '
        f'pid={record.get("pid")} '
        f'window={record.get("window_s")}s '
        f'spans={len(record.get("spans", []))}',
    ]
    extra = record.get('extra') or {}
    for key in sorted(extra):
        if key == 'step_log':
            continue
        lines.append(f'  {key}: {extra[key]}')
    step_log = extra.get('step_log') or []
    if step_log:
        lines.append(f'  step_log (last {len(step_log)} ticks):')
        for entry in step_log[-20:]:
            step, slots = entry[0], entry[1]
            lines.append(f'    step {step}: slots {slots}')
    tree = render_trace_tree(record.get('spans', []))
    if tree:
        lines.append('  spans:')
        lines.extend('  ' + line for line in tree)
    return lines


def _enable_from_env() -> None:
    # A boolean flip only — no thread, socket, or file at import
    # (the observability no-import-side-effects contract).
    if os.environ.get('SKYTPU_TRACING', '') == '1':
        enable()


_enable_from_env()
