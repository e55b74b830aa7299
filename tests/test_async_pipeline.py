"""Async decode pipeline (tier-1, CPU): device-resident token
feedback + lookahead dispatch (models/inference.py, async_depth=1, the
default).

Pins the acceptance bar of the async-pipeline issues:
  - greedy token streams BIT-IDENTICAL between sync and async modes
    across every termination (EOS / max_new_tokens / cache window),
    under admission/finish churn, with chunked prefill interleaving,
    in paged mode, and with decode_chunk scans;
  - the ring rides through churn: a finish the host can foresee and a
    slot that joins flush nothing, no step is dispatched for a slot
    that is known to be done, and what cannot be foreseen (EOS, a
    deadline kill, a preemption) is shed by request identity;
  - a steady-state decode tick performs at most ONE host→device upload
    (a transfer-counting shim around the module's jnp entry points —
    the zero-upload device-feedback property cannot silently regress);
  - a watchdog wedge recovery discards an in-flight lookahead dispatch
    cleanly (chaos): no token from the abandoned dispatch is ever
    emitted, and the recovered engine serves bit-identical output.
"""
import dataclasses
import threading
import time

import pytest

import jax

from skypilot_tpu import exceptions
from skypilot_tpu.utils import fault_injection


def _cfg(**kw):
    from skypilot_tpu.models import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


def _engine(**kw):
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    return ContinuousBatchingEngine(_cfg(), num_slots=2, **kw)


PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]


# Engines are shared per module where state allows: every engine
# re-JITs its decode programs, and tier-1 runs on a wall-clock budget.


@pytest.fixture(scope='module')
def sync_engine():
    engine = _engine()
    yield engine
    engine.stop()


@pytest.fixture(scope='module')
def async_engine():
    engine = _engine(async_depth=1)
    yield engine
    engine.stop()


@pytest.fixture(scope='module')
def ref_tokens(sync_engine):
    """The sync engine's greedy stream for PROMPT — the reference every
    async comparison is cut from (an engine emits the same greedy
    stream at any max_new_tokens prefix)."""
    toks, _ = sync_engine.generate(PROMPT, max_new_tokens=24)
    return toks


class TestAsyncBitIdentity:

    def test_depth_n_constructs(self):
        """async_depth>1 is no longer gated: a deep ring constructs
        (decode behavior is pinned by tests/test_composition_matrix.py;
        negative depths clamp to sync)."""
        engine = _engine(async_depth=2)
        try:
            assert engine.async_depth == 2
            assert engine._inflight is None  # pylint: disable=protected-access
        finally:
            engine.stop()
        engine = _engine(async_depth=-1)
        try:
            assert engine.async_depth == 0
        finally:
            engine.stop()

    def test_max_tokens_termination(self, sync_engine, async_engine,
                                    ref_tokens):
        for n in (2, 9, 24):
            got, stats = async_engine.generate(PROMPT, max_new_tokens=n)
            assert got == ref_tokens[:n], (n, got)
            assert stats['new_tokens'] == n
        # max_new_tokens=1 keeps the engine's historical off-by-one
        # (the admission-sampled token is only counted at the next
        # emit): whatever sync does, async must match bit-for-bit.
        want, _ = sync_engine.generate(PROMPT, max_new_tokens=1)
        got, _ = async_engine.generate(PROMPT, max_new_tokens=1)
        assert got == want

    def test_eos_termination(self, sync_engine, async_engine,
                             ref_tokens):
        """EOS is detected one dispatch late in async mode; the
        overshoot must be discarded, leaving the streams identical."""
        eos = ref_tokens[5]
        want, _ = sync_engine.generate(PROMPT, max_new_tokens=24,
                                       eos_id=eos)
        got, _ = async_engine.generate(PROMPT, max_new_tokens=24,
                                       eos_id=eos)
        assert got == want
        assert want == ref_tokens[:6]   # sanity: EOS really fired

    def test_window_termination(self, sync_engine, async_engine):
        """prompt 32 + 32 new tokens lands exactly on max_seq_len=64:
        the request terminates on the cache window, which _spent must
        treat as a finish the host foresees (no chained dispatch may
        write past the window)."""
        prompt = list(range(2, 34))
        want, _ = sync_engine.generate(prompt, max_new_tokens=32)
        got, stats = async_engine.generate(prompt, max_new_tokens=32)
        assert got == want
        assert stats['new_tokens'] == len(want)

    def test_mixed_churn_streams_identical(self, sync_engine,
                                           async_engine, ref_tokens):
        """Staggered concurrent requests with different lengths force
        admission/finish churn mid-pipeline (which the ring rides
        through: no flush); each per-request stream must still equal
        the solo sync reference — including the on_token streaming
        order."""
        streams = {}

        def _tap(key):
            streams[key] = []

            def cb(tok):
                if tok is not None:
                    streams[key].append(tok)
            return cb

        lens = (4, 16, 7, 12, 5, 9)
        futures = []
        for i, n in enumerate(lens):
            futures.append(async_engine.submit(
                PROMPT, max_new_tokens=n, on_token=_tap(i)))
            if i % 2:
                time.sleep(0.02)   # stagger: land mid-decode
        results = [f.result(timeout=120)[0] for f in futures]
        for i, n in enumerate(lens):
            assert results[i] == ref_tokens[:n], (i, n, results[i])
            assert streams[i] == ref_tokens[:n], (i, n, streams[i])
        assert async_engine.tick_stats['chained'] > 0
        assert async_engine.tick_stats['flushes'] == 0

    def test_decode_chunk_identical(self, ref_tokens):
        engine = _engine(decode_chunk=4, async_depth=1)
        try:
            got, _ = engine.generate(PROMPT, max_new_tokens=9)
            assert engine.tick_stats['chained'] >= 1
        finally:
            engine.stop()
        assert got == ref_tokens[:9]

    def test_speculative_flushes_and_matches(self, ref_tokens):
        """Spec ticks emit synchronously: the pipeline must flush
        around them without reordering any per-request stream."""
        engine = _engine(speculative=3, async_depth=1)
        try:
            got, _ = engine.generate(PROMPT, max_new_tokens=10)
        finally:
            engine.stop()
        assert got == ref_tokens[:10]


class TestAsyncPaged:

    @pytest.fixture(scope='class')
    def paged_pair(self):
        # One-block chunks, named: the default width would swallow the
        # 40-token prompt of the interleaving test in one chunk.
        s = _engine(paged_block_size=8, prefill_chunk=8)
        a = _engine(paged_block_size=8, prefill_chunk=8, async_depth=1)
        yield s, a
        s.stop()
        a.stop()

    def test_block_boundaries_identical(self, paged_pair):
        s, a = paged_pair
        for prompt in ([9, 9], list(range(2, 10)), list(range(2, 19))):
            want, _ = s.generate(prompt, max_new_tokens=10)
            got, _ = a.generate(prompt, max_new_tokens=10)
            assert got == want, (prompt, got, want)

    def test_chunked_prefill_interleaves_with_lookahead(
            self, paged_pair):
        """A long prompt prefilling chunk by chunk while another slot
        decodes through the lookahead pipeline: decode ticks still land
        BETWEEN prefill chunks, block growth happens ahead of the
        lookahead step's positions, and both streams stay exact."""
        s, a = paged_pair
        want_short, _ = s.generate([9, 9], max_new_tokens=30)
        want_long, _ = s.generate(list(range(1, 41)), max_new_tokens=4)
        marker = len(a.step_log)
        f_short = a.submit([9, 9], max_new_tokens=30)
        deadline = time.time() + 30
        while len(a.step_log) <= marker and time.time() < deadline:
            time.sleep(0.01)
        f_long = a.submit(list(range(1, 41)), max_new_tokens=4)
        assert f_short.result(timeout=120)[0] == want_short
        assert f_long.result(timeout=120)[0] == want_long
        log = list(a.step_log)[marker:]
        prefill = [i for i, (tag, _) in enumerate(log)
                   if tag == 'prefill']
        decode = [i for i, (tag, _) in enumerate(log)
                  if tag != 'prefill']
        assert len(prefill) >= 5, log
        assert any(prefill[j] < d < prefill[j + 1]
                   for d in decode
                   for j in range(len(prefill) - 1)), log


def _closed_loop(engine, work, clients, **submit_kw):
    """`clients` callers, each sending the next item of `work`
    ((prompt, max_new_tokens) pairs, in order) when its last answer is
    in. Returns every request's tokens and its on_token stream, by
    index in `work`."""
    lock = threading.Lock()
    futures, streams, ended = {}, {}, []
    done = threading.Event()

    def send_next():
        with lock:
            index = len(futures)
            if index >= len(work):
                return
            futures[index] = None
        prompt, new = work[index]
        streams[index] = []

        def on_token(tok):
            if tok is not None:
                streams[index].append(tok)
                return
            # the stream's end: the request's client sends its next
            ended.append(index)
            send_next()
            if len(ended) == len(work):
                done.set()

        futures[index] = engine.submit(prompt, max_new_tokens=new,
                                       on_token=on_token, **submit_kw)

    for _ in range(clients):
        send_next()
    assert done.wait(timeout=300), 'closed loop did not finish'
    return ({i: f.result(timeout=60)[0] for i, f in futures.items()},
            streams)


def _decode_steps_by_slot_count(engine, marker=0):
    """Slot-steps the engine dispatched: a decode step counts once for
    every slot it carried."""
    return sum(len(slots) for tag, slots in list(engine.step_log)[marker:]
               if tag != 'prefill')


# Staggered lengths, prompts of one chunk (8 tokens and fewer) and of
# two, a request of one token and one of two among them.
CHURN_WORK = [((PROMPT + PROMPT[:5]) if i % 3 == 0 else PROMPT[:3 + i % 5],
               n)
              for i, n in enumerate((4, 16, 7, 12, 5, 9, 1, 2, 11, 3, 14,
                                     6, 8, 10, 13, 5, 2, 9))]


class TestRingRidesThroughChurn:
    """A closed loop with more clients than slots, at depth 0 and at
    the default: the same tokens, and at the default not one flush."""

    @pytest.fixture(scope='class', params=['paged', 'contiguous'])
    def pair(self, request):
        kw = (dict(paged_block_size=8, prefill_chunk=8)
              if request.param == 'paged' else {})
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        sync = ContinuousBatchingEngine(_cfg(), num_slots=3,
                                        async_depth=0, **kw)
        ring = ContinuousBatchingEngine(_cfg(), num_slots=3, **kw)
        out = {}
        try:
            out['want'] = _closed_loop(sync, CHURN_WORK, clients=5)
            out['sync_steps'] = _decode_steps_by_slot_count(sync)
            out['got'] = _closed_loop(ring, CHURN_WORK, clients=5)
            out['ring_steps'] = _decode_steps_by_slot_count(ring)
            out['stats'] = dict(ring.tick_stats)
            out['sync_stats'] = dict(sync.tick_stats)
            out['depth'] = ring.async_depth
            out['programs'] = (ring._decode._cache_size(),  # pylint: disable=protected-access
                               ring._join_feed._cache_size())  # pylint: disable=protected-access
            out['occupancy'] = ring.paged_occupancy()
        finally:
            sync.stop()
            ring.stop()
        return out

    def test_the_default_is_the_ring(self, pair):
        assert pair['depth'] == 1
        assert pair['sync_stats']['chained'] == 0

    def test_tokens_and_stream_order_equal(self, pair):
        want, want_streams = pair['want']
        got, got_streams = pair['got']
        assert sorted(got) == list(range(len(CHURN_WORK)))
        for i, (_, n) in enumerate(CHURN_WORK):
            assert got[i] == want[i], (i, n)
            assert got_streams[i] == got[i], (i, n)
            assert want_streams[i] == want[i], (i, n)
            assert len(got[i]) == max(n, 2)   # the off-by-one at n == 1

    def test_no_flush_and_nearly_every_dispatch_ahead(self, pair):
        stats = pair['stats']
        assert stats['flushes'] == 0, stats
        assert stats['chained'] > 0.9 * stats['dispatches'], stats

    def test_no_step_for_a_slot_known_to_be_done(self, pair):
        """Without EOS a run dispatches exactly the steps the
        synchronous tick does: one a token after the first."""
        want = sum(max(n - 1, 1) for _, n in CHURN_WORK)
        assert pair['sync_steps'] == want
        assert pair['ring_steps'] == want

    def test_one_decode_program_and_one_join_program(self, pair):
        assert pair['programs'] == (1, 1)

    def test_occupancy_reports_the_ring(self, pair):
        occ = pair['occupancy']
        if not occ:     # the contiguous layout has no pool to report
            return
        assert occ['decode_dispatches'] == pair['stats']['dispatches']
        assert occ['decode_chained'] == pair['stats']['chained']
        assert occ['ring_flushes'] == 0


class TestUnforeseenIsShed:
    """EOS, a deadline kill and a preemption with the ring up: nothing
    is waited for, the columns already queued are dropped by request
    identity, and every surviving stream is exact."""

    @pytest.fixture(scope='class')
    def ring(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(
            _cfg(), num_slots=2, paged_block_size=8, prefill_chunk=8)
        yield engine
        engine.stop()

    def test_eos_overshoot_shed_while_the_slot_is_reused(
            self, ring, sync_engine, ref_tokens):
        eos = ref_tokens[5]
        want, _ = sync_engine.generate(PROMPT, max_new_tokens=24,
                                       eos_id=eos)
        other = PROMPT[:5]
        want_other, _ = sync_engine.generate(other, max_new_tokens=20)
        flushes = ring.tick_stats['flushes']
        futs = [ring.submit(PROMPT, max_new_tokens=24, eos_id=eos),
                ring.submit(other, max_new_tokens=20),
                ring.submit(PROMPT, max_new_tokens=24, eos_id=eos),
                ring.submit(other, max_new_tokens=20)]
        got = [f.result(timeout=120)[0] for f in futs]
        assert got == [want, want_other, want, want_other]
        assert want == ref_tokens[:6]       # EOS really fired
        assert ring.tick_stats['flushes'] == flushes
        ring._pool.check()  # pylint: disable=protected-access

    def test_deadline_kill_sheds_and_the_neighbour_is_exact(
            self, ring, ref_tokens):
        ring.generate(PROMPT, max_new_tokens=2)     # compiled
        flushes = ring.tick_stats['flushes']
        seen = threading.Event()

        def slowly(_tok):
            # on the engine's thread: 50 tokens take 2.5 s at the
            # least, so the deadline falls mid-decode on any machine
            seen.set()
            time.sleep(0.05)

        killed = ring.submit(PROMPT[:5], max_new_tokens=50,
                             deadline=time.time() + 1.0, on_token=slowly)
        neighbour = ring.submit(PROMPT, max_new_tokens=24)
        assert seen.wait(timeout=60)
        with pytest.raises(exceptions.RequestDeadlineExceededError):
            killed.result(timeout=120)
        # the freed slot is joined again while the neighbour decodes
        again = ring.submit(PROMPT, max_new_tokens=9)
        assert neighbour.result(timeout=120)[0] == ref_tokens[:24]
        assert again.result(timeout=120)[0] == ref_tokens[:9]
        assert ring.tick_stats['flushes'] == flushes
        ring._pool.check()  # pylint: disable=protected-access

    def test_preempted_request_resumes_exact_beside_a_decoding_slot(
            self, ring, ref_tokens):
        """The same request object comes back to a slot: its pending
        columns were blanked, not told apart by identity."""
        flushes = ring.tick_stats['flushes']
        started, seen = threading.Event(), []
        batch = ring.submit(
            PROMPT, max_new_tokens=24, priority='batch',
            on_token=lambda t: (seen.append(t),
                                len(seen) >= 4 and started.set(),
                                time.sleep(0.01)))  # still decoding when
        neighbour = ring.submit(PROMPT, max_new_tokens=24)  # the urgent one comes
        assert started.wait(timeout=60)
        preempts = ring.tenancy_stats['slot_preempts']
        urgent = ring.submit(PROMPT[:5], max_new_tokens=4,
                             priority='interactive')
        urgent.result(timeout=120)
        assert batch.result(timeout=120)[0] == ref_tokens[:24]
        assert neighbour.result(timeout=120)[0] == ref_tokens[:24]
        assert [t for t in seen if t is not None] == ref_tokens[:24]
        assert ring.tenancy_stats['slot_preempts'] > preempts
        assert ring.tick_stats['flushes'] == flushes
        ring._pool.check()  # pylint: disable=protected-access

    def test_sampled_stream_equals_the_synchronous_one(self):
        """One sampled request alone draws its keys in the same order
        at either depth: first token (on the host, or in the join
        program), then a key a step."""
        outs = []
        for depth in (0, 1):
            engine = _engine(async_depth=depth, rng_seed=7, top_k=8)
            try:
                outs.append(engine.generate(PROMPT, max_new_tokens=12,
                                            temperature=0.9)[0])
            finally:
                engine.stop()
        assert outs[0] == outs[1]
        assert len(set(outs[0])) > 1


class _CountingJnp:
    """Transfer-counting shim: stands in for the inference module's
    `jnp` binding so EVERY jnp.asarray that moves host data (lists,
    numpy arrays, scalars — anything not already a jax.Array) is
    counted. Already-device arrays and in-jit tracers (jax.Array
    subclasses) pass uncounted. Thread-safe enough for the engine
    thread + asserting thread (list.append under the GIL)."""

    def __init__(self, real):
        self._real = real
        self.uploads = []

    def asarray(self, value, *args, **kwargs):
        if not isinstance(value, jax.Array):
            self.uploads.append(type(value).__name__)
        return self._real.asarray(value, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._real, name)


class TestSteadyStateUploads:
    """THE hot-path regression guard: with one request mid-decode and
    no churn, a tick feeds the device from the device — the previous
    dispatch's in-graph feed — so it uploads NOTHING via the module's
    jnp entry points (the RNG split is key arithmetic on device keys,
    not an upload). Pinned at ≤1 across a multi-tick window to absorb
    a shim-installation boundary, which is still far below one-per-tick."""

    def _count_steady_window(self, engine, monkeypatch, ticks=6):
        from skypilot_tpu.models import inference
        fut = engine.submit(PROMPT, max_new_tokens=48)
        # Let the pipeline reach steady state (admission + first
        # dispatches done) before installing the shim.
        deadline = time.time() + 60
        while engine._decode_steps < 4 and time.time() < deadline:  # pylint: disable=protected-access
            time.sleep(0.01)
        shim = _CountingJnp(inference.jnp)
        monkeypatch.setattr(inference, 'jnp', shim)
        start = engine._decode_steps  # pylint: disable=protected-access
        while engine._decode_steps < start + ticks and \
                time.time() < deadline:  # pylint: disable=protected-access
            time.sleep(0.01)
        uploads = len(shim.uploads)
        window = engine._decode_steps - start  # pylint: disable=protected-access
        monkeypatch.setattr(inference, 'jnp', shim._real)  # pylint: disable=protected-access
        fut.result(timeout=120)
        assert window >= ticks, 'engine made no progress under shim'
        return uploads, window

    def test_sync_steady_tick_uploads_at_most_one(self, monkeypatch):
        engine = _engine()
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            uploads, window = self._count_steady_window(
                engine, monkeypatch)
        finally:
            engine.stop()
        assert uploads <= 1, (
            f'{uploads} host→device uploads over {window} steady '
            f'sync ticks (device feedback regressed)')

    def test_async_steady_tick_uploads_at_most_one(self, monkeypatch):
        engine = _engine(async_depth=1)
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            uploads, window = self._count_steady_window(
                engine, monkeypatch)
            assert engine.tick_stats['chained'] > 0
        finally:
            engine.stop()
        assert uploads <= 1, (
            f'{uploads} host→device uploads over {window} steady '
            f'chained ticks (lookahead feed regressed)')

    def test_paged_steady_uploads_bounded_by_block_growth(
            self, monkeypatch):
        """Paged mode re-uploads the block table only when the table
        actually grows (once per block_size tokens) — never per
        tick."""
        engine = _engine(paged_block_size=8, async_depth=1)
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            uploads, window = self._count_steady_window(
                engine, monkeypatch, ticks=10)
        finally:
            engine.stop()
        # ≤ one table rebuild per crossed block boundary (10 ticks
        # cross at most 2), plus the installation-boundary allowance.
        assert uploads <= 4, (
            f'{uploads} uploads over {window} paged ticks')


class TestJoiningTickUploads:

    def test_a_join_uploads_its_own_row_and_never_the_feed(
            self, monkeypatch):
        """With the ring up a joining slot's token and position are
        written on the device: the tick uploads the join's (slot,
        position) pair and temperature beside the chunk's own inputs
        and the changed table, and never the full-width token and
        position lists a rebuild from host state would."""
        from skypilot_tpu.models import inference
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        slots = 5
        engine = ContinuousBatchingEngine(
            _cfg(), num_slots=slots, paged_block_size=8, prefill_chunk=8)
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            uploads = []
            real = inference._upload  # pylint: disable=protected-access

            def counting(value, dtype=None, sharding=None):
                uploads.append(value)
                return real(value, dtype, sharding)

            monkeypatch.setattr(inference, '_upload', counting)
            got, _ = _closed_loop(engine, CHURN_WORK[:8], clients=slots + 2)
            monkeypatch.setattr(inference, '_upload', real)
            stats = dict(engine.tick_stats)
        finally:
            engine.stop()
        assert len(got) == 8
        assert stats['flushes'] == 0
        full_width = [u for u in uploads
                      if isinstance(u, list) and len(u) == slots
                      and all(isinstance(x, int) for x in u)]
        assert not full_width, full_width
        joins = [u for u in uploads if isinstance(u, list) and len(u) == 2
                 and all(isinstance(x, int) for x in u)]
        assert len(joins) == 8, joins


@pytest.mark.chaos
class TestAsyncWedgeRecovery:

    def test_wedge_discards_inflight_lookahead(self, sync_engine,
                                               ref_tokens):
        """Wedge the decode loop with a lookahead dispatch pending: the
        watchdog must fail the in-flight request cleanly, the abandoned
        dispatch must never emit (stream stays a clean prefix of the
        greedy reference), and the recovered engine must serve
        bit-identical output."""
        engine = _engine(async_depth=1, watchdog_timeout=1.0)
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            streamed = []
            seen_some = threading.Event()

            def cb(tok):
                if tok is not None:
                    streamed.append(tok)
                    if len(streamed) >= 3:
                        seen_some.set()
            fut = engine.submit(PROMPT, max_new_tokens=48, on_token=cb)
            assert seen_some.wait(timeout=60), 'no tokens before wedge'
            fault_injection.arm('engine.decode', 'wedge')
            with pytest.raises(exceptions.EngineWedgedError):
                fut.result(timeout=120)
            assert engine._generation >= 1  # pylint: disable=protected-access
            # Recovery dropped the pending lookahead wholesale.
            assert engine._inflight is None  # pylint: disable=protected-access
            fault_injection.disarm_all()
            emitted_at_fail = len(streamed)
            # The abandoned thread (released from the wedge) must not
            # emit its in-flight lookahead into the failed stream.
            time.sleep(0.3)
            assert len(streamed) == emitted_at_fail
            assert streamed == ref_tokens[:emitted_at_fail]
            got, _ = engine.generate(PROMPT, max_new_tokens=8,
                                     timeout=120)
            assert got == ref_tokens[:8]
        finally:
            fault_injection.disarm_all()
            engine.stop()
