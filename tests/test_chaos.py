"""Chaos tests: the resilience layer driven through armed injection
points (utils/fault_injection.py) — the robustness analogue of the
exactness-pinning discipline the compute stack already has.

Everything here is tier-1 (NOT slow) and deterministic: fault schedules
count firings (fail:N) or block on events (wedge), never wall clock.
Covers the acceptance matrix of the resilience issue:
  (a) a wedged engine thread fails in-flight requests with a clean
      error and the server keeps serving after watchdog recovery,
  (b) queue overload returns 429/503 (+ Retry-After) while
      already-admitted requests complete,
  (c) a circuit-breaker-ejected replica is re-admitted after a
      successful half-open probe,
  (d) a `jobs queue` CLI round-trip across fresh processes escalates
      to a forced cloud probe on the 3rd PERSISTED consecutive RPC
      failure,
plus: injection points verifiably inert when disarmed, the shared
retry/backoff policy, and the serve-side escalation mirror.
"""
import dataclasses
import os
import random
import socket
import subprocess
import sys
import threading
import time

import pytest
import requests

from skypilot_tpu import exceptions
from skypilot_tpu.utils import fault_injection
from skypilot_tpu.utils import retry as retry_lib

pytestmark = pytest.mark.chaos


def _cfg(**kw):
    from skypilot_tpu.models.configs import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(('', 0))
        return sock.getsockname()[1]


def _serve_in_thread(app) -> int:
    """Run an aiohttp app on a fresh loop in a daemon thread; returns
    the bound port once it answers TCP."""
    import asyncio
    from aiohttp import web
    port = _free_port()

    def _serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    threading.Thread(target=_serve, daemon=True).start()
    deadline = time.time() + 30
    while time.time() < deadline:
        with socket.socket() as sock:
            sock.settimeout(0.5)
            try:
                sock.connect(('127.0.0.1', port))
                return port
            except OSError:
                time.sleep(0.1)
    raise AssertionError('server thread never bound its port')


def _wrap_server(engine, request_timeout: float = 0.0):
    """A bare InferenceServer around an existing engine (the
    test_inference idiom — no model/tokenizer bring-up)."""
    from skypilot_tpu.serve.server import InferenceServer
    server = InferenceServer.__new__(InferenceServer)
    server.engine = engine
    server.tokenizer_kind = 'byte'
    server._hf_tokenizer = None  # pylint: disable=protected-access
    server.ready = True
    server.request_timeout = request_timeout
    server.draining = False
    return server


# ---------------------------------------------------------------------
# fault-injection framework
# ---------------------------------------------------------------------


class TestFaultInjectionFramework:

    def test_injection_points_inert_when_disarmed(self):
        """Disarmed (the default) every documented point is a no-op:
        nothing armed, nothing raised, nothing counted."""
        assert not fault_injection.armed()
        for name in fault_injection.KNOWN_POINTS:
            fault_injection.point(name)  # must not raise
            assert fault_injection.trip_count(name) == 0
        # Arming is fully reversible back to the inert state.
        fault_injection.arm('engine.decode', 'fail:1')
        assert fault_injection.armed()
        fault_injection.disarm_all()
        assert not fault_injection.armed()
        fault_injection.point('engine.decode')
        assert fault_injection.trip_count('engine.decode') == 0

    def test_fail_n_schedule_is_deterministic(self):
        fault_injection.arm('rpc.send', 'fail:2')
        for _ in range(2):
            with pytest.raises(fault_injection.InjectedFault):
                fault_injection.point('rpc.send')
        # Third and later firings pass: the schedule counts firings,
        # not wall clock.
        fault_injection.point('rpc.send')
        fault_injection.point('rpc.send')
        assert fault_injection.trip_count('rpc.send') == 4
        fault_injection.disarm_all()

    def test_env_spec_parsing(self):
        spec = fault_injection.parse_spec(
            'rpc.send=fail:3; engine.decode=wedge ;storage.chunk=delay:0.5')
        assert spec == {'rpc.send': 'fail:3', 'engine.decode': 'wedge',
                        'storage.chunk': 'delay:0.5'}
        with pytest.raises(ValueError, match='name=behavior'):
            fault_injection.parse_spec('rpc.send')
        with pytest.raises(ValueError, match='unknown fault behavior'):
            fault_injection.arm('rpc.send', 'explode')

    def test_storage_chunk_point(self):
        from skypilot_tpu.data import data_transfer
        import base64

        def transport(method, url, body=None):  # pylint: disable=unused-argument
            return 200, {'data_b64': base64.b64encode(b'blob').decode()}

        data_transfer.set_transport_override(transport)
        try:
            assert data_transfer._gcs_read_object('b', 'o') == b'blob'
            fault_injection.arm('storage.chunk', 'fail')
            with pytest.raises(exceptions.StorageError,
                               match='injected fault'):
                data_transfer._gcs_read_object('b', 'o')
            fault_injection.disarm_all()
            assert data_transfer._gcs_read_object('b', 'o') == b'blob'
        finally:
            fault_injection.disarm_all()
            data_transfer.set_transport_override(None)

    def test_replica_probe_point(self):
        import types
        from skypilot_tpu.serve.replica_managers import \
            SkyPilotReplicaManager
        fake = types.SimpleNamespace(spec=types.SimpleNamespace(
            readiness_path='/', post_data=None, readiness_headers=None))
        # Nothing listens on this url: disarmed, the probe fails via the
        # ordinary RequestException path...
        info = types.SimpleNamespace(url='http://127.0.0.1:9')
        assert SkyPilotReplicaManager._probe_one(fake, info) == 'down'
        # ...armed, the injected fault reads as a failed probe without
        # any network I/O.
        fault_injection.arm('replica.probe', 'fail')
        assert SkyPilotReplicaManager._probe_one(fake, info) == 'down'
        assert fault_injection.trip_count('replica.probe') == 1
        fault_injection.disarm_all()


# ---------------------------------------------------------------------
# retry / backoff / persistent failure tracking
# ---------------------------------------------------------------------


class TestRetryPolicy:

    def test_backoff_seeded_is_deterministic(self):
        def make():
            return retry_lib.Backoff(base=0.1, factor=2.0, cap=1.0,
                                     jitter=0.5, rng=random.Random(42))

        d1 = [make().next_delay() for _ in range(1)]
        b1, b2 = make(), make()
        s1 = [b1.next_delay() for _ in range(5)]
        s2 = [b2.next_delay() for _ in range(5)]
        assert s1 == s2 and s1[0] == d1[0]
        # Exponential growth up to the cap; jitter only shrinks.
        for got, ceiling in zip(s1, [0.1, 0.2, 0.4, 0.8, 1.0]):
            assert 0.5 * ceiling <= got <= ceiling

    def test_call_with_retry_transient_then_success(self):
        calls = {'n': 0}
        sleeps = []

        def flaky():
            calls['n'] += 1
            if calls['n'] < 3:
                raise OSError('transient')
            return 'ok'

        out = retry_lib.call_with_retry(flaky, attempts=4,
                                        retry_on=(OSError,),
                                        base=0.1,
                                        sleep=sleeps.append,
                                        rng=random.Random(0))
        assert out == 'ok' and calls['n'] == 3
        assert len(sleeps) == 2  # no wall-clock sleeps: collected only

    def test_call_with_retry_respects_deadline(self):
        clock = {'t': 0.0}
        sleeps = []

        def tick():
            return clock['t']

        def sleep(d):
            sleeps.append(d)
            clock['t'] += d

        def always_fails():
            clock['t'] += 5.0  # each attempt takes 5 "seconds"
            raise OSError('down')

        with pytest.raises(OSError):
            retry_lib.call_with_retry(always_fails, attempts=10,
                                      retry_on=(OSError,), base=1.0,
                                      deadline=6.0, sleep=sleep,
                                      clock=tick, rng=random.Random(0))
        # First attempt consumed 5s; one backoff could fit under the
        # 6s deadline at most — never all 10 attempts.
        assert len(sleeps) <= 1

    def test_non_retryable_exception_propagates_immediately(self):
        calls = {'n': 0}

        def wrong_type():
            calls['n'] += 1
            raise KeyError('not retryable')

        with pytest.raises(KeyError):
            retry_lib.call_with_retry(wrong_type, attempts=5,
                                      retry_on=(OSError,),
                                      sleep=lambda d: None)
        assert calls['n'] == 1

    def test_failure_tracker_persists_in_state_db(self):
        tracker = retry_lib.ConsecutiveFailureTracker('chaos-test')
        assert tracker.count('clu') == 0
        assert tracker.record_failure('clu') == 1
        assert tracker.record_failure('clu') == 2
        # A FRESH tracker object (new process analogue) continues the
        # count — it lives in the state db, not in memory.
        assert retry_lib.ConsecutiveFailureTracker(
            'chaos-test').count('clu') == 2
        tracker.reset('clu')
        assert tracker.count('clu') == 0


# ---------------------------------------------------------------------
# engine + server: wedge watchdog, overload shedding, deadlines, drain
# ---------------------------------------------------------------------


@pytest.fixture(scope='module')
def wd_server():
    """One warmed watchdog-enabled engine behind a live HTTP server,
    shared by the engine-chaos tests (engine bring-up JIT-compiles —
    one per module, not per test)."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                      watchdog_timeout=1.0)
    engine.generate([1, 2, 3], max_new_tokens=2, timeout=300)  # compile
    server = _wrap_server(engine)
    port = _serve_in_thread(server.make_app())
    yield server, f'http://127.0.0.1:{port}'
    fault_injection.disarm_all()
    engine.stop()


class TestEngineWatchdog:

    def test_wedged_engine_fails_inflight_cleanly_and_server_recovers(
            self, wd_server):
        """Acceptance (a): wedge the decode step → the in-flight HTTP
        request gets a clean 503 (not a hang, not a 500 traceback), and
        after the watchdog recovery + release the SAME server serves
        again."""
        server, url = wd_server
        fault_injection.arm('engine.decode', 'wedge')
        resp = requests.post(url + '/generate',
                             json={'prompt': 'hi', 'max_new_tokens': 4},
                             timeout=120)
        assert resp.status_code == 503, resp.text
        assert 'watchdog' in resp.json()['error']
        assert 'Retry-After' in resp.headers
        # Release the wedged (already abandoned) thread and serve again.
        fault_injection.disarm_all()
        resp = requests.post(url + '/generate',
                             json={'prompt': 'hi', 'max_new_tokens': 4},
                             timeout=120)
        assert resp.status_code == 200, resp.text
        assert len(resp.json()['token_ids'][0]) == 4
        assert server.engine._generation >= 1  # watchdog really fired

    def test_decode_fault_fails_inflight_then_engine_recovers(
            self, wd_server):
        """A decode-step EXCEPTION (fail, not wedge) takes the existing
        in-tick recovery path: in-flight futures fail with the injected
        error, the same engine thread keeps serving."""
        server, _ = wd_server
        gen_before = server.engine._generation
        fault_injection.arm('engine.decode', 'fail:1')
        fut = server.engine.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(fault_injection.InjectedFault):
            fut.result(timeout=120)
        fault_injection.disarm_all()
        toks, _ = server.engine.generate([1, 2, 3], max_new_tokens=4,
                                         timeout=120)
        assert len(toks) == 4
        # No watchdog involvement: this is tick-level self-healing.
        assert server.engine._generation == gen_before

    def test_request_deadline(self, wd_server):
        server, url = wd_server
        fut = server.engine.submit([1, 2, 3], max_new_tokens=4,
                                   deadline=time.time() - 1.0)
        with pytest.raises(exceptions.RequestDeadlineExceededError):
            fut.result(timeout=60)
        # Server-level: timeout_s → 504 with the deadline error.
        resp = requests.post(url + '/generate',
                             json={'prompt': 'hi', 'max_new_tokens': 4,
                                   'timeout_s': 1e-9}, timeout=60)
        assert resp.status_code == 504, resp.text
        assert 'expired' in resp.json()['error']


@pytest.fixture(scope='module')
def overload_server():
    """num_slots=1 + max_queue_depth=1: the smallest engine where a
    third concurrent request MUST be shed."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                      max_queue_depth=1)
    engine.generate([1, 2, 3], max_new_tokens=2, timeout=300)  # compile
    server = _wrap_server(engine)
    port = _serve_in_thread(server.make_app())
    yield server, f'http://127.0.0.1:{port}'
    fault_injection.disarm_all()
    engine.stop()


class TestOverloadAndDrain:

    def test_queue_overload_sheds_while_admitted_complete(
            self, overload_server):
        """Acceptance (b): with the slot busy (wedged) and the queue at
        cap, a new /generate gets 503 + Retry-After and /v1/completions
        gets 429 + Retry-After; the two already-accepted requests
        complete normally once the wedge releases."""
        server, url = overload_server
        engine = server.engine
        fault_injection.arm('engine.decode', 'wedge')
        results = {}

        def post(key):
            results[key] = requests.post(
                url + '/generate',
                json={'prompt': 'aa', 'max_new_tokens': 4}, timeout=120)

        t1 = threading.Thread(target=post, args=('first',), daemon=True)
        t1.start()
        # Deterministic sequencing: wait until request 1 is ADMITTED
        # (the tick reached the wedged decode point)...
        deadline = time.time() + 60
        while fault_injection.trip_count('engine.decode') < 1 and \
                time.time() < deadline:
            time.sleep(0.01)
        assert fault_injection.trip_count('engine.decode') >= 1
        # ...then fill the admission queue with request 2...
        t2 = threading.Thread(target=post, args=('second',), daemon=True)
        t2.start()
        deadline = time.time() + 60
        while engine._queue.qsize() < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert engine._queue.qsize() == 1
        # ...request 3 must be SHED, with retry guidance.
        resp = requests.post(url + '/generate',
                             json={'prompt': 'cc', 'max_new_tokens': 4},
                             timeout=30)
        assert resp.status_code == 503, resp.text
        assert 'Retry-After' in resp.headers
        assert 'queue' in resp.json()['error']
        # The OpenAI surface sheds with 429 (rate-limit semantics).
        resp = requests.post(url + '/v1/completions',
                             json={'prompt': 'dd', 'max_tokens': 4},
                             timeout=30)
        assert resp.status_code == 429, resp.text
        assert 'Retry-After' in resp.headers
        # Already-admitted requests complete once the wedge lifts.
        fault_injection.release('engine.decode')
        t1.join(timeout=120)
        t2.join(timeout=120)
        fault_injection.disarm_all()
        assert results['first'].status_code == 200
        assert results['second'].status_code == 200
        assert len(results['first'].json()['token_ids'][0]) == 4
        assert len(results['second'].json()['token_ids'][0]) == 4

    def test_draining_server_sheds_with_retry_after(self,
                                                    overload_server):
        server, url = overload_server
        server.draining = True
        try:
            resp = requests.get(url + '/health', timeout=30)
            assert resp.status_code == 503
            assert resp.json()['status'] == 'draining'
            resp = requests.post(url + '/generate',
                                 json={'prompt': 'x'}, timeout=30)
            assert resp.status_code == 503
            assert 'Retry-After' in resp.headers
            resp = requests.post(url + '/v1/chat/completions',
                                 json={'messages': [
                                     {'role': 'user', 'content': 'x'}]},
                                 timeout=30)
            assert resp.status_code == 503
        finally:
            server.draining = False

    def test_streaming_invalid_input_returns_400_not_500(
            self, overload_server):
        """Satellite: the /generate streaming branch must reject bad
        input with the same 400 JSON as the non-streaming path."""
        _, url = overload_server
        bad = {'prompt_ids': [[]], 'stream': True}  # empty prompt
        resp = requests.post(url + '/generate', json=bad, timeout=30)
        assert resp.status_code == 400, resp.text
        assert 'error' in resp.json()
        # Same class of error, non-streaming, for parity:
        resp = requests.post(url + '/generate',
                             json={'prompt_ids': [[]]}, timeout=30)
        assert resp.status_code == 400
        # Bad TYPES stream too: non-numeric max_new_tokens.
        resp = requests.post(url + '/generate',
                             json={'prompt': 'x', 'stream': True,
                                   'max_new_tokens': 'many'},
                             timeout=30)
        assert resp.status_code == 400

    def test_queued_deadline_fires_while_slot_busy(self,
                                                   overload_server):
        """A queued request's deadline must fire even while the single
        slot is occupied by another generation — not only at
        admission."""
        server, _ = overload_server
        engine = server.engine
        f1 = engine.submit([1, 2, 3], max_new_tokens=40)
        deadline = time.time() + 60
        while engine._slots[0] is None and time.time() < deadline:
            time.sleep(0.005)
        f2 = engine.submit([1, 2, 3], max_new_tokens=4,
                           deadline=time.time())
        with pytest.raises(exceptions.RequestDeadlineExceededError):
            f2.result(timeout=60)
        out, _stats = f1.result(timeout=120)  # unharmed
        assert len(out) == 40

    def test_shed_batch_cancels_submitted_head(self, overload_server):
        """A multi-prompt /generate shed mid-submit must cancel the
        prompts it already enqueued — orphans must not keep burning
        decode steps for a reader that got a 503."""
        server, url = overload_server
        engine = server.engine
        fault_injection.arm('engine.decode', 'wedge')
        results = {}

        def post():
            results['r'] = requests.post(
                url + '/generate',
                json={'prompt': 'zz', 'max_new_tokens': 4}, timeout=120)

        t1 = threading.Thread(target=post, daemon=True)
        t1.start()
        deadline = time.time() + 60
        while fault_injection.trip_count('engine.decode') < 1 and \
                time.time() < deadline:
            time.sleep(0.01)
        # Batch of 2: prompt[0] takes the last queue slot, prompt[1]
        # overflows → whole request shed, prompt[0] cancelled.
        resp = requests.post(url + '/generate',
                             json={'prompt': ['aa', 'bb'],
                                   'max_new_tokens': 4}, timeout=30)
        assert resp.status_code == 503, resp.text
        queued = list(engine._queue.queue)
        assert len(queued) == 1 and queued[0].future.cancelled()
        fault_injection.release('engine.decode')
        t1.join(timeout=120)
        fault_injection.disarm_all()
        assert results['r'].status_code == 200
        # The cancelled orphan was dropped at admission, not decoded;
        # the engine is idle and healthy again.
        deadline = time.time() + 60
        while engine._busy() and time.time() < deadline:
            time.sleep(0.01)
        assert not engine._busy()
        toks, _ = engine.generate([1, 2], max_new_tokens=3, timeout=120)
        assert len(toks) == 3

    def test_graceful_drain_finishes_inflight_then_refuses(
            self, overload_server):
        """MUST run last in this module: drain is terminal for the
        engine. In-flight work finishes, then submit refuses."""
        server, _ = overload_server
        engine = server.engine
        fut = engine.submit([1, 2, 3], max_new_tokens=4)
        assert engine.drain(timeout=120) is True
        out, _stats = fut.result(timeout=1)  # finished BEFORE drain returned
        assert len(out) == 4
        with pytest.raises(exceptions.EngineDrainingError):
            engine.submit([1], max_new_tokens=1)


# ---------------------------------------------------------------------
# load balancer: circuit breaking + half-open + idempotent retry
# ---------------------------------------------------------------------


class TestCircuitBreaker:

    def test_eject_halfopen_readmit_state_machine(self):
        """Acceptance (c), state-machine level, on an injected clock —
        no sleeps."""
        from skypilot_tpu.serve.load_balancer import ReplicaCircuitBreaker
        clock = {'t': 0.0}
        br = ReplicaCircuitBreaker(threshold=2, cooldown=10.0,
                                   clock=lambda: clock['t'])
        urls = ['u1', 'u2']
        br.record_failure('u1')
        assert br.blocked(urls) == set()          # below threshold
        br.record_failure('u1')
        assert br.blocked(urls) == {'u1'}         # ejected
        clock['t'] = 5.0
        assert br.blocked(urls) == {'u1'}         # cooling down
        clock['t'] = 10.5
        assert br.blocked(urls) == set()          # half-open: probe allowed
        br.record_failure('u1')                   # probe failed
        assert br.blocked(urls) == {'u1'}         # re-opened...
        clock['t'] = 15.0
        assert br.blocked(urls) == {'u1'}         # ...cooldown restarted
        clock['t'] = 21.0
        assert br.blocked(urls) == set()          # half-open again
        # Exactly ONE request is the probe: once claimed, concurrent
        # traffic keeps avoiding the replica until the probe reports.
        br.claim_probe('u1')
        assert br.blocked(urls) == {'u1'}
        br.record_success('u1')                   # probe succeeded
        assert br.blocked(urls) == set()          # closed
        br.record_failure('u1')                   # needs threshold anew
        assert br.blocked(urls) == set()

    def test_lb_retries_idempotent_ejects_and_readmits(self, monkeypatch):
        """Acceptance (c) end to end: one dead replica — GETs all
        succeed via retry-on-another-replica, the dead replica is
        ejected; once it comes back, the half-open probe re-admits
        it."""
        import http.server
        from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
        monkeypatch.setenv('SKYTPU_SERVE_LB_EJECT_THRESHOLD', '1')
        monkeypatch.setenv('SKYTPU_SERVE_LB_EJECT_COOLDOWN', '0.3')

        good_port, bad_port = _free_port(), _free_port()
        good_srv = http.server.ThreadingHTTPServer(
            ('127.0.0.1', good_port),
            http.server.SimpleHTTPRequestHandler)
        threading.Thread(target=good_srv.serve_forever,
                         daemon=True).start()
        lb_port = _free_port()
        lb = SkyServeLoadBalancer('http://127.0.0.1:1', lb_port)
        good = f'http://127.0.0.1:{good_port}'
        bad = f'http://127.0.0.1:{bad_port}'
        lb.policy.set_ready_replicas([good, bad])
        lb.start_in_thread()
        lb_url = f'http://127.0.0.1:{lb_port}/'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                requests.get(lb_url, timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)
        try:
            # Idempotent GETs never surface the dead replica.
            codes = [requests.get(lb_url, timeout=15).status_code
                     for _ in range(6)]
            assert codes == [200] * 6, codes
            assert lb.breaker.is_ejected(bad)
            # The replica comes back; after the cooldown the half-open
            # probe request re-admits it (breaker closes).
            bad_srv = http.server.ThreadingHTTPServer(
                ('127.0.0.1', bad_port),
                http.server.SimpleHTTPRequestHandler)
            threading.Thread(target=bad_srv.serve_forever,
                             daemon=True).start()
            time.sleep(0.4)  # > cooldown
            codes = [requests.get(lb_url, timeout=15).status_code
                     for _ in range(4)]
            assert codes == [200] * 4, codes
            assert not lb.breaker.is_ejected(bad)
            bad_srv.shutdown()
        finally:
            good_srv.shutdown()


# ---------------------------------------------------------------------
# fleet storm: cache-aware routing + breakers + drain + digest chaos
# ---------------------------------------------------------------------


_GROUP_A = list(range(1, 21))        # 20 tokens → chunk hashes at 8, 16
_GROUP_B = list(range(40, 60))
_GROUP_C = list(range(70, 90))


def _chunk_hashes(ids, chunk=8):
    from skypilot_tpu.models.kv_cache import prefix_route_hash
    return [prefix_route_hash(ids[:k * chunk])
            for k in range(1, (len(ids) - 1) // chunk + 1)]


class TestFleetStorm:
    """THE fleet-robustness acceptance scenario (ISSUE 9): a 3-replica
    fleet behind the prefix-aware LB survives a storm of preemption
    drains, transport deaths (breaker trips), stale digests, and
    corrupt digests — with a fake clock driving breaker cooldowns and
    digest staleness, zero requests lost non-retryably, bounded retry
    amplification, greedy output bit-identical to a single healthy
    replica regardless of which replica served, and the metrics
    autoscaler's storm decisions replayable from its log."""

    @pytest.fixture(scope='class')
    def fleet(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        from skypilot_tpu.serve.load_balancer import (
            ReplicaCircuitBreaker, SkyServeLoadBalancer)
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        engines, servers, urls = [], [], []
        for _ in range(3):
            engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                              paged_block_size=8,
                                              prefix_cache=4)
            engine.generate([1, 2, 3], max_new_tokens=2,
                            timeout=300)  # compile
            server = _wrap_server(engine)
            port = _serve_in_thread(server.make_app())
            engines.append(engine)
            servers.append(server)
            urls.append(f'http://127.0.0.1:{port}')
        # The bit-identity oracle: one never-stormed engine with the
        # same seed/config (engines are weight-identical by seed).
        ref = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                       paged_block_size=8,
                                       prefix_cache=4)

        clock = {'t': 0.0}
        policy = PrefixAwarePolicy(clock=lambda: clock['t'])
        lb_port = _free_port()
        lb = SkyServeLoadBalancer('http://127.0.0.1:1', lb_port,
                                  policy_name='prefix_aware')
        lb.policy = policy
        # threshold=1 + huge cooldown on the fake clock: one transport
        # error ejects a replica for the rest of the storm.
        lb.breaker = ReplicaCircuitBreaker(threshold=1, cooldown=1e9,
                                           clock=lambda: clock['t'])
        policy.set_ready_replicas(list(urls))
        lb.start_in_thread()
        lb_url = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                requests.get(lb_url + '/metrics', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)
        yield {'engines': engines, 'servers': servers, 'urls': urls,
               'ref': ref, 'lb': lb, 'policy': policy, 'clock': clock,
               'lb_url': lb_url}
        fault_injection.disarm_all()
        for engine in engines:
            engine.stop()
        ref.stop()

    def _post(self, lb_url, ids, attempts, max_attempts=4):
        """Client-side retry loop: every non-200 must be RETRYABLE
        (502 upstream error or 503 with Retry-After) — a request is
        'lost non-retryably' iff this helper raises."""
        for _ in range(max_attempts):
            attempts['n'] += 1
            resp = requests.post(
                lb_url + '/generate',
                json={'prompt_ids': [ids], 'max_new_tokens': 4},
                timeout=300)
            if resp.status_code == 200:
                return resp.json()['token_ids'][0]
            assert resp.status_code in (502, 503), resp.text
            if resp.status_code == 503:
                assert 'Retry-After' in resp.headers, resp.text
        raise AssertionError(f'request lost non-retryably: {ids[:4]}...')

    def test_storm_invariants(self, fleet):
        from skypilot_tpu.serve import autoscalers
        from skypilot_tpu.serve.serve_state import ReplicaStatus
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        engines = fleet['engines']
        servers = fleet['servers']
        urls = fleet['urls']
        ref, lb, policy = fleet['ref'], fleet['lb'], fleet['policy']
        clock, lb_url = fleet['clock'], fleet['lb_url']

        workload = [
            _GROUP_A, _GROUP_B,
            _GROUP_A + [30, 31], _GROUP_B + [61, 62],
            _GROUP_A + [30, 31, 32], _GROUP_B + [61, 62, 63],
        ]
        reference = {tuple(ids): ref.generate(ids, max_new_tokens=4,
                                              timeout=300)[0]
                     for ids in workload + [_GROUP_C, _GROUP_C + [91]]}
        attempts = {'n': 0}
        served = 0

        # Storm-long autoscaler, fed each phase; replayed at the end.
        spec = SkyServiceSpec(min_replicas=1, max_replicas=6,
                              target_queue_depth_per_replica=2.0,
                              upscale_delay_seconds=0,
                              downscale_delay_seconds=0)
        scaler = autoscalers.MetricsAutoscaler(spec)

        class _Info:

            def __init__(self, rid, status=ReplicaStatus.READY):
                self.replica_id = rid
                self.status = status
                self.version = 1
                self.is_spot = False

        def autoscale_tick(signals, statuses):
            scaler.collect_replica_metrics(signals)
            return scaler.evaluate_scaling(
                [_Info(i, st) for i, st in enumerate(statuses)])

        def engine_signals(extra=0.0):
            return {i: {'queue_depth': e.queue_load() + extra}
                    for i, e in enumerate(engines)}

        # ---- wave 1: warm traffic, cache-aware convergence ----
        for ids in workload:
            out = self._post(lb_url, ids, attempts)
            assert out == reference[tuple(ids)]
            served += 1
        # Repeats of a group converged onto the replica holding it.
        assert policy.stats['hit'] >= 3, policy.stats
        autoscale_tick(engine_signals(), [ReplicaStatus.READY] * 3)

        # ---- phase 2: a dead replica with the most attractive digest
        # (transport death mid-advertisement) → breaker trip + retry ----
        dead_url = f'http://127.0.0.1:{_free_port()}'
        policy.set_ready_replicas(list(urls) + [dead_url])
        policy.observe_response(dead_url, {
            'X-SkyTPU-Queue-Depth': '0',
            'X-SkyTPU-Prefix-Digest':
                'v1:8:1:' + ','.join(_chunk_hashes(_GROUP_C + [91])),
        })
        before = attempts['n']
        out = self._post(lb_url, _GROUP_C, attempts)
        assert out == reference[tuple(_GROUP_C)]
        served += 1
        # Exactly one wasted attempt: the digest pointed at the corpse,
        # the 502 charged its breaker, the retry landed elsewhere.
        assert attempts['n'] - before == 2
        assert lb.breaker.is_ejected(dead_url)
        # Follow-up traffic never touches the ejected replica again:
        # bounded amplification, not one 502 per request.
        before = attempts['n']
        out = self._post(lb_url, _GROUP_C + [91], attempts)
        assert out == reference[tuple(_GROUP_C + [91])]
        served += 1
        assert attempts['n'] - before == 1
        autoscale_tick({**engine_signals(), 3: {'queue_depth': 10.0}},
                       [ReplicaStatus.READY] * 3)

        # ---- phase 3: every digest goes stale (fake clock) — routing
        # falls back least-loaded, never blocks or errors ----
        clock['t'] += 1e5
        before_stale = policy.stats['stale']
        out = self._post(lb_url, _GROUP_A + [30, 31], attempts)
        assert out == reference[tuple(_GROUP_A + [30, 31])]
        served += 1
        assert policy.stats['stale'] > before_stale
        # That response re-advertised a fresh digest: hits resume.
        out = self._post(lb_url, _GROUP_A + [30, 31, 32], attempts)
        assert out == reference[tuple(_GROUP_A + [30, 31, 32])]
        served += 1

        # ---- phase 4: corrupt digest on the wire (lb.digest) ----
        rejected_before = policy.stats['digest_rejected']
        fault_injection.arm('lb.digest', 'fail:1')
        try:
            out = self._post(lb_url, _GROUP_B + [61, 62], attempts)
        finally:
            fault_injection.disarm_all()
        assert out == reference[tuple(_GROUP_B + [61, 62])]
        served += 1
        assert policy.stats['digest_rejected'] == rejected_before + 1

        # ---- phase 5: preemption drain of the replica holding GROUP_B
        # (notice semantics: 503 + X-SkyTPU-Draining, learned in-band,
        # excluded, traffic re-prefills elsewhere bit-identically) ----
        # One clean request first: phase 3 staled and phase 4 rejected
        # B's digest, so re-learn which replica holds it now.
        out = self._post(lb_url, _GROUP_B + [61, 62], attempts)
        assert out == reference[tuple(_GROUP_B + [61, 62])]
        served += 1
        hash_b = _chunk_hashes(_GROUP_B)[-1]
        # The replica whose FRESH digest advertises B (stale wave-1
        # digests may also mention it but cannot win a route).
        holder = next(
            u for u, d in policy._digests.items()  # pylint: disable=protected-access
            if u in urls and hash_b in d['hashes'] and
            clock['t'] - d['at'] < 30.0)
        servers[urls.index(holder)].draining = True
        before = attempts['n']
        out = self._post(lb_url, _GROUP_B + [61, 62, 63], attempts)
        assert out == reference[tuple(_GROUP_B + [61, 62, 63])]
        served += 1
        # The digest hit routed to the now-draining holder, whose 503
        # was learned in-band; exactly one replay landed elsewhere.
        assert holder in lb._draining_urls  # pylint: disable=protected-access
        assert attempts['n'] - before == 2
        # Storm-wide amplification bound: one extra attempt per
        # distinct failure EVENT (dead digest, drain flip), not per
        # request.
        assert attempts['n'] <= served + 3, (attempts['n'], served)
        autoscale_tick(
            {i: {'queue_depth': 0.0} for i in range(3)},
            [ReplicaStatus.READY, ReplicaStatus.DRAINING,
             ReplicaStatus.READY])

        # ---- the autoscaler's storm decisions replay exactly, and a
        # DRAINING replica was never picked as a downscale victim ----
        replayed = autoscalers.replay_decision_log(
            spec, scaler.decision_log)
        assert replayed == [entry['decisions']
                            for entry in scaler.decision_log]
        for entry in scaler.decision_log:
            draining_ids = {rid for rid, status, _v, _s
                            in entry['replicas']
                            if status == 'DRAINING'}
            for _op, target in entry['decisions']:
                assert target not in draining_ids

    def test_draining_replica_sheds_with_digest_headers_intact(
            self, fleet):
        """A draining replica's shed responses still carry fleet-intel
        headers (the middleware is unconditional) — and the LB keeps
        excluding it without charging its breaker."""
        servers, urls, lb = fleet['servers'], fleet['urls'], fleet['lb']
        draining_idx = next(
            (i for i, s in enumerate(servers) if s.draining), None)
        if draining_idx is None:
            servers[1].draining = True
            draining_idx = 1
        resp = requests.post(urls[draining_idx] + '/generate',
                             json={'prompt': 'x'}, timeout=30)
        assert resp.status_code == 503
        assert resp.headers.get('X-SkyTPU-Draining') == '1'
        assert 'X-SkyTPU-Queue-Depth' in resp.headers
        assert not lb.breaker.is_ejected(urls[draining_idx])


# ---------------------------------------------------------------------
# disaggregated prefill/decode: preemption-safe block-granular handoff
# ---------------------------------------------------------------------


class TestDisaggHandoff:
    """The disaggregation acceptance scenario (ISSUE 13): a tiered
    fleet (2 prefill + 2 decode replicas, real servers, real LB)
    survives handoff faults at every seam — `lb.handoff` (dispatch
    lost), `kv.stream` (prefill replica preempted mid-stream),
    `engine.ingest` (decode-side failure, unit-pinned in
    tests/test_disagg.py) — with every request completing BIT-IDENTICAL
    to a monolithic replica (retries allowed, zero non-retryable
    losses) and every partial ingest rolled back to refcount-0 (the
    pool `check()` invariant). Plus the long-prompt storm pin: the
    decode tier keeps serving short traffic while the prefill tier is
    saturated mid-handoff."""

    # Distinct prompt ranges per test: a digest learned by an earlier
    # test must not turn a later test's handoff into a plain hit.
    _P1 = list(range(1, 25))
    _P2 = list(range(40, 64))
    _P3 = list(range(70, 94))
    _P4 = list(range(100, 124))
    _P5 = list(range(130, 154))
    _SHORT = [7, 8, 9]

    @pytest.fixture(scope='class')
    def tiered_fleet(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        env_overrides = {
            # Long = 16+ tokens; one block per chunk so a handoff is a
            # REAL multi-chunk stream (24 tokens / bs 8 = 3 chunks).
            'SKYTPU_SERVE_LB_DISAGG_THRESHOLD': '16',
            'SKYTPU_SERVE_HANDOFF_CHUNK_BLOCKS': '1',
        }
        saved = {k: os.environ.get(k) for k in env_overrides}
        os.environ.update(env_overrides)
        engines, servers, urls, tiers = [], [], [], {}
        for tier in ('prefill', 'prefill', 'decode', 'decode'):
            engine = ContinuousBatchingEngine(
                _cfg(), num_slots=2, paged_block_size=8,
                prefix_cache=6, tier=tier)
            engine.generate([1, 2, 3], max_new_tokens=2,
                            timeout=300)  # compile
            server = _wrap_server(engine)
            server.tier = tier
            port = _serve_in_thread(server.make_app())
            engines.append(engine)
            servers.append(server)
            url = f'http://127.0.0.1:{port}'
            urls.append(url)
            tiers[url] = tier
        # Bit-identity oracle: a never-disaggregated monolithic engine
        # (weight-identical by seed).
        ref = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                       paged_block_size=8,
                                       prefix_cache=6)
        policy = PrefixAwarePolicy()
        lb_port = _free_port()
        lb = SkyServeLoadBalancer('http://127.0.0.1:1', lb_port,
                                  policy_name='prefix_aware')
        lb.policy = policy
        policy.set_ready_replicas(list(urls))
        policy.set_replica_tiers(tiers)
        lb.start_in_thread()
        lb_url = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                requests.get(lb_url + '/metrics', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)
        yield {'engines': engines, 'servers': servers, 'urls': urls,
               'tiers': tiers, 'ref': ref, 'lb': lb, 'policy': policy,
               'lb_url': lb_url}
        fault_injection.disarm_all()
        for engine in engines:
            engine.stop()
        ref.stop()
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value

    def _post(self, lb_url, ids, max_attempts=4, max_new=4):
        """Every non-200 must be RETRYABLE (502, or 503 with
        Retry-After) — a request is lost non-retryably iff this
        raises."""
        for _ in range(max_attempts):
            resp = requests.post(
                lb_url + '/generate',
                json={'prompt_ids': [ids], 'max_new_tokens': max_new},
                timeout=300)
            if resp.status_code == 200:
                return resp.json()['token_ids'][0]
            assert resp.status_code in (502, 503), resp.text
            if resp.status_code == 503:
                assert 'Retry-After' in resp.headers, resp.text
        raise AssertionError(f'request lost non-retryably: {ids[:4]}...')

    @staticmethod
    def _decode_engines(fleet):
        return [e for e, u in zip(fleet['engines'], fleet['urls'])
                if fleet['tiers'][u] == 'decode']

    @staticmethod
    def _check_pools(fleet):
        for engine in fleet['engines']:
            engine._pool.check()  # pylint: disable=protected-access

    def test_clean_handoff_bit_identical_and_attributed(self,
                                                        tiered_fleet):
        """No faults: a long prompt routes prefill tier → decode tier,
        the KV streams block-granularly, and the request decodes
        bit-identically to the monolithic oracle with the hit
        attributed to the handoff (prewarm semantics)."""
        fleet = tiered_fleet
        expect = fleet['ref'].generate(self._P1, max_new_tokens=4,
                                       timeout=300)[0]
        out = self._post(fleet['lb_url'], self._P1)
        assert out == expect
        assert fleet['policy'].stats['handoff'] >= 1
        decodes = self._decode_engines(fleet)
        assert sum(e.ingest_stats['streams_completed']
                   for e in decodes) == 1
        assert sum(e.prefix_stats['prewarm_hits'] for e in decodes) == 1
        # The handoff really streamed chunk-granularly: 3 blocks at
        # one block per chunk.
        assert sum(e.ingest_stats['chunks_ok'] for e in decodes) == 3
        assert sum(e.ingest_stats['blocks_ingested']
                   for e in decodes) == 3
        # A repeat is a digest HIT on the warm decode replica — no
        # second handoff, still bit-identical.
        handoffs = fleet['policy'].stats['handoff']
        assert self._post(fleet['lb_url'], self._P1) == expect
        assert fleet['policy'].stats['handoff'] == handoffs
        assert fleet['policy'].stats['hit'] >= 1
        self._check_pools(fleet)

    def test_lb_dispatch_fault_redispatches(self, tiered_fleet):
        """Armed lb.handoff: the two-stage dispatch itself fails once —
        the LB re-dispatches to another prefill replica; the request
        completes bit-identically, nothing is lost."""
        fleet = tiered_fleet
        expect = fleet['ref'].generate(self._P2, max_new_tokens=4,
                                       timeout=300)[0]
        fault_injection.arm('lb.handoff', 'fail:1')
        try:
            out = self._post(fleet['lb_url'], self._P2)
            trips = fault_injection.trip_count('lb.handoff')
        finally:
            fault_injection.disarm_all()
        assert out == expect
        assert trips >= 1
        self._check_pools(fleet)

    def test_prefill_preempted_midstream_redispatches(self,
                                                      tiered_fleet):
        """THE acceptance cell: a prefill replica dies mid-handoff
        (armed kv.stream). The LB aborts the partial ingest (refcount-0
        on the decode side), re-dispatches to the OTHER prefill
        replica, and the request completes bit-identically — retries
        allowed, zero non-retryable losses."""
        fleet = tiered_fleet
        decodes = self._decode_engines(fleet)
        aborted_before = sum(e.ingest_stats['streams_aborted'] +
                             e.ingest_stats['streams_expired']
                             for e in decodes)
        completed_before = sum(e.ingest_stats['streams_completed']
                               for e in decodes)
        expect = fleet['ref'].generate(self._P3, max_new_tokens=4,
                                       timeout=300)[0]
        fault_injection.arm('kv.stream', 'fail:1')
        try:
            out = self._post(fleet['lb_url'], self._P3)
            trips = fault_injection.trip_count('kv.stream')
        finally:
            fault_injection.disarm_all()
        assert out == expect
        assert trips >= 1
        # The re-dispatched handoff completed on the second prefill
        # replica; no partial stream survives anywhere (refcount-0:
        # pool invariants hold on every engine).
        assert sum(e.ingest_stats['streams_completed']
                   for e in decodes) == completed_before + 1
        for engine in decodes:
            assert not engine._ingest_sessions  # pylint: disable=protected-access
        del aborted_before  # first-chunk faults leave nothing to abort
        self._check_pools(fleet)

    def test_all_prefill_dead_falls_back_monolithic(self, tiered_fleet):
        """Every prefill replica failing mid-handoff degrades to
        monolithic serving ON the decode replica: strictly slower,
        bit-identical, never lost."""
        fleet = tiered_fleet
        decodes = self._decode_engines(fleet)
        completed_before = sum(e.ingest_stats['streams_completed']
                               for e in decodes)
        expect = fleet['ref'].generate(self._P4, max_new_tokens=4,
                                       timeout=300)[0]
        fault_injection.arm('kv.stream', 'fail')   # every firing
        try:
            out = self._post(fleet['lb_url'], self._P4)
        finally:
            fault_injection.disarm_all()
        assert out == expect
        # No stream completed — the decode replica prefilled locally.
        assert sum(e.ingest_stats['streams_completed']
                   for e in decodes) == completed_before
        for engine in decodes:
            assert not engine._ingest_sessions  # pylint: disable=protected-access
        self._check_pools(fleet)

    def test_partial_ingest_aborts_to_refcount_zero_over_http(
            self, tiered_fleet):
        """A genuinely PARTIAL stream (2 of 3 chunks landed over HTTP)
        aborts back to refcount-0 through the same /kv/abort the LB
        uses after a mid-stream death."""
        fleet = tiered_fleet
        prefill_url = next(u for u in fleet['urls']
                           if fleet['tiers'][u] == 'prefill')
        decode_url = next(u for u in fleet['urls']
                          if fleet['tiers'][u] == 'decode')
        prefill_engine = fleet['engines'][
            fleet['urls'].index(prefill_url)]
        decode_engine = fleet['engines'][
            fleet['urls'].index(decode_url)]
        prefill_engine.prefill_prefix(self._P5, timeout=300)
        chunks = prefill_engine.export_prefix_chunks(
            self._P5, 'chaos-partial', chunk_blocks=1)
        assert len(chunks) == 3
        used = decode_engine._pool.used  # pylint: disable=protected-access
        for chunk in chunks[:2]:
            resp = requests.post(decode_url + '/kv/ingest', data=chunk,
                                 timeout=60)
            assert resp.status_code == 200, resp.text
        assert decode_engine._pool.used == used + 2  # pylint: disable=protected-access
        resp = requests.post(decode_url + '/kv/abort',
                             json={'stream_id': 'chaos-partial'},
                             timeout=60)
        assert resp.status_code == 200 and resp.json()['aborted']
        assert decode_engine._pool.used == used  # pylint: disable=protected-access
        decode_engine._pool.check()  # pylint: disable=protected-access

    def test_long_prompt_storm_decode_tier_unstalled(self,
                                                     tiered_fleet):
        """The long-prompt storm pin: while the prefill tier is
        saturated mid-handoff (kv.stream wedged — a storm of long
        prompts in flight), short interactive traffic keeps completing
        on the decode tier, unstalled. Release the wedge and the long
        prompt completes bit-identically too."""
        fleet = tiered_fleet
        storm_ids = list(range(160, 184))
        expect_long = fleet['ref'].generate(storm_ids, max_new_tokens=4,
                                            timeout=300)[0]
        expect_short = fleet['ref'].generate(self._SHORT,
                                             max_new_tokens=4,
                                             timeout=300)[0]
        results = {}
        fault_injection.arm('kv.stream', 'wedge')

        def long_post():
            results['long'] = self._post(fleet['lb_url'], storm_ids)

        thread = threading.Thread(target=long_post, daemon=True)
        thread.start()
        try:
            # Deterministic sequencing: the handoff reached the wedged
            # chunk push — the prefill tier is now saturated.
            deadline = time.time() + 60
            while fault_injection.trip_count('kv.stream') < 1 and \
                    time.time() < deadline:
                time.sleep(0.01)
            assert fault_injection.trip_count('kv.stream') >= 1
            # Short interactive traffic completes promptly on the
            # decode tier while the storm holds the prefill tier.
            tier_before = fleet['policy'].stats['tier_decode']
            t0 = time.monotonic()
            for _ in range(3):
                assert self._post(fleet['lb_url'],
                                  self._SHORT) == expect_short
            short_wall = time.monotonic() - t0
            assert fleet['policy'].stats['tier_decode'] >= \
                tier_before + 3
            # Generous sanity bound — the point is "not blocked behind
            # the wedged handoff", which would hang to the timeout.
            assert short_wall < 60, short_wall
        finally:
            fault_injection.release('kv.stream')
            thread.join(timeout=300)
            fault_injection.disarm_all()
        assert results.get('long') == expect_long
        self._check_pools(fleet)

    def test_trace_context_round_trips_the_two_hop_handoff(
            self, tiered_fleet):
        """ISSUE 14 acceptance: a real 2-hop disaggregated request
        (LB → prefill → chunk stream → decode, live HTTP) produces
        ONE trace whose span tree keeps the full parentage — the
        lb.request root reaches the decode-side engine.ingest_publish
        through the prefill replica's server.request/server.kv_push
        (trace context via X-SkyTPU-Trace AND the chunk headers), and
        the served request's queue-wait/prefill/decode spans carry
        their timings."""
        from skypilot_tpu.observability import tracing
        fleet = tiered_fleet
        ids = list(range(190, 214))  # fresh range ⇒ a real handoff
        expect = fleet['ref'].generate(ids, max_new_tokens=4,
                                       timeout=300)[0]
        tracing.enable()
        tracing.reset()
        try:
            out = self._post(fleet['lb_url'], ids)
            # The LB closes its lb.proxy and lb.request spans after it
            # has written the response, which the client may have read
            # already: wait for the root (a loaded machine lost the
            # race twice in two whole runs).
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not any(
                    s['name'] == 'lb.request' for s in tracing.snapshot()):
                time.sleep(0.01)
            # (a first-time compile on the way is a span of a trace of
            # its own, not part of the request's)
            spans = [s for s in tracing.snapshot()
                     if s['name'] != 'engine.compile']
        finally:
            tracing.disable()
            tracing.reset()
        assert out == expect
        names = {s['name'] for s in spans}
        assert {'lb.request', 'lb.route', 'lb.handoff',
                'lb.handoff_attempt', 'lb.proxy', 'server.request',
                'server.kv_push', 'engine.queue_wait',
                'engine.prefill', 'engine.decode',
                'engine.ingest_chunk',
                'engine.ingest_publish'} <= names, sorted(names)
        # ONE trace end to end.
        assert len({s['trace_id'] for s in spans}) == 1
        by_id = {s['span_id']: s for s in spans}

        def chain(span):
            out_chain = [span['name']]
            while span.get('parent_id') in by_id:
                span = by_id[span['parent_id']]
                out_chain.append(span['name'])
            return list(reversed(out_chain))

        # The KV stream's publish on the DECODE replica chains back to
        # the LB root through the prefill replica: ≥ 4 hops.
        publish = next(s for s in spans
                       if s['name'] == 'engine.ingest_publish')
        publish_chain = chain(publish)
        assert publish_chain[0] == 'lb.request'
        assert 'server.kv_push' in publish_chain
        assert len(publish_chain) >= 5, publish_chain
        # The served (decode-tier) request's spans sit under lb.proxy
        # → server.request, with timings attached.
        decode = max((s for s in spans if s['name'] == 'engine.decode'),
                     key=lambda s: s['ts_us'])
        decode_chain = chain(decode)
        assert decode_chain[0] == 'lb.request'
        assert 'server.request' in decode_chain
        prefills = [s for s in spans if s['name'] == 'engine.prefill']
        assert all(s['attrs']['ttft_s'] >= 0 for s in prefills)
        # The routing decision recorded WHY it chose what it chose.
        route = next(s for s in spans if s['name'] == 'lb.route')
        assert route['attrs']['result'] == 'handoff'
        handoff = next(s for s in spans if s['name'] == 'lb.handoff')
        assert handoff['attrs']['outcome'] == 'ok'
        assert handoff['attrs']['chunks'] == 3
        self._check_pools(fleet)


# ---------------------------------------------------------------------
# controller-RPC escalation: serve mirror + cross-process jobs CLI
# ---------------------------------------------------------------------


class TestServeSyncEscalation:
    """Satellite: _sync_remote_service mirrors the jobs path — one
    transient CommandError keeps last-known state; only repeated
    failures (via the shared persistent tracker) escalate to the cloud
    probe and CONTROLLER_FAILED."""

    @pytest.fixture(autouse=True)
    def _env(self, _isolate_state, monkeypatch):
        from skypilot_tpu.serve import serve_state
        monkeypatch.setenv('SKYTPU_RPC_ATTEMPTS', '1')
        serve_state._db = None  # pylint: disable=protected-access
        yield
        fault_injection.disarm_all()

    def _make_remote_service(self, name):
        from skypilot_tpu.serve import serve_state
        assert serve_state.add_service(name, 'round_robin', '/dev/null')
        serve_state.set_service_remote_cluster(name, f'ctrl-{name}')
        serve_state.set_service_status(name,
                                       serve_state.ServiceStatus.READY)
        return serve_state.get_service(name)

    def test_transient_keeps_state_third_failure_escalates(self):
        from skypilot_tpu.serve import core as serve_core
        from skypilot_tpu.serve.serve_state import ServiceStatus
        from skypilot_tpu.serve import serve_state
        record = self._make_remote_service('rsync')
        fault_injection.arm('rpc.send', 'fail')
        for expected_fails in (1, 2):
            out = serve_core._sync_remote_service(dict(record))
            assert out['status'] == ServiceStatus.READY  # last-known kept
            assert serve_state.get_service('rsync')['status'] == \
                ServiceStatus.READY
            assert retry_lib.rpc_failure_tracker.count(
                'ctrl-rsync') == expected_fails
        # 3rd failure: cloud probe of the (nonexistent) cluster says
        # gone → CONTROLLER_FAILED, counter reset.
        out = serve_core._sync_remote_service(dict(record))
        assert out['status'] == ServiceStatus.CONTROLLER_FAILED
        assert serve_state.get_service('rsync')['status'] == \
            ServiceStatus.CONTROLLER_FAILED
        assert retry_lib.rpc_failure_tracker.count('ctrl-rsync') == 0

    def test_success_resets_counter(self, monkeypatch):
        from skypilot_tpu.serve import core as serve_core
        from skypilot_tpu.serve.serve_state import ServiceStatus
        record = self._make_remote_service('rok')
        fault_injection.arm('rpc.send', 'fail')
        serve_core._sync_remote_service(dict(record))
        assert retry_lib.rpc_failure_tracker.count('ctrl-rok') == 1
        fault_injection.disarm_all()
        from skypilot_tpu.utils import remote_rpc
        monkeypatch.setattr(
            remote_rpc, 'rpc',
            lambda *a, **k: {'status': 'READY', 'current_version': 1,
                             'controller_port': 1, 'lb_port': 2,
                             'replica_info': []})
        out = serve_core._sync_remote_service(dict(record))
        assert out['status'] == ServiceStatus.READY
        assert retry_lib.rpc_failure_tracker.count('ctrl-rok') == 0

    def test_cluster_not_up_is_definitive(self, monkeypatch):
        from skypilot_tpu.serve import core as serve_core
        from skypilot_tpu.serve.serve_state import ServiceStatus
        from skypilot_tpu.utils import remote_rpc
        record = self._make_remote_service('rgone')

        def not_up(*a, **k):
            raise exceptions.ClusterNotUpError('stopped')

        monkeypatch.setattr(remote_rpc, 'rpc', not_up)
        out = serve_core._sync_remote_service(dict(record))
        assert out['status'] == ServiceStatus.CONTROLLER_FAILED


class TestJobsCliEscalationAcrossProcesses:
    """Acceptance (d): `jobs queue` in FRESH processes — the
    consecutive-failure count persists in the state db, so the 3rd
    invocation (not the 3rd in-process call) escalates to the forced
    cloud probe and marks FAILED_CONTROLLER."""

    def test_three_fresh_processes_escalate(self, _isolate_state):
        from skypilot_tpu import global_user_state
        from skypilot_tpu.jobs import state as jobs_state
        from skypilot_tpu.jobs.state import ManagedJobStatus
        jobs_state._db = None  # pylint: disable=protected-access
        job_id = jobs_state.set_job_info('chaosjob', '')
        jobs_state.set_pending(job_id, 0, 'task-0', 'tpu-v5e-1')
        jobs_state.set_started(job_id, 0, 'task-cluster-x')
        jobs_state.set_remote_cluster(job_id, 'ctrl-chaos')
        assert jobs_state.get_status(job_id) == ManagedJobStatus.RUNNING
        global_user_state.set_enabled_clouds(['fake'])

        env = dict(os.environ)
        env['SKYTPU_FAULTS'] = 'rpc.send=fail'
        env['SKYTPU_RPC_ATTEMPTS'] = '1'
        env['JAX_PLATFORMS'] = 'cpu'
        cli = [sys.executable, '-m', 'skypilot_tpu', 'jobs', 'queue']

        for expected_fails in (1, 2):
            proc = subprocess.run(cli, env=env, capture_output=True,
                                  text=True, timeout=300,
                                  cwd='/root/repo')
            assert proc.returncode == 0, proc.stderr[-2000:]
            # Transient: last-known state kept, counter persisted.
            assert jobs_state.get_status(job_id) == \
                ManagedJobStatus.RUNNING
            assert retry_lib.rpc_failure_tracker.count(
                'ctrl-chaos') == expected_fails
        # Third fresh process: threshold reached → forced cloud probe
        # (the cluster does not exist anywhere) → FAILED_CONTROLLER.
        proc = subprocess.run(cli, env=env, capture_output=True,
                              text=True, timeout=300, cwd='/root/repo')
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert jobs_state.get_status(job_id) == \
            ManagedJobStatus.FAILED_CONTROLLER
        assert retry_lib.rpc_failure_tracker.count('ctrl-chaos') == 0
        record = jobs_state.get_task_records(job_id)[0]
        assert 'consecutive RPC failures' in record['failure_reason']
