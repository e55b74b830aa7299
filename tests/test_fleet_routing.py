"""Fleet routing + metrics-driven autoscaling (tier-1, CPU; engines are
built only by TestFleetOverRealEngines): the routing brain of ROADMAP
item 3, unit-level.

- kv_cache digest: stable cross-process hashes, chunk-aligned prefix
  coverage, epoch bumps on content mutation only;
- PrefixAwarePolicy: cache-aware deepest-match win, stale/corrupt
  digest fallback (never fail closed), phase-aware partition with
  graceful collapse, least-loaded fallback with deterministic
  tie-break, full-exclusion → None;
- RoundRobinPolicy edge cases: rotation reset on membership change,
  full-exclusion → None (the LB-policy satellite);
- prefix-aware vs round-robin on a shared-prefix workload: strictly
  more prefix hits, simulated with deterministic PrefixIndex-backed
  fake replicas, then over three real engines with a dead replica and
  a corrupt digest on the wire (TestFleetOverRealEngines);
- MetricsAutoscaler: pressure math, hysteresis, flap damping,
  DRAINING-awareness, decision-log replay;
- serve/server satellites: fleet-intel response headers
  (X-SkyTPU-Queue-Depth / X-SkyTPU-Prefix-Digest) and the
  _delta_decoder flush() corrected-tail fix (a round-5 review finding).
"""
import threading
import types

import pytest

from skypilot_tpu.models import kv_cache as kv_cache_lib
from skypilot_tpu.models.kv_cache import PrefixIndex, prefix_route_hash
from skypilot_tpu.serve import autoscalers
from skypilot_tpu.serve.load_balancing_policies import (PrefixAwarePolicy,
                                                        RoundRobinPolicy)
from skypilot_tpu.serve.serve_state import ReplicaStatus
from skypilot_tpu.serve.service_spec import SkyServiceSpec
from skypilot_tpu.utils import fault_injection


def _digest_header(index: PrefixIndex) -> dict:
    return {
        'X-SkyTPU-Queue-Depth': '0',
        'X-SkyTPU-Prefix-Digest':
            f'v1:{index.chunk}:{index.epoch}:' +
            ','.join(index.digest()),
    }


# ---------------------------------------------------------------------
# digest layer (kv_cache)
# ---------------------------------------------------------------------


class TestPrefixDigest:

    def test_route_hash_is_stable_and_type_insensitive(self):
        # Cross-process stability is the whole point (builtin hash() is
        # salted); pin the value so an accidental algorithm change —
        # which would silently zero every fleet's hit rate during a
        # rolling upgrade — fails loudly.
        assert prefix_route_hash([1, 2, 3]) == \
            prefix_route_hash((1, 2, 3))
        import zlib
        expected = f'{zlib.crc32(repr((1, 2, 3)).encode()):08x}'
        assert prefix_route_hash([1, 2, 3]) == expected

    def test_digest_covers_chunk_aligned_prefixes_newest_first(self):
        index = PrefixIndex(capacity=4, chunk=4)
        index.put(tuple(range(12)), 'a')          # chunks at 4, 8, 12
        index.put(tuple(range(100, 106)), 'b')    # chunk at 4
        digest = index.digest()
        for prefix in (range(4), range(8), range(12), range(100, 104)):
            assert prefix_route_hash(tuple(prefix)) in digest
        # Newest entry's hashes come first (deadline-friendly order).
        assert digest[0] == prefix_route_hash(tuple(range(100, 104)))
        # Bounded and deduped.
        assert len(digest) == len(set(digest)) == 4
        assert len(index.digest(max_hashes=2)) == 2

    def test_epoch_bumps_on_content_changes_only(self):
        index = PrefixIndex(capacity=2, chunk=4)
        epoch0 = index.epoch
        index.put((1, 2, 3, 4), 'a')
        assert index.epoch > epoch0
        e1 = index.epoch
        index.touch((1, 2, 3, 4))          # recency only
        assert index.epoch == e1
        index.put((5, 6, 7, 8), 'b')
        index.put((9, 10, 11, 12), 'c')    # evicts the oldest
        e2 = index.epoch
        assert e2 > e1
        index.pop_lru()
        assert index.epoch > e2


# ---------------------------------------------------------------------
# round-robin edge cases (the LB-policy satellite)
# ---------------------------------------------------------------------


class TestRoundRobinEdgeCases:

    def test_rotation_resets_on_membership_change(self):
        policy = RoundRobinPolicy()
        policy.set_ready_replicas(['a', 'b', 'c'])
        assert policy.select_replica() == 'a'
        assert policy.select_replica() == 'b'
        # Membership change (replacement replica): rotation restarts so
        # the fresh replica is not skipped a whole cycle.
        policy.set_ready_replicas(['a', 'b', 'd'])
        assert policy.select_replica() == 'a'
        # Same membership, different order: rotation is preserved.
        policy.set_ready_replicas(['d', 'b', 'a'])
        assert policy.index == 1

    def test_full_exclusion_returns_none(self):
        policy = RoundRobinPolicy()
        policy.set_ready_replicas(['a', 'b'])
        assert policy.select_replica(exclude={'a', 'b'}) is None
        # And with no replicas at all.
        policy.set_ready_replicas([])
        assert policy.select_replica() is None

    def test_base_select_wrapper_matches_select_replica(self):
        policy = RoundRobinPolicy()
        policy.set_ready_replicas(['a', 'b'])
        url, info = policy.select(hint={'token_ids': [1, 2, 3]})
        assert url == 'a' and info == {}


# ---------------------------------------------------------------------
# prefix-aware policy
# ---------------------------------------------------------------------


class TestPrefixAwarePolicy:

    def _policy(self, urls=('u1', 'u2', 'u3')):
        clock = {'t': 0.0}
        policy = PrefixAwarePolicy(clock=lambda: clock['t'])
        policy.set_ready_replicas(list(urls))
        return policy, clock

    def test_deepest_digest_match_wins(self):
        policy, _clock = self._policy()
        short = PrefixIndex(capacity=4, chunk=4)
        short.put(tuple(range(4)), 'x')
        deep = PrefixIndex(capacity=4, chunk=4)
        deep.put(tuple(range(12)), 'x')
        policy.observe_response('u3', _digest_header(short))
        policy.observe_response('u2', _digest_header(deep))
        url, info = policy.select(
            hint={'token_ids': list(range(14)), 'prompt_len': 14})
        assert url == 'u2'
        assert info == {'result': 'hit', 'matched_tokens': 12}

    def test_full_exclusion_returns_none_and_never_blocks(self):
        policy, _clock = self._policy()
        url, info = policy.select(exclude={'u1', 'u2', 'u3'},
                                  hint={'token_ids': [1, 2, 3]})
        assert url is None and info['result'] == 'no_replica'

    def test_excluded_replica_loses_its_digest_match(self):
        """Breaker-open / draining / already-tried replicas are excluded
        BEFORE digest matching: a warm but unreachable replica must not
        keep winning the route."""
        policy, _clock = self._policy()
        index = PrefixIndex(capacity=4, chunk=4)
        index.put(tuple(range(8)), 'x')
        policy.observe_response('u2', _digest_header(index))
        hint = {'token_ids': list(range(10)), 'prompt_len': 10}
        assert policy.select(hint=hint)[0] == 'u2'
        url, info = policy.select(exclude={'u2'}, hint=hint)
        assert url != 'u2' and info['result'] == 'miss'

    def test_stale_digest_falls_back_not_errors(self):
        policy, clock = self._policy()
        index = PrefixIndex(capacity=4, chunk=4)
        index.put(tuple(range(8)), 'x')
        policy.observe_response('u2', _digest_header(index))
        hint = {'token_ids': list(range(10)), 'prompt_len': 10}
        assert policy.select(hint=hint)[1]['result'] == 'hit'
        clock['t'] += 1e6                      # way past staleness
        url, info = policy.select(hint=hint)
        assert url is not None
        assert info['result'] == 'stale'
        assert policy.stats['stale'] == 1

    def test_corrupt_digest_rejected_and_injected_fault_degrades(self):
        policy, _clock = self._policy()
        # Garbage on the wire: dropped, counted, no exception.
        assert policy.observe_response(
            'u1', {'X-SkyTPU-Prefix-Digest': 'not-a-digest'}) == \
            'rejected'
        # Unknown version: same.
        assert policy.observe_response(
            'u1', {'X-SkyTPU-Prefix-Digest': 'v9:4:0:aa'}) == 'rejected'
        # Injected corruption (the lb.digest chaos seam) also degrades
        # — AND wipes any previously-learned digest, so routing cannot
        # keep trusting intel that failed to refresh.
        index = PrefixIndex(capacity=4, chunk=4)
        index.put(tuple(range(8)), 'x')
        policy.observe_response('u2', _digest_header(index))
        fault_injection.arm('lb.digest', 'fail:1')
        try:
            assert policy.observe_response(
                'u2', _digest_header(index)) == 'rejected'
        finally:
            fault_injection.disarm_all()
        url, info = policy.select(
            hint={'token_ids': list(range(10)), 'prompt_len': 10})
        assert url is not None and info['result'] == 'miss'
        assert policy.stats['digest_rejected'] == 3

    def test_least_loaded_fallback_with_deterministic_tie_break(self):
        policy, _clock = self._policy()
        policy.observe_response('u1', {'X-SkyTPU-Queue-Depth': '5'})
        policy.observe_response('u2', {'X-SkyTPU-Queue-Depth': '1'})
        policy.observe_response('u3', {'X-SkyTPU-Queue-Depth': '1'})
        # Tie between u2 and u3 breaks by URL, deterministically.
        assert policy.select()[0] == 'u2'
        assert policy.select()[0] == 'u2'
        # In-flight accounting shifts the balance until completion.
        policy.note_routed('u2')
        assert policy.select()[0] == 'u3'
        policy.note_done('u2')
        assert policy.select()[0] == 'u2'

    def test_stale_label_requires_no_fresh_digest_considered(self):
        """A fresh digest that simply misses is a 'miss', not 'stale'
        — 'stale' means ONLY expired digests were available (the
        documented metric semantics)."""
        policy, clock = self._policy()
        old = PrefixIndex(capacity=4, chunk=4)
        old.put(tuple(range(8)), 'x')
        policy.observe_response('u2', _digest_header(old))
        clock['t'] = 1e6                       # u2's digest expires
        fresh_nomatch = PrefixIndex(capacity=4, chunk=4)
        fresh_nomatch.put(tuple(range(500, 508)), 'y')
        policy.observe_response('u3', _digest_header(fresh_nomatch))
        _url, info = policy.select(
            hint={'token_ids': list(range(10)), 'prompt_len': 10})
        assert info['result'] == 'miss'

    def test_advertised_depth_expires_with_staleness_bound(self):
        """A queue depth advertised during a burst must not exile the
        replica from least-loaded routing forever once its queue
        drained: past the staleness bound it reads as unknown (0)."""
        policy, clock = self._policy()
        policy.observe_response('u1', {'X-SkyTPU-Queue-Depth': '9'})
        assert policy.select()[0] == 'u2'      # u1 looks busy
        clock['t'] = 1e6                       # ...until the bound
        assert policy.select()[0] == 'u1'      # back by url tie-break

    def test_phase_partition_and_graceful_collapse(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_SERVE_LB_PHASE_MIN_FLEET', '4')
        monkeypatch.setenv('SKYTPU_SERVE_LB_PHASE_THRESHOLD', '100')
        policy, _clock = self._policy(('u1', 'u2', 'u3', 'u4'))
        # Deterministic partition: first ceil(4*0.25)=1 sorted url.
        assert policy.prefill_urls() == {'u1'}
        long_hint = {'token_ids': None, 'prompt_len': 500}
        short_hint = {'token_ids': None, 'prompt_len': 3}
        assert policy.select(hint=long_hint)[1]['phase'] == 'prefill'
        assert policy.select(hint=long_hint)[0] == 'u1'
        url, info = policy.select(hint=short_hint)
        assert info['phase'] == 'decode' and url != 'u1'
        # Preferred phase fully excluded → collapse to the rest, never
        # fail closed.
        url, info = policy.select(exclude={'u1'}, hint=long_hint)
        assert url is not None and info['phase'] is None
        # Fleet shrinks below the specialization floor → uniform.
        policy.set_ready_replicas(['u1', 'u2', 'u3'])
        assert policy.prefill_urls() == set()
        assert policy.select(hint=long_hint)[1]['phase'] is None

    def test_membership_change_drops_stale_replica_state(self):
        policy, _clock = self._policy()
        index = PrefixIndex(capacity=4, chunk=4)
        index.put(tuple(range(8)), 'x')
        policy.observe_response('u2', _digest_header(index))
        policy.note_routed('u2')
        policy.set_ready_replicas(['u1', 'u3'])   # u2 torn down
        assert 'u2' not in policy._digests  # pylint: disable=protected-access
        assert 'u2' not in policy._outstanding  # pylint: disable=protected-access
        url, info = policy.select(
            hint={'token_ids': list(range(10)), 'prompt_len': 10})
        assert url in ('u1', 'u3') and info['result'] == 'miss'


# ---------------------------------------------------------------------
# prefix-aware beats round-robin on a shared-prefix workload
# ---------------------------------------------------------------------


class _FakeCachedReplica:
    """Deterministic replica cache model: a real PrefixIndex with the
    engine's store-after-admit behavior, no device anywhere."""

    def __init__(self, url, capacity=5, chunk=8):
        self.url = url
        self.index = PrefixIndex(capacity=capacity, chunk=chunk)
        self.hits = 0
        self.misses = 0

    def serve(self, ids):
        plen, _payload = self.index.lookup(ids, len(ids) - 1)
        if plen >= self.index.chunk:
            self.hits += 1
        else:
            self.misses += 1
        self.index.put(tuple(ids), list(ids))

    def headers(self):
        return _digest_header(self.index)


def _run_shared_prefix_workload(policy, replicas):
    """5 prefix groups × 3 requests, interleaved — the chat-history /
    shared-system-prompt shape. Returns total prefix hits."""
    by_url = {r.url: r for r in replicas}
    policy.set_ready_replicas(sorted(by_url))
    groups = [list(range(100 * g, 100 * g + 24)) for g in range(5)]
    for round_i in range(3):
        for group in groups:
            ids = group + [900 + round_i]     # growing conversation
            url, _info = policy.select(
                hint={'token_ids': ids, 'prompt_len': len(ids)})
            replica = by_url[url]
            policy.note_routed(url)
            replica.serve(ids)
            policy.note_done(url)
            policy.observe_response(url, replica.headers())
    return sum(r.hits for r in replicas)


class TestPrefixAwareBeatsRoundRobin:

    def test_strictly_more_hits_on_shared_prefix_workload(self):
        rr_hits = _run_shared_prefix_workload(
            RoundRobinPolicy(),
            [_FakeCachedReplica(f'u{i}') for i in range(3)])
        pa_hits = _run_shared_prefix_workload(
            PrefixAwarePolicy(clock=lambda: 0.0),
            [_FakeCachedReplica(f'u{i}') for i in range(3)])
        # Round-robin scatters each group across the fleet; the
        # prefix-aware policy converges each group onto the replica
        # that already holds its KV.
        assert pa_hits > rr_hits, (pa_hits, rr_hits)
        assert pa_hits == 10                  # every repeat is a hit
        assert rr_hits == 0                   # 5 groups never re-land


_FLEET_GROUPS = [list(range(s, s + 24)) for s in (1, 60, 120, 180, 240)]
_FLEET_ROUNDS = 3
_DEAD_URL = 'replica://zombie'


def _fleet_prompts():
    for round_i in range(_FLEET_ROUNDS):
        for gi, group in enumerate(_FLEET_GROUPS):
            yield gi, round_i, group + [400 + round_i]


def _fleet_engine():
    import dataclasses

    from skypilot_tpu.models import get_config
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    return ContinuousBatchingEngine(cfg, num_slots=2, paged_block_size=8,
                                    prefix_cache=6)


def _route_through_real_engines(policy, reference) -> dict:
    """The shared-prefix workload over three REAL engines (paged,
    prefix cache on), digests and queue depths fed back as the LB
    learns them in-band; a fourth replica is dead while advertising
    the most attractive digest for group 4, and one digest arrives
    corrupt. Counts only."""
    engines = [_fleet_engine() for _ in range(3)]
    urls = [f'replica://{i}' for i in range(3)]
    policy.set_ready_replicas(urls + [_DEAD_URL])
    policy.observe_response(_DEAD_URL, {
        'X-SkyTPU-Queue-Depth': '0',
        'X-SkyTPU-Prefix-Digest': 'v1:8:1:' + ','.join(
            prefix_route_hash(_FLEET_GROUPS[4][:k * 8])
            for k in range(1, 4)),
    })
    attempts = served = rejected = mismatches = 0
    try:
        for gi, round_i, ids in _fleet_prompts():
            tried = set()
            while True:
                attempts += 1
                url, _info = policy.select(
                    exclude=tried,
                    hint={'token_ids': ids, 'prompt_len': len(ids)})
                assert url is not None, 'routing failed closed'
                if url == _DEAD_URL:
                    # A transport error: the client retries elsewhere.
                    tried.add(url)
                    continue
                engine = engines[urls.index(url)]
                policy.note_routed(url)
                toks, _stats = engine.generate(ids, max_new_tokens=4,
                                               timeout=300)
                policy.note_done(url)
                headers = {'X-SkyTPU-Queue-Depth':
                           str(engine.queue_load())}
                digest = engine.prefix_digest()
                if digest:
                    headers['X-SkyTPU-Prefix-Digest'] = digest
                if gi == 0 and round_i == 1:
                    headers['X-SkyTPU-Prefix-Digest'] = 'garbage!!'
                if policy.observe_response(url, headers) == 'rejected':
                    rejected += 1
                mismatches += toks != reference[(gi, round_i)]
                served += 1
                break
        hits = sum(e.prefix_stats['hits'] for e in engines)
        misses = sum(e.prefix_stats['misses'] for e in engines)
    finally:
        for engine in engines:
            engine.stop()
    return {'hits': hits, 'misses': misses, 'attempts': attempts,
            'served': served, 'rejected': rejected,
            'mismatches': mismatches}


class TestFleetOverRealEngines:
    """One run a policy; each test reads one count of it."""

    @pytest.fixture(scope='class')
    def runs(self):
        oracle = _fleet_engine()
        try:
            reference = {
                (gi, ri): oracle.generate(ids, max_new_tokens=4,
                                          timeout=300)[0]
                for gi, ri, ids in _fleet_prompts()}
        finally:
            oracle.stop()
        return {
            'round_robin': _route_through_real_engines(
                RoundRobinPolicy(), reference),
            'prefix_aware': _route_through_real_engines(
                PrefixAwarePolicy(), reference),
        }

    def test_prefix_aware_hit_ratio_above_round_robin(self, runs):
        def ratio(run):
            return run['hits'] / max(1, run['hits'] + run['misses'])
        assert ratio(runs['prefix_aware']) > ratio(runs['round_robin']), \
            runs

    @pytest.mark.parametrize('policy', ['round_robin', 'prefix_aware'])
    def test_outputs_equal_a_single_healthy_replica(self, runs, policy):
        assert runs[policy]['served'] == \
            _FLEET_ROUNDS * len(_FLEET_GROUPS)
        assert runs[policy]['mismatches'] == 0, runs[policy]

    def test_corrupt_digest_is_rejected_and_counted(self, runs):
        assert runs['prefix_aware']['rejected'] >= 1, runs

    @pytest.mark.parametrize('policy', ['round_robin', 'prefix_aware'])
    def test_dead_replica_bounds_retry_amplification(self, runs, policy):
        run = runs[policy]
        assert run['attempts'] / run['served'] <= 2.0, run


# ---------------------------------------------------------------------
# metrics-driven autoscaler
# ---------------------------------------------------------------------


def _metrics_spec(**kw):
    defaults = dict(min_replicas=1, max_replicas=8,
                    target_queue_depth_per_replica=4.0,
                    upscale_delay_seconds=0, downscale_delay_seconds=0)
    defaults.update(kw)
    return SkyServiceSpec(**defaults)


class _Replica:

    def __init__(self, replica_id, status=ReplicaStatus.READY):
        self.replica_id = replica_id
        self.status = status
        self.version = 1
        self.is_spot = False


class TestMetricsAutoscaler:

    def test_spec_selects_metrics_autoscaler(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        assert isinstance(scaler, autoscalers.MetricsAutoscaler)
        # No metric targets → the historical QPS autoscaler.
        qps = autoscalers.make_autoscaler(SkyServiceSpec(
            min_replicas=1, max_replicas=2, target_qps_per_replica=1.0))
        assert not isinstance(qps, autoscalers.MetricsAutoscaler)

    def test_metric_targets_reject_spot_fallback_combo(self):
        """Metrics autoscaling + spot fallback must fail at VALIDATION:
        silently degrading to the QPS autoscaler (which has no QPS
        target here) would pin the fleet at min_replicas forever."""
        with pytest.raises(ValueError, match='fallback'):
            SkyServiceSpec(min_replicas=1, max_replicas=4,
                           target_ttft_seconds=0.5,
                           dynamic_ondemand_fallback=True)

    def test_pressure_never_scales_below_inflight_provisioning(self):
        """Replicas still PROVISIONING are the response to the current
        pressure: ceil(ready × pressure) alone would read them as
        excess and cut the launch short mid-overload."""
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.collect_replica_metrics({1: {'queue_depth': 6.0}})
        fleet = [_Replica(1),
                 _Replica(2, ReplicaStatus.PROVISIONING),
                 _Replica(3, ReplicaStatus.PROVISIONING)]
        # pressure 1.5 → ceil(1×1.5)=2 < current 3, but pressure > 1:
        # hold at 3, never downscale into an overload.
        assert scaler.evaluate_scaling(fleet) == []
        assert scaler.decision_log[-1]['outcome'] == 'hold'
        assert scaler.decision_log[-1]['desired'] == 3

    def test_queue_pressure_scales_up(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.collect_replica_metrics({1: {'queue_depth': 12.0},
                                        2: {'queue_depth': 12.0}})
        decisions = scaler.evaluate_scaling([_Replica(1), _Replica(2)])
        # pressure 3.0 → 2 ready × 3 = 6 wanted → 4 scale-ups.
        assert len(decisions) == 4
        assert all(d.operator ==
                   autoscalers.AutoscalerDecisionOperator.SCALE_UP
                   for d in decisions)

    def test_ttft_and_tpot_targets_feed_pressure(self):
        scaler = autoscalers.make_autoscaler(
            _metrics_spec(target_ttft_seconds=0.5,
                          target_tpot_seconds=0.05))
        # Queue fine, TTFT 4x over target → pressure 4 → 1 ready × 4.
        scaler.collect_replica_metrics(
            {1: {'queue_depth': 1.0, 'ttft_s': 2.0, 'tpot_s': 0.01}})
        decisions = scaler.evaluate_scaling([_Replica(1)])
        assert len(decisions) == 3

    def test_deadband_holds_at_target(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.collect_replica_metrics({1: {'queue_depth': 4.0},
                                        2: {'queue_depth': 3.0}})
        assert scaler.evaluate_scaling([_Replica(1), _Replica(2)]) == []
        assert scaler.decision_log[-1]['outcome'] == 'hold'

    def test_no_signals_holds_instead_of_flapping(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.collect_replica_metrics({})
        assert scaler.evaluate_scaling(
            [_Replica(1), _Replica(2), _Replica(3)]) == []
        assert scaler.decision_log[-1]['outcome'] == 'hold'

    def test_hysteresis_delays_the_move(self, monkeypatch):
        monkeypatch.setenv('SKYTPU_SERVE_DECISION_INTERVAL', '1')
        scaler = autoscalers.make_autoscaler(
            _metrics_spec(upscale_delay_seconds=3))
        scaler.collect_replica_metrics({1: {'queue_depth': 40.0}})
        assert scaler.evaluate_scaling([_Replica(1)]) == []
        assert scaler.evaluate_scaling([_Replica(1)]) == []
        assert len(scaler.evaluate_scaling([_Replica(1)])) > 0

    def test_flap_damping_suppresses_direction_flip(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.flap_damping = 2
        scaler.collect_replica_metrics({1: {'queue_depth': 12.0},
                                        2: {'queue_depth': 12.0}})
        assert len(scaler.evaluate_scaling(
            [_Replica(1), _Replica(2)])) == 4              # up to 6
        fleet = [_Replica(i) for i in range(1, 7)]
        scaler.collect_replica_metrics(
            {i: {'queue_depth': 0.0} for i in range(1, 7)})
        # Immediately-following quiet: the down-flip is damped...
        assert scaler.evaluate_scaling(fleet) == []
        assert scaler.decision_log[-1]['outcome'] == 'damped'
        assert scaler.evaluate_scaling(fleet) == []
        # ...until the damping window lapses.
        assert len(scaler.evaluate_scaling(fleet)) > 0
        assert scaler.decision_log[-1]['outcome'] == 'down'

    def test_draining_counts_toward_fleet_but_never_victim(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        fleet = [_Replica(1), _Replica(2, ReplicaStatus.DRAINING),
                 _Replica(3)]
        scaler.collect_replica_metrics({1: {'queue_depth': 0.0},
                                        3: {'queue_depth': 0.0}})
        decisions = scaler.evaluate_scaling(fleet)
        victims = [d.target for d in decisions]
        assert decisions and 2 not in victims
        # DRAINING counted toward current: 3 → 1 means two victims.
        assert sorted(victims) == [1, 3]

    def test_decision_log_replays_exactly(self):
        scaler = autoscalers.make_autoscaler(_metrics_spec())
        scaler.flap_damping = 2
        fleet2 = [_Replica(1), _Replica(2)]
        fleet6 = [_Replica(i) for i in range(1, 7)]
        script = [
            ({1: {'queue_depth': 12.0}, 2: {'queue_depth': 12.0}},
             fleet2),
            ({i: {'queue_depth': 0.0} for i in range(1, 7)}, fleet6),
            ({i: {'queue_depth': 0.0} for i in range(1, 7)}, fleet6),
            ({i: {'queue_depth': 9.0} for i in range(1, 7)}, fleet6),
            ({i: {'queue_depth': 0.0} for i in range(1, 7)}, fleet6),
        ]
        recorded = []
        for signals, fleet in script:
            scaler.collect_replica_metrics(signals)
            decisions = scaler.evaluate_scaling(fleet)
            recorded.append([(d.operator.value, d.target)
                             for d in decisions])
        spec = _metrics_spec()
        replayed = autoscalers.replay_decision_log(
            spec, scaler.decision_log)
        # flap_damping was overridden on the live instance; mirror it.
        fresh = autoscalers.MetricsAutoscaler(spec)
        fresh.flap_damping = 2
        replayed = []
        for entry in scaler.decision_log:
            fresh.collect_replica_metrics(entry['signals'])
            infos = [autoscalers._ReplayReplica(*row)  # pylint: disable=protected-access
                     for row in entry['replicas']]
            replayed.append([(d.operator.value, d.target)
                             for d in fresh.evaluate_scaling(infos)])
        assert replayed == recorded
        assert [e['decisions'] for e in scaler.decision_log] == \
            [[(op, t) for op, t in tick] for tick in recorded]

    def test_signals_from_exposition_reduction(self):
        from skypilot_tpu.serve.replica_managers import \
            _signals_from_exposition
        text = '\n'.join([
            '# HELP skytpu_engine_queue_depth q',
            '# TYPE skytpu_engine_queue_depth gauge',
            'skytpu_engine_queue_depth 7',
            '# HELP skytpu_engine_ttft_seconds t',
            '# TYPE skytpu_engine_ttft_seconds histogram',
            'skytpu_engine_ttft_seconds_bucket{le="1.0"} 4',
            'skytpu_engine_ttft_seconds_bucket{le="+Inf"} 4',
            'skytpu_engine_ttft_seconds_sum 2.0',
            'skytpu_engine_ttft_seconds_count 4',
        ]) + '\n'
        signals = _signals_from_exposition(text)
        assert signals == {'queue_depth': 7.0, 'ttft_s': 0.5}


# ---------------------------------------------------------------------
# server satellites: fleet-intel headers + delta-decoder flush fix
# ---------------------------------------------------------------------


def _bare_server():
    from skypilot_tpu.serve.server import InferenceServer
    server = InferenceServer.__new__(InferenceServer)
    server.tokenizer_kind = 'byte'
    server._hf_tokenizer = None  # pylint: disable=protected-access
    server.ready = True
    server.draining = False
    server.request_timeout = 0.0
    return server


class TestFleetIntelHeaders:

    def test_headers_reflect_engine_state(self):
        server = _bare_server()
        server.engine = types.SimpleNamespace(
            queue_load=lambda: 3,
            prefix_digest=lambda: 'v1:8:2:abcd1234')
        headers = server._fleet_intel_headers()  # pylint: disable=protected-access
        assert headers == {'X-SkyTPU-Queue-Depth': '3',
                           'X-SkyTPU-Tier': 'monolithic',
                           'X-SkyTPU-Tokenizer': 'byte',
                           'X-SkyTPU-Prefix-Digest': 'v1:8:2:abcd1234'}

    def test_headers_degrade_without_digest_or_engine(self):
        server = _bare_server()
        server.engine = types.SimpleNamespace(
            queue_load=lambda: 0, prefix_digest=lambda: None)
        assert server._fleet_intel_headers() == {  # pylint: disable=protected-access
            'X-SkyTPU-Queue-Depth': '0',
            'X-SkyTPU-Tier': 'monolithic',
            'X-SkyTPU-Tokenizer': 'byte'}
        server.engine = None
        assert server._fleet_intel_headers() == {}  # pylint: disable=protected-access

    def test_header_failure_never_raises(self):
        server = _bare_server()

        def boom():
            raise RuntimeError('engine mid-reset')

        server.engine = types.SimpleNamespace(queue_load=boom,
                                              prefix_digest=boom)
        assert server._fleet_intel_headers() == {}  # pylint: disable=protected-access


class TestDeltaDecoderResync:

    def _decoder_with_map(self, table):
        server = _bare_server()
        server._hf_tokenizer = types.SimpleNamespace(  # pylint: disable=protected-access
            decode=lambda ids: table[tuple(ids)],
            encode=lambda text: [])
        return server._delta_decoder()  # pylint: disable=protected-access

    def test_flush_emits_corrected_tail_after_stale_replacement_char(
            self):
        """The round-5 review finding: a stale '�' was emitted, then the
        canonical decode replaced it — flush must emit the corrected
        tail (diff against what was actually sent), not drop it."""
        table = {(1,): '�', (1, 2): '��', (1, 2, 3): '€x'}
        push, flush = self._decoder_with_map(table)
        assert push(1) == ''          # trailing '�' held back
        assert push(2) == '�'         # stable prefix '�' emitted
        assert push(3) == ''          # retroactive change: withheld
        # Previously returned '' — '€x' was silently dropped.
        assert flush() == '€x'

    def test_flush_plain_extension_unchanged(self):
        table = {(1,): 'a', (1, 2): 'ab�'}
        push, flush = self._decoder_with_map(table)
        assert push(1) == 'a'
        assert push(2) == 'ab'[1:]    # 'b'; trailing '�' held
        assert flush() == '�'         # genuine U+FFFD at stream end

    def test_flush_genuine_divergence_still_refuses(self):
        """Non-placeholder text already on the wire cannot be
        retracted: flush still returns '' (with a loud log) rather
        than emitting text that would duplicate or contradict it."""
        table = {(1,): 'abc', (1, 2): 'xyz'}
        push, flush = self._decoder_with_map(table)
        assert push(1) == 'abc'
        assert push(2) == ''
        assert flush() == ''

    def test_byte_tokenizer_pathological_sequence_end_to_end(self):
        """Real byte-level decode: an invalid byte mid-stream emits a
        final '�' and later valid text extends it — the concatenated
        stream equals the canonical decode."""
        server = _bare_server()
        push, flush = server._delta_decoder()  # pylint: disable=protected-access
        tokens = [104, 105, 0xE2, 0x82, 0xAC, 0xFF, 0xFF, 33]
        streamed = ''.join(push(t) for t in tokens) + flush()
        from skypilot_tpu.serve.server import byte_decode
        assert streamed == byte_decode(tokens) == 'hi€��!'
