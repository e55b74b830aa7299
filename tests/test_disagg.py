"""Disaggregated prefill/decode handoff: unit pins for the chunk-stream
wire format, the decode-side ingest state machine, the tier-aware
two-stage routing policy, and the chat-route token hint satellite.

The wire/ingest contract under test (docs/serving.md "Disaggregated
serving"): corrupt chunks are rejected wholesale, out-of-order chunks
are refused with the expected seq, retried chunks are acknowledged
idempotently (never double-allocated), pool pressure sheds rather than
corrupts, and every abort/expiry path rolls the partial stream back to
refcount-0 with the pool `check()` invariant intact. The end-to-end
fleet behavior (real servers + real LB + armed faults) lives in
tests/test_chaos.py::TestDisaggHandoff.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.models import kv_cache as kv
from skypilot_tpu.utils import fault_injection


def _cfg(**kw):
    from skypilot_tpu.models.configs import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


_LEAVES = [{'shape': [8, 2, 4], 'dtype': 'float32'},
           {'shape': [8, 2, 4], 'dtype': 'float32'}]


def _payload(num_blocks: int) -> bytes:
    elems = num_blocks * 8 * 2 * 4
    return (np.arange(2 * elems, dtype=np.float32) % 251).tobytes()


# ---------------------------------------------------------------------
# wire format (pure host, no jax)
# ---------------------------------------------------------------------


class TestChunkFraming:

    def test_round_trip(self):
        payload = _payload(2)
        data = kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, payload, 2)
        header, got = kv.unpack_kv_chunk(data)
        assert got == payload
        assert header['stream_id'] == 's1'
        assert header['seq'] == 0
        assert header['num_blocks'] == 2
        assert not header.get('final')

    def test_final_round_trip_carries_key(self):
        payload = _payload(1)
        data = kv.pack_kv_chunk('s1', 2, 2, 8, _LEAVES, payload, 1,
                                final=True, key=list(range(20)),
                                total_blocks=3)
        header, _ = kv.unpack_kv_chunk(data)
        assert header['final'] and header['total_blocks'] == 3
        assert header['key'] == list(range(20))

    def test_final_requires_key(self):
        with pytest.raises(ValueError, match='final chunk requires'):
            kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, b'', 1,
                             final=True)

    def test_corrupt_payload_rejected(self):
        data = bytearray(kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES,
                                          _payload(1), 1))
        data[-1] ^= 0xFF
        with pytest.raises(kv.ChunkError, match='CRC'):
            kv.unpack_kv_chunk(bytes(data))

    def test_truncated_payload_rejected(self):
        data = kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, _payload(1), 1)
        with pytest.raises(kv.ChunkError, match='CRC'):
            kv.unpack_kv_chunk(data[:-10])

    def test_tampered_header_rejected(self):
        # Flipping the seq inside the header invalidates the CRC: the
        # CRC covers (payload, stream, seq, start, block_size, sig).
        data = kv.pack_kv_chunk('s1', 3, 12, 8, _LEAVES, _payload(1), 1)
        tampered = data.replace(b'"seq": 3', b'"seq": 4')
        assert tampered != data
        with pytest.raises(kv.ChunkError):
            kv.unpack_kv_chunk(tampered)

    def test_bad_magic_and_version(self):
        with pytest.raises(kv.ChunkError, match='magic'):
            kv.unpack_kv_chunk(b'NOT-A-CHUNK' + b'\0' * 40)
        data = kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, _payload(1), 1)
        bad = data.replace(b'"version": 1', b'"version": 9')
        with pytest.raises(kv.ChunkError, match='version'):
            kv.unpack_kv_chunk(bad)

    def test_tampered_final_key_rejected(self):
        """The final chunk's token KEY is CRC-covered: a bit flip that
        changes one token (length unchanged, so the total_blocks
        cross-check alone would still pass) must be rejected — KV
        published under the wrong prefix key would silently serve
        wrong output to whoever owns the corrupted key."""
        payload = _payload(1)
        data = kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, payload, 1,
                                final=True, key=list(range(20)),
                                total_blocks=3)
        bad = data.replace(b'[0, 1, 2,', b'[9, 1, 2,')
        assert bad != data
        with pytest.raises(kv.ChunkError, match='CRC'):
            kv.unpack_kv_chunk(bad)
        # A tampered num_blocks is CRC-covered too.
        bad = data.replace(b'"num_blocks": 1', b'"num_blocks": 2')
        assert bad != data
        with pytest.raises(kv.ChunkError, match='CRC'):
            kv.unpack_kv_chunk(bad)

    def test_final_total_blocks_cross_checked_against_key(self):
        # total_blocks must equal ceil(len(key)/block_size); both key
        # and block_size sit under the CRC, so a corrupted count can
        # never smuggle a short block table into the receiver.
        payload = _payload(1)
        data = kv.pack_kv_chunk('s1', 0, 0, 8, _LEAVES, payload, 1,
                                final=True, key=list(range(20)),
                                total_blocks=3)
        bad = data.replace(b'"total_blocks": 3', b'"total_blocks": 2')
        with pytest.raises(kv.ChunkError):
            kv.unpack_kv_chunk(bad)

    def test_sequence_error_carries_expected(self):
        err = kv.ChunkSequenceError(2, 5)
        assert err.expected == 2 and err.got == 5
        assert 'expected seq 2' in str(err)


# ---------------------------------------------------------------------
# engine-level handoff: export → ingest → admit
# ---------------------------------------------------------------------


@pytest.fixture(scope='module')
def handoff_engines():
    """One prefill-tier and one decode-tier engine (weight-identical by
    seed) plus a monolithic oracle; module-scoped — bring-up compiles."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    pre = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                   paged_block_size=8, prefix_cache=6,
                                   tier='prefill')
    dec = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                   paged_block_size=8, prefix_cache=6,
                                   tier='decode')
    mono = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                    paged_block_size=8, prefix_cache=6)
    for engine in (pre, dec, mono):
        engine.generate([1, 2, 3], max_new_tokens=2, timeout=300)
    yield pre, dec, mono
    fault_injection.disarm_all()
    for engine in (pre, dec, mono):
        engine.stop()


class TestEngineHandoff:

    def test_tier_validation(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        with pytest.raises(ValueError, match='unknown engine tier'):
            ContinuousBatchingEngine(_cfg(), tier='gpu')
        with pytest.raises(ValueError, match='requires paged_block_size'):
            ContinuousBatchingEngine(_cfg(), tier='prefill')
        with pytest.raises(ValueError, match='requires paged_block_size'):
            ContinuousBatchingEngine(_cfg(), paged_block_size=8,
                                     tier='decode')

    def test_stream_round_trip_bit_identical(self, handoff_engines):
        """The whole hot path: prefill-tier prefill → chunk export →
        decode-tier ingest → the handed-off request admits as a
        full-prefix hit and decodes BIT-IDENTICALLY to a monolithic
        replica, with the hit attributed to the handoff."""
        pre, dec, mono = handoff_engines
        ids = list(range(1, 21))
        expect, _ = mono.generate(ids, max_new_tokens=4, timeout=300)
        stats = pre.prefill_prefix(ids, timeout=300)
        assert stats['cached'] and stats['prompt_tokens'] == 20
        chunks = pre.export_prefix_chunks(ids, 'rt-1', chunk_blocks=1)
        assert len(chunks) == 3          # ceil(20/8) blocks, 1/chunk
        hits_before = dec.prefix_stats['prewarm_hits']
        for chunk in chunks:
            result = dec.ingest_chunk(chunk)
        assert result['final'] and result['imported_blocks'] == 3
        out, _ = dec.generate(ids, max_new_tokens=4, timeout=300)
        assert out == expect
        assert dec.prefix_stats['prewarm_hits'] == hits_before + 1
        dec._pool.check()  # pylint: disable=protected-access

    def test_export_uncached_prefix_raises_retryably(self,
                                                     handoff_engines):
        pre, _dec, _mono = handoff_engines
        with pytest.raises(ValueError, match='not cached'):
            pre.export_prefix_chunks([9, 9, 9, 9], 'nope-1')

    def test_duplicate_chunks_dedup_without_double_allocation(
            self, handoff_engines):
        pre, dec, _mono = handoff_engines
        ids = list(range(30, 50))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'dup-1', chunk_blocks=1)
        dec.ingest_chunk(chunks[0])
        used = dec._pool.used  # pylint: disable=protected-access
        # Retried seq 0: acknowledged, nothing allocated.
        result = dec.ingest_chunk(chunks[0])
        assert result['duplicate']
        assert dec._pool.used == used  # pylint: disable=protected-access
        for chunk in chunks[1:]:
            dec.ingest_chunk(chunk)
        # Retried FINAL chunk of a published stream: still idempotent.
        used = dec._pool.used  # pylint: disable=protected-access
        result = dec.ingest_chunk(chunks[-1])
        assert result['duplicate']
        assert dec._pool.used == used  # pylint: disable=protected-access
        dec._pool.check()  # pylint: disable=protected-access

    def test_out_of_order_refused_with_expected_seq(self,
                                                    handoff_engines):
        pre, dec, _mono = handoff_engines
        ids = list(range(60, 80))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'ooo-1', chunk_blocks=1)
        dec.ingest_chunk(chunks[0])
        with pytest.raises(kv.ChunkSequenceError) as exc:
            dec.ingest_chunk(chunks[2])
        assert exc.value.expected == 1
        # A stream must also OPEN at seq 0.
        fresh = pre.export_prefix_chunks(ids, 'ooo-2', chunk_blocks=1)
        with pytest.raises(kv.ChunkSequenceError) as exc:
            dec.ingest_chunk(fresh[1])
        assert exc.value.expected == 0
        assert dec.abort_ingest('ooo-1')
        dec._pool.check()  # pylint: disable=protected-access

    def test_corrupt_chunk_rejected_without_mutation(self,
                                                     handoff_engines):
        pre, dec, _mono = handoff_engines
        ids = list(range(100, 120))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'cor-1', chunk_blocks=1)
        used = dec._pool.used  # pylint: disable=protected-access
        bad = bytearray(chunks[0])
        bad[-1] ^= 0xFF
        with pytest.raises(kv.ChunkError, match='CRC'):
            dec.ingest_chunk(bytes(bad))
        assert dec._pool.used == used  # pylint: disable=protected-access
        assert 'cor-1' not in dec._ingest_sessions  # pylint: disable=protected-access

    def test_abort_rolls_back_to_refcount_zero(self, handoff_engines):
        pre, dec, _mono = handoff_engines
        ids = list(range(130, 150))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'abr-1', chunk_blocks=1)
        used = dec._pool.used  # pylint: disable=protected-access
        dec.ingest_chunk(chunks[0])
        dec.ingest_chunk(chunks[1])
        assert dec._pool.used == used + 2  # pylint: disable=protected-access
        assert dec.abort_ingest('abr-1') is True
        assert dec.abort_ingest('abr-1') is False   # idempotent
        assert dec._pool.used == used  # pylint: disable=protected-access
        dec._pool.check()  # pylint: disable=protected-access
        assert dec.ingest_stats['streams_aborted'] >= 1

    def test_tick_sweep_reclaims_without_new_ingest(self,
                                                    handoff_engines):
        """The TTL sweep also runs every engine tick: a quiet decode
        replica (no further ingest traffic EVER) still reclaims an
        orphaned stream's blocks instead of holding them until the
        next chunk happens to arrive."""
        pre, dec, _mono = handoff_engines
        ids = list(range(200, 220))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'tick-1', chunk_blocks=1)
        used = dec._pool.used  # pylint: disable=protected-access
        dec.ingest_chunk(chunks[0])
        with dec._ingest_lock:  # pylint: disable=protected-access
            dec._ingest_sessions['tick-1'].touched -= 10_000  # pylint: disable=protected-access
        # No further ingest: the engine thread (alive since the
        # fixture's warmup generate) must expire it on its own.
        deadline = time.time() + 30
        while time.time() < deadline and \
                'tick-1' in dec._ingest_sessions:  # pylint: disable=protected-access
            time.sleep(0.05)
        assert 'tick-1' not in dec._ingest_sessions  # pylint: disable=protected-access
        assert dec._pool.used == used  # pylint: disable=protected-access
        dec._pool.check()  # pylint: disable=protected-access

    def test_ttl_sweep_reclaims_orphaned_stream(self, handoff_engines):
        """A prefill replica that died mid-stream leaves a session
        nobody will finish or abort: the lazy TTL sweep (driven by any
        later ingest) rolls it back to refcount-0."""
        pre, dec, _mono = handoff_engines
        ids = list(range(160, 180))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'ttl-1', chunk_blocks=1)
        used = dec._pool.used  # pylint: disable=protected-access
        dec.ingest_chunk(chunks[0])
        expired_before = dec.ingest_stats['streams_expired']
        with dec._ingest_lock:  # pylint: disable=protected-access
            dec._ingest_sessions['ttl-1'].touched -= 10_000  # pylint: disable=protected-access
        # Any later chunk (here: a fresh stream's opener) triggers the
        # sweep.
        fresh = pre.export_prefix_chunks(ids, 'ttl-2', chunk_blocks=1)
        dec.ingest_chunk(fresh[0])
        assert 'ttl-1' not in dec._ingest_sessions  # pylint: disable=protected-access
        assert dec.ingest_stats['streams_expired'] == expired_before + 1
        dec.abort_ingest('ttl-2')
        assert dec._pool.used == used  # pylint: disable=protected-access
        dec._pool.check()  # pylint: disable=protected-access

    def test_pool_pressure_sheds_new_streams(self):
        """The decode-side admission gate: a new stream must leave one
        full-depth request of headroom — pressure sheds with
        EngineOverloadedError (the server's 503 + Retry-After), never
        corrupts."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        tiny = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                        paged_block_size=8,
                                        paged_num_blocks=4,
                                        prefix_cache=1, tier='decode')
        try:
            meta = tiny._expected_leaf_meta()  # pylint: disable=protected-access
            elems = tiny._ingest_elems  # pylint: disable=protected-access
            payload = b''.join(
                np.zeros((1,) + tuple(m['shape']),
                         np.dtype(m['dtype'])).tobytes()
                for m in meta)
            del elems
            chunk = kv.pack_kv_chunk('shed-1', 0, 0, 8, meta, payload, 1)
            with pytest.raises(exceptions.EngineOverloadedError,
                               match='pool pressure'):
                tiny.ingest_chunk(chunk)
            assert tiny.ingest_stats['chunks_shed'] == 1
            tiny._pool.check()  # pylint: disable=protected-access
        finally:
            tiny.stop()

    def test_layout_mismatch_rejected(self, handoff_engines):
        _pre, dec, _mono = handoff_engines
        chunk = kv.pack_kv_chunk('lay-1', 0, 0, 8, _LEAVES,
                                 _payload(1), 1)
        with pytest.raises(kv.ChunkError, match='layout'):
            dec.ingest_chunk(chunk)
        # Wrong block size is rejected even with matching leaves.
        meta = dec._expected_leaf_meta()  # pylint: disable=protected-access
        chunk = kv.pack_kv_chunk('lay-2', 0, 0, 16, meta, b'', 1)
        with pytest.raises(kv.ChunkError, match='layout'):
            dec.ingest_chunk(chunk)

    def test_engine_ingest_fault_point(self, handoff_engines):
        """Armed 'engine.ingest' fails the chunk before anything is
        touched — the sender sees the error and re-dispatches; nothing
        leaks."""
        pre, dec, _mono = handoff_engines
        ids = list(range(190, 210))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'flt-1', chunk_blocks=1)
        used = dec._pool.used  # pylint: disable=protected-access
        fault_injection.arm('engine.ingest', 'fail:1')
        try:
            with pytest.raises(fault_injection.InjectedFault):
                dec.ingest_chunk(chunks[0])
        finally:
            fault_injection.disarm_all()
        assert dec._pool.used == used  # pylint: disable=protected-access
        # Retry after the fault clears succeeds from seq 0.
        dec.ingest_chunk(chunks[0])
        dec.abort_ingest('flt-1')
        dec._pool.check()  # pylint: disable=protected-access

    def test_draining_engine_sheds_ingest(self, handoff_engines):
        pre, dec, _mono = handoff_engines
        ids = list(range(220, 240))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'drn-1', chunk_blocks=1)
        dec._draining = True  # pylint: disable=protected-access
        try:
            with pytest.raises(exceptions.EngineDrainingError):
                dec.ingest_chunk(chunks[0])
        finally:
            dec._draining = False  # pylint: disable=protected-access


_STORM_LONGS = [list(range(300, 320)), list(range(330, 363)),
                list(range(400, 448))]
_STORM_CHUNK_BLOCKS = 2


class TestHandoffStorm:
    """Several long prompts through the policy's two-stage handoff onto
    real engines, one run; each test holds one count of it against what
    the prompts' lengths predict."""

    @pytest.fixture(scope='class')
    def storm(self, handoff_engines):
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        pre, dec, mono = handoff_engines
        policy = PrefixAwarePolicy(clock=lambda: 0.0)
        policy.set_ready_replicas(['replica://pre', 'replica://dec'])
        policy.set_replica_tiers({'replica://pre': 'prefill',
                                  'replica://dec': 'decode'})
        rejected0 = dec.ingest_stats['chunks_rejected']
        prewarm0 = dec.prefix_stats['prewarm_hits']
        out = {'handoffs': 0, 'chunks': 0, 'blocks': 0,
               'payload_bytes': 0, 'mismatches': 0}
        for i, ids in enumerate(_STORM_LONGS):
            expect, _ = mono.generate(ids, max_new_tokens=4, timeout=300)
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv('SKYTPU_SERVE_LB_DISAGG_THRESHOLD', '16')
                url, info = policy.select(hint={'token_ids': ids,
                                                'prompt_len': len(ids)})
            out['handoffs'] += (info['result'] == 'handoff' and
                                url == 'replica://dec' and
                                info['prefill_url'] == 'replica://pre')
            pre.prefill_prefix(ids, timeout=300)
            chunks = pre.export_prefix_chunks(
                ids, f'storm-{i}', chunk_blocks=_STORM_CHUNK_BLOCKS)
            for chunk in chunks:
                result = dec.ingest_chunk(chunk)
                out['payload_bytes'] += len(kv.unpack_kv_chunk(chunk)[1])
            out['chunks'] += len(chunks)
            out['blocks'] += result['imported_blocks']
            got, _ = dec.generate(ids, max_new_tokens=4, timeout=300)
            out['mismatches'] += got != expect
        out['rejected'] = dec.ingest_stats['chunks_rejected'] - rejected0
        out['prewarm_hits'] = dec.prefix_stats['prewarm_hits'] - prewarm0
        out['per_block_bytes'] = sum(
            int(np.prod(m['shape'], dtype=np.int64)) *
            np.dtype(m['dtype']).itemsize
            for m in pre._expected_leaf_meta())  # pylint: disable=protected-access
        dec._pool.check()  # pylint: disable=protected-access
        return out

    @staticmethod
    def _blocks(ids):
        return -(-len(ids) // 8)

    def test_every_long_prompt_routed_as_a_handoff(self, storm):
        assert storm['handoffs'] == len(_STORM_LONGS)

    def test_chunk_count_is_what_the_lengths_predict(self, storm):
        assert storm['chunks'] == sum(
            -(-self._blocks(ids) // _STORM_CHUNK_BLOCKS)
            for ids in _STORM_LONGS)

    def test_block_count_is_what_the_lengths_predict(self, storm):
        assert storm['blocks'] == sum(map(self._blocks, _STORM_LONGS))

    def test_payload_bytes_are_blocks_times_the_leaf_math(self, storm):
        assert storm['per_block_bytes'] > 0
        assert storm['payload_bytes'] == storm['per_block_bytes'] * sum(
            map(self._blocks, _STORM_LONGS))

    def test_no_chunk_rejected(self, storm):
        assert storm['rejected'] == 0

    def test_every_handoff_admits_as_a_prewarm_hit(self, storm):
        assert storm['prewarm_hits'] >= len(_STORM_LONGS)

    def test_outputs_equal_the_monolithic_replica(self, storm):
        assert storm['mismatches'] == 0


# ---------------------------------------------------------------------
# two-stage routing policy
# ---------------------------------------------------------------------


def _tiered_policy(monkeypatch, threshold=16):
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAwarePolicy
    monkeypatch.setenv('SKYTPU_SERVE_LB_DISAGG_THRESHOLD',
                       str(threshold))
    policy = PrefixAwarePolicy(clock=lambda: 0.0)
    urls = ['http://p0', 'http://p1', 'http://d0', 'http://d1']
    policy.set_ready_replicas(urls)
    policy.set_replica_tiers({'http://p0': 'prefill',
                              'http://p1': 'prefill',
                              'http://d0': 'decode',
                              'http://d1': 'decode'})
    return policy, urls


class TestHandoffPolicy:

    def test_long_prompt_routes_two_stage(self, monkeypatch):
        policy, _urls = _tiered_policy(monkeypatch)
        ids = list(range(32))
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids)})
        assert info['result'] == 'handoff'
        assert url in ('http://d0', 'http://d1')
        assert info['prefill_url'] in ('http://p0', 'http://p1')
        assert policy.stats['handoff'] == 1

    def test_short_prompt_stays_on_decode_tier(self, monkeypatch):
        policy, _urls = _tiered_policy(monkeypatch)
        ids = [1, 2, 3, 4]
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids)})
        assert info['result'] == 'miss'
        assert url in ('http://d0', 'http://d1')
        assert policy.stats['handoff'] == 0
        assert policy.stats['tier_decode'] == 1

    def test_digest_hit_on_decode_tier_preempts_handoff(self,
                                                        monkeypatch):
        from skypilot_tpu.models.kv_cache import prefix_route_hash
        policy, _urls = _tiered_policy(monkeypatch)
        ids = list(range(32))
        digest = 'v1:8:1:' + prefix_route_hash(ids[:24])
        policy.observe_response('http://d1',
                                {'X-SkyTPU-Prefix-Digest': digest})
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids)})
        assert info['result'] == 'hit' and url == 'http://d1'
        assert policy.stats['handoff'] == 0

    def test_warm_prefill_replica_never_attracts_decode_traffic(
            self, monkeypatch):
        """A prefix cached on a PREFILL-tier replica (it prefilled it!)
        must not pull the request onto that replica — the digest match
        is restricted to the serving pool."""
        from skypilot_tpu.models.kv_cache import prefix_route_hash
        policy, _urls = _tiered_policy(monkeypatch)
        ids = list(range(32))
        digest = 'v1:8:1:' + prefix_route_hash(ids[:24])
        policy.observe_response('http://p0',
                                {'X-SkyTPU-Prefix-Digest': digest})
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids)})
        assert info['result'] == 'handoff'
        assert url in ('http://d0', 'http://d1')

    def test_prefill_tier_excluded_falls_back_without_handoff(
            self, monkeypatch):
        policy, _urls = _tiered_policy(monkeypatch)
        ids = list(range(32))
        url, info = policy.select(
            exclude={'http://p0', 'http://p1'},
            hint={'token_ids': ids, 'prompt_len': len(ids)})
        assert info['result'] != 'handoff'
        assert url in ('http://d0', 'http://d1')

    def test_all_prefill_candidates_still_serve(self, monkeypatch):
        """Never fail closed: when only prefill-tier replicas remain
        selectable, they serve (monolithic capability is universal)."""
        policy, _urls = _tiered_policy(monkeypatch)
        url, info = policy.select(
            exclude={'http://d0', 'http://d1'},
            hint={'token_ids': [1, 2, 3], 'prompt_len': 3})
        assert url in ('http://p0', 'http://p1')
        assert info['result'] != 'handoff'

    def test_tiers_learned_in_band_from_headers(self):
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        policy = PrefixAwarePolicy(clock=lambda: 0.0)
        policy.set_ready_replicas(['http://a', 'http://b'])
        policy.observe_response('http://a', {'X-SkyTPU-Tier': 'prefill'})
        policy.observe_response('http://b', {'X-SkyTPU-Tier': 'bogus'})
        assert policy.replica_tiers() == {'http://a': 'prefill'}
        # Membership change prunes tier intel with the other tables.
        policy.set_ready_replicas(['http://b'])
        assert policy.replica_tiers() == {}

    def test_prefill_pick_is_least_loaded(self, monkeypatch):
        """Concurrent long prompts spread across the prefill tier: a
        prefill replica with advertised/in-flight load loses the pick
        to an idle one (without depth intel the tier would serialize
        on the smallest url)."""
        policy, _urls = _tiered_policy(monkeypatch)
        ids = list(range(32))
        policy.observe_response('http://p0',
                                {'X-SkyTPU-Queue-Depth': '5'})
        _url, info = policy.select(hint={'token_ids': ids,
                                         'prompt_len': len(ids)})
        assert info['result'] == 'handoff'
        assert info['prefill_url'] == 'http://p1'
        assert policy.replica_load('http://p0') == 5
        # In-flight accounting (the LB's note_routed around
        # /kv/prefill) steers the same way.
        policy.note_routed('http://p1')
        policy.note_routed('http://p1')
        policy.note_routed('http://p1')
        policy.note_routed('http://p1')
        policy.note_routed('http://p1')
        policy.note_routed('http://p1')
        _url, info = policy.select(hint={'token_ids': ids,
                                         'prompt_len': len(ids)})
        assert info['prefill_url'] == 'http://p0'

    def test_hf_fleet_skips_handoff_for_byte_guess_hints(
            self, monkeypatch):
        """A byte-encoded text/chat hint (ids_exact=False) must not
        hand off to a fleet that advertises an HF tokenizer — the
        streamed prefix would never match the replica's own
        tokenization (double prefill + LRU pollution). The request
        still serves on the decode tier."""
        policy, _urls = _tiered_policy(monkeypatch)
        policy.observe_response('http://d0',
                                {'X-SkyTPU-Tokenizer': 'hf'})
        ids = list(range(32))
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids),
                                        'ids_exact': False})
        assert info['result'] != 'handoff'
        assert url in ('http://d0', 'http://d1')
        assert policy.stats['handoff_skipped_tokenizer'] == 1
        assert policy.stats['handoff'] == 0

    def test_exact_ids_hand_off_even_on_hf_fleet(self, monkeypatch):
        """Client-supplied token arrays ARE the tokens the replica
        will see — the tokenizer gate never blocks them."""
        policy, _urls = _tiered_policy(monkeypatch)
        policy.observe_response('http://d0',
                                {'X-SkyTPU-Tokenizer': 'hf'})
        ids = list(range(32))
        _url, info = policy.select(hint={'token_ids': ids,
                                         'prompt_len': len(ids),
                                         'ids_exact': True})
        assert info['result'] == 'handoff'
        assert policy.stats['handoff_skipped_tokenizer'] == 0

    def test_untiered_fleet_keeps_phase_behavior(self, monkeypatch):
        """No tiers ⇒ the historical phase-aware partition still
        applies (explicit tiers supersede it, absence changes
        nothing)."""
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        monkeypatch.setenv('SKYTPU_SERVE_LB_PHASE_MIN_FLEET', '4')
        monkeypatch.setenv('SKYTPU_SERVE_LB_PHASE_THRESHOLD', '16')
        policy = PrefixAwarePolicy(clock=lambda: 0.0)
        urls = [f'http://r{i}' for i in range(4)]
        policy.set_ready_replicas(urls)
        ids = list(range(32))
        _url, info = policy.select(hint={'token_ids': ids,
                                         'prompt_len': len(ids)})
        assert info.get('phase') == 'prefill'
        assert policy.stats['handoff'] == 0


# ---------------------------------------------------------------------
# chat-route token hint (satellite)
# ---------------------------------------------------------------------


class TestChatRouteHint:

    @staticmethod
    def _hint(body: dict):
        import json
        from unittest import mock
        from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
        request = mock.Mock()
        request.method = 'POST'
        request.path = '/v1/chat/completions'
        return SkyServeLoadBalancer._routing_hint(  # pylint: disable=protected-access
            request, json.dumps(body).encode())

    def test_chat_messages_yield_token_ids_matching_server_template(
            self):
        """The LB reproduces the server's generic role-tagged template
        under the byte tokenizer, so chat routes carry real TOKEN
        counts (the handoff/phase threshold applies uniformly) and can
        digest-match byte-tokenized fleets."""
        from skypilot_tpu.serve.server import byte_encode
        messages = [{'role': 'system', 'content': 'be terse'},
                    {'role': 'user', 'content': 'hello there'}]
        hint = self._hint({'messages': messages})
        assert hint is not None
        expected = byte_encode('system: be terse\nuser: hello there'
                               '\nassistant:')
        assert hint['token_ids'] == expected
        assert hint['prompt_len'] == len(expected)

    def test_malformed_messages_fail_open(self):
        assert self._hint({'messages': 'not-a-list'}) is None
        hint = self._hint({'messages': [{'role': 'user'}, 'garbage']})
        # Non-dict entries are skipped; the rest still hints.
        assert hint is not None and hint['prompt_len'] > 0


# ---------------------------------------------------------------------
# server endpoint mapping (decode-side HTTP contract)
# ---------------------------------------------------------------------


@pytest.fixture(scope='module')
def ingest_server(handoff_engines):
    """The decode engine behind a live HTTP server (the test_chaos
    _wrap_server idiom), for the /kv/* status-code contract."""
    import asyncio
    import socket
    from aiohttp import web
    from skypilot_tpu.serve.server import InferenceServer
    _pre, dec, _mono = handoff_engines
    server = InferenceServer.__new__(InferenceServer)
    server.engine = dec
    server.tokenizer_kind = 'byte'
    server._hf_tokenizer = None  # pylint: disable=protected-access
    server.ready = True
    server.request_timeout = 0.0
    server.draining = False
    server.tier = 'decode'
    with socket.socket() as sock:
        sock.bind(('', 0))
        port = sock.getsockname()[1]

    def _serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, '127.0.0.1', port).start())
        loop.run_forever()

    threading.Thread(target=_serve, daemon=True).start()
    import requests
    deadline = time.time() + 30
    url = f'http://127.0.0.1:{port}'
    while time.time() < deadline:
        try:
            requests.get(url + '/health', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    return server, url


class TestIngestEndpoint:

    def test_status_code_contract(self, handoff_engines, ingest_server):
        import requests
        pre, dec, _mono = handoff_engines
        _server, url = ingest_server
        ids = list(range(250, 270))
        pre.prefill_prefix(ids, timeout=300)
        chunks = pre.export_prefix_chunks(ids, 'http-1', chunk_blocks=1)
        # Out-of-order → 409 with the expected seq (the pusher resumes).
        resp = requests.post(url + '/kv/ingest', data=chunks[1],
                             timeout=60)
        assert resp.status_code == 409 and resp.json()['expected'] == 0
        # Corrupt → 400.
        bad = bytearray(chunks[0])
        bad[-1] ^= 0xFF
        resp = requests.post(url + '/kv/ingest', data=bytes(bad),
                             timeout=60)
        assert resp.status_code == 400
        # In-order chunks apply; the tier header rides every response.
        resp = requests.post(url + '/kv/ingest', data=chunks[0],
                             timeout=60)
        assert resp.status_code == 200
        assert resp.headers.get('X-SkyTPU-Tier') == 'decode'
        # Abort over HTTP rolls the partial back to refcount-0.
        used = dec._pool.used  # pylint: disable=protected-access
        resp = requests.post(url + '/kv/abort',
                             json={'stream_id': 'http-1'}, timeout=60)
        assert resp.status_code == 200 and resp.json()['aborted']
        assert dec._pool.used == used - len(  # pylint: disable=protected-access
            [chunks[0]])
        dec._pool.check()  # pylint: disable=protected-access
        # /health reports the tier.
        resp = requests.get(url + '/health', timeout=60)
        assert resp.json()['tier'] == 'decode'


# ---------------------------------------------------------------------
# prefill-side push: retry budget + decode-shed relay
# ---------------------------------------------------------------------


def _bare_prefill_server():
    from skypilot_tpu.serve.server import InferenceServer
    server = InferenceServer.__new__(InferenceServer)
    server.tokenizer_kind = 'byte'
    server._hf_tokenizer = None  # pylint: disable=protected-access
    server.ready = True
    server.draining = False
    server.request_timeout = 0.0
    server.tier = 'prefill'
    return server


class _FakeRequests:
    """Stand-in for the requests module inside _push_stream: fails each
    seq's FIRST attempt transiently (or the same seq forever)."""

    class RequestException(Exception):
        pass

    def __init__(self, fail_each_once=True, wedge_seq=None):
        self.fail_each_once = fail_each_once
        self.wedge_seq = wedge_seq
        self.attempts = {}
        self.seq = 0

    def post(self, _url, data=None, headers=None, timeout=None):  # pylint: disable=unused-argument
        import types
        seq = self.seq
        n = self.attempts[seq] = self.attempts.get(seq, 0) + 1
        if self.wedge_seq == seq:
            raise self.RequestException(f'seq {seq} wedged')
        if self.fail_each_once and n == 1:
            raise self.RequestException(f'transient on seq {seq}')
        self.seq += 1
        return types.SimpleNamespace(status_code=200)


class TestPushStream:

    def test_transport_retry_budget_is_per_chunk(self, monkeypatch):
        """A long stream survives one transient hiccup on EVERY chunk
        (receiver dedups by seq) — the budget is per chunk, not two
        for the whole stream."""
        import sys
        server = _bare_prefill_server()
        fake = _FakeRequests(fail_each_once=True)
        monkeypatch.setitem(sys.modules, 'requests', fake)
        chunks = [b'c%d' % i for i in range(6)]
        result = server._push_stream('http://d', chunks, 's-1')  # pylint: disable=protected-access
        assert result['chunks'] == 6
        assert result['retries'] == 6          # one retry per chunk
        assert all(n == 2 for n in fake.attempts.values())

    def test_same_chunk_failing_twice_raises(self, monkeypatch):
        import sys
        from skypilot_tpu.serve.server import _HandoffPushError
        server = _bare_prefill_server()
        fake = _FakeRequests(fail_each_once=False, wedge_seq=2)
        monkeypatch.setitem(sys.modules, 'requests', fake)
        chunks = [b'c%d' % i for i in range(6)]
        with pytest.raises(_HandoffPushError) as exc:
            server._push_stream('http://d', chunks, 's-2')  # pylint: disable=protected-access
        assert exc.value.pushed == 2           # seqs 0,1 acknowledged

    def test_decode_shed_relayed_as_push_status(self):
        """A decode-side ingest shed (503) surfaces in the prefill
        replica's 502 body as push_status, so the LB can fall back
        monolithic instead of burning other prefill replicas on the
        same wall."""
        import asyncio
        import json as json_lib
        from unittest import mock
        from skypilot_tpu.serve.server import _HandoffPushError
        server = _bare_prefill_server()

        def shed(_ids, _target, _stream_id, _chunk_blocks,
                 _trace=None):
            raise _HandoffPushError('decode shed the ingest', 3,
                                    status=503)
        server._prefill_and_push = shed  # pylint: disable=protected-access
        request = mock.Mock()

        async def body():
            return {'prompt_ids': [1, 2, 3],
                    'target': 'http://decode'}
        request.json = body
        resp = asyncio.new_event_loop().run_until_complete(
            server.handle_kv_prefill(request))
        assert resp.status == 502
        data = json_lib.loads(resp.body.decode())
        assert data['push_status'] == 503
        assert data['pushed_chunks'] == 3


# ---------------------------------------------------------------------
# tiered fleet scaling: auto-tier preserves the disaggregated shape
# ---------------------------------------------------------------------


class TestAutoTier:

    @staticmethod
    def _replica(tier, version=1, counts=True):
        import types
        return types.SimpleNamespace(
            version=version, tier=tier,
            status=types.SimpleNamespace(
                counts_toward_fleet=lambda: counts))

    def test_auto_tier_refills_prefill_first(self):
        """scale_up(tier=None) — autoscaler growth, rolling updates,
        failed-replica replenishment — refills the prefill tier to
        spec before growing decode, so churn can never silently
        collapse a disaggregated fleet to decode-only."""
        import types
        from skypilot_tpu.serve.replica_managers import \
            SkyPilotReplicaManager
        pick = SkyPilotReplicaManager._tier_for_new_replica_locked  # pylint: disable=protected-access
        fake = types.SimpleNamespace(
            spec=types.SimpleNamespace(prefill_replicas=1),
            version=1, replicas={})
        assert pick(fake) == 'prefill'          # empty fleet
        fake.replicas[1] = self._replica('prefill')
        assert pick(fake) == 'decode'           # tier full → grow decode
        fake.replicas[1] = self._replica('prefill', counts=False)
        assert pick(fake) == 'prefill'          # failed prefill → refill
        fake.replicas[1] = self._replica('prefill', version=0)
        assert pick(fake) == 'prefill'          # rollout sizes ITS fleet
        fake.spec.prefill_replicas = 0
        assert pick(fake) == 'monolithic'       # untiered unchanged
