"""Paged KV cache (tier-1, CPU): block-pool allocator invariants,
paged==contiguous bit-identical greedy output, block-granular prefix
sharing with copy-on-write, chunked-prefill compile-count and
interleaving, and the prefix-index lookup-cost satellite.
"""
import dataclasses
import random
import time

import pytest

from skypilot_tpu.models.kv_cache import (BlockPool, PoolExhaustedError,
                                          PrefixIndex)


def _cfg(**kw):
    from skypilot_tpu.models import get_config
    cfg = get_config('test-tiny')
    kw.setdefault('max_seq_len', 64)
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', remat=False, **kw)


# ---------------------------------------------------------------------
# BlockPool: host-side allocator invariants (no device needed)
# ---------------------------------------------------------------------


class TestBlockPool:

    def test_scratch_block_reserved(self):
        pool = BlockPool(4, block_size=8)
        assert pool.used == 1                      # scratch only
        got = {pool.alloc() for _ in range(3)}
        assert 0 not in got                        # never handed out
        assert got == {1, 2, 3}
        with pytest.raises(PoolExhaustedError):
            pool.alloc()

    def test_refcount_lifecycle(self):
        pool = BlockPool(4, block_size=8)
        b = pool.alloc()
        pool.incref(b)                             # shared (rc=2)
        pool.decref(b)                             # owner done (rc=1)
        assert pool.refcount(b) == 1
        assert pool.free == 2                      # still held
        pool.decref(b)                             # last ref
        assert pool.free == 3
        pool.check()

    def test_double_free_and_bad_incref_raise(self):
        pool = BlockPool(4, block_size=8)
        b = pool.alloc()
        pool.decref(b)
        with pytest.raises(ValueError):
            pool.decref(b)
        with pytest.raises(ValueError):
            pool.incref(b)
        with pytest.raises(ValueError):
            pool.decref(0)                         # scratch is pinned

    def test_invariants_under_admit_finish_evict_churn(self):
        """Randomized admit/share/finish/evict churn: the free list and
        the referenced set must partition the pool at every step, and
        draining everything must return the pool to its initial state.
        Mirrors the engine's lifecycle: a request allocates suffix
        blocks, may share prefix blocks (incref), finishes (release),
        and prefix entries evict (release) in arbitrary order."""
        rng = random.Random(1234)
        pool = BlockPool(32, block_size=8)
        requests = []                              # live block lists
        entries = []                               # shared prefix refs
        for step in range(500):
            action = rng.random()
            if action < 0.4 and pool.free:
                n = rng.randint(1, min(4, pool.free))
                blocks = [pool.alloc() for _ in range(n)]
                if entries and rng.random() < 0.5:
                    shared = rng.choice(entries)
                    for b in shared:
                        pool.incref(b)
                    blocks = list(shared) + blocks
                requests.append(blocks)
            elif action < 0.6 and requests:
                blocks = requests.pop(rng.randrange(len(requests)))
                if rng.random() < 0.4:             # publish as a prefix
                    keep = blocks[:rng.randint(1, len(blocks))]
                    for b in keep:
                        pool.incref(b)
                    entries.append(keep)
                pool.release(blocks)
            elif entries:
                pool.release(entries.pop(rng.randrange(len(entries))))
            pool.check()
            assert pool.used + pool.free == pool.num_blocks
        for blocks in requests:
            pool.release(blocks)
        for blocks in entries:
            pool.release(blocks)
        pool.check()
        assert pool.used == 1                      # back to scratch-only
        assert pool.peak_used <= pool.num_blocks


# ---------------------------------------------------------------------
# PrefixIndex: chunked-trie longest-prefix lookup (satellite)
# ---------------------------------------------------------------------


class TestPrefixIndex:

    def test_longest_match_all_or_nothing(self):
        idx = PrefixIndex(capacity=8, chunk=4)
        idx.put(list(range(10)), 'short')
        idx.put(list(range(20)), 'long')
        idx.put([9, 9, 9, 9, 9], 'other')
        # Prompt extends the long entry: longest wins.
        plen, payload = idx.lookup(list(range(20)) + [99], limit=20)
        assert (plen, payload) == (20, 'long')
        # Divergence INSIDE an entry yields no partial credit (matches
        # the engine's historical all-or-nothing contract).
        diverged = list(range(8)) + [77, 78]
        plen, payload = idx.lookup(diverged + [99], limit=10)
        assert plen == 0 and payload is None

    def test_limit_caps_match_for_exact_repeat(self):
        """An exact repeat reuses all but the last token — the suffix
        must stay non-empty to produce logits."""
        idx = PrefixIndex(capacity=4, chunk=4)
        idx.put(list(range(10)), 'e')
        plen, payload = idx.lookup(list(range(10)), limit=9)
        assert (plen, payload) == (9, 'e')

    def test_entry_longer_than_prompt_matches_prompt_prefix(self):
        idx = PrefixIndex(capacity=4, chunk=4)
        idx.put(list(range(18)), 'deep')           # 4 chunks + tail 2
        plen, payload = idx.lookup(list(range(7)), limit=6)
        assert (plen, payload) == (6, 'deep')

    def test_lru_eviction_and_displaced_payloads(self):
        idx = PrefixIndex(capacity=2, chunk=4)
        assert idx.put([1, 2, 3, 4, 5], 'a') == []
        idx.put([6, 7, 8, 9], 'b')
        displaced = idx.put([10, 11, 12], 'c')     # evicts 'a'
        assert displaced == [((1, 2, 3, 4, 5), 'a')]
        assert list(idx) == [(6, 7, 8, 9), (10, 11, 12)]
        # Evicted entries no longer match.
        assert idx.lookup([1, 2, 3, 4, 5, 6], limit=5) == (0, None)
        # Re-storing an existing key displaces ITS old payload only.
        assert idx.put([6, 7, 8, 9], 'b2') == [((6, 7, 8, 9), 'b')]
        assert list(idx) == [(10, 11, 12), (6, 7, 8, 9)]

    def test_chunk_aligned_limit_still_matches_longer_entry(self):
        """Regression: when limit is an exact chunk multiple, longer
        entries live one full-chunk edge below the final walked node and
        every descendant matches all `limit` tokens — the lookup must
        not return (0, None)."""
        idx = PrefixIndex(capacity=4, chunk=16)
        idx.put(list(range(48)), 'deep')
        plen, payload = idx.lookup(list(range(33)), limit=32)
        assert (plen, payload) == (32, 'deep')

    def test_lookup_cost_is_chunks_not_entries_times_prompt(self):
        """The satellite's bound, counted: lookup work stays
        O(prompt + entries·chunk) token compares, NOT the old
        O(entries × prompt) full re-comparison per entry."""
        chunk, n_entries, plen = 16, 8, 160
        idx = PrefixIndex(capacity=n_entries, chunk=chunk)
        shared = list(range(1000, 1000 + plen))
        for i in range(n_entries):
            idx.put(shared + [i] * 4, f'e{i}')     # deep shared trie path
        matched, _ = idx.lookup(shared + [3] * 4 + [9], limit=plen + 4)
        assert matched == plen + 4
        old_cost = n_entries * (plen + 4)          # what the list scan paid
        bound = (plen + 4) + n_entries * chunk
        assert idx.last_compares <= bound < old_cost, (
            idx.last_compares, bound, old_cost)


# ---------------------------------------------------------------------
# Paged engine: correctness + accounting on CPU
# ---------------------------------------------------------------------
# Engines are shared per fixture scope where state allows: every
# ContinuousBatchingEngine re-JITs its programs, and tier-1 runs on a
# wall-clock budget.


@pytest.fixture(scope='module')
def ref_engine():
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(), num_slots=2)
    yield engine
    engine.stop()


@pytest.fixture(scope='module')
def paged_engine():
    """Shared paged engine WITHOUT prefix cache (stateless across
    requests once each finishes)."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                      paged_block_size=8)
    yield engine
    engine.stop()


class TestPagedEngine:

    def test_greedy_bit_identical_across_chunk_boundaries(
            self, ref_engine, paged_engine):
        """Prompt lengths straddling block/chunk boundaries (below, at,
        above a multiple of block_size) must decode bit-identically to
        the contiguous engine — the correctness bar for the scatter/
        gather cache layout AND for chunked prefill."""
        prompts = [
            list(range(2, 9)),        # 7  < block
            list(range(2, 10)),       # 8  == block
            list(range(2, 19)),       # 17 = 2 blocks + 1
            list(range(2, 26)),       # 24 = 3 blocks exactly
        ]
        for prompt in prompts:
            want, _ = ref_engine.generate(prompt, max_new_tokens=8)
            got, stats = paged_engine.generate(prompt, max_new_tokens=8)
            assert got == want, (prompt, got, want)
            assert stats['new_tokens'] == 8

    def test_concurrent_slots_bit_identical(self, ref_engine,
                                            paged_engine):
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        want, _ = ref_engine.generate(prompt, max_new_tokens=10)
        futures = [paged_engine.submit(prompt, max_new_tokens=10)
                   for _ in range(4)]
        results = [f.result(timeout=120) for f in futures]
        for toks, _ in results:
            assert toks == want


class TestPagedPrefixSharing:
    """One prefix-caching engine, tests in definition order: first the
    pool-accounting pin on a fresh pool, then CoW sharing on top of the
    entry the first test cached."""

    BASE = list(range(2, 22))                      # L=20 → 2 full + 4

    @pytest.fixture(scope='class')
    def pfx_engine(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                          paged_block_size=8,
                                          prefix_cache=4)
        yield engine
        engine.stop()

    def test_cached_prefix_costs_ceil_blocks_not_full_cache(
            self, pfx_engine):
        """THE capacity win: a cached prefix of length L holds
        ceil(L/block_size) pool blocks — not a full max_seq_len cache —
        asserted via pool accounting after the owning request freed its
        private blocks."""
        pfx_engine.generate(self.BASE, max_new_tokens=4)
        occ = pfx_engine.paged_occupancy()
        want_blocks = -(-len(self.BASE) // 8)      # ceil(20/8) = 3
        # scratch + the prefix entry's blocks; everything else
        # (decode-suffix blocks) returned to the free list.
        assert occ['blocks_used'] == 1 + want_blocks, occ
        assert occ['prefix_entries'] == 1
        pfx_engine._pool.check()  # pylint: disable=protected-access

    def test_cow_two_requests_extend_same_prefix(self, ref_engine,
                                                 pfx_engine):
        """Two requests extending one cached prefix: each clones the
        partial boundary block (CoW) and shares the full blocks
        read-only; both outputs equal the uncached reference — sharing
        never leaks one request's suffix into the other."""
        ext_a = self.BASE + [3, 9, 27]
        ext_b = self.BASE + [4, 8, 16]
        want_a, _ = ref_engine.generate(ext_a, max_new_tokens=6)
        want_b, _ = ref_engine.generate(ext_b, max_new_tokens=6)
        got_a, _ = pfx_engine.generate(ext_a, max_new_tokens=6)
        got_b, _ = pfx_engine.generate(ext_b, max_new_tokens=6)
        assert got_a == want_a
        assert got_b == want_b
        assert pfx_engine.paged_stats['cow_copies'] == 2
        assert pfx_engine.paged_stats['blocks_reused'] == 4  # 2 full x 2
        assert pfx_engine.prefix_stats['hits'] == 2
        assert pfx_engine.prefix_stats['tokens_reused'] == \
            2 * len(self.BASE)
        pfx_engine._pool.check()  # pylint: disable=protected-access


class TestChunkedPrefill:

    def test_chunked_prefill_compiles_one_shape_buckets_compile_many(self):
        """The compile-count pin: three prompt lengths spanning three
        power-of-two buckets compile THREE prefill programs on the
        contiguous engine but exactly ONE fixed chunk shape on the
        paged engine."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        prompts = [list(range(2, 12)),             # bucket 16
                   list(range(2, 26)),             # bucket 32
                   list(range(2, 40))]             # bucket 64
        bucketed = ContinuousBatchingEngine(_cfg(), num_slots=1)
        try:
            for p in prompts:
                bucketed.generate(p, max_new_tokens=2)
            bucket_compiles = bucketed._prefill._cache_size()  # pylint: disable=protected-access
        finally:
            bucketed.stop()
        paged = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                         paged_block_size=8)
        try:
            for p in prompts:
                paged.generate(p, max_new_tokens=2)
            paged_compiles = paged._prefill_chunk_fn._cache_size()  # pylint: disable=protected-access
            assert paged._prefill._cache_size() == 0  # pylint: disable=protected-access
        finally:
            paged.stop()
        assert bucket_compiles == 3
        assert paged_compiles == 1

    def test_decode_ticks_interleave_with_long_prompt_chunks(self):
        """step_log interleaving: while a long prompt prefills chunk by
        chunk (an explicit narrow prefill_chunk=8 — the default width
        would swallow this tiny context whole — so 40 tokens → 5
        chunks), the in-flight slot keeps emitting decode ticks BETWEEN
        chunks — the TPOT-stall chunked prefill exists to remove."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        paged_engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                                paged_block_size=8,
                                                prefill_chunk=8)
        try:
            self._check_interleaving(paged_engine)
        finally:
            paged_engine.stop()

    @staticmethod
    def _check_interleaving(paged_engine):
        marker = len(paged_engine.step_log)
        short_fut = paged_engine.submit([9, 9], max_new_tokens=40)
        deadline = time.time() + 30
        while len(paged_engine.step_log) <= marker and \
                time.time() < deadline:
            time.sleep(0.01)
        long_fut = paged_engine.submit(list(range(1, 41)),
                                       max_new_tokens=4)
        short_fut.result(timeout=120)
        long_fut.result(timeout=120)
        log = list(paged_engine.step_log)[marker:]
        prefill_ticks = [i for i, (tag, _) in enumerate(log)
                         if tag == 'prefill']
        decode_ticks = [i for i, (tag, _) in enumerate(log)
                        if tag != 'prefill']
        assert len(prefill_ticks) >= 5, log
        interleaved = any(
            prefill_ticks[j] < d < prefill_ticks[j + 1]
            for d in decode_ticks
            for j in range(len(prefill_ticks) - 1))
        assert interleaved, (
            f'no decode tick landed between prefill chunks: {log}')

    def test_paged_with_decode_chunk_matches_reference(self, ref_engine):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        prompt = [5, 7, 11, 13]
        want, _ = ref_engine.generate(prompt, max_new_tokens=9)
        engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                          paged_block_size=8,
                                          decode_chunk=4)
        try:
            got, stats = engine.generate(prompt, max_new_tokens=9)
        finally:
            engine.stop()
        assert got == want
        assert stats['new_tokens'] == 9

    def test_pool_exhaustion_sheds_instead_of_wedging(self):
        """An undersized pool sheds the oversized request with
        EngineOverloadedError; the engine keeps serving."""
        from skypilot_tpu import exceptions
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        # 3 data blocks = 24 tokens of capacity (max_seq_len 64).
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                          paged_block_size=8,
                                          paged_num_blocks=4)
        try:
            with pytest.raises(exceptions.EngineOverloadedError):
                engine.generate(list(range(1, 41)), max_new_tokens=4)
            # Small requests still fit and still serve.
            toks, _ = engine.generate([5, 7, 11], max_new_tokens=4)
            assert len(toks) == 4
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()

    def test_cow_alloc_failure_releases_shared_increfs(self):
        """Regression: when the CoW clone cannot allocate (pool
        exhausted, matched entry's blocks pinned by a live owner), the
        shed must UNDO the prefix increfs — leaked refs would shrink
        the pool permanently. Driven through _admit_paged directly so
        the exhaustion is deterministic."""
        from skypilot_tpu.models import inference as inference_lib
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(), num_slots=2,
                                          paged_block_size=8,
                                          paged_num_blocks=4,
                                          prefix_cache=2)
        try:
            pool = engine._pool  # pylint: disable=protected-access
            # A 3-block entry (2 full + 1 partial) whose owner is still
            # in flight: eviction can drop the entry's refs but frees
            # nothing, and the pool has no other block for the CoW.
            owner_blocks = [pool.alloc() for _ in range(3)]
            base = list(range(2, 22))              # 20 tokens, 3 blocks
            for b in owner_blocks:
                pool.incref(b)                     # the prefix entry ref
            engine._prefix_entries.put(tuple(base), list(owner_blocks))  # pylint: disable=protected-access
            assert pool.free == 0
            refs_before = [pool.refcount(b) for b in owner_blocks]
            req = inference_lib._Request(  # pylint: disable=protected-access
                base + [1, 2, 3, 4], 4, 0.0, None, None)
            with pytest.raises(PoolExhaustedError):
                engine._admit_paged(0, req)  # pylint: disable=protected-access
            # Entry evicted under pressure (refs dropped), but the
            # admission's own increfs were rolled back: owner refs only.
            assert [pool.refcount(b) for b in owner_blocks] == \
                [r - 1 for r in refs_before]
            pool.check()
        finally:
            engine.stop()

    def test_composed_combos_construct(self):
        """The PR-3 gates are gone: paged composes with speculative AND
        int8-KV (decode behavior pinned by test_composition_matrix.py).
        Block size must still divide the window."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                          paged_block_size=8,
                                          speculative=2,
                                          kv_quant='int8')
        try:
            assert engine.speculative == 2
            assert engine.cfg.kv_cache_quant == 'int8'
            assert engine.paged_int8_bytes_saved > 0
        finally:
            engine.stop()
        with pytest.raises(ValueError, match='divisible'):
            ContinuousBatchingEngine(_cfg(), num_slots=1,
                                     paged_block_size=7)

    def test_eviction_only_frees_at_refcount_zero(self):
        """Filling the prefix LRU past capacity evicts entries; blocks
        go back to the free list exactly when nothing references them,
        and the pool balances afterwards."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                          paged_block_size=8,
                                          prefix_cache=2)
        try:
            for start in (2, 30, 60, 90, 120):
                engine.generate(list(range(start, start + 20)),
                                max_new_tokens=2)
            occ = engine.paged_occupancy()
            # 2 surviving entries x ceil(20/8)=3 blocks, + scratch.
            assert occ['prefix_entries'] == 2
            assert occ['blocks_used'] == 1 + 2 * 3, occ
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()


# ---------------------------------------------------------------------
# The default chunk width: one pass over the weights carries as many
# prompt tokens as it can (default_prefill_chunk), not one KV block.
# ---------------------------------------------------------------------


@pytest.mark.parametrize('block,context,weight_bytes,want', [
    (16, 768, 2, 256),   # the benchmark's serving cells
    (16, 896, 2, 256),
    (16, 4096, 2, 256),  # the context does not widen it
    (16, 768, 1, 128),   # int8 weights: half the bytes, half the ridge
    (8, 64, 2, 64),      # a context under the target: the whole of it
    (16, 128, 2, 128),
    (24, 240, 2, 240),   # a whole number of blocks, rounded down
    (48, 480, 2, 240),
    (512, 1024, 2, 512),  # a block above the target: one block
])
def test_default_prefill_chunk_follows_the_rule(block, context,
                                                weight_bytes, want):
    from skypilot_tpu.models.inference import default_prefill_chunk
    width = default_prefill_chunk(block, context, weight_bytes)
    assert width == want
    assert width % block == 0 and block <= width <= context


WIDE_CTX = 512           # default width 256 < context: prompts can span


@pytest.fixture(scope='module')
def wide_engine():
    """Paged engine at the DEFAULT chunk width on a context wide enough
    that prompts fall short of, on and past the width."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(max_seq_len=WIDE_CTX),
                                      num_slots=2, paged_block_size=8)
    yield engine
    engine.stop()


@pytest.fixture(scope='module')
def narrow_engine():
    """The same engine with the old default: one block a chunk."""
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(_cfg(max_seq_len=WIDE_CTX),
                                      num_slots=2, paged_block_size=8,
                                      prefill_chunk=8)
    yield engine
    engine.stop()


def _prompt(n, salt=0):
    return [(7 * i + 3 + salt) % 509 + 1 for i in range(n)]


class TestDefaultChunkWidth:

    def test_engine_takes_the_rule_or_the_override(self, wide_engine,
                                                   narrow_engine,
                                                   paged_engine):
        from skypilot_tpu.models.inference import default_prefill_chunk
        assert wide_engine.prefill_chunk == 256 == \
            default_prefill_chunk(8, WIDE_CTX)
        assert isinstance(wide_engine.prefill_chunk, int)
        assert paged_engine.prefill_chunk == 64    # context 64
        assert narrow_engine.prefill_chunk == 8    # explicit override
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        int8 = ContinuousBatchingEngine(_cfg(max_seq_len=WIDE_CTX),
                                        num_slots=1, paged_block_size=8,
                                        quantize='int8')
        try:
            assert int8.prefill_chunk == 128       # a byte a weight
        finally:
            int8.stop()

    @pytest.mark.parametrize('length', [100, 256, 300, 257, 19])
    def test_greedy_equals_one_block_chunks(self, wide_engine,
                                            narrow_engine, length):
        """Shorter than, equal to and longer than the width (and one
        token past it, and under three blocks): token for token what
        block-sized chunks give."""
        prompt = _prompt(length)
        want, _ = narrow_engine.generate(prompt, max_new_tokens=8)
        got, stats = wide_engine.generate(prompt, max_new_tokens=8)
        assert got == want, (length, got, want)
        assert stats['new_tokens'] == 8

    def test_counters_count_dispatches_and_real_tokens(self):
        """prefill_chunks stays one per dispatch, prefill_tokens counts
        the real prompt tokens in them (pads excluded), and exactly one
        prefill program compiles whatever the prompts' lengths."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(max_seq_len=WIDE_CTX),
                                          num_slots=2, paged_block_size=8)
        try:
            lengths = [19, 256, 300]
            for n in lengths:
                engine.generate(_prompt(n, salt=n), max_new_tokens=2)
            occ = engine.paged_occupancy()
            assert occ['prefill_tokens'] == sum(lengths)
            assert occ['prefill_chunks'] == 1 + 1 + 2
            assert occ['prefill_tokens'] < \
                occ['prefill_chunks'] * engine.prefill_chunk
            assert engine._prefill_chunk_fn._cache_size() == 1  # pylint: disable=protected-access
        finally:
            engine.stop()

    @pytest.mark.parametrize('length', [5, 20, 64, 100])
    def test_single_wide_chunk_reserves_blocks_for_real_tokens(
            self, wide_engine, length):
        """After a short prompt's one wide chunk the request holds
        ceil(prompt / block) blocks — not the padded chunk's 32: a
        sized pool must not shed requests for pad positions."""
        seen = []
        first_token = wide_engine._first_token  # pylint: disable=protected-access

        def after_the_chunk(slots, slot, req, row, position):
            # before the decode step is queued, which reserves the
            # block of the position it writes
            seen.append(len(req.blocks))
            return first_token(slots, slot, req, row, position)

        before = wide_engine.paged_stats['prefill_chunks']
        wide_engine._first_token = after_the_chunk  # pylint: disable=protected-access
        try:
            wide_engine.submit(_prompt(length, salt=1),
                               max_new_tokens=3).result(timeout=120)
        finally:
            wide_engine._first_token = first_token  # pylint: disable=protected-access
        assert wide_engine.paged_stats['prefill_chunks'] == before + 1
        assert seen == [-(-length // 8)]

    def test_sized_pool_serves_short_prompts_at_the_wide_width(self):
        """3 data blocks = 24 tokens of pool: a 10-token prompt with 6
        new tokens fits, though its padded chunk spans 8 blocks."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1,
                                          paged_block_size=8,
                                          paged_num_blocks=4)
        try:
            assert engine.prefill_chunk == 64
            toks, _ = engine.generate(_prompt(10), max_new_tokens=6)
            assert len(toks) == 6
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()

    def test_prefix_hit_starting_off_the_width(self, narrow_engine):
        """A prefix-cache hit leaves the suffix starting at position
        100: neither a multiple of the width nor of the block (CoW),
        with a second chunk starting at 356."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(_cfg(max_seq_len=WIDE_CTX),
                                          num_slots=2, paged_block_size=8,
                                          prefix_cache=4)
        try:
            base = _prompt(100, salt=2)
            ext = base + _prompt(300, salt=5)
            engine.generate(base, max_new_tokens=2)
            chunks = engine.paged_stats['prefill_chunks']
            got, _ = engine.generate(ext, max_new_tokens=8)
            assert engine.prefix_stats['hits'] == 1
            assert engine.prefix_stats['tokens_reused'] == 100
            assert engine.paged_stats['prefill_chunks'] == chunks + 2
            assert engine.paged_stats['prefill_tokens'] == 100 + 300
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()
        want, _ = narrow_engine.generate(ext, max_new_tokens=8)
        assert got == want

    def test_unscanned_layers_agree_at_the_default_width(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        cfg = _cfg(max_seq_len=WIDE_CTX, scan_layers=False)
        prompt = _prompt(300, salt=9)
        outs = []
        for chunk in (0, 8):
            engine = ContinuousBatchingEngine(
                cfg, num_slots=1, paged_block_size=8, prefill_chunk=chunk)
            try:
                outs.append(engine.generate(prompt, max_new_tokens=6)[0])
            finally:
                engine.stop()
        assert outs[0] == outs[1]


class TestStepLogBounded:

    def test_step_log_is_capped(self):
        """The satellite fix: step_log must stop growing at the cap (a
        serve replica decodes for weeks) while still supporting the
        slicing the interleaving tests use."""
        from skypilot_tpu.models.inference import (_STEP_LOG_CAP,
                                                   _StepLog)
        log = _StepLog(maxlen=_STEP_LOG_CAP)
        for i in range(_STEP_LOG_CAP + 500):
            log.append((i, frozenset({0})))
        assert len(log) == _STEP_LOG_CAP
        assert log[0][0] == 500                    # oldest rotated out
        tail = log[-3:]
        assert [t[0] for t in tail] == [_STEP_LOG_CAP + 497,
                                        _STEP_LOG_CAP + 498,
                                        _STEP_LOG_CAP + 499]

    def test_engine_step_log_supports_marker_slicing(self, ref_engine):
        ref_engine.generate([5, 7, 11], max_new_tokens=4)
        marker = len(ref_engine.step_log)
        ref_engine.generate([5, 7, 11], max_new_tokens=4)
        new = ref_engine.step_log[marker:]
        assert isinstance(new, list) and new
