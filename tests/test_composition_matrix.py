"""Serving fast-path COMPOSITION matrix (tier-1, CPU): every
optimization on one engine (models/inference.py).

PR 3 gated paged+speculative and paged+int8-KV; PR 4 capped lookahead
at async_depth=1. This suite pins the un-gated world:

  - greedy token streams BIT-IDENTICAL to the ungated sync contiguous
    baseline for {paged, int8-KV, speculative, chunked prefill} x
    {sync, async_depth=1, async_depth=3} — int8 cells compare against
    the contiguous-int8 sync baseline (quantization changes numerics;
    the layout/pipeline must not);
  - zero steady-state host→device uploads under async_depth=N
    paged+int8 (transfer-counting shim over the module's single
    _upload funnel / jnp binding), and host-gap 0.0 for every chained
    dispatch in the ring;
  - EOS overshoot discarded by request identity up to N steps late,
    admission/finish churn flushing the whole ring, and a watchdog
    wedge recovery dropping a DEEP ring wholesale (chaos);
  - paged x speculative rolls rejected drafts' blocks back to the pool
    (allocator invariants hold after churn).
"""
import dataclasses
import threading
import time

import pytest

import jax

from skypilot_tpu import exceptions
from skypilot_tpu.utils import fault_injection

# Chosen so the int8-KV reference stream visibly DIVERGES from fp within
# a few tokens (the refs-fixture sanity check: int8 must demonstrably
# engage). The mesh-invariant init landed by parallel/
# (jax_threefry_partitionable) changed the seeded test-tiny weights, and
# with the previous prompt ([3,1,4,1,5,9,2,6], pi digits) the int8
# rounding no longer flipped any greedy argmax in the whole window.
PROMPT = [9, 9, 8, 8, 7, 7, 6, 6]


def _cfg(**kw):
    from skypilot_tpu.models import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


def _engine(**kw):
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    return ContinuousBatchingEngine(_cfg(), num_slots=2, **kw)


@pytest.fixture(scope='module')
def refs():
    """Greedy reference streams: fp and int8-KV, both sync contiguous
    (an engine emits the same greedy stream at any max_new_tokens
    prefix, so every cell compares against a prefix of these)."""
    fp = _engine()
    ref, _ = fp.generate(PROMPT, max_new_tokens=30)
    fp.stop()
    q8 = _engine(kv_quant='int8')
    ref8, _ = q8.generate(PROMPT, max_new_tokens=30)
    q8.stop()
    assert ref != ref8, 'int8 reference suspiciously equals fp'
    return {'': ref, 'int8': ref8}


# The matrix: feature cells x async depths. `prefill_chunk=4` forces
# chunked prefill over the 8-token prompt (two chunks) in paged cells.
_CELLS = [
    ('paged', dict(paged_block_size=8)),
    ('int8', dict(kv_quant='int8')),
    ('spec', dict(speculative=3)),
    ('paged-int8', dict(paged_block_size=8, kv_quant='int8')),
    ('paged-spec', dict(paged_block_size=8, speculative=3)),
    ('paged-int8-spec',
     dict(paged_block_size=8, kv_quant='int8', speculative=3)),
    ('paged-int8-spec-chunkedprefill',
     dict(paged_block_size=8, kv_quant='int8', speculative=3,
          prefill_chunk=4)),
]


class TestCompositionBitIdentity:

    @pytest.mark.parametrize('depth', [0, 1, 3])
    @pytest.mark.parametrize('name,kw', _CELLS,
                             ids=[c[0] for c in _CELLS])
    def test_cell_matches_baseline(self, refs, name, kw, depth):
        ref = refs['int8' if 'int8' in name else '']
        engine = _engine(async_depth=depth, **kw)
        try:
            got, stats = engine.generate(PROMPT, max_new_tokens=16)
            assert got == ref[:16], (name, depth, got)
            assert stats['new_tokens'] == 16
            if depth >= 1 and not kw.get('speculative'):
                # Spec cells emit through verify ticks (which flush the
                # ring); plain cells must actually exercise chaining.
                assert engine.tick_stats['chained'] > 0, (name, depth)
            if kw.get('paged_block_size'):
                engine._pool.check()  # pylint: disable=protected-access
            # EOS overshoot: detected up to `depth` steps late, the
            # overshoot discarded by identity — stream still exact.
            eos = ref[5]
            got, _ = engine.generate(PROMPT, max_new_tokens=16,
                                     eos_id=eos)
            assert got == ref[:6], (name, depth, got)
        finally:
            engine.stop()

    def test_full_composition_constructs_and_serves(self, refs):
        """The acceptance-criteria cell verbatim: paged + speculative +
        int8-KV + async_depth=3 on ONE engine."""
        engine = _engine(paged_block_size=8, speculative=3,
                         kv_quant='int8', async_depth=3)
        try:
            got, _ = engine.generate(PROMPT, max_new_tokens=16)
            assert got == refs['int8'][:16]
            assert engine.paged_int8_bytes_saved > 0
            assert engine.spec_stats['accepted'] >= 0
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()


class TestDeepRingChurn:

    @pytest.fixture(scope='class')
    def deep_engine(self):
        engine = _engine(paged_block_size=8, kv_quant='int8',
                         async_depth=3)
        yield engine
        engine.stop()

    def test_staggered_churn_streams_identical(self, refs, deep_engine):
        """Staggered concurrent requests with different lengths force
        admission/finish churn mid-pipeline: the deep ring rides
        through every finish by length and every join without a flush,
        and each per-request stream (including the on_token order)
        must equal the solo baseline."""
        ref = refs['int8']
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
            futures.append(deep_engine.submit(
                PROMPT, max_new_tokens=n, on_token=_tap(i)))
            if i % 2:
                time.sleep(0.02)
        results = [f.result(timeout=120)[0] for f in futures]
        for i, n in enumerate(lens):
            assert results[i] == ref[:n], (i, n, results[i])
            assert streams[i] == ref[:n], (i, n, streams[i])
        assert deep_engine.tick_stats['chained'] > 0
        assert deep_engine.tick_stats['flushes'] == 0
        deep_engine._pool.check()  # pylint: disable=protected-access


class TestSpecPagedRollback:

    def test_rejected_drafts_return_blocks(self, refs, monkeypatch):
        """paged x speculative: the verify span reserves blocks for all
        K+1 write positions; rejected drafts must roll the block table
        back (refcount rollback, the paged analogue of the contiguous
        cache truncation) instead of holding the tail to completion —
        and the allocator must balance after the request finishes.
        Drafts are deliberate garbage (never the model's own greedy
        choice — the test_inference oracle pattern, inverted), so EVERY
        verify tick rejects all K drafts, emits only the bonus token,
        and the trim path runs deterministically."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        ref = refs['']
        full = PROMPT + ref
        vocab = _cfg().vocab_size

        def garbage_draft(context, k):
            n = len(context)
            assert context == full[:n]
            return [(full[min(n + j, len(full) - 1)] + 1) % vocab
                    for j in range(k)]

        engine = ContinuousBatchingEngine(
            _cfg(), num_slots=1, paged_block_size=2, speculative=3)
        monkeypatch.setattr(engine, "_draft_tokens", garbage_draft)
        try:
            got, _ = engine.generate(PROMPT, max_new_tokens=12)
            assert got == ref[:12]
            assert engine.spec_stats['ticks'] > 0
            # Partial acceptance every tick + 2-token blocks over a
            # 4-position verify span: the rollback must have fired.
            assert engine.paged_stats['spec_trimmed_blocks'] > 0
            pool = engine._pool  # pylint: disable=protected-access
            pool.check()
            # Everything released: only the scratch block stays.
            assert pool.used == 1, pool.used
        finally:
            engine.stop()

    def test_pool_exhausted_fallback_rolls_back_reservation(
            self, refs, monkeypatch):
        """Pool pressure mid-reserve: when the verify-span loop hits
        PoolExhaustedError on a LATER slot, blocks already reserved
        for earlier slots (and the failing slot's partial growth) must
        go back to the pool before the single-step fallback — holding
        them would deepen the very exhaustion that forced the
        fallback. Drafts are always CORRECT here, so the success-path
        trim reclaims nothing and the counter can only move via the
        exhaustion rollback."""
        from skypilot_tpu.models import kv_cache as kv_cache_lib
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        ref = refs['']
        full = PROMPT + ref

        def perfect_draft(context, k):
            n = len(context)
            return [full[min(n + j, len(full) - 1)] for j in range(k)]

        engine = ContinuousBatchingEngine(
            _cfg(), num_slots=2, paged_block_size=2, speculative=3)
        monkeypatch.setattr(engine, '_draft_tokens', perfect_draft)
        real_ensure = engine._ensure_blocks  # pylint: disable=protected-access
        state = {'armed': False, 'span_calls': 0, 'fired': False}

        def flaky_ensure(req, upto_pos):
            # A verify-span reservation covers next_pos+K+1; fail the
            # SECOND one after arming, so slot 0 has already reserved.
            if (state['armed'] and not state['fired'] and
                    upto_pos - req.next_pos == engine.speculative + 1):
                state['span_calls'] += 1
                if state['span_calls'] == 2:
                    state['fired'] = True
                    raise kv_cache_lib.PoolExhaustedError('injected')
            return real_ensure(req, upto_pos)

        monkeypatch.setattr(engine, '_ensure_blocks', flaky_ensure)
        try:
            counts = [0, 0]
            seen = [threading.Event(), threading.Event()]

            def _tap(i):
                def cb(tok):
                    if tok is not None:
                        counts[i] += 1
                        if counts[i] >= 4:
                            seen[i].set()
                return cb

            futs = [engine.submit(PROMPT, max_new_tokens=24,
                                  on_token=_tap(i)) for i in (0, 1)]
            assert all(e.wait(timeout=60) for e in seen), \
                'requests never reached steady decode'
            state['armed'] = True
            results = [f.result(timeout=120)[0] for f in futs]
            assert state['fired'], 'injection never hit a verify span'
            # The rollback (not the all-accepted success path, which
            # trims nothing) returned the over-reservation.
            assert engine.paged_stats['spec_trimmed_blocks'] > 0
            # And the streams survived the fallback bit-identical.
            assert results[0] == ref[:24]
            assert results[1] == ref[:24]
            pool = engine._pool  # pylint: disable=protected-access
            pool.check()
            assert pool.used == 1, pool.used
        finally:
            engine.stop()


class _CountingJnp:
    """Transfer-counting shim (tests/test_async_pipeline.py pattern):
    counts every jnp.asarray over non-device values — the module's
    single host→device upload funnel."""

    def __init__(self, real):
        self._real = real
        self.uploads = []

    def asarray(self, value, *args, **kwargs):
        if not isinstance(value, jax.Array):
            self.uploads.append(type(value).__name__)
        return self._real.asarray(value, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._real, name)


class TestComposedSteadyStateUploads:

    def test_paged_int8_deep_ring_uploads_bounded(self, monkeypatch):
        """The acceptance pin: zero steady-state host→device uploads
        under async_depth=N paged+int8 — the deep ring feeds the device
        from the device. Bounded like the PR-4 pins: ≤ one table
        rebuild per crossed block boundary plus the shim-installation
        allowance, far below one upload per tick."""
        from skypilot_tpu.models import inference
        engine = _engine(paged_block_size=8, kv_quant='int8',
                         async_depth=3)
        try:
            engine.generate(PROMPT, max_new_tokens=2)   # compile
            fut = engine.submit(PROMPT, max_new_tokens=48)
            deadline = time.time() + 60
            while engine._decode_steps < 6 and \
                    time.time() < deadline:  # pylint: disable=protected-access
                time.sleep(0.01)
            shim = _CountingJnp(inference.jnp)
            monkeypatch.setattr(inference, 'jnp', shim)
            start = engine._decode_steps  # pylint: disable=protected-access
            while engine._decode_steps < start + 10 and \
                    time.time() < deadline:  # pylint: disable=protected-access
                time.sleep(0.01)
            uploads = len(shim.uploads)
            window = engine._decode_steps - start  # pylint: disable=protected-access
            monkeypatch.setattr(inference, 'jnp', shim._real)  # pylint: disable=protected-access
            fut.result(timeout=120)
            assert window >= 10, 'engine made no progress under shim'
            assert engine.tick_stats['chained'] > 0
        finally:
            engine.stop()
        assert uploads <= 4, (
            f'{uploads} host→device uploads over {window} steady '
            f'paged+int8 deep-ring ticks (device feedback regressed)')


class TestInt8GaugeLateExporter:

    def test_bytes_saved_visible_after_late_enable(self):
        """serve/server.py builds the engine BEFORE make_app() enables
        recording, so a construction-time-only gauge set is a no-op
        and /metrics would read 0 forever. The tick loop must re-set
        skytpu_engine_paged_int8_bytes_saved (like the capacity/used
        gauges) so a late-attaching exporter still sees the value."""
        from skypilot_tpu.observability import exposition
        from skypilot_tpu.observability import metrics as obs
        was = obs.enabled()
        obs.disable()
        try:
            engine = _engine(paged_block_size=8, kv_quant='int8')
            try:
                obs.enable()           # exporter attaches post-build
                engine.generate(PROMPT, max_new_tokens=4)
                line = [l for l in exposition.generate_latest()
                        .splitlines()
                        if l.startswith(
                            'skytpu_engine_paged_int8_bytes_saved ')]
                assert line, 'gauge missing from exposition'
                assert (float(line[0].split()[1])
                        == engine.paged_int8_bytes_saved > 0)
            finally:
                engine.stop()
        finally:
            if was:
                obs.enable()
            else:
                obs.disable()


@pytest.mark.chaos
class TestDeepRingWedgeRecovery:

    def test_wedge_drops_whole_ring(self, refs):
        """Wedge the decode loop with a FULL ring pending: recovery
        must drop every in-flight dispatch under the generation lock —
        no token from any abandoned dispatch is ever emitted, the
        stream stays a clean prefix of the baseline, and the recovered
        engine (fresh pool, fresh ring) serves bit-identical output."""
        ref = refs['int8']
        engine = _engine(paged_block_size=8, kv_quant='int8',
                         async_depth=3, watchdog_timeout=1.0)
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
            # Recovery dropped the ENTIRE pending ring wholesale.
            assert len(engine._ring) == 0  # pylint: disable=protected-access
            assert engine._inflight is None  # pylint: disable=protected-access
            fault_injection.disarm_all()
            emitted_at_fail = len(streamed)
            time.sleep(0.3)
            assert len(streamed) == emitted_at_fail
            assert streamed == ref[:emitted_at_fail]
            got, _ = engine.generate(PROMPT, max_new_tokens=8,
                                     timeout=120)
            assert got == ref[:8]
        finally:
            fault_injection.disarm_all()
            engine.stop()


# ---------------------------------------------------------------------
# Fused pallas decode kernel cells (ISSUE 18): the kernel across the
# matrix, asked for as 'pallas_interpret' BY NAME — the same kernel
# program under the Pallas interpreter, which is what makes these cells
# tier-1 ('pallas' itself needs the chip and raises without one; the
# compiled kernel is checked by chip_smoke.py). The pin is greedy-token
# equivalence to the same-knobs XLA engine via the shared reference
# streams: streaming softmax reorders reductions, so bit identity of
# logits is NOT the contract (ops/paged_attention.py docstring);
# identical greedy streams over the full window are.
# ---------------------------------------------------------------------

_PALLAS_CELLS = [
    ('pallas-paged', dict(paged_block_size=8)),
    ('pallas-paged-int8', dict(paged_block_size=8, kv_quant='int8')),
    ('pallas-paged-spec', dict(paged_block_size=8, speculative=3)),
    ('pallas-paged-int8-async3',
     dict(paged_block_size=8, kv_quant='int8', async_depth=3)),
    ('pallas-paged-chunkedprefill',
     dict(paged_block_size=8, prefill_chunk=4)),
]


class TestPallasDecodeKernel:

    @pytest.mark.parametrize('name,kw', _PALLAS_CELLS,
                             ids=[c[0] for c in _PALLAS_CELLS])
    def test_cell_matches_xla_stream(self, refs, name, kw):
        ref = refs['int8' if 'int8' in name else '']
        engine = _engine(decode_kernel='pallas_interpret', **kw)
        try:
            assert engine.decode_kernel == 'pallas_interpret'
            assert engine.cfg.decode_kernel == 'pallas_interpret'
            got, stats = engine.generate(PROMPT, max_new_tokens=16)
            assert got == ref[:16], (name, got)
            assert stats['new_tokens'] == 16
            engine._pool.check()  # pylint: disable=protected-access
        finally:
            engine.stop()

    def test_multi_lora_cell_matches_xla_twin(self):
        """The kernel knob also swaps MultiLoRADenseGeneral onto
        the fused gather+dot kernel; a mixed base+adapter batch must
        stream identically to the XLA engine sharing its params."""
        import jax.numpy as jnp
        import numpy as np
        from flax import linen as nn
        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.serve import tenancy
        lora_kw = dict(adapter_rank=4, adapter_alpha=8.0,
                       adapter_targets='q,v')
        lora_cfg = _cfg(lora_rank=4, lora_alpha=8.0, lora_targets='q,v',
                        decode=True)
        variables = nn.unbox(Transformer(lora_cfg).init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32),
            jnp.zeros((1, 8), jnp.int32)))
        template = tenancy.adapter_tree_from_lora_params(
            variables['params'])
        leaves, treedef = jax.tree.flatten(template)
        keys = jax.random.split(jax.random.PRNGKey(42), len(leaves))
        tree = jax.tree.unflatten(treedef, [
            np.asarray(jax.random.normal(k, leaf.shape, jnp.float32))
            * 0.05 for k, leaf in zip(keys, leaves)])

        xla = _engine(paged_block_size=8, max_adapters=2, **lora_kw)
        pal = _engine(paged_block_size=8, max_adapters=2,
                      decode_kernel='pallas_interpret',
                      params=xla.params,
                      **lora_kw)
        try:
            for engine in (xla, pal):
                engine.load_adapter('ad0', tree)
            for adapter in (None, 'ad0'):
                ref, _ = xla.generate(PROMPT, max_new_tokens=12,
                                      adapter=adapter)
                got, _ = pal.generate(PROMPT, max_new_tokens=12,
                                      adapter=adapter)
                assert got == ref, (adapter, got, ref)
        finally:
            xla.stop()
            pal.stop()

    def test_rejects_non_paged_engine(self):
        with pytest.raises(NotImplementedError, match='paged'):
            _engine(decode_kernel='pallas_interpret')

    def test_pallas_off_tpu_raises(self):
        """No fallback that hides the device: 'pallas' means the
        compiled kernel, so without a TPU it refuses, naming the
        platform and the interpreter's own name, instead of serving
        from the interpreter under the kernel's name."""
        with pytest.raises(RuntimeError, match='needs a TPU') as e:
            _engine(paged_block_size=8, decode_kernel='pallas')
        assert 'cpu' in str(e.value)
        assert 'pallas_interpret' in str(e.value)

    def test_pallas_under_tp_is_refused_at_construction(self):
        """jax does not partition a Mosaic call and the kernel is not
        yet wrapped over the kv-head axis (ROADMAP S6): a tp engine
        says so when it is built, not from a failed tick."""
        from skypilot_tpu.models.inference import _resolve_decode_kernel
        with pytest.raises(NotImplementedError, match='shard_map'):
            _resolve_decode_kernel('pallas', _cfg(paged_block_size=8),
                                   tp=2)

    def test_rejects_unknown_kernel(self):
        with pytest.raises(ValueError, match='decode_kernel'):
            _engine(paged_block_size=8, decode_kernel='fused')

    def test_rejects_softcap(self):
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        with pytest.raises(NotImplementedError, match='softcap'):
            ContinuousBatchingEngine(
                _cfg(attn_logit_softcap=30.0), num_slots=2,
                paged_block_size=8, decode_kernel='pallas_interpret')

    def test_kernel_probe_eliminates_pool_window_gathers(self, refs):
        """A count, not a speed: the fused kernel's compiled decode
        step must carry strictly FEWER gather
        ops than the XLA twin's — the pool-window gather
        (`kf[gidx]`/`vf[gidx]`) is what the in-kernel table walk
        deletes. Pinned on 'gather' specifically: interpreter-mode
        emulation adds dynamic-slices on CPU, so 'total' is not
        comparable across kernels."""
        xla = _engine(paged_block_size=8)
        pal = _engine(paged_block_size=8,
                      decode_kernel='pallas_interpret')
        try:
            xs = xla.decode_kernel_hlo_stats()
            ps = pal.decode_kernel_hlo_stats()
            assert xs['decode_kernel'] == 'xla'
            assert ps['decode_kernel'] == 'pallas_interpret'
            assert ps['gather'] < xs['gather'], (ps, xs)
            assert ps['fused_bytes_per_step'] > 0
            assert xs['fused_bytes_per_step'] == 0
            # Gauge parity: the engine's public accounting agrees with
            # the probe's snapshot.
            assert pal.fused_bytes_per_step() == \
                ps['fused_bytes_per_step']
        finally:
            xla.stop()
            pal.stop()


# ---------------------------------------------------------------------
# Tensor-parallel sharded cells (ISSUE 8): every composition must also
# survive SHARDING. tests/sharded_driver.py runs the whole tp=2 matrix
# once in a subprocess on 8 fake CPU devices (the sharded_subprocess
# conftest fixture keeps this process's single-device jit caches
# clean); the tests below assert individual results from that one run.
# ---------------------------------------------------------------------

_SHARDED_CELLS = ['contig', 'paged', 'int8', 'paged-int8', 'spec',
                  'async3', 'chunkedprefill', 'pallas-paged']


@pytest.mark.sharded
@pytest.mark.deadline(540)
class TestShardedComposition:

    @pytest.fixture(scope='class')
    def sharded(self, sharded_subprocess):
        proc, parsed = sharded_subprocess('tests/sharded_driver.py', 2,
                                          timeout=480)
        assert proc.returncode == 0, (
            f'sharded driver failed rc={proc.returncode}\n'
            f'--- stdout ---\n{proc.stdout[-4000:]}\n'
            f'--- stderr ---\n{proc.stderr[-4000:]}')
        assert parsed is not None, proc.stdout[-2000:]
        return parsed

    @pytest.mark.parametrize('cell', _SHARDED_CELLS)
    def test_tp2_cell_bit_identical_to_single_chip(self, sharded, cell):
        """tp=2 greedy stream == the single-chip engine's with the same
        knobs, for every composition cell (the acceptance pin)."""
        result = sharded['cells'][cell]
        assert result['match'], (cell, result)
        assert result['new_tokens'] == 16, (cell, result)

    def test_tp2_async_ring_actually_chained(self, sharded):
        """The async_depth=3 cell must exercise chaining under the
        mesh — dispatch shapes don't change, only layouts, so the
        lookahead ring composes with sharding."""
        assert sharded['cells']['async3'].get('chained', 0) > 0, \
            sharded['cells']['async3']

    def test_tp2_artifact_roundtrip_through_sharded_pool(self, sharded):
        """PR-6 prefix artifact: export from a tp=2 pool, pre-warm a
        fresh tp=2 engine — imported blocks credit a prewarm hit and
        the warmed engine's stream stays bit-identical."""
        rt = sharded['roundtrip']
        assert rt['exported'] >= 1 and rt['imported'] >= 1, rt
        assert rt['prewarm_hits'] >= 1, rt
        assert rt['match'], rt
        # And the artifact is tp-PORTABLE: the same tp=2 export
        # pre-warms a single-chip pool (gather/scatter trade in
        # global block bytes, so leaf signatures match across tp).
        assert rt['cross_tp_imported'] >= 1, rt
        assert rt['cross_tp_match'], rt

    def test_tp2_per_device_memory_halves(self, sharded):
        """Weights + KV pool per device <= (1/tp + eps) of the
        single-chip footprint: sharded, not replicated."""
        mem = sharded['memory']
        assert mem['frac'] <= 0.5 + 0.05, mem

    def test_tp2_decode_step_pays_allreduces(self, sharded):
        """The compiled decode step carries the per-layer tp
        all-reduces the mesh axis ordering puts on ICI."""
        hlo = sharded['hlo']
        assert hlo['tp'] == 2 and hlo['all_reduce'] > 0, hlo
        assert hlo['all_reduce_bytes'] > 0, hlo

    def test_get_engine_auto_picks_tp_from_device_count(self, sharded):
        """The documented accessor: on 8 local devices, test-tiny
        (2 kv heads) auto-selects tp=2 and generates end-to-end
        through the sharded InferenceEngine path."""
        assert sharded['get_engine'] == {'tp': 2, 'new_tokens': 4}, \
            sharded['get_engine']

    def test_tp2_gauges_survive_late_exporter(self, sharded):
        """Recording enabled AFTER construction+warmup+probe must
        still see the tp gauges — the engine re-publishes them per
        tick (the PR-5 late-exporter lesson, extended to sharding)."""
        gauges = sharded['late_exporter_gauges']
        assert gauges['tp_size'] == 2, gauges
        assert (gauges['tp_allreduce_bytes'] or 0) > 0, gauges
