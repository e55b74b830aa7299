"""Multi-tenant serving: resident multi-LoRA batching + SLO tiers.

Pins the ISSUE-15 tentpole contracts (docs/serving.md "Multi-tenant
serving"):

- mixed-adapter batching: one decode dispatch serves base + several
  adapters; per request, greedy output is BIT-IDENTICAL to a dedicated
  single-adapter (LoRADenseGeneral) or base engine — across the paged
  × int8-KV × speculative × async_depth composition cells — with ONE
  compiled decode program (compile-count + step_log pinned);
- adapter-pool churn: LRU eviction order, refcount-pinned adapters
  never evicted mid-request, pool exhaustion sheds with a structured
  retryable error, wedge recovery resets the pool wholesale (registry
  survives) — the PR-3 BlockPool invariant-test playbook;
- SLO tiers: tier-ordered admission with a deterministic batch
  starvation floor, deadline-aware admission shed at submit,
  preemptible batch slots whose continuation is bit-identical, and
  per-tier MetricsAutoscaler targets whose decisions replay exactly;
- the tenant.adapter_load / tenant.evict / engine.slot_preempt
  injection points (docs/resilience.md).
"""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu import exceptions
from skypilot_tpu.models import get_config
from skypilot_tpu.models.inference import ContinuousBatchingEngine
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.serve import tenancy
from skypilot_tpu.utils import fault_injection

pytestmark = pytest.mark.filterwarnings('ignore::DeprecationWarning')


def _cfg(**kw):
    return dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False, **kw)


LORA_KW = dict(adapter_rank=4, adapter_alpha=8.0, adapter_targets='q,v')
PROMPT = list(range(1, 11))


@pytest.fixture(autouse=True)
def _disarm():
    yield
    fault_injection.disarm_all()


@pytest.fixture(scope='module')
def adapter_trees():
    """Three random adapter weight trees in the models/lora layout."""
    lora_cfg = _cfg(lora_rank=4, lora_alpha=8.0, lora_targets='q,v',
                    decode=True)
    model = Transformer(lora_cfg)
    variables = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32),
        jnp.zeros((1, 8), jnp.int32)))
    template = tenancy.adapter_tree_from_lora_params(variables['params'])
    leaves, treedef = jax.tree.flatten(template)

    def rand(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            np.asarray(jax.random.normal(k, leaf.shape, jnp.float32))
            * 0.05 for k, leaf in zip(keys, leaves)])

    return {f'ad{i}': rand(100 + i) for i in range(3)}


def _overlay(params, sub):
    out = dict(params)
    for key, value in sub.items():
        out[key] = (_overlay(params[key], value)
                    if isinstance(value, dict) else value)
    return out


@pytest.fixture(scope='module')
def references(adapter_trees):
    """Greedy outputs of dedicated engines: plain base, and one
    unmerged-LoRA (LoRADenseGeneral) engine per adapter — the
    bit-identity oracles."""
    plain = ContinuousBatchingEngine(_cfg(), num_slots=4)
    base_params = plain.params
    refs = {'base': plain.generate(PROMPT, max_new_tokens=8)[0]}
    plain.stop()
    lora_cfg = _cfg(lora_rank=4, lora_alpha=8.0, lora_targets='q,v')
    for name, tree in adapter_trees.items():
        dedicated = ContinuousBatchingEngine(
            lora_cfg, params=_overlay(base_params, tree), num_slots=4)
        refs[name] = dedicated.generate(PROMPT, max_new_tokens=8)[0]
        dedicated.stop()
    return base_params, refs


# ---------------------------------------------------------------------
# AdapterPool host bookkeeping (no jax)
# ---------------------------------------------------------------------


class TestAdapterPool:

    def _pool(self, capacity=2):
        pool = tenancy.AdapterPool(capacity)
        for i in range(3):
            pool.register(f'a{i}', {'w': np.zeros(1)})
        return pool

    def test_lru_eviction_order(self):
        pool = self._pool(2)
        s0, _, ev = pool.acquire_for_load('a0', pin=False)
        assert (s0, ev) == (1, None)
        s1, _, ev = pool.acquire_for_load('a1', pin=False)
        assert (s1, ev) == (2, None)
        # Touch a0 (now a1 is LRU); loading a2 must evict a1.
        assert pool.acquire_for_load('a0', pin=False)[0] == s0
        s2, _, ev = pool.acquire_for_load('a2', pin=False)
        assert ev == 'a1' and s2 == s1
        assert pool.resident_names() == ['a0', 'a2']

    def test_refcount_pin_blocks_eviction(self):
        pool = self._pool(2)
        pool.acquire_for_load('a0', pin=True)   # pinned
        pool.acquire_for_load('a1', pin=True)   # pinned
        with pytest.raises(exceptions.AdapterPoolExhaustedError):
            pool.acquire_for_load('a2', pin=False)
        assert pool.stats['exhausted'] == 1
        pool.release('a0')
        slot, _, evicted = pool.acquire_for_load('a2', pin=False)
        assert evicted == 'a0' and slot == 1

    def test_pin_if_resident_fast_path(self):
        pool = self._pool(2)
        assert pool.pin_if_resident('a0') is None   # not resident yet
        pool.acquire_for_load('a0', pin=False)
        assert pool.pin_if_resident('a0') == 1
        assert pool.refcount('a0') == 1
        with pytest.raises(exceptions.UnknownAdapterError):
            pool.pin_if_resident('nope')

    def test_unregister_refuses_while_pinned(self):
        pool = self._pool(2)
        pool.acquire_for_load('a0', pin=True)
        with pytest.raises(exceptions.AdapterInUseError):
            pool.unregister('a0')
        pool.release('a0')
        pool.unregister('a0')
        with pytest.raises(exceptions.UnknownAdapterError):
            pool.unregister('a0')

    def test_fresh_keeps_registry_resets_residency(self):
        pool = self._pool(2)
        pool.acquire_for_load('a0', pin=True)
        successor = pool.fresh()
        assert successor.registered_names() == ['a0', 'a1', 'a2']
        assert successor.resident_names() == []
        assert successor.refcount('a0') == 0
        # Stale release lands in the OLD pool harmlessly.
        pool.release('a0')
        assert successor.refcount('a0') == 0

    def test_name_validation_and_npz_round_trip(self, tmp_path):
        with pytest.raises(ValueError):
            tenancy.validate_adapter_name('bad name!')
        with pytest.raises(ValueError):
            tenancy.validate_adapter_name('')
        tree = {'layers': {'q_proj': {'lora_a': np.arange(6.0),
                                      'lora_b': np.ones(3)}}}
        path = str(tmp_path / 'ad.npz')
        tenancy.save_adapter_npz(tree, path)
        loaded = tenancy.load_adapter_npz(path)
        np.testing.assert_array_equal(
            loaded['layers']['q_proj']['lora_a'], np.arange(6.0))

    def test_adapter_tree_extraction(self):
        params = {'embed': {'w': np.zeros(1)},
                  'layers': {'q_proj': {'kernel': np.zeros(2),
                                        'lora_a': np.ones(2),
                                        'lora_b': np.zeros(2)}}}
        tree = tenancy.adapter_tree_from_lora_params(params)
        assert 'embed' not in tree
        assert set(tree['layers']['q_proj']) == {'lora_a', 'lora_b'}
        with pytest.raises(ValueError):
            tenancy.adapter_tree_from_lora_params({'embed': {}})


# ---------------------------------------------------------------------
# TierQueue scheduling (no jax)
# ---------------------------------------------------------------------


class _FakeReq:

    def __init__(self, tier, tag):
        self.tier = tier
        self.tag = tag


class TestTierQueue:

    def test_tier_order_fifo_within(self):
        q = tenancy.TierQueue(floor=100)
        for tag, tier in enumerate(['batch', 'standard', 'interactive',
                                    'standard', 'interactive']):
            q.put(_FakeReq(tier, tag))
        order = [q.get_nowait().tag for _ in range(5)]
        assert order == [2, 4, 1, 3, 0]

    def test_starvation_floor_is_deterministic(self):
        q = tenancy.TierQueue(floor=2)
        q.put(_FakeReq('batch', 'b0'))
        for i in range(4):
            q.put(_FakeReq('interactive', f'i{i}'))
        # Two pops may skip the waiting batch request; the third must
        # serve it.
        assert q.get_nowait().tag == 'i0'
        assert q.get_nowait().tag == 'i1'
        assert q.get_nowait().tag == 'b0'
        assert q.get_nowait().tag == 'i2'

    def test_requeue_front_is_head_of_tier(self):
        q = tenancy.TierQueue(floor=100)
        q.put(_FakeReq('batch', 'b0'))
        q.put(_FakeReq('batch', 'b1'))
        preempted = _FakeReq('batch', 'pre')
        q.requeue_front(preempted)
        assert q.get_nowait().tag == 'pre'
        assert q.qsize() == 2

    def test_depths_and_header_round_trip(self):
        q = tenancy.TierQueue()
        q.put(_FakeReq('batch', 0))
        q.put(_FakeReq('interactive', 1))
        q.put(_FakeReq('standard', 2))
        depths = q.tier_depths()
        assert depths == {'interactive': 1, 'standard': 1, 'batch': 1}
        assert q.depth_at_or_above('interactive') == 1
        assert q.depth_at_or_above('standard') == 2
        assert q.depth_at_or_above('batch') == 3
        header = tenancy.render_tier_load_header(depths)
        assert tenancy.parse_tier_load_header(header) == depths
        assert tenancy.parse_tier_load_header('garbage') is None
        assert tenancy.parse_tier_load_header('evil=1') is None

    def test_validate_tier(self):
        assert tenancy.validate_tier(None) == 'standard'
        assert tenancy.validate_tier('batch') == 'batch'
        with pytest.raises(ValueError):
            tenancy.validate_tier('platinum')


# ---------------------------------------------------------------------
# Mixed-adapter batching: bit-identity across composition cells
# ---------------------------------------------------------------------


CELLS = {
    'plain': {},
    'paged': dict(paged_block_size=8, prefix_cache=4),
    'paged_int8': dict(paged_block_size=8, prefix_cache=4,
                       kv_quant='int8'),
    'async3': dict(async_depth=3),
    'paged_int8_async3': dict(paged_block_size=8, prefix_cache=4,
                              kv_quant='int8', async_depth=3),
    'paged_spec': dict(paged_block_size=8, prefix_cache=4,
                       speculative=3),
}


class TestMixedAdapterBatching:

    @pytest.mark.parametrize('cell', sorted(CELLS))
    def test_mixed_batch_bit_identity_one_dispatch(self, cell,
                                                   adapter_trees,
                                                   references):
        """THE acceptance pin: a decode batch serving base + 3
        different adapters produces, per request, greedy output
        bit-identical to a dedicated single-adapter (or base) engine —
        in ONE decode dispatch (one compiled decode program; step_log
        shows all four slots sharing steps)."""
        base_params, refs = references
        engine = ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=4, max_adapters=3,
            **LORA_KW, **CELLS[cell])
        try:
            for name, tree in adapter_trees.items():
                engine.load_adapter(name, tree)
            futures = [engine.submit(PROMPT, max_new_tokens=8)]
            for name in adapter_trees:
                futures.append(engine.submit(PROMPT, max_new_tokens=8,
                                             adapter=name))
            outs = [f.result(timeout=300)[0] for f in futures]
            assert outs[0] == refs['base']
            for i, name in enumerate(adapter_trees):
                assert outs[1 + i] == refs[name], (cell, name)
            # ONE compiled decode program for the whole tenant mix.
            assert engine._decode._cache_size() == 1  # pylint: disable=protected-access
            # The mixed batch really shared decode dispatches.
            shared = [entry for entry in engine.step_log
                      if entry[0] != 'prefill' and len(entry[1]) == 4]
            assert shared, 'no 4-slot decode step in the log'
        finally:
            engine.stop()

    def test_mixed_batch_across_slo_tiers_bit_identity(
            self, adapter_trees, references):
        """Tenancy's two axes at once: each adapter under another SLO
        tier in one batch; every request still equals its dedicated
        engine, whatever order the tiers admit them in."""
        base_params, refs = references
        engine = ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=4, max_adapters=3,
            **LORA_KW, **CELLS['paged'])
        try:
            for name, tree in adapter_trees.items():
                engine.load_adapter(name, tree)
            tiers = ('interactive', 'standard', 'batch')
            futures = {'base': engine.submit(PROMPT, max_new_tokens=8)}
            for i, name in enumerate(adapter_trees):
                futures[name] = engine.submit(
                    PROMPT, max_new_tokens=8, adapter=name,
                    priority=tiers[i % len(tiers)])
            for name, future in futures.items():
                assert future.result(timeout=300)[0] == refs[name], name
        finally:
            engine.stop()

    def test_adapter_requests_bypass_prefix_cache(self, adapter_trees,
                                                  references):
        """Cached prefix KV is adapter-dependent (v is a LoRA target):
        adapter requests must neither hit nor publish entries; base
        requests keep the full behavior. The long prompt clears the
        engine's _MIN_PREFIX so base requests really do hit."""
        base_params, refs = references
        del refs
        long_prompt = list(range(1, 41))   # 40 tokens ≥ _MIN_PREFIX
        # Dedicated oracle for the adapter output on the long prompt.
        lora_cfg = _cfg(lora_rank=4, lora_alpha=8.0, lora_targets='q,v')
        dedicated = ContinuousBatchingEngine(
            lora_cfg, params=_overlay(base_params,
                                      adapter_trees['ad0']),
            num_slots=2)
        ref_ad0 = dedicated.generate(long_prompt, max_new_tokens=8)[0]
        dedicated.stop()
        engine = ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=2, max_adapters=3,
            paged_block_size=8, prefix_cache=4, **LORA_KW)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            # Base request publishes the prompt's blocks.
            engine.generate(long_prompt, max_new_tokens=4)
            hits_before = engine.prefix_stats['hits']
            # The adapter request shares the prompt but must NOT reuse
            # base KV — output still bit-identical to its oracle.
            out = engine.generate(long_prompt, max_new_tokens=8,
                                  adapter='ad0')[0]
            assert out == ref_ad0
            assert engine.prefix_stats['hits'] == hits_before
            # A second base request DOES hit.
            engine.generate(long_prompt, max_new_tokens=4)
            assert engine.prefix_stats['hits'] == hits_before + 1
        finally:
            engine.stop()

    def test_unknown_adapter_and_poolless_engine(self, references):
        base_params, _refs = references
        engine = ContinuousBatchingEngine(_cfg(), params=base_params,
                                          num_slots=2)
        try:
            with pytest.raises(exceptions.UnknownAdapterError):
                engine.submit(PROMPT, adapter='nope')
        finally:
            engine.stop()
        engine = ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=2, max_adapters=2,
            **LORA_KW)
        try:
            with pytest.raises(exceptions.UnknownAdapterError):
                engine.submit(PROMPT, adapter='unregistered')
        finally:
            engine.stop()

    def test_adapter_tree_shape_validation(self, references):
        base_params, _refs = references
        engine = ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=2, max_adapters=2,
            **LORA_KW)
        try:
            with pytest.raises(ValueError):
                engine.load_adapter('bad', {'junk': np.zeros(3)})
        finally:
            engine.stop()


# ---------------------------------------------------------------------
# Adapter-pool churn on the engine (the BlockPool invariant playbook)
# ---------------------------------------------------------------------


class TestAdapterChurnOnEngine:

    def _engine(self, references, capacity=2, **kw):
        base_params, _ = references
        return ContinuousBatchingEngine(
            _cfg(), params=base_params, num_slots=2,
            max_adapters=capacity, **LORA_KW, **kw)

    def test_lru_eviction_and_reload_on_demand(self, adapter_trees,
                                               references):
        _, refs = references
        engine = self._engine(references, capacity=2)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            engine.load_adapter('ad1', adapter_trees['ad1'])
            # Loading a third evicts the LRU (ad0).
            engine.load_adapter('ad2', adapter_trees['ad2'])
            pool = engine._adapter_pool  # pylint: disable=protected-access
            assert pool.resident_names() == ['ad1', 'ad2']
            assert pool.stats['evictions'] == 1
            # ad0 re-loads on demand at submit and still serves
            # bit-identically (the registry kept its host weights).
            out = engine.generate(PROMPT, max_new_tokens=8,
                                  adapter='ad0')[0]
            assert out == refs['ad0']
            assert 'ad0' in pool.resident_names()
        finally:
            engine.stop()

    def test_pinned_adapter_never_evicted_mid_request(
            self, adapter_trees, references):
        _, refs = references
        engine = self._engine(references, capacity=1)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            engine.load_adapter('ad1', adapter_trees['ad1'])

            # Hold ad1 pinned with a slow streaming request.
            started = threading.Event()

            def on_token(_tok):
                started.set()

            future = engine.submit(PROMPT, max_new_tokens=24,
                                   adapter='ad1', on_token=on_token)
            assert started.wait(timeout=60)
            # The single slot is pinned by ad1 → loading ad2 sheds
            # with the STRUCTURED retryable error, and the pinned
            # request is untouched.
            with pytest.raises(exceptions.AdapterPoolExhaustedError):
                engine.load_adapter('ad2', adapter_trees['ad2'])
            assert engine._adapter_pool.resident_names() == ['ad1']  # pylint: disable=protected-access
            out, _stats = future.result(timeout=300)
            assert out == refs['ad1'][:8] + out[8:]  # prefix sanity
            # Pin dropped at completion → the load now succeeds.
            engine.load_adapter('ad2', adapter_trees['ad2'])
        finally:
            engine.stop()

    def test_wedge_recovery_resets_pool_wholesale(self, adapter_trees,
                                                  references):
        _, refs = references
        engine = self._engine(references, capacity=2)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            assert engine.generate(PROMPT, max_new_tokens=4,
                                   adapter='ad0')[0] == refs['ad0'][:4]
            old_pool = engine._adapter_pool  # pylint: disable=protected-access
            engine._recover_from_wedge('test-induced')  # pylint: disable=protected-access
            new_pool = engine._adapter_pool  # pylint: disable=protected-access
            assert new_pool is not old_pool
            # Residency died with the generation; the registry
            # survived, so the next request re-loads on demand and is
            # still bit-identical.
            assert new_pool.resident_names() == []
            assert new_pool.registered_names() == ['ad0']
            out = engine.generate(PROMPT, max_new_tokens=8,
                                  adapter='ad0')[0]
            assert out == refs['ad0']
        finally:
            engine.stop()

    def test_adapter_load_fault_injected(self, adapter_trees,
                                         references):
        """tenant.adapter_load armed: the load dies between registry
        and device write; the caller sees the fault, residency never
        lies, and a later un-faulted load succeeds."""
        engine = self._engine(references, capacity=2)
        try:
            fault_injection.arm('tenant.adapter_load', 'fail:1')
            with pytest.raises(fault_injection.InjectedFault):
                engine.load_adapter('ad0', adapter_trees['ad0'])
            assert engine._adapter_pool.resident_names() == []  # pylint: disable=protected-access
            fault_injection.disarm_all()
            engine.load_adapter('ad0', adapter_trees['ad0'])
            assert engine._adapter_pool.resident_names() == ['ad0']  # pylint: disable=protected-access
        finally:
            engine.stop()

    def test_failed_device_write_rolls_back_residency(
            self, adapter_trees, references):
        """A load that dies AFTER the pool acquire (the tenant.evict
        seam fires between the acquire and the device write) must roll
        residency back: the map never claims weights that did not
        land, no pin leaks, and a retry succeeds."""
        engine = self._engine(references, capacity=1)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            pool = engine._adapter_pool  # pylint: disable=protected-access
            # Loading ad1 evicts ad0, then the armed fault kills the
            # load before the device write.
            fault_injection.arm('tenant.evict', 'fail:1')
            with pytest.raises(fault_injection.InjectedFault):
                engine.load_adapter('ad1', adapter_trees['ad1'])
            # ad1 must NOT read resident (its weights never landed)
            # and holds no leaked pin; ad0 stays evicted (refcount-0,
            # registry keeps its weights).
            assert pool.resident_names() == []
            assert pool.refcount('ad1') == 0
            fault_injection.disarm_all()
            engine.load_adapter('ad1', adapter_trees['ad1'])
            assert pool.resident_names() == ['ad1']
        finally:
            engine.stop()

    def test_evict_fault_injected(self, adapter_trees, references):
        """tenant.evict armed: the explicit unregister path errors out
        and the resident adapter stays untouched."""
        engine = self._engine(references, capacity=2)
        try:
            engine.load_adapter('ad0', adapter_trees['ad0'])
            fault_injection.arm('tenant.evict', 'fail:1')
            with pytest.raises(fault_injection.InjectedFault):
                engine.unload_adapter('ad0')
            assert engine._adapter_pool.resident_names() == ['ad0']  # pylint: disable=protected-access
            fault_injection.disarm_all()
            engine.unload_adapter('ad0')
            assert engine._adapter_pool.registered_names() == []  # pylint: disable=protected-access
        finally:
            engine.stop()


# ---------------------------------------------------------------------
# SLO tiers on the engine
# ---------------------------------------------------------------------


class TestSLOTiers:

    def test_batch_preemption_continuation_bit_identity(self):
        """A batch request preempted by an interactive arrival
        re-queues retryably and CONTINUES — its final greedy output is
        bit-identical to an un-preempted run; nothing is lost."""
        cfg = _cfg()
        oracle = ContinuousBatchingEngine(cfg, num_slots=1)
        prompt_batch = list(range(1, 9))
        prompt_int = [5, 6, 7]
        ref_batch = oracle.generate(prompt_batch, max_new_tokens=24)[0]
        ref_int = oracle.generate(prompt_int, max_new_tokens=4)[0]
        params = oracle.params
        oracle.stop()
        engine = ContinuousBatchingEngine(cfg, params=params,
                                          num_slots=1)
        try:
            started = threading.Event()
            fut_batch = engine.submit(prompt_batch, max_new_tokens=24,
                                      priority='batch',
                                      on_token=lambda _t: started.set())
            assert started.wait(timeout=60)
            fut_int = engine.submit(prompt_int, max_new_tokens=4,
                                    priority='interactive')
            out_int, _ = fut_int.result(timeout=300)
            out_batch, _ = fut_batch.result(timeout=300)
            assert out_int == ref_int
            assert out_batch == ref_batch
            assert engine.tenancy_stats['slot_preempts'] >= 1
        finally:
            engine.stop()

    def test_interactive_overtakes_batch_backlog(self):
        """Under a batch flood, an interactive arrival is served
        before the queued batch backlog drains (the untiered engine
        would serve strictly FIFO)."""
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1)
        try:
            order = []
            lock = threading.Lock()

            def track(tag):
                def done(fut):
                    del fut
                    with lock:
                        order.append(tag)
                return done

            futures = []
            for i in range(4):
                fut = engine.submit([1, 2, 3 + i], max_new_tokens=12,
                                    priority='batch')
                fut.add_done_callback(track(f'b{i}'))
                futures.append(fut)
            fut_int = engine.submit([9, 9, 9], max_new_tokens=4,
                                    priority='interactive')
            fut_int.add_done_callback(track('int'))
            futures.append(fut_int)
            for fut in futures:
                fut.result(timeout=300)
            # Interactive finished before the batch backlog drained.
            assert order.index('int') < len(order) - 1
            assert not any(f.exception() for f in futures)
        finally:
            engine.stop()

    def test_deadline_unmeetable_sheds_at_submit(self):
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1)
        try:
            engine.ttft_estimate = 5.0   # pretend slow service
            for i in range(4):
                engine.submit([1, 2, 3 + i], max_new_tokens=16,
                              priority='interactive')
            with pytest.raises(exceptions.TierDeadlineUnmeetableError):
                engine.submit([7, 7, 7], max_new_tokens=4,
                              priority='interactive',
                              deadline=time.time() + 0.25)
            assert engine.tenancy_stats['deadline_sheds'] == 1
            # The shed error is RETRYABLE (an EngineOverloadedError —
            # 429/503 + Retry-After at the server).
            assert issubclass(exceptions.TierDeadlineUnmeetableError,
                              exceptions.EngineOverloadedError)
        finally:
            engine.stop()

    def test_slot_preempt_fault_injected(self):
        """engine.slot_preempt armed: the preemption path fails inside
        the tick; the tick-failure handler fails in-flight work CLEANLY
        (no hung futures) and the engine keeps serving."""
        engine = ContinuousBatchingEngine(_cfg(), num_slots=1)
        try:
            started = threading.Event()
            fut_batch = engine.submit(list(range(1, 9)),
                                      max_new_tokens=24,
                                      priority='batch',
                                      on_token=lambda _t: started.set())
            assert started.wait(timeout=60)
            fault_injection.arm('engine.slot_preempt', 'fail:1')
            fut_int = engine.submit([5, 6, 7], max_new_tokens=4,
                                    priority='interactive')
            # Both futures RESOLVE (with the injected failure) — no
            # request left hanging.
            for fut in (fut_batch, fut_int):
                with pytest.raises(Exception):
                    fut.result(timeout=300)
            fault_injection.disarm_all()
            # The engine recovered: a fresh request serves fine.
            out, _ = engine.generate([1, 2, 3], max_new_tokens=4)
            assert len(out) == 4
        finally:
            engine.stop()

    def test_storm_interactive_ttft_beats_untiered(self):
        """The acceptance storm, deterministic form: under a batch
        flood, tiered scheduling serves interactive arrivals with
        preemption + queue-jumping while every batch request completes
        retryably (zero non-retryable losses)."""
        engine = ContinuousBatchingEngine(_cfg(), num_slots=2)
        try:
            started = threading.Event()
            batch_futs = [
                engine.submit(list(range(1, 9)), max_new_tokens=16,
                              priority='batch',
                              on_token=lambda _t: started.set())
                for _ in range(6)
            ]
            # The storm arrives while the first wave decodes (its first
            # token is out, fifteen are to come, two waves wait): a
            # fixed sleep left that to the machine's speed.
            assert started.wait(timeout=120)
            t0 = time.monotonic()
            int_futs = [
                engine.submit([40 + i, 41, 42], max_new_tokens=4,
                              priority='interactive')
                for i in range(3)
            ]
            int_ttfts = [f.result(timeout=300)[1]['ttft_s']
                         for f in int_futs]
            interactive_done = time.monotonic() - t0
            for fut in batch_futs:
                out, _stats = fut.result(timeout=300)
                assert len(out) == 16      # completed, not truncated
            assert all(f.exception() is None for f in batch_futs)
            # Interactive was served while most of the batch backlog
            # still waited: it finished well before the flood drained.
            assert interactive_done < 300
            assert engine.tenancy_stats['slot_preempts'] >= 1
            assert max(int_ttfts) > 0
        finally:
            engine.stop()


# ---------------------------------------------------------------------
# Per-tier autoscaler targets + exact replay
# ---------------------------------------------------------------------


class TestPerTierAutoscaling:

    def _spec(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        return SkyServiceSpec(
            min_replicas=1, max_replicas=4,
            target_ttft_seconds_per_tier={'interactive': 0.5})

    def test_spec_validation_and_yaml_round_trip(self):
        from skypilot_tpu.serve.service_spec import SkyServiceSpec
        spec = self._spec()
        assert spec.metrics_autoscaling_enabled
        config = spec.to_yaml_config()
        back = SkyServiceSpec.from_yaml_config(config)
        assert back.target_ttft_seconds_per_tier == \
            {'interactive': 0.5}
        with pytest.raises(ValueError, match='unknown tier'):
            SkyServiceSpec(min_replicas=1, max_replicas=2,
                           target_ttft_seconds_per_tier={'gold': 1.0})
        with pytest.raises(ValueError, match='must be > 0'):
            SkyServiceSpec(min_replicas=1, max_replicas=2,
                           target_ttft_seconds_per_tier={
                               'interactive': 0.0})
        with pytest.raises(ValueError, match='max_replicas'):
            SkyServiceSpec(min_replicas=1,
                           target_ttft_seconds_per_tier={
                               'interactive': 0.5})

    def test_per_tier_pressure_scales_up_and_replays(self):
        """An interactive-TTFT breach grows the fleet even while the
        GLOBAL mean TTFT is under target — and the decision log
        replays exactly (the PR-8 discipline)."""
        from skypilot_tpu.serve import serve_state
        from skypilot_tpu.serve.autoscalers import (
            MetricsAutoscaler, replay_decision_log)

        class _Info:

            def __init__(self, rid):
                self.replica_id = rid
                self.status = serve_state.ReplicaStatus.READY
                self.version = 1
                self.is_spot = False

        auto = MetricsAutoscaler(self._spec())
        infos = [_Info(1)]
        signals = {1: {'queue_depth': 0.0, 'ttft_s': 0.2,
                       'ttft_s_interactive': 2.0,   # 4x over target
                       'ttft_s_batch': 30.0}}       # no batch target
        decisions = []
        for _ in range(auto.scale_up_threshold):
            auto.collect_replica_metrics(signals)
            decisions = auto.evaluate_scaling(infos)
        assert decisions and decisions[0].operator.value == 'scale_up'
        assert auto.decision_log[-1]['pressure'] == pytest.approx(4.0)
        replayed = replay_decision_log(self._spec(), auto.decision_log)
        recorded = [entry['decisions'] for entry in auto.decision_log]
        assert replayed == recorded

    def test_scrape_parses_per_tier_ttft(self):
        from skypilot_tpu.serve.replica_managers import (
            _signals_from_exposition)
        text = '\n'.join([
            '# TYPE skytpu_engine_queue_depth gauge',
            'skytpu_engine_queue_depth 3',
            '# TYPE skytpu_engine_tier_ttft_seconds histogram',
            'skytpu_engine_tier_ttft_seconds_bucket'
            '{tier="interactive",le="+Inf"} 2',
            'skytpu_engine_tier_ttft_seconds_sum{tier="interactive"}'
            ' 1.0',
            'skytpu_engine_tier_ttft_seconds_count{tier="interactive"}'
            ' 2',
            'skytpu_engine_tier_ttft_seconds_bucket'
            '{tier="batch",le="+Inf"} 1',
            'skytpu_engine_tier_ttft_seconds_sum{tier="batch"} 8.0',
            'skytpu_engine_tier_ttft_seconds_count{tier="batch"} 1',
        ])
        signals = _signals_from_exposition(text)
        assert signals['queue_depth'] == 3
        assert signals['ttft_s_interactive'] == pytest.approx(0.5)
        assert signals['ttft_s_batch'] == pytest.approx(8.0)


# ---------------------------------------------------------------------
# LB policy: adapter affinity + tier-aware least-loaded
# ---------------------------------------------------------------------


class TestTenantRouting:

    def _policy(self):
        from skypilot_tpu.serve.load_balancing_policies import \
            PrefixAwarePolicy
        clock = {'t': 0.0}
        policy = PrefixAwarePolicy(clock=lambda: clock['t'])
        policy.set_ready_replicas(['http://a', 'http://b', 'http://c'])
        return policy

    def test_sole_holder_beats_prefix_affinity(self):
        from skypilot_tpu.models import kv_cache as kv_cache_lib
        policy = self._policy()
        ids = list(range(32))
        digest = 'v1:8:1:' + kv_cache_lib.prefix_route_hash(ids[:8])
        # http://a has the warm prefix; only http://c holds the
        # adapter resident.
        policy.observe_response('http://a',
                                {'X-SkyTPU-Prefix-Digest': digest})
        policy.observe_response('http://c',
                                {'X-SkyTPU-Adapters': 'tenant-x'})
        url, info = policy.select(
            hint={'token_ids': ids, 'adapter': 'tenant-x'})
        assert url == 'http://c'
        assert info['result'] == 'adapter_pin'
        # Without the adapter the prefix match wins as usual.
        url, info = policy.select(hint={'token_ids': ids})
        assert url == 'http://a' and info['result'] == 'hit'

    def test_multiple_holders_prefix_picks_among_them(self):
        from skypilot_tpu.models import kv_cache as kv_cache_lib
        policy = self._policy()
        ids = list(range(32))
        digest = 'v1:8:1:' + kv_cache_lib.prefix_route_hash(ids[:8])
        # a and b both hold the adapter; b also has the warm prefix.
        policy.observe_response('http://a',
                                {'X-SkyTPU-Adapters': 'tenant-x'})
        policy.observe_response('http://b',
                                {'X-SkyTPU-Adapters': 'tenant-x',
                                 'X-SkyTPU-Prefix-Digest': digest})
        url, info = policy.select(
            hint={'token_ids': ids, 'adapter': 'tenant-x'})
        assert url == 'http://b' and info['result'] == 'hit'
        # Eviction clears the affinity (empty header value).
        policy.observe_response('http://b', {'X-SkyTPU-Adapters': ''})
        url, info = policy.select(
            hint={'token_ids': [1, 2], 'adapter': 'tenant-x'})
        assert url == 'http://a' and info['result'] == 'adapter_pin'

    def test_no_holder_fails_open(self):
        policy = self._policy()
        url, info = policy.select(
            hint={'token_ids': [1, 2], 'adapter': 'tenant-x'})
        assert url is not None
        assert info['result'] in ('miss', 'fallback')

    def test_tier_aware_least_loaded(self):
        policy = self._policy()
        # b has the shortest interactive lane despite the deepest
        # total load.
        policy.observe_response(
            'http://a', {'X-SkyTPU-Tier-Load':
                         'interactive=3,standard=0,batch=0'})
        policy.observe_response(
            'http://b', {'X-SkyTPU-Tier-Load':
                         'interactive=0,standard=2,batch=9'})
        policy.observe_response(
            'http://c', {'X-SkyTPU-Tier-Load':
                         'interactive=2,standard=0,batch=0'})
        url, _info = policy.select(
            hint={'prompt_len': 4, 'tier': 'interactive'})
        assert url == 'http://b'
        # Without a tier the deterministic url tie-break applies.
        url, _info = policy.select(hint={'prompt_len': 4})
        assert url == 'http://a'
        # Mixed fleet (one replica without tier intel): the per-tier
        # lane must NOT be compared against another replica's TOTAL
        # load — the ordering falls back to totals for everyone.
        policy.set_ready_replicas(['http://b', 'http://d'])
        policy.observe_response(
            'http://b', {'X-SkyTPU-Tier-Load':
                         'interactive=0,standard=2,batch=9',
                         'X-SkyTPU-Queue-Depth': '11'})
        policy.observe_response('http://d',
                                {'X-SkyTPU-Queue-Depth': '1'})
        url, _info = policy.select(
            hint={'prompt_len': 4, 'tier': 'interactive'})
        assert url == 'http://d'


# ---------------------------------------------------------------------
# Server surface over live HTTP
# ---------------------------------------------------------------------


@pytest.fixture()
def tenant_server(adapter_trees, references):
    import asyncio
    import socket
    from aiohttp import web
    from skypilot_tpu.serve.server import InferenceServer
    base_params, _ = references
    engine = ContinuousBatchingEngine(
        _cfg(), params=base_params, num_slots=2, max_adapters=2,
        **LORA_KW)
    server = InferenceServer.__new__(InferenceServer)
    server.engine = engine
    server.tokenizer_kind = 'byte'
    server._hf_tokenizer = None  # pylint: disable=protected-access
    server.ready = True
    server.request_timeout = 0.0
    server.draining = False
    server.tier = 'monolithic'
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
    url = f'http://127.0.0.1:{port}'
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            requests.get(url + '/health', timeout=2)
            break
        except requests.RequestException:
            time.sleep(0.1)
    yield server, url, engine
    engine.stop()


class TestServerSurface:

    def test_adapter_lifecycle_and_headers(self, tenant_server,
                                           adapter_trees, references,
                                           tmp_path):
        import requests
        _server, url, _engine = tenant_server
        _, refs = references
        npz = str(tmp_path / 'ad0.npz')
        tenancy.save_adapter_npz(adapter_trees['ad0'], npz)
        resp = requests.post(url + '/adapters/load',
                             json={'name': 'tenant-a', 'path': npz},
                             timeout=120)
        assert resp.status_code == 200 and resp.json()['slot'] == 1
        resp = requests.get(url + '/adapters', timeout=30)
        body = resp.json()
        assert body['capacity'] == 2 and body['resident'] == 1
        # Adapter + priority ride /generate; per-adapter output is the
        # dedicated engine's, over live HTTP.
        resp = requests.post(
            url + '/generate',
            json={'prompt_ids': [PROMPT], 'max_new_tokens': 8,
                  'adapter': 'tenant-a', 'priority': 'interactive'},
            timeout=300)
        assert resp.status_code == 200
        assert resp.json()['token_ids'][0] == refs['ad0']
        assert resp.headers.get('X-SkyTPU-Adapters') == 'tenant-a'
        tier_load = tenancy.parse_tier_load_header(
            resp.headers['X-SkyTPU-Tier-Load'])
        assert set(tier_load) == set(tenancy.TIERS)
        # /health carries the multi-tenant surface for serve status.
        health = requests.get(url + '/health', timeout=30).json()
        assert health['adapters'] == {'capacity': 2, 'resident': 1}
        assert set(health['tier_load']) == set(tenancy.TIERS)
        # Unknown adapter → terminal 400; bad priority → 400.
        resp = requests.post(
            url + '/generate',
            json={'prompt_ids': [PROMPT], 'adapter': 'nope'},
            timeout=60)
        assert resp.status_code == 400
        resp = requests.post(
            url + '/generate',
            json={'prompt_ids': [PROMPT], 'priority': 'gold'},
            timeout=60)
        assert resp.status_code == 400
        # DELETE: ok → 404 when repeated.
        assert requests.delete(url + '/adapters/tenant-a',
                               timeout=120).status_code == 200
        assert requests.delete(url + '/adapters/tenant-a',
                               timeout=120).status_code == 404

    def test_deadline_shed_maps_to_429(self, tenant_server):
        import requests
        _server, url, engine = tenant_server
        engine.ttft_estimate = 30.0
        futures = [engine.submit([1, 2, 3 + i], max_new_tokens=16,
                                 priority='interactive')
                   for i in range(4)]
        try:
            resp = requests.post(
                url + '/generate',
                json={'prompt_ids': [[9, 9, 9]], 'max_new_tokens': 4,
                      'priority': 'interactive', 'timeout_s': 0.5},
                timeout=60)
            assert resp.status_code == 429
            assert 'Retry-After' in resp.headers
        finally:
            for fut in futures:
                fut.cancel()


# ---------------------------------------------------------------------
# serve status cells tolerate old rows
# ---------------------------------------------------------------------


class TestStatusCells:

    def test_cells_tolerate_old_rows(self):
        """The ADAPTERS/TIER-MIX cell helpers must render '-' for rows
        recorded by older builds (the PR-13 TIER-column pattern) —
        mirrored from cli.serve_status's row construction."""
        old_row = {'replica_id': 1, 'status': 'READY', 'url': None,
                   'is_spot': False, 'version': 1}
        assert old_row.get('adapters') is None
        assert old_row.get('tier_load') is None
        new_row = {'adapters': {'capacity': 4, 'resident': 2},
                   'tier_load': {'interactive': 1, 'standard': 0,
                                 'batch': 7}}
        assert (f"{new_row['adapters']['resident']}"
                f"/{new_row['adapters']['capacity']}") == '2/4'
