"""Inference engine: prefill + autoregressive decode over the KV cache.

The reference serves LLMs by launching external engines (vLLM/TGI —
SURVEY §2.9); here the engine is in-tree and TPU-native: the same
Transformer (same checkpoint tree) flips to `decode=True`, the KV cache
shards over the mesh (kv heads on tp, batch on dp/fsdp), prefill is one
jitted call over the whole prompt, and decode is one jitted
single-token step — two compilations total, static shapes throughout.

This is the engine behind serve replicas (skypilot_tpu/serve/server.py)
and the TTFT benchmark.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import logging
import math
import time as time_lib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from skypilot_tpu import exceptions
from skypilot_tpu.models import kv_cache as kv_cache_lib
from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.models.configs import ModelConfig, get_config
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.observability import metrics as obs
from skypilot_tpu.observability import tracing
from skypilot_tpu.ops import paged_attention as paged_attention_lib
from skypilot_tpu.parallel import sharding as sharding_lib
from skypilot_tpu.serve import tenancy
from skypilot_tpu.utils import fault_injection

logger = logging.getLogger(__name__)

# Engine metrics (docs/observability.md). Label children are pre-bound
# here so the hot paths never build a labels dict per event; with no
# exporter attached every recording below is a single enabled-check
# (pinned by tests/test_observability.py, same pattern as fault
# injection's disarmed path).
_TTFT_HIST = obs.histogram(
    'skytpu_engine_ttft_seconds',
    'Time from submit to first emitted token')
_TPOT_HIST = obs.histogram(
    'skytpu_engine_tpot_seconds',
    'Per-request mean inter-token latency (decode span / tokens-1)',
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0))
_QUEUE_DEPTH = obs.gauge(
    'skytpu_engine_queue_depth',
    'Requests queued for admission (not yet in a decode slot)')
_ACTIVE_SLOTS = obs.gauge(
    'skytpu_engine_active_slots', 'Decode slots currently occupied')
_TOKENS_TOTAL = obs.counter(
    'skytpu_engine_tokens_generated_total', 'Decode tokens emitted')
_REQUESTS_TOTAL = obs.counter(
    'skytpu_engine_requests_finished_total',
    'Requests that resolved their future', ('outcome',))
_REQ_OK = _REQUESTS_TOTAL.labels(outcome='ok')
_REQ_FAILED = _REQUESTS_TOTAL.labels(outcome='failed')
_REJECTS = obs.counter(
    'skytpu_engine_admission_rejects_total',
    'Requests refused at admission', ('reason',))
_REJECT_OVERLOADED = _REJECTS.labels(reason='overloaded')
_REJECT_DRAINING = _REJECTS.labels(reason='draining')
_PREFIX = obs.counter(
    'skytpu_engine_prefix_cache_total',
    'Prefix-cache lookups at admission', ('result',))
_PREFIX_HIT = _PREFIX.labels(result='hit')
_PREFIX_MISS = _PREFIX.labels(result='miss')
_PREFIX_TOKENS = obs.counter(
    'skytpu_engine_prefix_tokens_reused_total',
    'Prompt tokens whose prefill was skipped via the prefix cache')
_SPEC_DRAFTED = obs.counter(
    'skytpu_engine_spec_drafted_total',
    'Speculative tokens drafted by prompt-lookup')
_SPEC_ACCEPTED = obs.counter(
    'skytpu_engine_spec_accepted_total',
    'Speculative drafts accepted by verification')
_WEDGE_RECOVERIES = obs.counter(
    'skytpu_engine_wedge_recoveries_total',
    'Watchdog recoveries (engine thread wedged or died)')
_PAGED_CAPACITY = obs.gauge(
    'skytpu_engine_paged_blocks_capacity',
    'Paged KV pool size in blocks (incl. the scratch block)')
_PAGED_USED = obs.gauge(
    'skytpu_engine_paged_blocks_used',
    'Paged KV pool blocks currently referenced')
_PAGED_REUSED = obs.counter(
    'skytpu_engine_paged_blocks_reused_total',
    'Whole blocks attached read-only from cached prefixes at admission')
_PAGED_COW = obs.counter(
    'skytpu_engine_paged_cow_copies_total',
    'Copy-on-write block copies (partial prefix block made private)')
_CHUNKED_PREFILL = obs.counter(
    'skytpu_engine_chunked_prefill_ticks_total',
    'Prefill chunks processed (interleaved between decode ticks)')
_CHUNKED_PREFILL_TOKENS = obs.counter(
    'skytpu_engine_chunked_prefill_tokens_total',
    'Real prompt tokens dispatched in prefill chunks (pads excluded): '
    'over chunks x prefill_chunk it is how full the chunks run')
_PAGED_INT8_SAVED = obs.gauge(
    'skytpu_engine_paged_int8_bytes_saved',
    'HBM bytes the int8-quantized paged pool saves vs the same pool '
    'at the float dtype (payload fp->1 byte minus the fp32 scale rows, '
    'both K and V, all layers)')
_SPEC_PAGED_ACCEPTED = obs.counter(
    'skytpu_engine_spec_paged_accepted_total',
    'Speculative drafts accepted by verification through paged '
    'block-table gathers (the paged x speculative composition)')
_DISPATCH_AHEAD_DEPTH = obs.histogram(
    'skytpu_engine_dispatch_ahead_depth',
    'In-flight decode dispatches (ring depth) observed as each '
    'dispatch is issued — how deep the async lookahead actually runs',
    buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0))
_DISPATCH_AHEAD = obs.gauge(
    'skytpu_engine_dispatch_ahead',
    'Decode dispatches in flight beyond the last consumed result '
    '(the async lookahead depth currently in effect)')
_PREFIX_EXPORT_BLOCKS = obs.counter(
    'skytpu_prefix_export_blocks_total',
    'KV blocks serialized into prefix artifacts on preemption notice')
_PREFIX_PREWARM_BLOCKS = obs.counter(
    'skytpu_prefix_prewarm_blocks_total',
    'KV blocks restored into the pool from a prefix artifact')
_PREFIX_PREWARM_HIT = obs.counter(
    'skytpu_prefix_prewarm_hit_total',
    'Admission prefix-cache hits served from a PRE-WARMED (imported) '
    'entry — the TTFT saved across a preemption')
_HANDOFF_EXPORT_CHUNKS = obs.counter(
    'skytpu_handoff_export_chunks_total',
    'KV handoff chunks serialized by the prefill tier for '
    'engine→engine streaming (docs/serving.md "Disaggregated '
    'serving")')
_HANDOFF_EXPORT_BYTES = obs.counter(
    'skytpu_handoff_export_bytes_total',
    'KV handoff payload bytes serialized by the prefill tier')
_HANDOFF_INGEST_CHUNKS = obs.counter(
    'skytpu_handoff_ingest_chunks_total',
    'KV handoff chunks received on the decode side, by result: ok '
    '(applied), duplicate (retried seq acknowledged idempotently), '
    'rejected (corrupt / out-of-order / layout mismatch), shed '
    '(decode-side pool pressure — 503 rather than corruption)',
    ('result',))
_INGEST_OK = _HANDOFF_INGEST_CHUNKS.labels(result='ok')
_INGEST_DUP = _HANDOFF_INGEST_CHUNKS.labels(result='duplicate')
_INGEST_REJECTED = _HANDOFF_INGEST_CHUNKS.labels(result='rejected')
_INGEST_SHED = _HANDOFF_INGEST_CHUNKS.labels(result='shed')
_HANDOFF_INGEST_STREAMS = obs.counter(
    'skytpu_handoff_ingest_streams_total',
    'KV handoff streams resolved on the decode side: completed '
    '(published to the prefix index), aborted (sender abort or apply '
    'failure — blocks rolled back to refcount 0), expired (TTL sweep '
    'reclaimed a stream whose sender died mid-handoff)', ('outcome',))
_HANDOFF_INGEST_BLOCKS = obs.counter(
    'skytpu_handoff_ingest_blocks_total',
    'KV pool blocks published from completed handoff streams')
_TP_SIZE = obs.gauge(
    'skytpu_engine_tp_size',
    'Tensor-parallel degree of the serving mesh (1 = single-chip)')
_TP_COLLECTIVES = obs.gauge(
    'skytpu_engine_tp_collectives',
    'Collective ops in the compiled all-slots decode step '
    '(compiled-HLO probe, parallel/hlo_probe; 0 until probed)')
_TP_ALLREDUCE_BYTES = obs.gauge(
    'skytpu_engine_tp_allreduce_bytes',
    'Bytes one compiled decode step moves through all-reduce (the '
    'per-layer tensor-parallel activation reductions over ICI; '
    'compiled-HLO probe, 0 until probed or single-chip)')
_PAGED_USED_PER_DEV = obs.gauge(
    'skytpu_engine_paged_blocks_used_per_device',
    'Paged KV pool blocks referenced, per mesh device. Block tables '
    'are replicated host-side so counts match across devices; the '
    'BYTES each block costs per device differ with tp — see '
    'skytpu_engine_paged_pool_bytes_per_device', ('device',))
_POOL_BYTES_PER_DEV = obs.gauge(
    'skytpu_engine_paged_pool_bytes_per_device',
    'HBM bytes of the paged KV pool resident on each mesh device '
    '(every device holds its kv-head shard of every block: '
    'pool bytes / tp)', ('device',))
# Multi-tenant serving (docs/serving.md "Multi-tenant serving").
_ADAPTER_SLOTS = obs.gauge(
    'skytpu_engine_adapter_slots',
    'Device-side adapter pool capacity (loadable slots; slot 0 = the '
    'base-model identity is extra)')
_ADAPTER_RESIDENT = obs.gauge(
    'skytpu_engine_adapter_resident',
    'Adapters currently resident in the device-side pool')
_ADAPTER_LOADS = obs.counter(
    'skytpu_engine_adapter_loads_total',
    'Adapter loads into a device slot (first load + re-load after '
    'eviction)')
_ADAPTER_EVICTIONS = obs.counter(
    'skytpu_engine_adapter_evictions_total',
    'LRU evictions of refcount-0 resident adapters under slot '
    'pressure')
_ADAPTER_SHED = obs.counter(
    'skytpu_engine_adapter_shed_total',
    'Requests/loads shed because every adapter slot was pinned '
    '(AdapterPoolExhaustedError; retryable)')
_TIER_QUEUE_DEPTH = obs.gauge(
    'skytpu_engine_tier_queue_depth',
    'Admission-queue depth by SLO tier', ('tier',))
_TIER_TTFT_HIST = obs.histogram(
    'skytpu_engine_tier_ttft_seconds',
    'Submit → first token by SLO tier (the per-tier autoscaler '
    'signal: target_ttft_seconds_per_tier)', ('tier',))
_TIER_REQUESTS = obs.counter(
    'skytpu_engine_tier_requests_total',
    'Requests submitted by SLO tier', ('tier',))
_TIER_DEADLINE_SHED = obs.counter(
    'skytpu_engine_tier_deadline_shed_total',
    'Requests shed at submit because their deadline was unmeetable '
    'at the current queue depth (429 + Retry-After)', ('tier',))
_SLOT_PREEMPTS = obs.counter(
    'skytpu_engine_slot_preempts_total',
    'batch-tier requests preempted out of a decode slot by an '
    'interactive arrival and re-queued retryably')
_DECODE_KERNEL = obs.gauge(
    'skytpu_engine_decode_kernel',
    'Decode attention implementation in effect: 0 = xla '
    '(scatter/gather through the block pool), 1 = pallas (fused '
    'block-table-walk kernel, ops/paged_attention), 2 = '
    'pallas_interpret (the same kernel under the Pallas interpreter '
    'on CPU)')
_DECODE_FUSED_BYTES = obs.gauge(
    'skytpu_engine_decode_fused_bytes',
    'HBM bytes ONE fused decode step streams through the pallas '
    'kernel: live pool blocks x (K+V payload + int8 scale rows) x '
    'layers, each read exactly once per step '
    '(ops/paged_attention.fused_hbm_bytes_per_step; 0 on the XLA '
    'path, where the gathered-window intermediate adds a further '
    'write+read on top of this floor)')
_DECODE_KERNEL_CODE = {'xla': 0, 'pallas': 1, 'pallas_interpret': 2}

# step_log cap: enough history for any interleaving assertion while
# bounding a serve replica that decodes for weeks (the old unbounded
# list grew one tuple per tick forever — a slow leak).
_STEP_LOG_CAP = 4096


class _StepLog(collections.deque):
    """Capped deque that still supports the list-style slicing the
    interleaving tests (and debuggers) use: log[marker:]."""

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return list(self)[idx]
        return super().__getitem__(idx)


class _StaleEngineError(Exception):
    """Raised inside a tick when the watchdog has abandoned this engine
    thread (generation bumped): the thread must exit WITHOUT touching
    the (already replaced) slots/queue/cache of its successor."""


def _upload(value, dtype=None, sharding=None):
    """The engine's single host→device upload funnel. Every hot-path
    host-list/scalar → device-array conversion routes through here so
    the tier-1 transfer-counting test can shim ONE symbol and pin the
    steady-state zero-upload property (a steady decode tick feeds the
    previous dispatch's output arrays straight back — see _tick).

    `sharding` (a NamedSharding; tensor-parallel engines pass their
    replicated placement) commits the array to every mesh device —
    feeds, block tables and temps are tiny and every device needs them
    whole, so replication is THE right layout and pinning it here keeps
    jit signatures stable (no resharding, no recompiles when a feed
    alternates between host-built and in-graph-chained)."""
    arr = jnp.asarray(value, dtype)
    if sharding is not None:
        arr = jax.device_put(arr, sharding)
    return arr


def _land(value) -> np.ndarray:
    """The download twin of `_upload`: the engine's single device→host
    landing funnel. Every hot-path materialization of a device value on
    the host routes through here so skylint's hot-path-host-sync
    checker (docs/static-analysis.md) can pin raw `np.asarray`/
    `jax.device_get`/`.block_until_ready()` crossings to exactly one
    reviewed site — a landing is a host sync by definition, and the
    protocol decides where that block is paid: the async ring starts
    the copy at dispatch (`copy_to_host_async`) so landing the oldest
    entry here is a wait on an already-in-flight transfer, while the
    sync path (async_depth=0) pays the full transfer because it has
    nothing to overlap it with."""
    return np.asarray(value)


# Monotone per-request ids: the device-feed / lookahead signatures key
# on (seq, next_pos) so a finished request and its slot's next occupant
# can never alias (unlike id(), which recycles).
_REQ_SEQ = itertools.count()


# ---------------- tensor-parallel serving helpers ----------------
#
# The sharding RULES live in parallel/sharding.py (the same table
# training consumes); everything here is placement plumbing: validate
# the mesh, translate the model's logical axis names into per-leaf
# NamedShardings, and account bytes per device.


def _mesh_tp(mesh) -> int:
    """Tensor-parallel degree of a mesh (1 for None / axis absent)."""
    if mesh is None:
        return 1
    try:
        return int(dict(mesh.shape).get('tp', 1))
    except (AttributeError, TypeError):
        return 1


def _validate_serving_mesh(cfg: ModelConfig, mesh) -> None:
    """Serving meshes are tensor-parallel only (for now): kv-heads/
    heads/mlp/vocab shard on `tp`, everything else stays replicated.
    dp/fsdp-sharded decode batches are the fleet-scale roadmap item —
    refuse them explicitly instead of letting GSPMD pad a 4-slot batch
    over an 8-way fsdp axis."""
    extra = {a: s for a, s in dict(mesh.shape).items()
             if a != 'tp' and int(s) > 1}
    if extra:
        raise ValueError(
            f'serving mesh supports tensor parallelism only; got extra '
            f'axes {extra} (build it with parallel.decode_mesh(tp))')
    cfg.assert_tp_compatible(_mesh_tp(mesh))


def _abstract_init(model: Transformer, cfg: ModelConfig, batch: int):
    """Boxed eval_shape of model.init in decode mode: the logical-axis
    metadata source for param AND cache placement (paged cfgs thread a
    dummy block table so Attention takes the paged path)."""
    kw = {}
    if cfg.paged_block_size:
        width = cfg.max_seq_len // cfg.paged_block_size + 1
        kw['block_tables'] = jnp.zeros((batch, width), jnp.int32)
    if cfg.state_slots:
        # Recurrent-state leaves are per SLOT, not per batch row: a
        # batch-1 init names the slot it stands for.
        kw['state_rows'] = (jnp.zeros((batch,), jnp.int32), None)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((batch, 1), jnp.int32),
        jnp.zeros((batch, 1), jnp.int32), **kw))


def _place_params(model: Transformer, cfg: ModelConfig, params,
                  mesh):
    """Shard a param tree onto the mesh per the shared logical-axis
    rules: QKV/O on heads/kv_heads, MLP hidden on mlp, (un)embedding
    on vocab — all mapped to `tp`. A random-init tree is already born
    sharded (_resolve_cfg_and_params), so this is a no-op for it;
    checkpoint-restored and quantized trees get the real reshard."""
    boxed = _abstract_init(model, cfg, 1)['params']
    shardings = nn.unbox(sharding_lib.tree_shardings(mesh, boxed))
    return jax.device_put(params, shardings)


def _zeros_from_shapes(boxed_shapes, mesh=None):
    """Zeroed tree for eval_shape'd (boxed) cache shapes. With a mesh,
    the zeros are BORN sharded (jit out_shardings from the logical
    metadata: kv_heads → tp) — the pool never materializes whole on one
    device, which is the entire point of sharding it."""
    plain = nn.unbox(boxed_shapes)

    def mk():
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            plain, is_leaf=lambda x: hasattr(x, 'shape'))

    if mesh is None:
        return mk()
    shardings = nn.unbox(sharding_lib.tree_shardings(mesh, boxed_shapes))
    return jax.jit(mk, out_shardings=shardings)()


def _tree_bytes(tree) -> Tuple[int, int]:
    """(global_bytes, per_device_bytes) over a tree's array leaves.
    Per-device sums each leaf's shard shape under its sharding;
    replicated (or unsharded) leaves count whole on every device."""
    total = per_dev = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, 'nbytes'):
            continue
        total += int(leaf.nbytes)
        sharding = getattr(leaf, 'sharding', None)
        if sharding is None:
            per_dev += int(leaf.nbytes)
        else:
            per_dev += (math.prod(sharding.shard_shape(leaf.shape))
                        * leaf.dtype.itemsize)
    return total, per_dev


# Prompt tokens a prefill chunk carries for each byte of a weight (see
# default_prefill_chunk; the sweep behind it is in PERF.md section 6).
_PREFILL_TOKENS_PER_WEIGHT_BYTE = 128


def default_prefill_chunk(block_size: int, max_seq_len: int,
                          weight_bytes: int = 2) -> int:
    """Width of the engine's one fixed (1, width) prefill chunk when
    the caller gives none: 128 tokens a weight byte (256 for bf16
    weights, 128 for int8), held to a whole number of KV blocks (at
    least one) and to the context.

    A chunk program streams every layer's weights once whatever its
    width, so a narrow chunk pays a whole pass for a few rows of
    matmul. A weight does 2 FLOPs a token, so the pass's compute
    catches up with its read at ridge x weight_bytes / 2 tokens (v5e:
    240 FLOPs a byte, so 240 tokens in bf16 and 120 in int8). Measured
    there at 7B widths and half depth (PERF.md section 6): a 256-token
    bf16 chunk takes 1.15x a 16-token one and less than one decode step
    of the same engine, so a tick that carries a chunk at most doubles;
    a 512-token one takes 1.7x and more than a decode step, and both
    serving cells complete fewer tokens a second with it. With int8
    weights a 256-token chunk outgrows the decode step on one of the
    two configurations (1.24x) and a 128-token one on neither."""
    width = min(_PREFILL_TOKENS_PER_WEIGHT_BYTE * weight_bytes,
                max_seq_len)
    return max(block_size, width // block_size * block_size)


def infer_serving_tp(cfg: ModelConfig, n_devices: int) -> int:
    """Largest tp that divides the local device count AND every
    tp-sharded model dimension — the auto choice get_engine makes, so
    a model too big for one chip serves over all of them without a
    flag."""
    best = 1
    for t in range(1, n_devices + 1):
        if n_devices % t:
            continue
        try:
            cfg.assert_tp_compatible(t)
        except (ValueError, NotImplementedError):
            continue    # uneven dims, or a mixer not sharded over tp
        best = t
    return best


ENGINE_TIERS = ('monolithic', 'prefill', 'decode')


def _refuse_pattern(cfg: ModelConfig, quantize, speculative: int,
                    decode_kernel: str) -> None:
    """Levers that cannot take a dropless expert layer or a layer
    pattern refuse here, by name, before any weight is made (a tp mesh
    is refused by `assert_tp_compatible`; docs/serving.md "Expert
    layers and layer patterns")."""
    routed = cfg.is_moe and cfg.moe_impl == 'dropless'
    what = ('a dropless expert layer' if routed else 'a layer pattern')
    for lever, on, why in (
            (f'quantize={quantize!r}',
             (routed or cfg.attn_gate) and quantize == 'int8',
             'models/quantize.py rounds the dense kernels it knows by '
             'name: expert stacks, most of the weights, would stay as '
             'they are, and the attention\'s gate_proj is not the '
             'MLP\'s of that name'),
            (f'speculative={speculative}', routed and speculative > 0,
             'the verify program is not told which rows are inert, so '
             'their drafts would be routed and counted as tokens'),
            (f'decode_kernel={decode_kernel!r}',
             cfg.has_layer_pattern and decode_kernel != 'xla',
             'the fused paged-attention kernel compiles one window in, '
             'and here the window is the layer\'s, carried by the loop')):
        if on:
            raise NotImplementedError(
                f'{lever} is not supported for {cfg.name}, a model '
                f'with {what}: {why}')


def _refuse_recurrent(cfg: ModelConfig, lever: str, why: str) -> None:
    """Levers that take a request's state to be a list of KV blocks
    refuse a model whose state is more than that (models/ssm.py), by
    name and with the reason (docs/serving.md "Models with recurrent
    state")."""
    if cfg.has_recurrent_state:
        raise NotImplementedError(
            f'{lever} is not supported for {cfg.name}, a model with '
            f'recurrent state: {why}')


def _refuse_blocks(cfg: ModelConfig, lever: str, why: str) -> None:
    """Levers that cannot take generation by diffusion over blocks
    (`cfg.block_length`) refuse here, by name and with the reason
    (docs/serving.md "Generation by diffusion over blocks")."""
    if cfg.block_length:
        raise NotImplementedError(
            f'{lever} is not supported for {cfg.name}, a model that '
            f'generates by diffusion over blocks: {why}')


_NO_BLOCK_SHARING = (
    'a block\'s K/V is held only from its commit pass and a prefix ends '
    'where its prompt does, inside a block whose first pass has not run: '
    'sharing needs snapshots at block boundaries')
_NO_BLOCK_STREAM = (
    'a KV chunk stream hands over a prompt\'s blocks up to its last '
    'token, and a prompt\'s tail is not committed before its block is '
    'denoised')


def _refuse_block_levers(cfg: ModelConfig, *, speculative, decode_chunk,
                         prefix_cache, tier, decode_kernel, quantize,
                         kv_quant, max_adapters, paged_block_size) -> None:
    """Every constructor lever that block mode cannot take, before any
    weight is made (a tp mesh is refused by `assert_tp_compatible`)."""
    b = cfg.block_length
    for lever, on, why in (
            (f'speculative={speculative}', speculative > 0,
             'a draft is verified token by token under the causal mask; '
             'a block step unmasks by confidence under the block-causal '
             'one'),
            (f'decode_chunk={decode_chunk}', decode_chunk > 1,
             'a scanned dispatch feeds each step the last step\'s one '
             'token; a pass yields none or several and its next feed is '
             'a whole block'),
            (f'prefix_cache={prefix_cache}', prefix_cache > 0,
             _NO_BLOCK_SHARING),
            (f'tier={tier!r}', tier != 'monolithic', _NO_BLOCK_STREAM),
            (f'decode_kernel={decode_kernel!r}', decode_kernel != 'xla',
             'the fused paged-attention kernel compiles the causal mask '
             'in (`k_pos <= q_pos`), as the flash and ring kernels do'),
            (f'quantize={quantize!r}', quantize == 'int8',
             'confidences decide which position is unmasked, and no '
             'test holds the int8 path\'s order of unmasking to the '
             'reference\'s'),
            (f'kv_quant={kv_quant!r}', bool(kv_quant),
             'a block\'s keys are rewritten every pass and read back in '
             'the same pass; the int8 pool\'s rounding of them is held '
             'to no reference'),
            (f'max_adapters={max_adapters}', max_adapters > 0,
             'the block step carries no per-slot adapter index'),
            (f'paged_block_size={paged_block_size}',
             paged_block_size <= 0 or paged_block_size % b != 0,
             f'block mode runs over the paged pool in chunks of whole '
             f'blocks: a KV block holds a whole number of blocks of '
             f'{b}')):
        if on:
            _refuse_blocks(cfg, lever, why)


_NO_STATE_IN_BLOCKS = (
    'a shared KV block carries no snapshot of the scan and convolution '
    'state at its last position')
_NO_STATE_IN_STREAM = (
    'a KV chunk stream hands over blocks, and the recurrent state is in '
    'none of them')


class _IngestSession:
    """One in-flight prefill→decode handoff stream being assembled on
    the decode side (docs/serving.md "Disaggregated serving").

    Blocks are allocated from the pool as chunks land (so pool
    pressure surfaces immediately as a shed, before any data is
    staged), but the payload is STAGED host-side — nothing touches the
    device pool until the final chunk's batched apply runs in the
    engine tick thread. Rollback is therefore exact: releasing
    `blocks` returns the stream to refcount-0 with the pool invariant
    (`check()`) intact, no matter how many chunks had landed.

    `pool` pins the BlockPool object the blocks came from: a watchdog
    recovery or tick-failure reset swaps the engine's pool wholesale,
    and a stale session must release against ITS pool (harmless on an
    abandoned object), never against the successor's."""

    __slots__ = ('stream_id', 'pool', 'blocks', 'next_seq',
                 'staged_idx', 'staged_arr', 'chunks', 'bytes',
                 'touched')

    def __init__(self, stream_id: str, pool, now: float,
                 n_leaves: int) -> None:
        self.stream_id = stream_id
        self.pool = pool
        self.blocks: list = []
        self.next_seq = 0
        self.staged_idx: list = [[] for _ in range(n_leaves)]
        self.staged_arr: list = [[] for _ in range(n_leaves)]
        self.chunks = 0
        self.bytes = 0
        self.touched = now


class _Inflight:
    """One dispatched-but-not-yet-consumed decode step (async_depth>0).

    Lives in the engine's lookahead RING (oldest first, at most
    async_depth entries after each tick consumes one): every entry was
    fed in-graph off the previous one's feed, with the rows of slots
    that joined since laid over it (`_join_feed`). Entries need not
    share a slot population: a slot whose finish the host foresees
    drops out of later entries, a slot that joins appears in them.
    `out` is the device array of sampled columns (num_slots, k) with
    copy_to_host_async already started (the NEXT step's input it
    returned in-graph is the engine's `_feed`); `reqs` snapshots
    slot→request identity at dispatch time so
    emission up to async_depth ticks later can discard columns whose
    slot changed hands (EOS overshoot, deadline kills, admission
    churn; a preemption blanks its slot's entry, because the same
    request object may come back); `gen` ties the dispatch to the
    engine generation that issued it — a watchdog recovery discards
    the whole ring. `counts` is what rides in with the tokens: the
    routing counts of this step and of the chunks queued before it
    (`_note_routing`), their host copies started with `out`'s."""

    __slots__ = ('out', 'reqs', 'active', 'k', 'gen', 'counts')

    def __init__(self, out, reqs, active, k, gen, counts=()):
        self.out = out
        self.reqs = reqs
        self.active = active
        self.k = k
        self.gen = gen
        self.counts = counts


def greedy_sample(logits: jax.Array, rng: jax.Array,
                  temperature: float) -> jax.Array:
    """(B, vocab) → (B,) next token. temperature<=0 ⇒ argmax."""
    del rng
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def filter_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Keep only the k highest logits per row (k is jit-STATIC: an
    engine-level knob, so the step compiles once)."""
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, -jnp.inf, logits)


def filter_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filter: keep the smallest set of tokens whose cumulative
    probability reaches p (top-1 always kept). p is jit-static."""
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A token stays if the mass BEFORE it is < p (keeps top-1 even when
    # its own probability already exceeds p).
    keep = cum - probs < p
    thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < thresh, -jnp.inf, logits)


def apply_logit_filters(scaled: jax.Array, top_k: int,
                        top_p: float) -> jax.Array:
    """HF convention: filters apply AFTER temperature scaling, top-k
    THEN top-p — and top-p's mass is computed on the RENORMALIZED
    top-k distribution (masked entries carry no mass), so the two
    sorts cannot be fused into one threshold pass without changing
    which tokens survive. Two sorts per step is minor next to the
    decode matmuls."""
    if top_k and top_k > 0:
        scaled = filter_top_k(scaled, top_k)
    if top_p and 0.0 < top_p < 1.0:
        scaled = filter_top_p(scaled, top_p)
    return scaled


def temperature_sample(logits: jax.Array, rng: jax.Array,
                       temperature: float, top_k: int = 0,
                       top_p: float = 0.0) -> jax.Array:
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    scaled = apply_logit_filters(scaled, top_k, top_p)
    return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


def _resolve_decode_kernel(decode_kernel: str, cfg, tp: int = 1) -> str:
    """Validate + normalize the decode_kernel knob AT CONSTRUCTION —
    unsupported combinations raise here with an actionable message,
    never mid-dispatch inside a traced decode step.

    'xla' (default) always works. 'pallas' requires the paged pool
    (the kernel IS the block-table walk; contiguous decode has no
    tables to prefetch), no attention logit softcap (XLA-only, the
    ops/flash_attention policy) and a TPU: off the chip it raises, so
    that a server asked for the kernel never reports the interpreter's
    work as the kernel's. 'pallas_interpret' asks for the interpreter
    by name (tests, CPU rehearsals)."""
    if decode_kernel not in _DECODE_KERNEL_CODE:
        raise ValueError(
            f'unknown decode_kernel {decode_kernel!r}; expected one '
            f"of {tuple(_DECODE_KERNEL_CODE)}")
    if decode_kernel == 'xla':
        return 'xla'
    if not cfg.paged_block_size:
        raise NotImplementedError(
            "decode_kernel='pallas' requires the paged KV cache "
            '(paged_block_size > 0): the fused kernel walks per-row '
            'block tables in kernel — the contiguous layout has none. '
            "Use decode_kernel='xla' or enable paging.")
    if cfg.attn_logit_softcap:
        raise NotImplementedError(
            "decode_kernel='pallas' does not support attn_logit_"
            'softcap (the tanh cap runs on the XLA path only — the '
            'ops/flash_attention policy); use decode_kernel=\'xla\' '
            'for softcapped models')
    if decode_kernel == 'pallas' and tp > 1:
        raise NotImplementedError(
            f"decode_kernel='pallas' under tp={tp}: jax does not "
            'partition a Mosaic call ("wrap the call in a shard_map"), '
            'and the kernel is not yet wrapped over the kv-head axis '
            "(ROADMAP S6). Use decode_kernel='xla' for tp>1.")
    if decode_kernel == 'pallas' and jax.default_backend() != 'tpu':
        raise RuntimeError(
            f"decode_kernel='pallas' needs a TPU; this process runs on "
            f"{jax.default_backend()!r}. Ask for 'pallas_interpret' by "
            f"name to run the same kernel under the Pallas interpreter.")
    return decode_kernel


def _resolve_cfg_and_params(cfg: 'ModelConfig | str',
                            params: Optional[Any],
                            max_seq_len: Optional[int],
                            rng_seed: int,
                            quantize: Optional[str] = None,
                            kv_quant: Optional[str] = None,
                            mesh: Optional[Any] = None):
    """Shared engine bring-up: normalize config to decode mode, init
    random weights when no checkpoint is given (bring-up / load-testing;
    real deployments restore via train/checkpoints.py), and optionally
    quantize the float params for weight-only int8 serving.

    `mesh` with tp>1: random init runs with sharded out_shardings (the
    trainer's create_sharded_state pattern), so the weight tree is BORN
    split across devices — a model too big for one chip must never
    materialize whole on device 0 on its way to being sharded.
    Checkpoint params arrive however the caller restored them; the
    engine's _place_params reshards those (sharded orbax restore onto
    the serving mesh is the remaining follow-up for 70B-class
    restores)."""
    if quantize not in (None, 'int8'):
        raise ValueError(f'unknown quantize mode {quantize!r}; '
                         f"supported: 'int8'")
    if kv_quant not in (None, '', 'int8'):
        raise ValueError(f'unknown kv_quant mode {kv_quant!r}; '
                         f"supported: 'int8'")
    if isinstance(cfg, str):
        cfg = get_config(cfg)
    if max_seq_len is not None:
        cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
    cfg = dataclasses.replace(cfg, decode=True, remat=False,
                              kv_cache_quant=kv_quant or '')
    if mesh is not None and _mesh_tp(mesh) > 1:
        # Fail with the divisibility/axis message BEFORE a sharded init
        # can die inside XLA with an opaque partitioning error.
        _validate_serving_mesh(cfg, mesh)
    if params is None:
        logger.info('Initializing random weights for %s', cfg.name)
        init_cfg = dataclasses.replace(cfg, decode=False,
                                       weight_quant='none')
        if cfg.serve_adapters > 0:
            # Plain-params init for a multi-LoRA engine: the multi-LoRA
            # module's base params are name/shape-identical to
            # nn.DenseGeneral's but the two flavors DRAW differently
            # (DenseGeneral's kernel init flattens fan dims) —
            # random-init weights must equal a plain engine's so
            # per-adapter bit-identity holds against it. The adapter
            # stacks are built separately (zeros) by the engine.
            init_cfg = dataclasses.replace(init_cfg, serve_adapters=0,
                                           lora_rank=0)
        # jit the whole init: unjitted flax init dispatches hundreds of
        # small ops one by one, each its own compile and launch.
        model0 = Transformer(init_cfg)
        rng = jax.random.PRNGKey(rng_seed)
        dummy = jnp.ones((1, 8), jnp.int32)
        if mesh is not None and _mesh_tp(mesh) > 1:
            abstract = jax.eval_shape(lambda: model0.init(rng, dummy))
            variables = jax.jit(
                lambda r: model0.init(r, dummy),
                out_shardings=sharding_lib.tree_shardings(
                    mesh, abstract))(rng)
        else:
            variables = jax.jit(model0.init)(rng, dummy)
        params = nn.unbox(variables)['params']
    if quantize:
        from skypilot_tpu.models.quantize import quantize_params
        cfg = dataclasses.replace(cfg, weight_quant='int8')
        params = quantize_params(params, cfg)
        logger.info('Quantized %s weights to int8 for serving', cfg.name)
    return cfg, params


class InferenceEngine:
    """One loaded model + its compiled prefill/decode steps.

    Batch is a fixed `batch_size`; prompts are right-padded token id
    arrays. For slot-based continuous batching use
    ContinuousBatchingEngine below.
    """

    def __init__(self, cfg: 'ModelConfig | str',
                 params: Optional[Any] = None,
                 batch_size: int = 1,
                 max_seq_len: Optional[int] = None,
                 rng_seed: int = 0,
                 quantize: Optional[str] = None,
                 decode_chunk: int = 1,
                 kv_quant: Optional[str] = None,
                 top_k: int = 0,
                 top_p: float = 0.0,
                 mesh: Optional[Any] = None,
                 decode_kernel: str = 'xla') -> None:
        _refuse_blocks(get_config(cfg) if isinstance(cfg, str) else cfg,
                       'InferenceEngine',
                       'its generate() feeds back one token a step; '
                       'ContinuousBatchingEngine has the block mode')
        self.cfg, self.params = _resolve_cfg_and_params(
            cfg, params, max_seq_len, rng_seed, quantize, kv_quant,
            mesh=mesh)
        # Fused-vs-XLA decode attention (docs/performance.md "Fused
        # decode kernel"): validated here, consumed inside
        # Attention._paged_decode_attention. This engine is paged only
        # when the caller's ModelConfig already carries pool geometry
        # (ContinuousBatchingEngine owns the usual paged bring-up).
        self.decode_kernel = _resolve_decode_kernel(
            decode_kernel, self.cfg, _mesh_tp(mesh))
        self.cfg = dataclasses.replace(self.cfg,
                                       decode_kernel=self.decode_kernel)
        _DECODE_KERNEL.set(_DECODE_KERNEL_CODE[self.decode_kernel])
        self.batch_size = batch_size
        # Engine-level sampling filters (jit-static: one compile).
        self.top_k, self.top_p = top_k, top_p
        self._sampler = functools.partial(temperature_sample,
                                          top_k=top_k, top_p=top_p)
        # >1 ⇒ generate() emits this many tokens per device dispatch
        # (lax.scan inside one jit): fewer host↔device round trips, at
        # the price of EOS being honored at chunk granularity. What a
        # round trip costs on a locally attached chip is not measured
        # (ROADMAP S3).
        self.decode_chunk = max(1, decode_chunk)
        self.model = Transformer(self.cfg)
        self._rng = jax.random.PRNGKey(rng_seed)
        # Tensor-parallel serving (parallel.decode_mesh): weights and
        # the KV cache shard on `tp` per the shared rule table; one
        # engine then serves a model too big for one chip. tp=1 (or no
        # mesh) is the historical single-chip path, bit for bit.
        self.mesh = mesh
        self._tp = _mesh_tp(mesh)
        if self._tp > 1:
            # Mesh already validated by _resolve_cfg_and_params.
            self.params = _place_params(self.model, self.cfg,
                                        self.params, mesh)
            _TP_SIZE.set(self._tp)

        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=('prompt_len',))
        self._decode_step = jax.jit(self._decode_impl,
                                    donate_argnames=('cache',))
        self._decode_chunk_fn = jax.jit(
            self._decode_chunk_impl, donate_argnames=('cache',),
            static_argnames=('greedy',))

    # ---------------- cache ----------------

    def init_cache(self) -> Any:
        """Fresh zeroed KV cache for one batch (born sharded on the
        kv-head axis under a tp mesh)."""
        shapes = _abstract_init(self.model, self.cfg,
                                self.batch_size)['cache']
        return _zeros_from_shapes(
            shapes, self.mesh if self._tp > 1 else None)

    # ---------------- steps ----------------

    def _prefill_impl(self, params, cache, tokens, prompt_len: int):
        """Run the whole (padded) prompt through the model; returns
        (logits at the last real prompt token, cache)."""
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
        logits, mutated = self.model.apply(
            {'params': params, 'cache': cache}, tokens, positions,
            mutable=['cache'])
        return logits[:, prompt_len - 1, :], mutated['cache']

    def _decode_impl(self, params, cache, token, index):
        """One decode step: (B, 1) token at position `index`."""
        positions = jnp.full((token.shape[0], 1), index, jnp.int32)
        logits, mutated = self.model.apply(
            {'params': params, 'cache': cache}, token, positions,
            mutable=['cache'])
        return logits[:, -1, :], mutated['cache']

    def _decode_chunk_impl(self, params, cache, token, start_index, rngs,
                           temperature, *, greedy: bool):
        """K decode+sample steps in ONE dispatch (lax.scan), K = the
        leading dim of `rngs`: returns ((B, K) tokens, cache). token:
        (B,) the last emitted token; temperature is TRACED so
        per-request temperatures never recompile (only greedy-vs-sampled
        is static)."""
        sampler = greedy_sample if greedy else self._sampler

        def body(carry, rng):
            cache, token, index = carry
            logits, cache = self._decode_impl(params, cache,
                                              token[:, None], index)
            nxt = sampler(logits, rng, temperature)
            return (cache, nxt, index + 1), nxt

        (cache, _, _), toks = jax.lax.scan(
            body, (cache, token, start_index), rngs)
        return toks.swapaxes(0, 1), cache  # (B, num_steps)

    # ---------------- generation ----------------

    @staticmethod
    def _trim_at_eos(toks, eos_id):
        """Host EOS scan of one emitted chunk (its copy_to_host_async
        is already in flight): truncate at the first all-EOS column.
        Returns (kept columns, done)."""
        cols = np.asarray(toks)
        for c in range(cols.shape[1]):
            if (cols[:, c] == eos_id).all():
                return toks[:, :c + 1], True
        return toks, False

    def generate(self,
                 prompt: jnp.ndarray,
                 max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """prompt: (B, prompt_len) int32. Returns
        ((B, <=max_new_tokens) generated ids, stats)."""
        with (self.mesh if self.mesh is not None
              else contextlib.nullcontext()):
            return self._generate_under_mesh(prompt, max_new_tokens,
                                             temperature, eos_id)

    def _generate_under_mesh(self, prompt, max_new_tokens, temperature,
                             eos_id):
        """generate() body; runs inside the mesh context so the model's
        logical sharding constraints resolve (XLA inserts the per-layer
        tp all-reduces; a trivial no-mesh context leaves the historical
        single-chip program untouched)."""
        assert prompt.ndim == 2 and prompt.shape[0] == self.batch_size, (
            f'prompt must be ({self.batch_size}, L); got {prompt.shape}')
        prompt_len = int(prompt.shape[1])
        assert prompt_len + max_new_tokens <= self.cfg.max_seq_len, (
            f'{prompt_len}+{max_new_tokens} exceeds max_seq_len '
            f'{self.cfg.max_seq_len}')
        sampler = (greedy_sample
                   if temperature <= 0 else self._sampler)

        cache = self.init_cache()
        # monotonic: latencies must not go negative on wall-clock steps.
        t0 = time_lib.monotonic()
        logits, cache = self._prefill(self.params, cache,
                                      prompt.astype(jnp.int32),
                                      prompt_len=prompt_len)
        self._rng, rng = jax.random.split(self._rng)
        token = sampler(logits, rng, temperature)
        token.block_until_ready()
        ttft = time_lib.monotonic() - t0

        # Both loops below run ONE dispatch ahead of the host's EOS
        # scan: the next chunk/step is dispatched off the previous
        # output's DEVICE array (no host round-trip on the critical
        # path) while copy_to_host_async lands the previous output for
        # the scan. EOS is therefore detected one dispatch late; the
        # already-dispatched overshoot is discarded, so the emitted
        # stream is bit-identical to the synchronous scan.
        if self.decode_chunk > 1:
            # Chunked: K tokens per dispatch. EOS honored at chunk
            # granularity (the host truncates at the first all-EOS
            # column after readback). The chunk size stays FIXED even on
            # the final partial chunk when the cache window allows —
            # overshoot is truncated on the host — so generate compiles
            # exactly one scan program per engine.
            chunks = [token[:, None]]
            last = token
            step = 1
            done = False
            pending = None    # youngest dispatch, EOS scan outstanding
            while step < max_new_tokens and not done:
                remaining = max_new_tokens - step
                k = self.decode_chunk
                if (k > remaining and
                        prompt_len + step - 1 + k > self.cfg.max_seq_len):
                    k = remaining
                self._rng, sub = jax.random.split(self._rng)
                rngs = jax.random.split(sub, k)
                toks, cache = self._decode_chunk_fn(
                    self.params, cache, last,
                    jnp.asarray(prompt_len + step - 1, jnp.int32), rngs,
                    jnp.asarray(temperature, jnp.float32),
                    greedy=temperature <= 0)
                toks = toks[:, :remaining]
                last = toks[:, -1]            # device feed, no sync
                step += int(toks.shape[1])
                if eos_id is None:
                    chunks.append(toks)
                    continue
                toks.copy_to_host_async()     # overlaps the next chunk
                if pending is not None:
                    trimmed, done = self._trim_at_eos(pending, eos_id)
                    chunks.append(trimmed)
                    # done ⇒ the chunk just dispatched is overshoot:
                    # drop it on the floor (its cache writes sit beyond
                    # every kept query position — causally masked).
                pending = toks if not done else None
            if pending is not None:   # only ever set when eos_id given
                trimmed, _ = self._trim_at_eos(pending, eos_id)
                chunks.append(trimmed)
            generated = jnp.concatenate(chunks, axis=1)
        else:
            out = [token]
            for step in range(1, max_new_tokens):
                self._rng, rng = jax.random.split(self._rng)
                logits, cache = self._decode_step(
                    self.params, cache, out[-1][:, None],
                    jnp.asarray(prompt_len + step - 1, jnp.int32))
                token = sampler(logits, rng, temperature)
                out.append(token)
                if eos_id is None:
                    continue
                token.copy_to_host_async()
                # Scan the PREVIOUS step's token while this one
                # computes: if it was EOS, the step just dispatched is
                # overshoot — truncate it away (identical output to the
                # synchronous per-step check, which also never scanned
                # the prefill-sampled token out[0]).
                if len(out) >= 3 and \
                        bool((np.asarray(out[-2]) == eos_id).all()):
                    out = out[:-1]
                    break
            generated = jnp.stack(out, axis=1)
        generated.block_until_ready()
        total = time_lib.monotonic() - t0
        num_tokens = int(generated.shape[1])
        stats = {
            'ttft_s': ttft,
            'total_s': total,
            'new_tokens': num_tokens,
            'decode_tokens_per_s':
                ((num_tokens - 1) / (total - ttft)
                 if num_tokens > 1 and total > ttft else None),
        }
        return generated, stats


class _Request:
    """One in-flight generation (continuous-batching bookkeeping)."""

    __slots__ = ('ids', 'max_new_tokens', 'temperature', 'eos_id',
                 'future', 'submit_time', 'first_token_time', 'tokens',
                 'next_pos', 'on_token', 'deadline', 'blocks',
                 'prefilling', 'prefill_pos', 'seq', 'trace',
                 'admit_time', 'tier', 'adapter', 'adapter_slot',
                 'adapter_pool', 'context', 'preemptions',
                 'admit_mono', 'prefill_chunks', 'inflight',
                 'passes_total', 'passes_done', 'first_passes', 'block0',
                 'unmask_pass', 'last_cols')

    def __init__(self, ids, max_new_tokens, temperature, eos_id, future,
                 on_token=None, deadline=None, tier='standard',
                 adapter=None, adapter_slot=0, adapter_pool=None):
        self.seq = next(_REQ_SEQ)
        self.ids = list(ids)
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.eos_id = eos_id
        self.future = future
        # monotonic: feeds ttft_s/total_s durations (and the TTFT/TPOT
        # histograms), which must not go negative on wall-clock steps.
        # The `deadline` below stays wall-clock by API contract.
        self.submit_time = time_lib.monotonic()
        self.first_token_time: Optional[float] = None
        self.tokens: list = []
        self.next_pos = 0  # cache position the NEXT input token writes to
        # Decode steps dispatched for this request whose tokens the host
        # has not consumed yet (the lookahead ring's share of it): the
        # next dispatch feeds position next_pos + inflight.
        self.inflight = 0
        # Streaming hook: called from the ENGINE thread with each token
        # as it lands, then once with None after the future resolves.
        self.on_token = on_token
        # Absolute epoch deadline (time.time()); None = no deadline.
        # Checked at admission and per tick — an expired request fails
        # with RequestDeadlineExceededError instead of occupying a slot.
        self.deadline = deadline
        # Paged-KV bookkeeping (unused on the contiguous path): the
        # physical block ids this request's table maps, whether it is
        # still mid-chunked-prefill, and how many prompt tokens have
        # been prefilled so far.
        self.blocks: list = []
        self.prefilling = False
        self.prefill_pos = 0
        # Prefill dispatches this request took (chunks of the paged
        # path, 1 on the bucketed one): the `engine.prefill` span's
        # `chunks`.
        self.prefill_chunks = 0
        # Tracing (docs/observability.md "Tracing"): the submitting
        # request's span context, captured by submit() when tracing is
        # enabled. None otherwise — every engine-side tracing hook
        # guards on this identity check, so the decode tick pays NO
        # tracing cost (no spans, no clocks) while tracing is off
        # (pinned by tests/test_tracing.py).
        self.trace = None
        self.admit_time: Optional[float] = None
        # -------- multi-tenant serving (serve/tenancy) --------
        # SLO tier ('interactive'/'standard'/'batch'): drives admission
        # order, deadline-aware shed, and batch-slot preemption.
        self.tier = tier
        # Adapter identity: registered name, the device slot index its
        # weights occupy (0 = base-model identity), and the POOL OBJECT
        # the pin was taken against — release always goes to that
        # object, so a wedge recovery's pool swap can never corrupt the
        # successor's refcounts (the slots/queue-swap isolation
        # pattern). adapter_pool is set to None once released.
        self.adapter = adapter
        self.adapter_slot = adapter_slot
        self.adapter_pool = adapter_pool
        # Prefill context: == ids until a slot preemption folds the
        # already-generated tokens in (ids + tokens) so the re-admitted
        # request CONTINUES instead of restarting — greedy continuation
        # is bit-identical to the uninterrupted stream.
        self.context = self.ids
        self.preemptions = 0
        # Admission stamp (monotonic, unconditional — unlike the
        # tracing-only admit_time): feeds the admission→first-token
        # service EWMA behind deadline-aware admission.
        self.admit_mono: Optional[float] = None
        # -------- generation by diffusion over blocks --------
        # Set when the request joins the block step (_join_block): the
        # passes it needs in all and those whose results have landed
        # (`inflight` counts the queued ones between), the passes of
        # its first block (fewer masks: the prompt's tail opens it) and
        # that block's first position. unmask_pass: for every emitted
        # token the pass of its block that unmasked it (what a
        # comparison with a reference replays); last_cols: the tokens of
        # the last block emitted, whole, so that what max_new_tokens cut
        # off is known too.
        self.passes_total = 0
        self.passes_done = 0
        self.first_passes = 0
        self.block0 = 0
        self.unmask_pass: list = []
        self.last_cols: list = []


class ContinuousBatchingEngine:
    """Slot-based continuous batching (JetStream-style, simplified).

    The decode batch is `num_slots` persistent slots over one shared KV
    cache; a scheduler thread admits queued prompts into free slots
    BETWEEN decode ticks, so new requests do not wait for in-flight ones
    to finish — the defining property of continuous batching. Prefill is
    one jitted call per power-of-two prompt bucket; decode is one jitted
    all-slots step. Rows sit at different depths via the per-row cache
    positions in Attention._decode_attention.

    (The reference gets this from vLLM — SURVEY §2.9; here it is the
    in-tree TTFT-critical path behind serve replicas.)
    """

    def __init__(self, cfg: 'ModelConfig | str',
                 params: Optional[Any] = None,
                 num_slots: int = 4,
                 max_seq_len: Optional[int] = None,
                 rng_seed: int = 0,
                 mesh: Optional[Any] = None,
                 quantize: Optional[str] = None,
                 decode_chunk: int = 1,
                 kv_quant: Optional[str] = None,
                 top_k: int = 0,
                 top_p: float = 0.0,
                 speculative: int = 0,
                 prefix_cache: int = 0,
                 max_queue_depth: int = 0,
                 watchdog_timeout: Optional[float] = None,
                 paged_block_size: int = 0,
                 paged_num_blocks: Optional[int] = None,
                 prefill_chunk: int = 0,
                 async_depth: int = 1,
                 tier: str = 'monolithic',
                 ingest_ttl: float = 60.0,
                 max_adapters: int = 0,
                 adapter_rank: int = 0,
                 adapter_alpha: float = 16.0,
                 adapter_targets: str = '',
                 decode_kernel: str = 'xla') -> None:
        import threading
        # -------- multi-LoRA serving (docs/serving.md) --------
        # max_adapters=N ⇒ the engine holds up to N adapters RESIDENT
        # in a fixed device-side stack and batches requests for
        # different adapters (and the base model) into ONE decode
        # dispatch — a per-slot adapter-index vector drives a gathered
        # low-rank delta inside the targeted projections
        # (transformer.MultiLoRADenseGeneral). Residency/LRU/refcounts
        # live in serve/tenancy.AdapterPool; device writes run in the
        # tick thread via _run_in_tick, off the steady decode path.
        self.max_adapters = max(0, max_adapters)
        if self.max_adapters:
            if quantize == 'int8':
                # Fail at construction, not inside the first traced
                # dispatch: the adapter delta applies to the FLOAT base
                # projection (transformer.dense_general refuses too).
                raise NotImplementedError(
                    'max_adapters does not compose with int8 WEIGHTS '
                    '(int8 KV is fine); serve unquantized, or merge a '
                    'single adapter and quantize that')
            base_cfg = get_config(cfg) if isinstance(cfg, str) else cfg
            rank = adapter_rank or base_cfg.lora_rank
            if rank <= 0:
                raise ValueError(
                    'max_adapters > 0 requires adapter_rank > 0 (the '
                    'uniform rank every resident adapter must share)')
            cfg = dataclasses.replace(
                base_cfg, serve_adapters=self.max_adapters,
                lora_rank=rank, lora_alpha=adapter_alpha,
                lora_targets=adapter_targets or base_cfg.lora_targets)
        # Levers that take a request's state to be a list of KV blocks
        # refuse a recurrent-state model here, before any weight is
        # made (a tp mesh is refused by assert_tp_compatible, below).
        base_cfg = get_config(cfg) if isinstance(cfg, str) else cfg
        for lever, on, why in (
                (f'speculative={speculative}', speculative > 0,
                 'a rejected draft cannot be rolled back out of a scan '
                 'state (no snapshot a position is kept)'),
                (f'prefix_cache={prefix_cache}', prefix_cache > 0,
                 _NO_STATE_IN_BLOCKS),
                (f'tier={tier!r}', tier != 'monolithic',
                 _NO_STATE_IN_STREAM)):
            if on:
                _refuse_recurrent(base_cfg, lever, why)
        if base_cfg.block_length:
            _refuse_block_levers(
                base_cfg, speculative=speculative,
                decode_chunk=decode_chunk, prefix_cache=prefix_cache,
                tier=tier, decode_kernel=decode_kernel, quantize=quantize,
                kv_quant=kv_quant, max_adapters=max_adapters,
                paged_block_size=paged_block_size)
        _refuse_pattern(base_cfg, quantize, speculative, decode_kernel)
        self.cfg, self.params = _resolve_cfg_and_params(
            cfg, params, max_seq_len, rng_seed, quantize, kv_quant,
            mesh=mesh)
        self.num_slots = num_slots
        self.mesh = mesh
        self.top_k, self.top_p = top_k, top_p
        # >1 ⇒ when no request is waiting to be admitted, a tick decodes
        # this many steps per dispatch (scan in one jit) — fewer
        # host round trips; admission latency is bounded by one chunk.
        self.decode_chunk = max(1, decode_chunk)
        # >0 ⇒ prompt-lookup speculative decoding: each tick drafts K
        # tokens per greedy slot by n-gram lookup in the slot's own
        # context and verifies them in ONE forward — every accepted
        # draft saves a full decode dispatch. Greedy output is
        # bit-identical to plain decode (pinned by test); sampling
        # slots fall back to one token per tick. Takes precedence over
        # decode_chunk.
        self.speculative = max(0, speculative)
        self.spec_stats = {'ticks': 0, 'drafted': 0, 'accepted': 0}
        # >0 ⇒ keep the last N prompts' prefilled KV in an LRU; a new
        # prompt sharing a cached PREFIX prefills only the suffix (chat
        # turns append to history; shared system prompts). Contiguous
        # mode: each entry holds a full-capacity batch-1 cache in device
        # memory — size N to the HBM you can spare. Paged mode: an entry
        # is a list of ref-counted shared blocks, ceil(L/block_size)
        # blocks for a length-L prefix — N can be much larger for the
        # same HBM (docs/performance.md has the sizing math).
        self.prefix_cache = max(0, prefix_cache)
        self.prefix_stats = {'hits': 0, 'misses': 0, 'tokens_reused': 0,
                             'prewarm_hits': 0}
        # Keys restored via import_prefixes (preemption pre-warm): a
        # hit on one of these counts toward
        # skytpu_prefix_prewarm_hit_total.
        self._prewarmed_keys: set = set()
        # -------- paged KV cache (docs/performance.md) --------
        # Opt-in via paged_block_size=N: KV lives in a shared pool of
        # fixed-size blocks (kv_cache.BlockPool) indexed through
        # per-slot block tables, prefixes share blocks read-only with
        # copy-on-write at the partial-block boundary, and prefill runs
        # in fixed-size chunks interleaved between decode ticks (ONE
        # compiled prefill shape instead of one per prompt bucket; a
        # long prompt no longer stalls in-flight slots' TPOT).
        self.paged_block_size = max(0, paged_block_size)
        if self.paged_block_size:
            if self.cfg.max_seq_len % self.paged_block_size:
                raise ValueError(
                    f'max_seq_len {self.cfg.max_seq_len} not divisible '
                    f'by paged_block_size {self.paged_block_size}')
            self._blocks_per_seq = (self.cfg.max_seq_len //
                                    self.paged_block_size)
            # Default pool: every slot can reach max_seq_len plus full
            # headroom for the prefix LRU, plus the scratch block. Size
            # explicitly (paged_num_blocks) to fit real HBM budgets.
            nb = paged_num_blocks or (
                (num_slots + self.prefix_cache) * self._blocks_per_seq
                + 1)
            self.cfg = dataclasses.replace(
                self.cfg, paged_block_size=self.paged_block_size,
                paged_num_blocks=nb,
                # the pool is batch-free, the recurrent leaves are not:
                # one row a slot, whatever a dispatch's batch
                state_slots=(num_slots if self.cfg.has_recurrent_state
                             else 0))
            self._pool: 'Optional[kv_cache_lib.BlockPool]' = \
                kv_cache_lib.BlockPool(nb, self.paged_block_size)
            # One fixed (1, width) prefill shape per engine. 0 = the
            # engine's rule (default_prefill_chunk): as many prompt
            # tokens as one pass over the weights carries for free.
            self.prefill_chunk = max(1, prefill_chunk or
                                     default_prefill_chunk(
                                         self.paged_block_size,
                                         self.cfg.max_seq_len,
                                         1 if quantize == 'int8' else 2))
            _PAGED_CAPACITY.set(nb)
        else:
            self._blocks_per_seq = 0
            self._pool = None
            self.prefill_chunk = 0
        # scan_positions / scan_tokens: positions the prefill scans of
        # a recurrent-state model ran over (pads included) and those
        # that advanced a state; state_bytes / kv_pool_bytes: device
        # bytes of the recurrent leaves and of the block pool (filled
        # in by the first paged_occupancy()).
        self.paged_stats = {'cow_copies': 0, 'blocks_reused': 0,
                            'prefill_chunks': 0, 'prefill_tokens': 0,
                            'prefix_evictions': 0,
                            'spec_trimmed_blocks': 0,
                            'scan_positions': 0, 'scan_tokens': 0,
                            'state_bytes': 0, 'kv_pool_bytes': 0}
        # -------- fused decode kernel (docs/performance.md) --------
        # decode_kernel='pallas' routes paged attention (and, on
        # multi-LoRA engines, the adapter gather+dot) through the
        # fused ops/ kernels. Validated HERE — after the paged-config
        # replace, so the paged requirement checks the effective
        # geometry — and stored into cfg so the model dispatches on
        # it. XLA stays the default and the automatic fallback
        # recommendation in every rejection message.
        self.decode_kernel = _resolve_decode_kernel(
            decode_kernel, self.cfg, _mesh_tp(mesh))
        self.cfg = dataclasses.replace(self.cfg,
                                       decode_kernel=self.decode_kernel)
        _DECODE_KERNEL.set(_DECODE_KERNEL_CODE[self.decode_kernel])
        # Probe cache for decode_kernel_hlo_stats (one AOT compile).
        self._kernel_probe_cache: Optional[Dict[str, Any]] = None
        # int8 block pool (the paged x int8-KV composition): the HBM
        # win multiplies — the pool holds ~(fp_bytes x head_dim) /
        # (head_dim + 4) times the tokens per byte on top of paged's
        # tokens-held (not slots x max_seq_len) scaling.
        self.paged_int8_bytes_saved = 0
        if self.paged_block_size and self.cfg.kv_cache_quant == 'int8':
            self.paged_int8_bytes_saved = \
                kv_cache_lib.int8_pool_bytes_saved(
                    self.cfg.paged_num_blocks, self.paged_block_size,
                    self.cfg.num_kv_heads, self.cfg.head_dim,
                    self.cfg.num_layers,
                    jnp.dtype(self.cfg.dtype).itemsize)
            _PAGED_INT8_SAVED.set(self.paged_int8_bytes_saved)
        # -------- async decode pipeline (docs/performance.md) --------
        # async_depth=N ⇒ a RING of up to N in-flight decode
        # dispatches: a tick queues the next step off the newest one's
        # in-graph device feed BEFORE it waits on any result (JAX async
        # dispatch queues them back to back); copy_to_host_async lands
        # the oldest while the device computes the rest, and all host
        # work — deadlines, queue purge, admission, _emit, metrics —
        # overlaps device compute. The ring rides through churn: a slot
        # whose finish the host can foresee (max_new_tokens, the
        # window) is left out of later dispatches while its last step
        # is still pending (_spent); a slot that joins has its first
        # token and position laid over the feed on the device
        # (_join_feed), and that token is landed after the step is
        # queued. What cannot be foreseen — EOS, a deadline kill, a
        # cancellation, a preemption — is detected up to N steps late
        # and its columns discarded by request identity (causally
        # masked stale cache, same argument as speculative rejects).
        # Speculative engines still flush around every spec tick and
        # sample first tokens on the host. 1 is the default; 0 =
        # synchronous ticks. Deeper rings pay where one host round-trip
        # spans several device steps; they also multiply EOS-overshoot
        # waste (docs/performance.md: when deeper lookahead pays).
        self.async_depth = max(0, async_depth)
        # Decode-tick block-table cache (see _tick): rebuilt only when
        # the per-slot fingerprint changes.
        self._table_sig: Optional[tuple] = None
        self._table_cache = None
        # Device-resident decode feed: every dispatch returns, IN
        # GRAPH, the next step's (tokens, positions) so a steady-state
        # tick feeds the device from the device — no np.asarray on the
        # critical path, no host→device re-upload of tokens/positions.
        # `sig` keys each row of the feed to the host state it
        # predicts ((req.seq, next_pos + inflight) for a slot the
        # dispatch carried, None otherwise): a dispatch takes the feed
        # when every row it carries matches. With the ring up a joining
        # slot's row is written on the device (_join_feed); a
        # synchronous engine rebuilds the feed from host lists instead.
        # Temps change only with slot occupancy, so they cache under
        # their own value signature (the _table_sig pattern). Steady
        # state uploads nothing (pinned by test).
        self._feed: Optional[tuple] = None          # (tok, pos, sig)
        self._temps_sig: Optional[tuple] = None
        self._temps_cache = None
        # Lookahead ring: dispatched-but-unconsumed decode steps,
        # oldest first (≤ async_depth after each tick consumes one).
        self._ring: 'collections.deque[_Inflight]' = collections.deque()
        # First tokens sampled on the device this tick and not landed
        # yet: (the tick's slot table, slot, request, device scalar).
        # Landed after the tick's decode step is queued (_land_joins);
        # empty between ticks.
        self._joins: list = []
        # A spec tick drafts from host tokens and emits in its own tick,
        # so a speculative engine keeps the host-side first token.
        self._join_ahead = self.async_depth > 0 and speculative <= 0
        self.tick_stats = {'dispatches': 0, 'chained': 0, 'flushes': 0}
        self._prefix_entries = self._new_prefix_index()
        # Cached routing-digest header value, keyed on (index identity,
        # index epoch) — see prefix_digest().
        self._digest_cache: Optional[tuple] = None
        # -------- disaggregated serving (docs/serving.md) --------
        # tier labels this engine's role in a disaggregated fleet:
        # 'prefill' computes KV and streams it out (prefill_prefix +
        # export_prefix_chunks), 'decode' assembles incoming streams
        # into its own pool (ingest_chunk) so handed-off requests admit
        # as full-prefix cache hits, 'monolithic' (default) does both
        # phases locally. The tier is routing metadata — the engine
        # surface is identical — but the specialized tiers REQUIRE the
        # paged pool + prefix cache (block identity is the handoff
        # unit).
        if tier not in ENGINE_TIERS:
            raise ValueError(f'unknown engine tier {tier!r}; expected '
                             f'one of {ENGINE_TIERS}')
        if tier != 'monolithic' and not (self.paged_block_size and
                                         self.prefix_cache):
            raise ValueError(
                f'tier={tier!r} requires paged_block_size and '
                f'prefix_cache (KV streams are block-granular and land '
                f'in the prefix index)')
        self.tier = tier
        self._ingest_ttl = max(1.0, ingest_ttl)
        self._ingest_lock = threading.Lock()
        self._ingest_sessions: Dict[str, _IngestSession] = {}
        self._ingest_meta: Optional[list] = None
        self._ingest_elems: Optional[list] = None
        self.ingest_stats = {'streams_completed': 0,
                             'streams_aborted': 0, 'streams_expired': 0,
                             'chunks_ok': 0, 'chunks_duplicate': 0,
                             'chunks_rejected': 0, 'chunks_shed': 0,
                             'blocks_ingested': 0}
        # Work items needing exclusive access to the device pool tree
        # (handoff gathers, ingest finalizes) run in the engine tick
        # thread between dispatches — see _run_in_tick.
        self._engine_work: 'collections.deque' = collections.deque()
        self.model = Transformer(self.cfg)
        self._rng = jax.random.PRNGKey(rng_seed)
        self._recurrent = self.cfg.has_recurrent_state
        # -------- dropless expert layers (models/moe.py) --------
        # A routed model's programs return one more output, the routing
        # counts of the call summed over its expert layers
        # (moe.ROUTE_COUNTS); it rides to the host with the step's
        # tokens (`_Inflight.counts`), never with a wait of its own, and
        # is summed here by the kind of program that counted it:
        # route_stats['decode'] / ['chunk'] = [calls, *ROUTE_COUNTS].
        self._routed = (self.cfg.is_moe
                        and self.cfg.moe_impl == 'dropless')
        self._mutable = ['cache'] + (['moe_stats'] if self._routed
                                     else [])
        self.route_stats = {kind: [0] * (1 + len(moe_lib.ROUTE_COUNTS))
                            for kind in ('decode', 'chunk')}
        self._counts_pending: list = []
        # Which rows of a dispatch are real tokens: a recurrent state
        # must not advance over the others, a router must not route
        # them.
        # -------- generation by diffusion over blocks --------
        # block_length B > 0: the engine's one loop runs in block mode.
        # Prefill stops at the prompt's whole blocks and samples
        # nothing; a decode step is one PASS over every slot's current
        # block of B positions (_decode_block_impl), whose next feed is
        # computed in-graph like the autoregressive step's, so the
        # lookahead ring runs unchanged; a slot joins by having its
        # row laid over the device feed (_join_block). A pass emits no
        # token, or the block's new tokens at once.
        self._block = self.cfg.block_length
        # Passes that clear m masks under the model's schedule (one
        # more, the commit pass, writes the clean block's K/V).
        self._clear_passes = [0]
        if self._block:
            unmasked = list(itertools.accumulate(
                self.cfg.unmask_schedule()))
            self._clear_passes += [
                next(n + 1 for n, done in enumerate(unmasked) if done >= m)
                for m in range(1, self._block + 1)]
        self.block_stats = {'block_passes': 0, 'block_commits': 0,
                            'block_tokens': 0,
                            'block_masked_positions': 0}
        self._row_valid = (self._recurrent or self._routed
                           or bool(self._block))
        # Decode-tick valid-row cache (recurrent-state models only; see
        # _valid_for): 1 for a decoding slot, 0 for an inert one.
        self._valid_sig: Optional[tuple] = None
        self._valid_cache = None
        self._kind_bytes: Optional[Dict[str, int]] = None
        # -------- tensor-parallel serving (docs/performance.md) -----
        # mesh with tp>1 (parallel.decode_mesh): weights shard per the
        # SAME logical-axis rules training uses (heads/kv_heads/mlp/
        # vocab → tp), the KV substrate — contiguous cache or paged
        # block pool — splits on the kv-head axis per device, feeds
        # and block tables stay replicated, and XLA inserts the
        # per-layer all-reduce over ICI. Dispatch SHAPES are identical
        # to single-chip, only layouts change, so the async ring /
        # speculative / chunked-prefill paths compose unchanged.
        self._tp = _mesh_tp(self.mesh)
        self._repl = None
        self._per_dev_gauges: list = []
        self._pool_dev_bytes: Optional[int] = None
        # Last decode_hlo_stats() result: the tick re-publishes its
        # gauges (exporters usually enable AFTER engine construction
        # and warmup — a probe-time-only set would read 0 forever, the
        # PR-5 int8-gauge lesson).
        self._hlo_probe_cache: Optional[Dict[str, Any]] = None
        if self._tp > 1:
            # Mesh already validated by _resolve_cfg_and_params.
            self._repl = sharding_lib.replicated(self.mesh)
            self.params = _place_params(self.model, self.cfg,
                                        self.params, self.mesh)
            _TP_SIZE.set(self._tp)
            if self.paged_block_size:
                self._per_dev_gauges = [
                    (_PAGED_USED_PER_DEV.labels(device=str(i)),
                     _POOL_BYTES_PER_DEV.labels(device=str(i)))
                    for i in range(self._tp)]

        # -------- adapter pool state (multi-LoRA serving) --------
        self._adapter_pool: 'Optional[tenancy.AdapterPool]' = None
        self._adapters = None          # device-side stacked A/B tree
        self._adapter_axis = None      # per-leaf slot-axis pytree
        self._aids_sig: Optional[tuple] = None
        self._aids_cache = None
        if self.max_adapters:
            self._adapter_pool = tenancy.AdapterPool(self.max_adapters)
            boxed = _abstract_init(self.model, self.cfg, 1)['adapters']
            shapes = nn.unbox(boxed)
            # Slot axis per leaf, found structurally (scanned layouts
            # carry a leading num_layers axis): the one axis that grows
            # when serve_adapters grows by one.
            probe_cfg = dataclasses.replace(
                self.cfg, serve_adapters=self.max_adapters + 1)
            probe = nn.unbox(_abstract_init(
                Transformer(probe_cfg), probe_cfg, 1)['adapters'])
            self._adapter_axis = jax.tree.map(
                lambda a, b: next(i for i in range(a.ndim)
                                  if a.shape[i] != b.shape[i]),
                shapes, probe)
            # Born zeroed (slot 0 stays zero forever = the identity);
            # replicated under a tp mesh (all-None logical axes) —
            # adapters are tiny next to the weights. boxed/shapes kept
            # for wedge-recovery rebuilds and load-time validation.
            self._adapter_boxed = boxed
            self._adapter_shapes = shapes
            self._adapters = _zeros_from_shapes(
                boxed, self.mesh if self._tp > 1 else None)
            _ADAPTER_SLOTS.set(self.max_adapters)
        # Admission→first-token service EWMA: the deadline-aware
        # admission estimate (None until the first completion — early
        # requests are never shed on a guess).
        self.ttft_estimate: Optional[float] = None
        self.tenancy_stats = {'slot_preempts': 0, 'deadline_sheds': 0,
                              'adapter_sheds': 0}
        # True once any non-'standard' request has been submitted —
        # gates the server's per-response tier-load header (an
        # O(queue) scan a tier-less deployment should never pay).
        self._tiers_active = False

        self._prefill = jax.jit(self._prefill_impl)
        self._prefill_continue = jax.jit(self._prefill_continue_impl)
        self._insert = jax.jit(self._insert_impl,
                               donate_argnames=('cache',))
        # Both decode steps return the NEXT step's device feed in-graph
        # (sampled tokens + advanced positions) — the device-resident
        # feedback loop behind zero-upload ticks and async lookahead.
        self._decode = jax.jit(self._decode_step_impl,
                               donate_argnames=('cache',))
        self._decode_multi = jax.jit(self._decode_multi_feed_impl,
                                     donate_argnames=('cache',))
        self._verify = jax.jit(self._verify_impl,
                               donate_argnames=('cache',))
        self._prefill_chunk_fn = jax.jit(self._prefill_chunk_impl,
                                         donate_argnames=('cache',))
        self._cow_fn = jax.jit(self._cow_copy_impl,
                               donate_argnames=('cache',))
        self._join_feed = jax.jit(self._join_feed_impl)
        self._decode_block = jax.jit(self._decode_block_impl,
                                     donate_argnames=('cache',))
        self._join_block_feed = jax.jit(self._join_block_impl)
        # Adapter slot write: donate the old stack (one device-side
        # dynamic_update_slice per leaf; runs in the tick thread only).
        self._adapter_write = jax.jit(self._adapter_write_impl,
                                      donate_argnames=('adapters',))

        # Tier-ordered admission queue (serve/tenancy/scheduling.py):
        # drop-in queue.Queue — FIFO when every request is 'standard',
        # interactive-first with a deterministic batch starvation floor
        # otherwise.
        self._queue: 'tenancy.TierQueue' = tenancy.TierQueue()
        self._slots: list = [None] * num_slots  # _Request or None
        self._cache = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        # -------- resilience (see docs/resilience.md) --------
        # Admission control: >0 caps queued-not-yet-admitted requests;
        # beyond it submit() raises EngineOverloadedError and the server
        # sheds load with 429/503 + Retry-After instead of letting the
        # queue (and every request's latency) grow without bound.
        self.max_queue_depth = max(0, max_queue_depth)
        # Watchdog: with a timeout set, a monitor thread fails in-flight
        # futures cleanly when the engine thread wedges (a hung device
        # dispatch) or dies, then lets a fresh engine thread take over.
        self.watchdog_timeout = watchdog_timeout
        self._watchdog: Optional[threading.Thread] = None
        self._heartbeat = time_lib.monotonic()
        # Bumped by watchdog recovery; an abandoned engine thread
        # notices the mismatch and exits without touching shared state.
        self._generation = 0
        self._draining = False
        # False until the current engine thread completes its first
        # tick: that tick JIT-compiles the decode program, which can
        # legitimately take far longer than a steady-state tick, so the
        # watchdog widens its allowance until then. _admitting_tick
        # extends the same allowance to any tick that admitted a
        # request: a new prompt-length bucket prefill also compiles.
        self._warm_tick = False
        self._admitting_tick = False
        # (decode_step, frozenset(active slot ids)) history — lets tests
        # assert that requests really interleaved. Chunked-prefill work
        # logs as ('prefill', frozenset({slot})). CAPPED: a serve
        # replica ticks for weeks; an unbounded list is a slow leak.
        self.step_log = _StepLog(maxlen=_STEP_LOG_CAP)
        self._decode_steps = 0

    # ---------------- jitted pieces ----------------

    def _cache_bytes_by_kind(self) -> Dict[str, int]:
        """Device bytes of this engine's cache tree by kind: the
        recurrent leaves and the K/V substrate. Read once, from the
        live tree, or from its shapes alone while there is none yet
        (nothing is allocated for it)."""
        if self._kind_bytes is None:
            tree = self._cache
            if tree is None:
                tree = nn.unbox(_abstract_init(
                    self.model, self.cfg, 1 if self.paged_block_size
                    else self.num_slots)['cache'])
            self._kind_bytes = kv_cache_lib.cache_bytes_by_kind(
                ([str(getattr(k, 'key', k)) for k in path],
                 math.prod(leaf.shape) * leaf.dtype.itemsize)
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0])
        return self._kind_bytes

    def _single_cache_shapes(self):
        return jax.eval_shape(
            lambda: self.model.init(
                jax.random.PRNGKey(0), jnp.ones((1, 1), jnp.int32),
                jnp.zeros((1, 1), jnp.int32))['cache'])

    def _init_slot_cache(self) -> Any:
        """Zeroed cache with batch == num_slots (kv-head axis sharded
        per device under a tp mesh)."""
        shapes = jax.eval_shape(
            lambda: self.model.init(
                jax.random.PRNGKey(0),
                jnp.ones((self.num_slots, 1), jnp.int32),
                jnp.zeros((self.num_slots, 1), jnp.int32))['cache'])
        return _zeros_from_shapes(
            shapes, self.mesh if self._tp > 1 else None)

    def _init_paged_cache(self) -> Any:
        """Zeroed BLOCK POOL — batch-free (num_blocks, block, kv_heads,
        head_dim) leaves shared by prefill (batch 1) and decode
        (batch num_slots) dispatches alike. Under a tp mesh every leaf
        (int8 scale rows included — same kv_heads axis) is born split
        on the kv-head dim: each device holds 1/tp of every block, the
        host-side block tables stay replicated."""
        shapes = _abstract_init(self.model, self.cfg, 1)['cache']
        return _zeros_from_shapes(
            shapes, self.mesh if self._tp > 1 else None)

    def _init_cache_for_mode(self) -> Any:
        return (self._init_paged_cache() if self.paged_block_size
                else self._init_slot_cache())

    def _new_prefix_index(self) -> 'kv_cache_lib.PrefixIndex':
        """Prefix LRU keyed by hashable tuple chunks (satellite: lookup
        is O(prompt/chunk) dict probes, not O(entries × prompt) list
        re-comparison). Paged mode chunks at block granularity so a hit
        maps directly onto whole shareable blocks."""
        chunk = self.paged_block_size or self._MIN_PREFIX
        return kv_cache_lib.PrefixIndex(
            capacity=max(1, self.prefix_cache), chunk=chunk)

    def _variables(self, params, cache, adapters):
        """Apply-time variable collections: the 'adapters' stack rides
        along only on multi-LoRA engines (None otherwise, keeping the
        jit signatures of adapter-less engines unchanged)."""
        variables = {'params': params, 'cache': cache}
        if adapters is not None:
            variables['adapters'] = adapters
        return variables

    def _adapter_write_impl(self, adapters, one, slot):
        """Write ONE adapter's weight tree into stack slot `slot`
        across every 'adapters' leaf (the slot axis varies per leaf —
        scanned layouts carry a leading num_layers axis — so it is
        resolved structurally at engine construction)."""

        def write(full, leaf, axis):
            start = [jnp.zeros((), jnp.int32)] * full.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(
                full, jnp.expand_dims(leaf, axis).astype(full.dtype),
                tuple(start))

        return jax.tree.map(write, adapters, one, self._adapter_axis)

    def _prefill_impl(self, params, tokens, true_len, adapters=None,
                      aids=None):
        """tokens: (1, bucket) right-padded; returns (logits at token
        true_len-1, a fresh batch-1 cache holding the prompt KV)."""
        cache1 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            nn.unbox(self._single_cache_shapes()),
            is_leaf=lambda x: hasattr(x, 'shape'))
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
        # Recurrent state: the bucket's right pads must not advance it.
        rows = ((None, jnp.reshape(true_len, (1,)))
                if self._row_valid else None)
        logits, mutated = self.model.apply(
            self._variables(params, cache1, adapters), tokens, positions,
            adapter_ids=aids, state_rows=rows, mutable=self._mutable)
        last = jax.lax.dynamic_index_in_dim(logits, true_len - 1, axis=1,
                                            keepdims=False)
        return (last[0], nn.unbox(mutated['cache'])) + self._riding(
            self._route_counts(mutated))

    def _prefill_continue_impl(self, params, cache1, tokens, start_pos,
                               suffix_true_len, adapters=None,
                               aids=None):
        """Prefix-cache continuation: `cache1` already holds KV for
        positions [0, start_pos); process the (1, bucket) right-padded
        suffix at positions [start_pos, start_pos+bucket). Positional
        masking makes this exactly equivalent to prefilling the whole
        prompt (same invariants as _prefill_impl's pad region)."""
        positions = start_pos + jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
        rows = ((None, jnp.reshape(suffix_true_len, (1,)))
                if self._routed else None)
        logits, mutated = self.model.apply(
            self._variables(params, cache1, adapters), tokens, positions,
            adapter_ids=aids, state_rows=rows, mutable=self._mutable)
        last = jax.lax.dynamic_index_in_dim(logits, suffix_true_len - 1,
                                            axis=1, keepdims=False)
        return (last[0], nn.unbox(mutated['cache'])) + self._riding(
            self._route_counts(mutated))

    def _insert_impl(self, cache, cache1, slot):
        """Copy a batch-1 prefilled cache into slot `slot` of the big
        cache. Leaf ranks vary (KV payload (B,S,KV,D), int8-KV scales
        (B,S,KV), each optionally with a leading scanned-layers axis),
        so the batch axis is found structurally: the one axis where the
        full cache (num_slots) and the batch-1 cache differ."""

        def ins(full, one):
            axis = next((i for i in range(full.ndim)
                         if full.shape[i] != one.shape[i]), None)
            if axis is None:
                # num_slots == 1: the single slot IS the whole cache.
                return one
            start = [jnp.zeros((), jnp.int32)] * full.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(full, one, tuple(start))

        return jax.tree.map(ins, cache, cache1)

    def _decode_impl(self, params, cache, tokens, positions, temps, rng,
                     tables=None, adapters=None, aids=None, valid=None):
        """One all-slots decode tick WITH in-jit sampling (one host sync
        per tick instead of one per slot). tokens/positions:
        (num_slots, 1); temps: (num_slots,) — <=0 means greedy. `tables`
        (paged mode only): per-row block tables for the shared pool.
        `aids` (multi-LoRA only): per-slot adapter-slot indices — THE
        mixed-adapter batching mechanism (one dispatch, many
        tenants). `valid` (recurrent-state models only): (num_slots,)
        1 for a decoding slot, 0 for an empty or prefilling one, whose
        recurrent state the step must leave bit for bit — unlike K and
        V, it has no scratch block to absorb an inert row's write — and
        which an expert layer neither routes nor counts. Returns
        (tokens, cache, routing counts or None)."""
        logits, mutated = self.model.apply(
            self._variables(params, cache, adapters), tokens, positions,
            block_tables=tables, adapter_ids=aids,
            state_rows=None if valid is None else (None, valid),
            mutable=self._mutable)
        last = logits[:, -1, :].astype(jnp.float32)
        greedy = jnp.argmax(last, axis=-1)
        scaled = apply_logit_filters(
            last / jnp.maximum(temps, 1e-6)[:, None],
            self.top_k, self.top_p)
        sampled = jax.random.categorical(rng, scaled, axis=-1)
        out = jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32)
        return (out, nn.unbox(mutated['cache']),
                self._route_counts(mutated))

    @staticmethod
    def _route_counts(mutated):
        """moe.ROUTE_COUNTS of one apply, summed over its expert layers
        (every leaf of 'moe_stats' is (layers, counts)); None for a
        model that counts nothing."""
        leaves = jax.tree.leaves(mutated.get('moe_stats', {}))
        if not leaves:
            return None
        return sum(jnp.sum(leaf.reshape(-1, leaf.shape[-1]), axis=0)
                   for leaf in leaves)

    @staticmethod
    def _riding(counts) -> tuple:
        """The one more output of a routed model's program; nothing
        for any other, whose programs stay what they were."""
        return () if counts is None else (counts,)

    def _decode_multi_impl(self, params, cache, tokens, positions, temps,
                           rngs, tables=None, adapters=None, aids=None,
                           valid=None):
        """K all-slots decode steps in one dispatch (K = rngs' leading
        dim): returns ((num_slots, K) tokens, cache, routing counts
        of the K steps or None). tokens/positions:
        (num_slots,). Paged mode: the engine pre-allocates blocks to
        cover all K positions, so `tables` stays fixed across the
        scan."""

        def body(carry, rng):
            cache, toks, pos = carry
            out, cache, counts = self._decode_impl(
                params, cache, toks[:, None], pos[:, None], temps, rng,
                tables, adapters, aids, valid)
            return (cache, out, pos + 1), (out, counts)

        (cache, _, _), (toks, counts) = jax.lax.scan(
            body, (cache, tokens, positions), rngs)
        return (toks.swapaxes(0, 1), cache,
                None if counts is None else jnp.sum(counts, axis=0))

    def _decode_step_impl(self, params, cache, tokens, positions, temps,
                          rng, tables=None, adapters=None, aids=None,
                          valid=None):
        """One all-slots step from 1-D feed arrays; returns
        ((num_slots, 1) emit columns, the NEXT step's (tokens,
        positions) feed, cache). The feed is computed in-graph — the
        sampled tokens become the next input and positions advance by
        +1 on device — so a steady-state tick never round-trips either
        through the host. Inert rows (empty/prefilling slots) ride
        along with advancing positions: their writes clamp into
        harmless cache (contiguous: their own row, overwritten whole by
        the next _insert; paged: the scratch block) and are never
        read."""
        out, cache, counts = self._decode_impl(
            params, cache, tokens[:, None], positions[:, None], temps,
            rng, tables, adapters, aids, valid)
        out = self._repl_constrain(out)
        return (out[:, None],
                (out, self._repl_constrain(positions + 1)),
                cache) + self._riding(counts)

    def _decode_multi_feed_impl(self, params, cache, tokens, positions,
                                temps, rngs, tables=None, adapters=None,
                                aids=None, valid=None):
        """K-step variant of _decode_step_impl (K = rngs' leading dim):
        ((num_slots, K) columns, next feed, cache)."""
        toks, cache, counts = self._decode_multi_impl(
            params, cache, tokens, positions, temps, rngs, tables,
            adapters, aids, valid)
        toks = self._repl_constrain(toks)
        return (toks, (toks[:, -1],
                       self._repl_constrain(positions + rngs.shape[0])),
                cache) + self._riding(counts)

    def _repl_constrain(self, x):
        """Pin an in-graph feed/emit array to REPLICATED under a tp
        mesh: the feedback loop (sampled tokens + advanced positions
        re-entering the next dispatch) must present the same sharding
        as a host-built feed (_upload with self._repl), or the first
        chained dispatch would compile a second program and every
        host↔chain alternation would reshard. No-op single-chip."""
        if self._tp <= 1:
            return x
        return jax.lax.with_sharding_constraint(x, self._repl)

    def _prefill_chunk_impl(self, params, cache, tokens, tables, start,
                            true_n, adapters=None, aids=None, slot=None):
        """One chunked-prefill step on the PAGED pool: process the
        (1, prefill_chunk) right-padded chunk at positions
        [start, start+chunk) through the slot's block table. The chunk
        shape is FIXED, so exactly one prefill program compiles per
        engine — vs one per power-of-two prompt bucket on the contiguous
        path (pinned by tests/test_paged_cache.py). Returns (logits at
        chunk token true_n-1 — only meaningful on the final chunk — and
        the updated pool); only that row goes through the unembedding
        (head_rows): the (width, vocab) logits array and its slice
        measured 6-15% of the chunk program on the v5e. Pad-token
        writes land in the request's last private block, where later
        real writes overwrite them, in the scratch block behind
        unmapped table entries, or clip into the table's scratch column
        (same stale-entry masking argument as _prefill_impl). `slot`
        (recurrent-state models only) names the row of the state leaves
        this request owns: the chunk reads it (a zero state when start
        is 0), advances it over the true_n real positions only — a pad
        that advanced a scan state could not be masked afterwards — and
        writes it back."""
        positions = start + jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
        rows = None if slot is None else (jnp.reshape(slot, (1,)),
                                          jnp.reshape(true_n, (1,)))
        # Block mode prefills a prompt's whole blocks and samples
        # nothing: the chunk stops before the final norm and the head.
        logits, mutated = self.model.apply(
            self._variables(params, cache, adapters), tokens, positions,
            mode='hidden' if self._block else 'full',
            block_tables=tables, adapter_ids=aids,
            head_rows=jnp.reshape(true_n - 1, (1,)), state_rows=rows,
            mutable=self._mutable)
        return (logits[0, 0], nn.unbox(mutated['cache'])) + self._riding(
            self._route_counts(mutated))

    def _cow_copy_impl(self, cache, src, dst):
        """Copy-on-write: clone physical block `src` into `dst` across
        every pool leaf. Used at admission when a request extends a
        cached prefix whose last block is PARTIAL: the shared block
        stays read-only for everyone else; this request appends into its
        private copy. Pool leaves are (*, num_blocks, block, kv_heads,
        head_dim) with an optional leading scanned-layers axis, so the
        block axis is always ndim-4."""

        def cp(arr):
            axis = arr.ndim - 4
            blk = jax.lax.dynamic_slice_in_dim(arr, src, 1, axis=axis)
            return jax.lax.dynamic_update_slice_in_dim(arr, blk, dst,
                                                       axis=axis)

        return jax.tree.map(cp, cache)

    def _join_feed_impl(self, tokens, positions, logits_row, temp, rng,
                        where):
        """Lay a joining slot over the device feed: sample its first
        token from the last prefill logits (as `_sample` does on the
        host: argmax, or a filtered categorical draw above temperature
        0) and write token and position into row where[0] of the
        (tokens, positions) the next decode step is fed with; where[1]
        is the position. Returns (first token, tokens, positions). The
        first token never visits the host on its way into the step: the
        step is queued behind this program and the token is landed
        after it. One small program an engine, run on joining ticks
        only; the decode program keeps its signature."""
        scaled = apply_logit_filters(
            logits_row.astype(jnp.float32) / jnp.maximum(temp, 1e-6),
            self.top_k, self.top_p)
        first = jnp.where(temp <= 0, jnp.argmax(logits_row),
                          jax.random.categorical(rng, scaled)
                          ).astype(jnp.int32)
        slot = where[0]
        return (self._repl_constrain(first),
                self._repl_constrain(tokens.at[slot].set(first)),
                self._repl_constrain(positions.at[slot].set(where[1])))

    def _block_fed(self, tokens, is_masked):
        """The ids a pass feeds the model: the mask token wherever the
        FLAG says masked (never by comparing ids: a prompt may hold the
        mask token's id, and a model may sample it)."""
        return jnp.where(is_masked, jnp.int32(self.cfg.mask_token_id),
                         tokens)

    def _decode_block_impl(self, params, cache, feed, tables, valid):
        """One PASS over every slot's current block (block mode's decode
        step; `decode` stays in the name, the benchmark's readers find
        the program by it). feed = (tokens (S, B), masked (S, B) 0/1,
        start (S,) the block's first position, step (S,) the pass
        number within the block, given (S,) how many leading positions
        the prompt's tail filled, upass (S, B) the pass that unmasked
        each position, -1 for given ones). valid (S,) 1 for a slot the
        pass carries: an inert row routes nowhere and counts nowhere.

        The pass forwards the B positions under the block-causal mask
        (earlier blocks' clean K/V through the table, the block's own
        keys as they stand now, written at their own positions: nothing
        reads them but this block, and its commit pass overwrites them),
        takes x0 = argmax and its probability at every masked position,
        and unmasks the schedule's count of the most confident masked
        ones (ties to the lower position). A pass over a block with no
        mask left is its COMMIT: it has written the clean block's K/V,
        and the next feed is the next block, all masks.

        Returns (out (S, 2B + 2) int32: the block's new tokens in
        position order | the pass that unmasked each | how many of them
        to emit, non-zero on the pass that clears the last mask | masks
        before the pass (0 marks a commit); the next feed; the cache)
        and a routed model's counts."""
        cfg = self.cfg
        b = cfg.block_length
        tokens, masked, start, step, given, upass = feed
        idx = jnp.arange(b, dtype=jnp.int32)
        positions = start[:, None] + idx[None, :]
        is_masked = masked > 0
        logits, mutated = self.model.apply(
            self._variables(params, cache, None),
            self._block_fed(tokens, is_masked), positions,
            block_tables=tables, state_rows=(None, valid * b),
            mutable=self._mutable)
        logits = logits.astype(jnp.float32)
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = jnp.exp(jnp.max(logits, axis=-1)
                       - jax.nn.logsumexp(logits, axis=-1))
        n_masked = jnp.sum(is_masked, axis=-1, dtype=jnp.int32)
        sched = jnp.asarray(cfg.unmask_schedule(), jnp.int32)
        n_take = jnp.minimum(sched[jnp.minimum(step, sched.shape[0] - 1)],
                             n_masked)
        # rank of each masked position by confidence, the larger first
        # and on a tie the lower position: B x B comparisons a row
        conf = jnp.where(is_masked, conf, -1.0)
        mine, other = conf[:, :, None], conf[:, None, :]
        ahead = (other > mine) | ((other == mine)
                                  & (idx[None, None, :] < idx[None, :, None]))
        rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
        take = is_masked & (rank < n_take[:, None])
        tokens = jnp.where(take, x0, tokens)
        upass = jnp.where(take, step[:, None], upass)
        left = (is_masked & ~take).astype(jnp.int32)
        cleared = (n_masked > 0) & (n_masked == n_take)
        commit = n_masked == 0
        roll = (idx[None, :] + given[:, None]) % b
        out = jnp.concatenate([
            jnp.take_along_axis(tokens, roll, axis=1),
            jnp.take_along_axis(upass, roll, axis=1),
            jnp.where(cleared, b - given, 0)[:, None],
            n_masked[:, None]], axis=1)
        new = commit[:, None]
        feed = (jnp.where(new, 0, tokens), jnp.where(new, 1, left),
                jnp.where(commit, start + b, start),
                jnp.where(commit, 0, step + 1),
                jnp.where(commit, 0, given), jnp.where(new, -1, upass))
        return (out, feed, nn.unbox(mutated['cache'])) + self._riding(
            self._route_counts(mutated))

    def _join_block_impl(self, feed, row):
        """Lay a joining slot over the device's block feed. row
        (2B + 3,) int32: the block's tokens | its mask flags | its first
        position, the positions given, the slot."""
        b = self._block
        tokens, masked, start, step, given, upass = feed
        slot = row[2 * b + 2]
        return (tokens.at[slot].set(row[:b]),
                masked.at[slot].set(row[b:2 * b]),
                start.at[slot].set(row[2 * b]), step.at[slot].set(0),
                given.at[slot].set(row[2 * b + 1]),
                upass.at[slot].set(-1))

    def _verify_impl(self, params, cache, tokens, positions, temps, rng,
                     tables=None, adapters=None, aids=None):
        """Speculative verification: ONE forward over (num_slots, K+1)
        chunks [last_token, draft_1..draft_K] at per-row positions.

        Greedy rows (temp<=0): out[:, j] is the model's argmax given the
        drafts up to j; `accepted` = leading drafts matching those
        argmaxes, so emitting out[:, :accepted+1] reproduces token-by-
        token greedy decode EXACTLY — any draft content is safe, wrong
        drafts just get 0 accepted. Sampling rows: accepted forced to 0
        and out[:, 0] is sampled from the first position's logits,
        identical to a normal decode tick. Cache entries written for
        rejected positions sit at-or-after every future query position
        (causal-masked) until the following ticks overwrite them —
        the same stale-entry argument as finished-slot overshoot.

        Paged mode (`tables` given): the multi-token verify reads each
        row's logical KV window through its block table — the same
        gather-then-contiguous-math path chunked prefill uses — and
        the engine pre-reserves blocks covering all K+1 write
        positions, so the verify chunk never writes through an
        unmapped table entry. Rejected drafts roll the block table
        back host-side (_trim_blocks) instead of a contiguous cache
        truncation."""
        logits, mutated = self.model.apply(
            self._variables(params, cache, adapters), tokens, positions,
            block_tables=tables, adapter_ids=aids, mutable=['cache'])
        logits = logits.astype(jnp.float32)        # (B, K+1, V)
        greedy = jnp.argmax(logits, axis=-1)       # (B, K+1)
        match = tokens[:, 1:] == greedy[:, :-1]    # (B, K) draft hits
        accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(
            axis=1)
        accepted = jnp.where(temps <= 0, accepted, 0)
        scaled = apply_logit_filters(
            logits[:, 0, :] / jnp.maximum(temps, 1e-6)[:, None],
            self.top_k, self.top_p)
        sampled0 = jax.random.categorical(rng, scaled, axis=-1)
        first = jnp.where(temps <= 0, greedy[:, 0], sampled0)
        out = greedy.at[:, 0].set(first).astype(jnp.int32)
        return out, accepted, nn.unbox(mutated['cache'])

    # ---------------- scheduler ----------------

    # Backward-scan cap for prompt-lookup drafting: bounds the host-side
    # cost per tick to O(window) regardless of context length (an
    # uncapped scan at 32k tokens costs ~10ms — rivaling the dispatch it
    # tries to save). Repetition useful for drafting is overwhelmingly
    # local.
    _DRAFT_SCAN_WINDOW = 2048

    @classmethod
    def _draft_tokens(cls, context, k: int):
        """Prompt-lookup drafting: find the most recent occurrence of
        the context's trailing n-gram (n = 3, then 2, then 1) within the
        scan window and propose the k tokens that followed it. Returns
        None when nothing matches — the tick then falls back to the
        plain/chunked path instead of burning a known-useless verify
        (filler drafts are SAFE, just pointless: verification only ever
        accepts drafts equal to the model's own greedy choice)."""
        n_ctx = len(context)
        lo = max(0, n_ctx - cls._DRAFT_SCAN_WINDOW)
        for n in (3, 2, 1):
            if n_ctx < n + 1:
                continue
            tail = context[-n:]
            # Scan right-to-left, excluding the trailing n-gram itself.
            # start+n <= n_ctx-1, so `follow` is never empty.
            for start in range(n_ctx - n - 1, lo - 1, -1):
                if context[start:start + n] == tail:
                    follow = context[start + n:start + n + k]
                    return follow + [0] * (k - len(follow))
        return None

    def _spec_tick(self, slots, active, gen: int) -> 'Optional[Any]':
        """One speculative tick: draft K per slot, verify in one
        forward. Returns the (num_slots, <=K+1) emit columns + per-slot
        valid counts, or None when the tick must fall back (a slot too
        close to the cache window)."""
        k = self.speculative
        for i in active:
            req = slots[i]
            if self.cfg.max_seq_len - req.next_pos <= k:
                return None
        if self.paged_block_size:
            # Reserve blocks covering every verify write position
            # (next_pos .. next_pos+k) BEFORE dispatching, so the
            # K+1-token chunk never writes through an unmapped table
            # entry. Pool pressure degrades gracefully: fall back to
            # the plain single-step path this tick.
            try:
                for i in active:
                    self._ensure_blocks(
                        slots[i], min(slots[i].next_pos + k + 1,
                                      self.cfg.max_seq_len))
            except kv_cache_lib.PoolExhaustedError:
                # Roll back whatever the loop DID reserve before it
                # hit the wall: holding unused verify-span blocks
                # would deepen the very exhaustion that forced the
                # single-step fallback.
                for i in active:
                    self._trim_blocks(slots[i])
                return None
        tokens, positions = [], []
        real_draft_slots = set()
        for slot in range(self.num_slots):
            req = slots[slot]
            if req is None:
                tokens.append([0] * (k + 1))
                positions.append([0] * (k + 1))
                continue
            draft = (self._draft_tokens(req.ids + req.tokens, k)
                     if req.temperature <= 0 else None)
            if draft is None:
                draft = [0] * k
            else:
                real_draft_slots.add(slot)
            tokens.append([req.tokens[-1]] + draft)
            positions.append(list(range(req.next_pos,
                                        req.next_pos + k + 1)))
        if not real_draft_slots:
            # Every greedy slot drew a lookup blank: a verify tick would
            # emit 1 token/slot at (K+1)x forward cost — let the
            # plain/chunked path take this round instead (it reserves
            # its own, shallower span — the verify-span blocks go back).
            if self.paged_block_size:
                for i in active:
                    self._trim_blocks(slots[i])
            return None
        temps = [(slots[i].temperature
                  if slots[i] is not None else 0.0)
                 for i in range(self.num_slots)]
        tables = (self._tables_for(slots, set(active))
                  if self.paged_block_size else None)
        self._rng, rng = jax.random.split(self._rng)
        out, accepted, cache = self._verify(
            self.params, self._cache,
            _upload(tokens, jnp.int32, self._repl),
            _upload(positions, jnp.int32, self._repl),
            _upload(temps, jnp.float32, self._repl), rng, tables,
            self._adapters, self._aids_for(slots, set(active)))
        self._commit_gen(gen, lambda: setattr(self, '_cache', cache))
        out_cols = _land(out)
        acc = _land(accepted)
        # Acceptance-rate bookkeeping counts only slots that contributed
        # a real prompt-lookup draft; [0]*k fillers for greedy slots
        # whose n-gram lookup came up empty would inflate the
        # denominator and under-report the true acceptance rate.
        drafted_active = [i for i in active if i in real_draft_slots]
        self.spec_stats['ticks'] += 1
        self.spec_stats['drafted'] += k * len(drafted_active)
        self.spec_stats['accepted'] += int(acc[drafted_active].sum())
        _SPEC_DRAFTED.inc(k * len(drafted_active))
        _SPEC_ACCEPTED.inc(int(acc[drafted_active].sum()))
        if self.paged_block_size:
            _SPEC_PAGED_ACCEPTED.inc(int(acc[drafted_active].sum()))
        valid = acc + 1               # emit accepted drafts + 1 bonus
        return out_cols, valid

    def _ensure_thread(self) -> None:
        import threading
        with self._thread_lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._heartbeat = time_lib.monotonic()
                self._warm_tick = False
                self._thread = threading.Thread(target=self._loop,
                                                daemon=True,
                                                name='cbatch-engine')
                self._thread.start()
            if self.watchdog_timeout and (
                    self._watchdog is None or
                    not self._watchdog.is_alive()):
                self._watchdog = threading.Thread(
                    target=self._watchdog_loop, daemon=True,
                    name='cbatch-watchdog')
                self._watchdog.start()

    # ---------------- watchdog ----------------

    def _busy(self) -> bool:
        return any(r is not None for r in self._slots) or \
            not self._queue.empty()

    def _watchdog_loop(self) -> None:
        """Detects a wedged (no completed tick while work is pending)
        or dead engine thread and recovers: in-flight futures fail with
        a clean EngineWedgedError and the next submit starts a fresh
        engine thread over fresh state."""
        interval = max(0.01, min(self.watchdog_timeout / 4, 1.0))
        while not self._stop.is_set():
            self._stop.wait(interval)
            if self._stop.is_set():
                return
            if not self._busy():
                continue
            thread = self._thread
            if thread is None:
                # Not started yet (fresh engine, or a submit raced a
                # recovery): the cure is the spawn submit() is about
                # to do, not another recovery.
                continue
            dead = not thread.is_alive()
            # 10x allowance while ticks can legitimately be slow:
            # the thread's first tick JIT-compiles the decode program,
            # and any admitting tick may compile a new prompt-bucket
            # prefill. Exotic first-use paths (decode_chunk, spec
            # verify) fall under the first-tick/admitting cases in
            # practice; size watchdog_timeout above worst-case compile
            # regardless.
            slow_ok = (not self._warm_tick) or self._admitting_tick
            allowed = self.watchdog_timeout * (10 if slow_ok else 1)
            stalled = (time_lib.monotonic() - self._heartbeat > allowed)
            if dead or stalled:
                self._recover_from_wedge(
                    'engine thread died' if dead else
                    f'engine thread made no progress in '
                    f'{allowed}s')

    def _recover_from_wedge(self, why: str) -> None:
        import queue as queue_lib
        # Flight-recorder trigger (docs/observability.md "Tracing"):
        # the spans/step_log of the seconds BEFORE the wedge are the
        # postmortem — capture the recovery start before swapping
        # state. active() is enabled-or-flight-dir; off the tick path.
        t_rec = tracing.now() if tracing.active() else 0.0
        with self._thread_lock:
            self._generation += 1
            old_slots = self._slots
            old_queue = self._queue
            # Pending engine-thread work (handoff gathers, ingest
            # finalizes) dies with the generation: the successor must
            # not run it against fresh state.
            old_work = list(self._engine_work)
            self._engine_work.clear()
            self._slots = [None] * self.num_slots
            self._queue = tenancy.TierQueue()
            # The wedged thread may hold (or have donated) the old
            # cache mid-dispatch; the successor re-initializes its own.
            self._cache = None
            # Pipeline state dies with the generation: every in-flight
            # lookahead dispatch in the ring (and any device feed
            # chained off it) belongs to requests that are being
            # failed right here — the successor must never emit or
            # chain from any of them. (The stale thread also re-checks
            # generation before emitting, so this is belt and braces.)
            self._ring.clear()
            self._joins = []
            self._counts_pending = []
            _DISPATCH_AHEAD.set(0)
            self._feed = None
            self._temps_sig = None
            self._temps_cache = None
            self._table_sig = None
            self._table_cache = None
            self._aids_sig = None
            self._aids_cache = None
            if self.max_adapters:
                # Adapter pool resets WHOLESALE: residency/refcounts die
                # with the generation (the registry of host weights
                # survives — requests re-load on demand); the device
                # stack rebuilds zeroed, because the stale thread may
                # have donated the old one mid-write. Stale releases go
                # to the old pool object harmlessly.
                self._adapter_pool = self._adapter_pool.fresh()
                self._adapters = _zeros_from_shapes(
                    self._adapter_boxed,
                    self.mesh if self._tp > 1 else None)
                _ADAPTER_RESIDENT.set(0)
            if self.paged_block_size:
                # Fresh pool/prefix objects (not clears): the abandoned
                # thread keeps mutating ITS objects harmlessly, same
                # isolation pattern as the slots/queue swap above.
                self._pool = kv_cache_lib.BlockPool(
                    self.cfg.paged_num_blocks, self.paged_block_size)
                self._prefix_entries = self._new_prefix_index()
                # Pre-warmed entries died with the pool.
                self._prewarmed_keys = set()
            self._thread = None
            self._heartbeat = time_lib.monotonic()
        logger.error('engine watchdog: %s; failing in-flight requests '
                     'and resetting engine state (generation %d)', why,
                     self._generation)
        _WEDGE_RECOVERIES.inc()
        if tracing.active():
            tracing.record_span(
                'engine.wedge_recovery', t_rec, tracing.now(),
                attrs={'why': why, 'generation': self._generation})
            extra = self._flight_extra(why)
            # The postmortem wants the WEDGED world, not the freshly
            # swapped empty one.
            extra['active_slots'] = [i for i, r in enumerate(old_slots)
                                     if r is not None]
            extra['queue_depth'] = old_queue.qsize()
            tracing.flight_record('wedge_recovery', extra=extra)
        err = exceptions.EngineWedgedError(
            f'{why}; request aborted by the engine watchdog')
        for _fn, future in old_work:
            if not future.done():
                future.set_exception(err)
        for req in old_slots:
            if req is not None:
                self._fail_request(req, err)
        while True:
            try:
                req = old_queue.get_nowait()
            except queue_lib.Empty:
                break
            self._fail_request(req, err)

    @staticmethod
    def _release_adapter(req: '_Request') -> None:
        """Drop the request's adapter pin, exactly once, into the POOL
        OBJECT the pin was taken against (a wedge recovery swaps the
        engine's pool; stale releases land in the old object
        harmlessly)."""
        pool = req.adapter_pool
        if pool is not None:
            req.adapter_pool = None
            if req.adapter is not None:
                pool.release(req.adapter)

    def _fail_request(self, req: '_Request', exc: BaseException) -> None:
        _REQ_FAILED.inc()
        self._release_adapter(req)
        if not req.future.done():
            req.future.set_exception(exc)
        self._notify(req, None)

    # ---------------- tracing hooks (docs/observability.md "Tracing") -
    #
    # Every hook guards on `req.trace is None` (an identity check) so
    # an untraced request — and the whole engine while tracing is
    # disabled — pays no span allocation and no clock reads on the
    # tick path (pinned by tests/test_tracing.py). Spans are recorded
    # AFTER the fact from monotonic stamps the request already
    # carries, coalesced per request: queue-wait (submit→admit),
    # prefill (admit→first token, chunked or bucketed), decode (first
    # token→finish, slot-labeled) — never one span per tick per slot.

    def _trace_admitted(self, req: '_Request') -> None:
        if req.trace is None:
            return
        req.admit_time = tracing.now()
        tracing.record_span('engine.queue_wait', req.submit_time,
                            req.admit_time, parent=req.trace,
                            attrs={'prompt_tokens': len(req.ids)})

    def _note_first_token(self, req: '_Request', slot: int) -> None:
        """First-token bookkeeping shared by the bucketed and chunked
        prefill paths: TTFT histograms (global + per-tier), the
        admission→first-token service EWMA behind deadline-aware
        admission, and the prefill trace span. A preemption
        CONTINUATION (first_token_time already set) records nothing —
        its TTFT was the original one."""
        if req.first_token_time is not None:
            return
        now = time_lib.monotonic()
        req.first_token_time = now
        ttft = now - req.submit_time
        _TTFT_HIST.observe(ttft,
                           exemplar=req.trace.trace_id
                           if req.trace is not None else None)
        _TIER_TTFT_HIST.labels(tier=req.tier).observe(ttft)
        if req.admit_mono is not None:
            service = now - req.admit_mono
            self.ttft_estimate = (
                service if self.ttft_estimate is None
                else 0.2 * service + 0.8 * self.ttft_estimate)
        self._trace_first_token(req, slot)

    def _trace_first_token(self, req: '_Request', slot: int) -> None:
        if req.trace is None:
            return
        tracing.record_span(
            'engine.prefill', req.admit_time or req.submit_time,
            req.first_token_time, parent=req.trace,
            attrs={'slot': slot, 'prompt_tokens': len(req.ids),
                   'chunks': req.prefill_chunks,
                   'ttft_s': round(
                       req.first_token_time - req.submit_time, 6)})

    def _trace_finished(self, req: '_Request', slot: int,
                        now: float) -> None:
        if req.trace is None or req.first_token_time is None:
            return
        attrs = {'slot': slot, 'new_tokens': len(req.tokens)}
        if self._block:
            attrs.update(passes=req.passes_done,
                         blocks=self._block_of(req, req.passes_done - 1)
                         + 1)
        tracing.record_span('engine.decode', req.first_token_time, now,
                            parent=req.trace, attrs=attrs)

    def _flight_extra(self, why: str) -> dict:
        """Engine state for a flight record: the step_log tail + tick
        stats that show what the engine was doing in the seconds
        before the trigger (frozensets rendered JSON-safe)."""
        return {
            'why': why,
            'tier': self.tier,
            'generation': self._generation,
            'decode_steps': self._decode_steps,
            'tick_stats': dict(self.tick_stats),
            # seconds per `engine.tick.*` phase since tracing came on
            # (empty with tracing off): which phase the wedge sits in
            'phase_totals': tracing.phase_totals(),
            'active_slots': [i for i, r in enumerate(self._slots)
                             if r is not None],
            'queue_depth': self._queue.qsize(),
            'step_log': [[step, sorted(slots)]
                         for step, slots in list(self.step_log)[-200:]],
        }

    def _check_gen(self, gen: int) -> None:
        if self._generation != gen:
            raise _StaleEngineError()

    def _commit_gen(self, gen: int, fn) -> None:
        """Run a shared-state write (cache/slot commit) atomically with
        the generation check: _recover_from_wedge swaps state under the
        same lock, so a stale thread can never interleave a commit
        between the successor's check and write — it raises
        _StaleEngineError and exits instead."""
        with self._thread_lock:
            self._check_gen(gen)
            fn()

    def _sample(self, logits_row, temperature: float) -> int:
        # Prefill-time first-token sampling: a once-per-request host
        # sync, paid at admission (never in the steady decode loop) —
        # the landings route through the audited _land funnel.
        if temperature <= 0:
            return int(_land(jnp.argmax(logits_row)))
        self._rng, rng = jax.random.split(self._rng)
        scaled = apply_logit_filters(
            logits_row.astype(jnp.float32) / max(temperature, 1e-6),
            self.top_k, self.top_p)
        return int(_land(jax.random.categorical(rng, scaled)))

    def _first_token(self, slots, slot: int, req: '_Request',
                     logits_row, position: int) -> None:
        """A prompt's last prefill logits are in: seed the request's
        first token and its decode position. With the ring up the
        token is sampled on the device and laid over the decode feed
        (`_join_feed`), so the tick can queue the next decode step
        before it waits for it; `_land_joins` hands it to the client
        later in the same tick. A synchronous or speculative engine
        samples and lands it here."""
        req.next_pos = position
        req.inflight = 0
        if not self._join_ahead:
            self._deliver_first(req, slot,
                                self._sample(logits_row, req.temperature))
            return
        rng = self._rng     # a greedy row never reads it
        if req.temperature > 0:
            self._rng, rng = jax.random.split(self._rng)
        feed = self._feed
        if feed is None:
            blank = _upload([0] * self.num_slots, jnp.int32, self._repl)
            feed = (blank, blank, (None,) * self.num_slots)
        first, tok, pos = self._join_feed(
            feed[0], feed[1], logits_row,
            _upload(req.temperature, jnp.float32, self._repl), rng,
            _upload([slot, position], jnp.int32, self._repl))
        first.copy_to_host_async()
        sig = feed[2][:slot] + ((req.seq, position),) + feed[2][slot + 1:]
        self._feed = (tok, pos, sig)
        self._joins.append((slots, slot, req, first))

    def _prefill_total(self, req: '_Request') -> int:
        """Positions a request's prefill covers: its context, or in
        block mode the context's whole blocks (the tail opens the first
        generated block)."""
        n = len(req.context)
        return n - n % self._block if self._block else n

    def _join_block(self, slots, slot: int, req: '_Request') -> None:
        """A prompt's whole blocks are prefilled (block mode; there is
        no first token to sample): plan the request's passes and lay
        its first block over the device feed, the prompt's tail and
        masks behind it. The plan is exact, so `_spent` foresees the
        finish: a block of m masks is `_clear_passes[m]` denoising
        passes and a commit, and the last block needs no commit."""
        b = self._block
        p0 = self._prefill_total(req)
        tail = req.context[p0:]
        left = req.max_new_tokens - len(req.tokens)
        blocks = -(-(len(tail) + left) // b)
        req.block0 = p0
        req.first_passes = self._clear_passes[b - len(tail)] + 1
        req.passes_total = (req.first_passes + (blocks - 1)
                            * (self._clear_passes[b] + 1) - 1)
        req.passes_done = req.inflight = 0
        # as after an autoregressive prefill: the position of the last
        # token the request holds, so that `_emit` ends it at the window
        req.next_pos = len(req.context) - 1
        feed = self._feed
        if feed is None:
            zeros = lambda *shape: _upload(
                np.zeros((self.num_slots,) + shape, np.int32),
                sharding=self._repl)
            feed = ((zeros(b), zeros(b), zeros(), zeros(), zeros(),
                     zeros(b)), None, (None,) * self.num_slots)
        row = (tail + [0] * (b - len(tail)) + [0] * len(tail)
               + [1] * (b - len(tail)) + [p0, len(tail), slot])
        state = self._join_block_feed(
            feed[0], _upload(row, jnp.int32, self._repl))
        sig = (feed[2][:slot] + (self._feed_key(req),)
               + feed[2][slot + 1:])
        self._feed = (state, None, sig)

    def _block_of(self, req: '_Request', n: int) -> int:
        """Which of the request's blocks (from 0) its pass `n` works
        on."""
        if n < req.first_passes:
            return 0
        return 1 + (n - req.first_passes) // (
            self._clear_passes[self._block] + 1)

    def _deliver_first(self, req: '_Request', slot: int,
                       first: int) -> None:
        self._note_first_token(req, slot)
        req.tokens.append(first)
        _TOKENS_TOTAL.inc()  # the first token lands here, not in _emit
        self._notify(req, first)

    def _land_joins(self, gen: int) -> None:
        """Land the first tokens this tick sampled on the device and
        send each to its client: after the tick's decode step is
        queued, or before anything that reads a request's tokens on the
        host (a flush, a rebuild of the feed)."""
        if not self._joins:
            return
        joins, self._joins = self._joins, []
        with tracing.phase('engine.tick.land'):
            landed = [int(_land(first)) for *_, first in joins]
        # The wait above may span a watchdog recovery: never emit into
        # a successor's world.
        self._check_gen(gen)
        with tracing.phase('engine.tick.emit'):
            for (slots, slot, req, _), first in zip(joins, landed):
                if slots[slot] is req:  # its admission held
                    self._deliver_first(req, slot, first)

    def _bucket(self, length: int) -> int:
        bucket = 16
        while bucket < length:
            bucket *= 2
        return min(bucket, self.cfg.max_seq_len)

    # Prefixes shorter than this are cheaper to re-prefill than to
    # match + continue (one extra jit specialization per suffix bucket).
    _MIN_PREFIX = 16

    def _longest_cached_prefix(self, ids: list):
        """(prefix_len, payload) of the best LRU entry that is a prefix
        of `ids`, or (0, None). An exact-length hit reuses all but the
        last token (the suffix must be non-empty to produce logits).
        Chunk-trie lookup: O(prompt/chunk) probes, not a full re-compare
        per entry (kv_cache.PrefixIndex; work counted in
        _prefix_entries.last_compares)."""
        return self._prefix_entries.lookup(ids, len(ids) - 1)

    def _store_prefix(self, ids: list, cache1) -> None:
        # Displaced contiguous payloads are batch-1 device caches with
        # no other owner — dropping the reference frees them. Evicted
        # keys lose pre-warmed credit: the same prefix re-inserted by a
        # local prefill is no longer the import's doing.
        for key, _payload in self._prefix_entries.put(ids, cache1):
            self._prewarmed_keys.discard(key)

    # ---------------- paged-KV host bookkeeping ----------------

    def _alloc_block(self) -> int:
        """Allocate one pool block, evicting prefix-LRU entries under
        pressure. Eviction only DEREFS: a block shared with an active
        slot stays alive until its refcount hits 0 (kv_cache.BlockPool),
        so evicting the LRU can never corrupt in-flight requests."""
        try:
            return self._pool.alloc()
        except kv_cache_lib.PoolExhaustedError:
            while len(self._prefix_entries):
                popped = self._prefix_entries.pop_lru()
                if popped is None:
                    break
                _key, blocks = popped
                self._pool.release(blocks)
                self.paged_stats['prefix_evictions'] += 1
                if self._pool.free:
                    return self._pool.alloc()
            raise

    def _ensure_blocks(self, req: '_Request', upto_pos: int) -> None:
        """Grow the request's block table to cover positions
        [0, upto_pos) — lazy allocation, clamped to the logical
        window."""
        bs = self.paged_block_size
        need = min(-(-upto_pos // bs), self._blocks_per_seq)
        while len(req.blocks) < need:
            req.blocks.append(self._alloc_block())

    def _release_blocks(self, req: '_Request') -> None:
        """Return a finished/failed request's block refs to the pool
        (shared prefix blocks survive via the prefix entry's refs)."""
        if self._pool is None or not req.blocks:
            return
        self._pool.release(req.blocks)
        req.blocks = []

    def _table_array(self, reqs) -> jnp.ndarray:
        """(len(reqs), blocks_per_seq + 1) int32 block tables. Unmapped
        logical blocks — and the extra last column that absorbs
        clipped pad-token writes — point at the scratch block (0).
        `None` rows (empty/prefilling slots in a decode tick) are all
        scratch."""
        width = self._blocks_per_seq + 1
        table = np.zeros((len(reqs), width), np.int32)
        for row, req in enumerate(reqs):
            if req is not None and req.blocks:
                table[row, :len(req.blocks)] = req.blocks
        return _upload(table, sharding=self._repl)

    def _trim_blocks(self, req: '_Request') -> None:
        """Roll the block table back after a speculative tick: rejected
        drafts' tail blocks (allocated to cover the K+1 verify span but
        holding only causally-masked stale writes) return to the pool
        NOW instead of riding the request to completion — the paged
        analogue of the contiguous path's implicit cache truncation.
        Keeps the block holding the next write position, so steady
        acceptance never thrashes alloc/free. Trimmed blocks are always
        private suffix blocks (published prefix entries cover at most
        ceil(len(ids)/bs) ≤ ceil(next_pos/bs) blocks), so the decref
        frees them outright."""
        keep = -(-(req.next_pos + 1) // self.paged_block_size)
        while len(req.blocks) > keep:
            self._pool.decref(req.blocks.pop())
            self.paged_stats['spec_trimmed_blocks'] += 1

    def _tables_for(self, slots, active_set) -> jnp.ndarray:
        """Per-slot block tables for a dispatch, cached under the
        block-id fingerprint (tables only change at admission/finish/
        block growth — steady-state ticks reuse the device array
        instead of rebuilding + re-uploading it). Shared by the decode
        and speculative-verify dispatch paths."""
        sig = tuple(
            tuple(slots[i].blocks) if i in active_set else None
            for i in range(self.num_slots))
        if sig != self._table_sig:
            self._table_cache = self._table_array(
                [slots[i] if i in active_set else None
                 for i in range(self.num_slots)])
            self._table_sig = sig
        return self._table_cache

    def _aids_for(self, slots, active_set):
        """Per-slot adapter-slot index vector for an all-slots dispatch
        (multi-LoRA engines only; None otherwise so adapter-less jit
        signatures stay unchanged). Cached under a value signature the
        way temps are — steady-state ticks re-use the device array.
        Inert rows read slot 0 (the identity); their outputs are never
        consumed."""
        if not self.max_adapters:
            return None
        sig = tuple(
            slots[i].adapter_slot
            if i in active_set and slots[i] is not None else 0
            for i in range(self.num_slots))
        if sig != self._aids_sig:
            self._aids_cache = _upload(list(sig), jnp.int32, self._repl)
            self._aids_sig = sig
        return self._aids_cache

    def _valid_for(self, active_set):
        """(num_slots,) int32, 1 for each decoding slot (recurrent-state
        and routed models only; None otherwise so other models' jit
        signatures stay unchanged). Cached under the active set, which changes
        only with slot churn: steady-state ticks upload nothing."""
        if not self._row_valid:
            return None
        sig = tuple(sorted(active_set))
        if sig != self._valid_sig:
            self._valid_cache = _upload(
                [int(i in active_set) for i in range(self.num_slots)],
                jnp.int32, self._repl)
            self._valid_sig = sig
        return self._valid_cache

    def _aids_single(self, req: '_Request'):
        """(1,) adapter-index vector for a batch-1 prefill dispatch."""
        if not self.max_adapters:
            return None
        return _upload([req.adapter_slot], jnp.int32, self._repl)

    def _admit_paged(self, slot: int, req: '_Request',
                     gen: int = -1) -> None:
        """Paged admission: CHEAP — attach shared prefix blocks
        (incref), copy-on-write the partial boundary block, and mark the
        request as prefilling. The prompt itself prefills chunk by chunk
        across subsequent ticks (_prefill_tick), so a long prompt never
        stalls in-flight slots for more than one chunk."""
        if gen >= 0:
            # Same guard as _prefill_tick: a watchdog-abandoned thread
            # must not incref/alloc against its SUCCESSOR's fresh pool
            # (or donate the successor's cache through _cow_fn).
            self._check_gen(gen)
        # Adapter requests bypass the prefix cache (adapter-dependent
        # KV — see _admit); base-model requests share blocks as before.
        use_prefix = self.prefix_cache and req.adapter_slot == 0
        plen, entry = (self._longest_cached_prefix(req.context)
                       if use_prefix else (0, None))
        if plen < self._MIN_PREFIX:
            plen, entry = 0, None
        bs = self.paged_block_size
        blocks: list = []
        if entry is not None:
            full = plen // bs
            for block in entry[:full]:
                self._pool.incref(block)
            blocks.extend(entry[:full])
            # Visible on the request from here on, so the admission
            # failure handler can release them if the CoW dispatch
            # fails mid-way (same list object; the dst append below
            # flows through).
            req.blocks = blocks
            cow = 0
            if plen % bs:
                # The boundary block is shared read-only AND partially
                # filled: clone it so this request can append. If the
                # pool is exhausted, UNDO the increfs above before
                # re-raising — the shed path never sees req.blocks, so
                # leaked refs would shrink the pool permanently.
                try:
                    dst = self._alloc_block()
                except kv_cache_lib.PoolExhaustedError:
                    self._pool.release(blocks)
                    blocks.clear()   # shed path must not double-release
                    raise
                pool_arr = self._cow_fn(
                    self._cache,
                    _upload(entry[full], jnp.int32, self._repl),
                    _upload(dst, jnp.int32, self._repl))
                if gen >= 0:
                    self._commit_gen(
                        gen, lambda: setattr(self, '_cache', pool_arr))
                else:
                    self._cache = pool_arr
                blocks.append(dst)
                cow = 1
            self.paged_stats['blocks_reused'] += full
            self.paged_stats['cow_copies'] += cow
            _PAGED_REUSED.inc(full)
            if cow:
                _PAGED_COW.inc()
            self.prefix_stats['hits'] += 1
            self.prefix_stats['tokens_reused'] += plen
            _PREFIX_HIT.inc()
            _PREFIX_TOKENS.inc(plen)
            if self._prefix_entries.last_key in self._prewarmed_keys:
                self.prefix_stats['prewarm_hits'] += 1
                _PREFIX_PREWARM_HIT.inc()
        elif use_prefix:
            self.prefix_stats['misses'] += 1
            _PREFIX_MISS.inc()
        req.blocks = blocks
        req.prefill_pos = plen
        req.next_pos = plen
        req.prefilling = True

        def _commit():
            self._slots[slot] = req

        if gen >= 0:
            self._commit_gen(gen, _commit)
        else:
            _commit()
        if self._block and not self._prefill_total(req):
            # shorter than a block: nothing to prefill
            req.prefilling = False
            self._join_block(self._slots, slot, req)

    def _store_prefix_paged(self, req: '_Request') -> None:
        """Publish the freshly prefilled prompt's blocks as a shared
        prefix: ceil(L/block_size) ref-counted blocks — NOT a full
        max_seq_len cache (the HBM waste the paged layout removes).
        Adapter requests never publish (adapter-dependent KV — see
        _admit)."""
        if not self.prefix_cache or req.adapter_slot != 0:
            return
        num = -(-len(req.context) // self.paged_block_size)
        blocks = list(req.blocks[:num])
        for block in blocks:
            self._pool.incref(block)
        displaced = self._prefix_entries.put(req.context, blocks)
        for key, old_blocks in displaced:
            self._pool.release(old_blocks)
            # Same prefix re-inserted later by a local prefill must
            # not keep crediting the import in the prewarm-hit metric.
            self._prewarmed_keys.discard(key)

    def _prefill_tick(self, slots, prefilling, gen: int) -> None:
        """Advance every mid-prefill slot by ONE fixed-size chunk. The
        final chunk's logits seed the first sampled token (TTFT) and
        flip the slot to decoding; the prompt's blocks publish to the
        prefix LRU."""
        self._check_gen(gen)  # don't let a stale thread leak blocks
                              # from a successor's pool
        for slot in prefilling:
            req = slots[slot]
            total = self._prefill_total(req)
            start = req.prefill_pos
            n = min(self.prefill_chunk, total - start)
            try:
                # Blocks for the REAL tokens only: the chunk's pad
                # positions fall on unmapped table entries, which
                # point at the scratch block (_table_array).
                self._ensure_blocks(req, start + n)
            except kv_cache_lib.PoolExhaustedError:
                slots[slot] = None
                self._release_blocks(req)
                self._fail_request(req, exceptions.EngineOverloadedError(
                    'KV block pool exhausted mid-prefill; request shed '
                    '(size paged_num_blocks to the load)'))
                continue
            chunk = req.context[start:start + n] + \
                [0] * (self.prefill_chunk - n)
            logits, pool_arr, *counts = self._prefill_chunk_fn(
                self.params, self._cache,
                _upload([chunk], jnp.int32, self._repl),
                self._table_array([req]),
                _upload(start, jnp.int32, self._repl),
                _upload(n, jnp.int32, self._repl),
                self._adapters, self._aids_single(req),
                _upload(slot, jnp.int32, self._repl)
                if self._row_valid else None)
            self._queue_counts('chunk', 1, counts)
            self._commit_gen(gen,
                             lambda: setattr(self, '_cache', pool_arr))
            req.prefill_pos = start + n
            req.prefill_chunks += 1
            self.paged_stats['prefill_chunks'] += 1
            self.paged_stats['prefill_tokens'] += n
            if self._recurrent:
                self.paged_stats['scan_positions'] += self.prefill_chunk
                self.paged_stats['scan_tokens'] += n
            _CHUNKED_PREFILL.inc()
            _CHUNKED_PREFILL_TOKENS.inc(n)
            self.step_log.append(('prefill', frozenset([slot])))
            if req.prefill_pos >= total:
                req.prefilling = False
                if self._block:
                    self._join_block(slots, slot, req)
                    continue
                self._store_prefix_paged(req)
                self._first_token(slots, slot, req, logits, total)

    # ------------- multi-LoRA adapter pool (serve/tenancy) -------------

    def _require_adapter_pool(self) -> 'tenancy.AdapterPool':
        if self._adapter_pool is None:
            raise exceptions.UnknownAdapterError(
                'this engine has no adapter pool (serve with '
                '--max-adapters N)')
        return self._adapter_pool

    def _validate_adapter_tree(self, tree):
        """Shape/structure-check one adapter's weight tree against the
        model's adapter layout (stack leaves minus the slot axis);
        returns the tree as numpy leaves."""

        class _ShapeMismatch(ValueError):
            """Our own shape verdict — already self-explanatory, so it
            passes through the layout-context wrapper below (which
            exists for jax's raw structure-mismatch errors)."""

        def check(full, axis, leaf):
            want = full.shape[:axis] + full.shape[axis + 1:]
            arr = np.asarray(leaf)
            if tuple(arr.shape) != tuple(want):
                raise _ShapeMismatch(
                    f'adapter leaf shape {tuple(arr.shape)} != expected '
                    f'{tuple(want)}')
            return arr

        try:
            return jax.tree.map(check, self._adapter_shapes,
                                self._adapter_axis, tree)
        except _ShapeMismatch:
            raise
        except Exception as e:
            raise ValueError(
                f'adapter tree does not match the model\'s adapter '
                f'layout (targets {self.cfg.lora_targets!r}, rank '
                f'{self.cfg.lora_rank}): {e}') from e

    def _ensure_resident(self, name: str, pin: bool) -> int:
        """Make `name` resident (device write in the tick thread via
        _run_in_tick — never racing the donation-cycled decode), with
        `pin` taking a refcount for a request about to queue. Fast-path:
        an already-resident adapter pins under the pool lock alone."""
        pool = self._require_adapter_pool()
        if pin:
            slot = pool.pin_if_resident(name)
            if slot is not None:
                return slot

        def load(gen):
            t0 = tracing.now() if tracing.enabled() else 0.0
            # Chaos seam: an armed fault here is a load dying between
            # acquire and the device write (docs/resilience.md).
            fault_injection.point('tenant.adapter_load')
            slot, host, evicted = pool.acquire_for_load(name, pin=pin)
            try:
                if evicted is not None:
                    # LRU victim left residency to free this slot.
                    fault_injection.point('tenant.evict')
                    _ADAPTER_EVICTIONS.inc()
                if host is not None:
                    one = jax.tree.map(
                        lambda leaf: _upload(leaf, None, self._repl),
                        host)
                    new = self._adapter_write(
                        self._adapters, one,
                        _upload(slot, jnp.int32, self._repl))
                    self._commit_gen(
                        gen, lambda: setattr(self, '_adapters', new))
                    _ADAPTER_LOADS.inc()
            except BaseException:
                # The residency map must never claim weights that did
                # not land (and a failed load must not leak its pin):
                # roll back, then surface the error. On a stale-
                # generation abort `pool` may already be the OLD
                # object — rolling it back is harmless.
                if host is not None:
                    pool.abort_load(name, pinned=pin)
                raise
            _ADAPTER_RESIDENT.set(len(pool.resident_names()))
            if tracing.enabled():
                tracing.record_span(
                    'engine.adapter_load', t0, tracing.now(),
                    attrs={'adapter': name, 'slot': slot,
                           'evicted': evicted or '',
                           'written': host is not None})
            return slot

        return self._run_in_tick(load)

    def load_adapter(self, name: str, adapter_tree) -> int:
        """Register one adapter's weight tree (lora_a/lora_b leaves in
        models/lora layout — tenancy.adapter_tree_from_lora_params
        extracts it from an unmerged LoRA param tree) and make it
        resident. Returns the device slot. Raises
        AdapterPoolExhaustedError when every slot is pinned (the server
        sheds retryably)."""
        pool = self._require_adapter_pool()
        tenancy.validate_adapter_name(name)
        host = self._validate_adapter_tree(adapter_tree)
        pool.register(name, host)
        try:
            return self._ensure_resident(name, pin=False)
        except exceptions.AdapterPoolExhaustedError:
            _ADAPTER_SHED.inc()
            self.tenancy_stats['adapter_sheds'] += 1
            raise

    def unload_adapter(self, name: str) -> None:
        """Unregister an adapter. Refuses (AdapterInUseError → HTTP
        409) while in-flight requests pin it. The vacated device slot
        is NOT zeroed — nothing references it until a later load
        overwrites it."""
        pool = self._require_adapter_pool()

        def drop(gen):
            del gen
            # The explicit-evict chaos seam (docs/resilience.md).
            fault_injection.point('tenant.evict')
            pool.unregister(name)
            _ADAPTER_RESIDENT.set(len(pool.resident_names()))
            return True

        self._run_in_tick(drop)

    def adapters_info(self) -> Dict[str, Any]:
        """Registry/residency snapshot for GET /adapters, /health and
        `serve status` (ADAPTERS column)."""
        if self._adapter_pool is None:
            return {'capacity': 0, 'resident': 0, 'adapters': []}
        info = self._adapter_pool.info()
        return {
            'capacity': self.max_adapters,
            'resident': sum(1 for a in info if a['resident']),
            'adapters': info,
            'stats': dict(self._adapter_pool.stats),
        }

    def tier_load(self) -> Dict[str, int]:
        """Per-SLO-tier load (queued + slotted) — the X-SkyTPU-Tier-
        Load header value the LB's tier-aware routing reads."""
        depths = self._queue.tier_depths()
        for req in self._slots:
            if req is not None:
                tier = req.tier if req.tier in depths else 'standard'
                depths[tier] += 1
        return depths

    def queue_load(self) -> int:
        """Requests this engine is holding right now: queued awaiting
        admission + occupying decode slots. The serve server advertises
        it in-band (X-SkyTPU-Queue-Depth) so the load balancer's
        least-loaded fallback routes on real backlog, not guesses."""
        return (self._queue.qsize() +
                sum(1 for r in self._slots if r is not None))

    def prefix_digest(self) -> Optional[str]:
        """Routing digest of the prefix cache, as the header value the
        server piggybacks on every response (X-SkyTPU-Prefix-Digest):

            v1:<chunk>:<epoch>:<h1>,<h2>,...

        where each h is kv_cache.prefix_route_hash of a chunk-aligned
        prefix of a cached entry (newest first, bounded). None when
        prefix caching is off. Cached per index epoch, so the serving
        hot path re-reads one string; called from HTTP handler threads
        while the engine thread mutates the index, so a torn read is
        possible — it degrades to the last cached (stale) digest, which
        the routing layer is REQUIRED to tolerate anyway."""
        if not self.prefix_cache:
            return None
        index = self._prefix_entries
        epoch = index.epoch
        cached = self._digest_cache
        if cached is not None and cached[0] is index and \
                cached[1] == epoch:
            return cached[2]
        try:
            hashes = index.digest()
        except RuntimeError:
            # Index mutated mid-walk (engine thread admitting): serve
            # the previous digest — staleness is the contract.
            return cached[2] if cached is not None else None
        value = f'v1:{index.chunk}:{epoch}:' + ','.join(hashes)
        self._digest_cache = (index, epoch, value)
        return value

    def paged_occupancy(self) -> Dict[str, Any]:
        """Pool accounting snapshot (the benchmark's readers under
        `perf/` report it; tests pin ceil(L/block_size) prefix-entry
        costs against it)."""
        if not self.paged_block_size:
            return {}
        self.paged_stats.update(self._cache_bytes_by_kind())
        occ = {
            'block_size': self.paged_block_size,
            'blocks_capacity': self._pool.num_blocks,
            'blocks_used': self._pool.used,
            'peak_blocks_used': self._pool.peak_used,
            'prefix_entries': len(self._prefix_entries),
            **self.paged_stats,
            # slots whose recurrent state belongs to a request now
            'state_slots_used': (sum(r is not None for r in self._slots)
                                 if self._recurrent else 0),
            # how often the lookahead engages (tick_stats): decode
            # dispatches, those queued off the device feed while an
            # earlier one was still unconsumed, and ring flushes
            'decode_dispatches': self.tick_stats['dispatches'],
            'decode_chained': self.tick_stats['chained'],
            'ring_flushes': self.tick_stats['flushes'],
        }
        if self._block:
            # block mode, of the passes whose results have landed:
            # row-passes, those that were commits, tokens emitted, and
            # masked positions forwarded (each needed a row of logits)
            occ.update(self.block_stats, block_length=self._block)
        if self._routed:
            # what the dropless expert layers routed, summed over the
            # expert layers and over every decode step / prefill chunk
            # whose tokens have landed (moe.ROUTE_COUNTS; docs/
            # observability.md)
            occ['expert_layers'] = (self.cfg.num_layers
                                    - self.cfg.num_dense_layers)
            occ['experts_held'] = self.cfg.held_experts
            for kind, total in self.route_stats.items():
                occ[f'route_{kind}_calls'] = total[0]
                for name, value in zip(moe_lib.ROUTE_COUNTS, total[1:]):
                    occ[f'route_{kind}_{name}'] = value
        if self._tp > 1 and self._cache is not None:
            # Per-device view: each device holds its kv-head shard of
            # every block, so bytes — not block counts — divide by tp.
            total, per_dev = _tree_bytes(self._cache)
            occ['tp'] = self._tp
            occ['pool_bytes'] = total
            occ['pool_bytes_per_device'] = per_dev
        return occ

    def memory_footprint(self) -> Dict[str, int]:
        """Global and per-device bytes for the weights and the live KV
        substrate (contiguous cache or paged pool). Per-device sums
        each leaf's shard shape under its NamedSharding — the quantity
        the MULTICHIP_serve dryrun pins at ≤ (1/tp + ε) of the
        single-chip footprint. Initializes the cache if no tick ran
        yet; call before serving traffic or while the engine is
        quiescent (same contract as import_prefixes)."""
        if self._cache is None:
            self._cache = self._init_cache_for_mode()
        weight, weight_dev = _tree_bytes(self.params)
        kv, kv_dev = _tree_bytes(self._cache)
        return {
            'tp': self._tp,
            'weight_bytes': weight,
            'weight_bytes_per_device': weight_dev,
            # the whole cache tree: K and V and, for a recurrent-state
            # model, the per-slot state leaves (`state_bytes` of it)
            'kv_bytes': kv,
            'kv_bytes_per_device': kv_dev,
            'state_bytes': self._cache_bytes_by_kind()['state_bytes'],
            'total_bytes': weight + kv,
            'total_bytes_per_device': weight_dev + kv_dev,
        }

    def decode_hlo_stats(self) -> Dict[str, Any]:
        """Compile the all-slots decode step and parse its optimized
        HLO for collectives (parallel/hlo_probe): how many all-reduces
        one tick pays and the bytes they move — the compile-time proxy
        for ICI traffic while the chip is unreachable. Publishes
        skytpu_engine_tp_collectives / skytpu_engine_tp_allreduce_bytes
        and returns the stats dict.

        COST: lower().compile() is the AOT path — it does NOT reuse
        (or populate) the jit dispatch cache, so the first call pays
        one full extra decode-step compile. The result is cached on
        the engine, and callers keep it off the serving path (server
        warmup before ready, tests)."""
        from skypilot_tpu.parallel import hlo_probe
        if self._hlo_probe_cache is not None:
            return self._hlo_probe_cache
        if self._cache is None:
            self._cache = self._init_cache_for_mode()
        tok = _upload([0] * self.num_slots, jnp.int32, self._repl)
        pos = _upload([0] * self.num_slots, jnp.int32, self._repl)
        temps = _upload([0.0] * self.num_slots, jnp.float32, self._repl)
        tables = (self._table_array([None] * self.num_slots)
                  if self.paged_block_size else None)
        with (self.mesh if self.mesh is not None
              else contextlib.nullcontext()):
            compiled = self._decode.lower(
                self.params, self._cache, tok, pos, temps,
                jax.random.PRNGKey(0), tables).compile()
        stats = hlo_probe.collective_stats(compiled.as_text())
        stats['tp'] = self._tp
        self._hlo_probe_cache = stats
        _TP_COLLECTIVES.set(stats['total'])
        _TP_ALLREDUCE_BYTES.set(stats['all_reduce_bytes'])
        return stats

    def fused_bytes_per_step(self) -> int:
        """HBM bytes one fused decode step streams through the pallas
        kernel at the CURRENT pool occupancy (0 on the XLA path /
        contiguous engines) — the skytpu_engine_decode_fused_bytes
        gauge value, re-published per tick."""
        if self.decode_kernel == 'xla' or self._pool is None:
            return 0
        kv_quant = self.cfg.kv_cache_quant == 'int8'
        return paged_attention_lib.fused_hbm_bytes_per_step(
            self._pool.used, self.paged_block_size,
            self.cfg.num_kv_heads, self.cfg.head_dim,
            self.cfg.num_layers,
            1 if kv_quant else jnp.dtype(self.cfg.dtype).itemsize,
            kv_quant)

    def decode_kernel_hlo_stats(self) -> Dict[str, Any]:
        """Compile the all-slots decode step and count the
        scatter/gather op cluster in its optimized HLO
        (parallel/hlo_probe.gather_stats) — the compile-time proxy
        showing the fused pallas call REPLACES the gathered-window
        cluster: a decode_kernel='pallas' engine's program carries
        fewer gather ops than its XLA twin's
        (tests/test_composition_matrix.py builds both and diffs the
        counts).
        Same AOT-compile cost caveat as decode_hlo_stats; cached."""
        from skypilot_tpu.parallel import hlo_probe
        if self._kernel_probe_cache is not None:
            return self._kernel_probe_cache
        if self._cache is None:
            self._cache = self._init_cache_for_mode()
        tok = _upload([0] * self.num_slots, jnp.int32, self._repl)
        pos = _upload([0] * self.num_slots, jnp.int32, self._repl)
        temps = _upload([0.0] * self.num_slots, jnp.float32, self._repl)
        tables = (self._table_array([None] * self.num_slots)
                  if self.paged_block_size else None)
        with (self.mesh if self.mesh is not None
              else contextlib.nullcontext()):
            compiled = self._decode.lower(
                self.params, self._cache, tok, pos, temps,
                jax.random.PRNGKey(0), tables).compile()
        stats = hlo_probe.gather_stats(compiled.as_text())
        stats['decode_kernel'] = self.decode_kernel
        stats['fused_bytes_per_step'] = self.fused_bytes_per_step()
        self._kernel_probe_cache = stats
        return stats

    # ---------------- prefix export / pre-warm (preemption path) -----
    #
    # docs/resilience.md "Preemption lifecycle". Both methods touch the
    # pool tree directly, so they must run while no engine thread is
    # mid-tick: export after drain() (the preemption-notice flow),
    # import before the first request (replacement pre-warm) — the
    # serve server sequences both.

    @staticmethod
    def _block_axis(leaf) -> int:
        """Every pool leaf keeps its block axis at ndim-4 — scanned
        layers prepend a layers dim, int8 scale rows keep a trailing
        singleton (the _cow_copy_impl contract from PR 5)."""
        return leaf.ndim - 4

    def _pool_leaf_meta(self, leaves) -> list:
        out = []
        for leaf in leaves:
            axis = self._block_axis(leaf)
            shape = list(leaf.shape[:axis]) + list(leaf.shape[axis + 1:])
            out.append({'shape': shape, 'dtype': str(leaf.dtype)})
        return out

    def export_prefixes(self, path: str,
                        budget_s: Optional[float] = None,
                        clock=time_lib.monotonic) -> Dict[str, Any]:
        """Serialize the prefix LRU's blocks into a versioned artifact
        at `path` (kv_cache.export_prefixes). `budget_s` bounds the
        gather — under deadline pressure the NEWEST (hottest) prefixes
        export first and the artifact is published partially; a fault
        or kill mid-export publishes nothing (atomic rename).
        Returns the kv_cache stats dict."""
        _refuse_recurrent(self.cfg, 'export_prefixes', _NO_STATE_IN_BLOCKS)
        _refuse_blocks(self.cfg, 'export_prefixes', _NO_BLOCK_SHARING)
        empty = {'exported': 0, 'blocks': 0, 'skipped': 0,
                 'truncated': False, 'path': path}
        if not (self.paged_block_size and self.prefix_cache):
            return dict(empty, reason='prefix export requires '
                        'paged_block_size and prefix_cache')
        if self._cache is None or not len(self._prefix_entries):
            return dict(empty, reason='no cached prefixes')
        leaves, _treedef = jax.tree.flatten(self._cache)
        # One device→host transfer per leaf for the WHOLE export, not
        # per prefix: np.asarray on a pool leaf copies the entire
        # multi-GB pool, and paying that inside the per-prefix gather
        # burns the notice budget after a handful of prefixes. Lazy so
        # a deadline that fires before the first gather pays nothing.
        host_leaves: List[Optional[np.ndarray]] = [None] * len(leaves)

        def gather(blocks):
            # Chaos seam: an armed 'storage.export' fault aborts the
            # export mid-artifact — nothing is published.
            fault_injection.point('storage.export')
            idx = np.asarray(list(blocks), np.int32)
            out = []
            for i, leaf in enumerate(leaves):
                if host_leaves[i] is None:
                    host_leaves[i] = np.asarray(leaf)
                axis = self._block_axis(leaf)
                # Artifact layout: block axis FIRST, whatever its
                # position in the pool leaf (scanned layers prepend a
                # layers dim).
                out.append(np.ascontiguousarray(np.moveaxis(
                    np.take(host_leaves[i], idx, axis=axis), axis, 0)))
            return out

        deadline = clock() + budget_s if budget_s else None
        should_stop = ((lambda: clock() > deadline)
                       if deadline is not None else None)
        with tracing.span('engine.preempt_export',
                          attrs={'budget_s': budget_s}) as sp:
            stats = kv_cache_lib.export_prefixes(
                self._prefix_entries, self._pool, gather, path,
                should_stop=should_stop)
            sp.set_attr('exported', stats['exported'])
            sp.set_attr('blocks', stats['blocks'])
            sp.set_attr('truncated', stats['truncated'])
        _PREFIX_EXPORT_BLOCKS.inc(stats['blocks'])
        logger.info('exported %d prefixes (%d blocks%s) to %s',
                    stats['exported'], stats['blocks'],
                    ', truncated by deadline' if stats['truncated']
                    else '', path)
        return stats

    def import_prefixes(self, path: str) -> Dict[str, Any]:
        """Pre-warm the prefix LRU from an artifact: re-allocate pool
        blocks, scatter the serialized KV into the device pool, rebuild
        index entries, and mark the keys pre-warmed (hits on them count
        toward skytpu_prefix_prewarm_hit_total). Per-prefix corruption
        is skipped; a full pool stops the pre-warm partially; an
        artifact from an incompatible pool (block_size / cache layout)
        raises kv_cache.ArtifactError without mutating anything."""
        _refuse_recurrent(self.cfg, 'import_prefixes', _NO_STATE_IN_BLOCKS)
        _refuse_blocks(self.cfg, 'import_prefixes', _NO_BLOCK_SHARING)
        if not (self.paged_block_size and self.prefix_cache):
            raise ValueError('prefix import requires paged_block_size '
                             'and prefix_cache')
        if self._cache is None:
            self._cache = self._init_cache_for_mode()
        leaves, treedef = jax.tree.flatten(self._cache)
        meta = self._pool_leaf_meta(leaves)
        per_block_elems = [int(np.prod(m['shape'], dtype=np.int64))
                           for m in meta]

        # Scatters are STAGED on host and applied as ONE batched
        # `.at[].set` per leaf: the functional update materializes a
        # full pool-leaf copy on device, so doing it per prefix made
        # pre-warm cost O(prefixes × pool) — directly delaying the
        # replacement's /health-ready flip. Block ids are unique across
        # prefixes (freshly allocated; double-import skips existing),
        # so batching cannot collide.
        pending_idx: List[List[np.ndarray]] = [[] for _ in leaves]
        pending_arr: List[List[np.ndarray]] = [[] for _ in leaves]

        def scatter(blocks, blob):
            idx = np.asarray(list(blocks), np.int32)
            off = 0
            for i in range(len(leaves)):
                dt = np.dtype(leaves[i].dtype)
                count = len(blocks) * per_block_elems[i]
                # Artifact layout is block-axis-first; kept that way
                # until the batched apply below.
                arr = np.frombuffer(blob, dtype=dt, count=count,
                                    offset=off).reshape(
                                        (len(blocks),) +
                                        tuple(meta[i]['shape']))
                pending_idx[i].append(idx)
                pending_arr[i].append(arr)
                off += count * dt.itemsize

        def _apply_staged():
            for i in range(len(leaves)):
                if not pending_idx[i]:
                    continue
                axis = self._block_axis(leaves[i])
                idx = np.concatenate(pending_idx[i])
                arr = np.concatenate(pending_arr[i], axis=0)
                # A later prefix may have re-used block ids an LRU
                # eviction freed mid-import; `.at[].set` with duplicate
                # indices has no defined winner, so keep only the LAST
                # staged write per block id.
                _, first_rev = np.unique(idx[::-1], return_index=True)
                if len(first_rev) != len(idx):
                    keep = np.sort(len(idx) - 1 - first_rev)
                    idx, arr = idx[keep], arr[keep]
                arr = np.moveaxis(arr, 0, axis)
                sel = (slice(None),) * axis + \
                    (_upload(idx, sharding=self._repl),)
                leaves[i] = leaves[i].at[sel].set(
                    _upload(np.ascontiguousarray(arr),
                            sharding=self._repl))

        try:
            stats = kv_cache_lib.import_prefixes(
                path, self._prefix_entries, self._pool, scatter,
                expect_leaves=meta,
                on_prefix=lambda: fault_injection.point('storage.import'))
        finally:
            # Commit whatever was staged even on a mid-import fault:
            # the index/pool already reference those blocks, so the
            # pool tree must hold their data. (A prefix whose fault
            # fired before its scatter ran has no staged writes AND no
            # index entry — nothing leaks.)
            _apply_staged()
            self._cache = jax.tree.unflatten(treedef, leaves)
        self._prewarmed_keys.update(stats['keys'])
        # The import itself can LRU-evict older entries (including
        # previously pre-warmed ones) inside kv_cache.import_prefixes,
        # where this engine cannot observe the eviction — reconcile
        # against the live index so stale keys never inflate the
        # prewarm-hit counter.
        self._prewarmed_keys.intersection_update(
            k for k, _ in self._prefix_entries.entries())
        _PREFIX_PREWARM_BLOCKS.inc(stats['blocks'])
        logger.info(
            'pre-warmed %d prefixes (%d blocks) from %s '
            '(%d corrupt skipped, %d already present%s)',
            stats['imported'], stats['blocks'], path,
            stats['skipped_corrupt'], stats['skipped_existing'],
            ', stopped on full pool' if stats['stopped_pool_full']
            else '')
        return stats

    # ---------- disaggregated prefill/decode handoff (hot path) ------
    #
    # docs/serving.md "Disaggregated serving". The prefill tier computes
    # a prompt's KV into pool blocks (prefill_prefix), serializes them
    # into CRC'd, sequence-numbered chunks (export_prefix_chunks —
    # kv_cache.pack_kv_chunk framing) and the serve layer pushes them to
    # a decode replica's POST /kv/ingest, which assembles them into ITS
    # pool (ingest_chunk) and publishes the prefix entry — the
    # handed-off request then admits there as a full-prefix cache hit
    # (the PR-6 pre-warm path, bit-identity already pinned). Unlike
    # export/import_prefixes — the whole-index, quiesced-engine
    # preemption-RESCUE path — this is incremental and runs on LIVE
    # engines: device access happens in the engine tick thread between
    # dispatches (_run_in_tick), host staging on the caller's thread,
    # and a torn/duplicated/reordered transfer rolls back or dedups
    # instead of poisoning the pool.

    def _run_in_tick(self, fn, timeout: float = 120.0):
        """Run `fn(gen)` inside the engine tick thread (between
        dispatches) and return its result. The decode/prefill jits
        DONATE the cache, so any other thread touching the pool tree
        races the donation cycle — everything device-facing in the
        handoff path funnels through here instead."""
        import concurrent.futures
        future: 'concurrent.futures.Future' = concurrent.futures.Future()
        # Enqueue under _thread_lock: _recover_from_wedge snapshots and
        # clears this deque under the same lock, so the item lands
        # either before the snapshot (and is failed by the recovery) or
        # after the clear (and is served by the successor thread) —
        # never in the gap, where it would be wiped with its future
        # unresolved (the submit()/queue-swap discipline, applied
        # here).
        with self._thread_lock:
            self._engine_work.append((fn, future))
        self._ensure_thread()
        self._wake.set()
        return future.result(timeout=timeout)

    def _drain_engine_work(self, gen: int) -> None:
        """Run queued engine-thread work items. A failing item resolves
        its own future and never kills the tick; a stale-generation
        abort propagates (the thread must exit without touching its
        successor's state)."""
        while self._engine_work:
            try:
                fn, future = self._engine_work.popleft()
            except IndexError:
                return
            try:
                result = fn(gen)
            except _StaleEngineError:
                if not future.done():
                    future.set_exception(exceptions.EngineWedgedError(
                        'engine recovery interrupted the operation'))
                raise
            except BaseException as e:  # pylint: disable=broad-except
                if not future.done():
                    future.set_exception(e)
            else:
                if not future.done():
                    future.set_result(result)

    def _expected_leaf_meta(self) -> list:
        """Per-leaf {shape, dtype} of the pool WITHOUT materializing it
        (ingest validates chunk layout before the first tick ever
        runs)."""
        if self._ingest_meta is None:
            shapes = nn.unbox(_abstract_init(self.model, self.cfg,
                                             1)['cache'])
            leaves = jax.tree.leaves(
                shapes, is_leaf=lambda x: hasattr(x, 'shape'))
            self._ingest_meta = self._pool_leaf_meta(leaves)
            self._ingest_elems = [
                int(np.prod(m['shape'], dtype=np.int64))
                for m in self._ingest_meta]
        return self._ingest_meta

    def prefill_prefix(self, ids, timeout: float = 300.0
                       ) -> Dict[str, Any]:
        """Prefill-tier entry point: compute `ids`' KV into pool blocks
        and publish them to the prefix index (the chunked-prefill path
        a normal admission takes; the single sampled token is
        discarded). Returns {'prompt_tokens', 'ttft_s', 'cached'} —
        cached=False means the index evicted the entry already (storm
        pressure) and a subsequent export will fail retryably."""
        _refuse_recurrent(self.cfg, 'prefill_prefix', _NO_STATE_IN_STREAM)
        _refuse_blocks(self.cfg, 'prefill_prefix', _NO_BLOCK_STREAM)
        ids = [int(t) for t in ids]
        if not (self.paged_block_size and self.prefix_cache):
            raise ValueError('prefill_prefix requires paged_block_size '
                             'and prefix_cache')
        _out, stats = self.generate(ids, max_new_tokens=1,
                                    temperature=0.0, timeout=timeout)
        return {'prompt_tokens': len(ids), 'ttft_s': stats['ttft_s'],
                'cached': tuple(ids) in self._prefix_entries}

    def export_prefix_chunks(self, ids, stream_id: str,
                             chunk_blocks: int = 4,
                             trace_header: Optional[str] = None
                             ) -> List[bytes]:
        """Serialize the cached prefix for exactly `ids` into framed
        handoff chunks (list of packed bytes, seq order). The device
        gather runs in the engine tick thread and reads ONLY the
        prefix's own blocks (a few KB–MB), never the whole pool — this
        is the hot path, not the preemption export. Raises ValueError
        when the prefix is not cached (evicted / never prefilled):
        retryable — the caller re-prefills or falls back monolithic.

        `trace_header` (an X-SkyTPU-Trace value) rides every chunk's
        header so the decode replica's ingest spans join the sender's
        trace (docs/observability.md "Tracing")."""
        _refuse_recurrent(self.cfg, 'export_prefix_chunks', _NO_STATE_IN_STREAM)
        _refuse_blocks(self.cfg, 'export_prefix_chunks', _NO_BLOCK_STREAM)
        if not (self.paged_block_size and self.prefix_cache):
            raise ValueError('export_prefix_chunks requires '
                             'paged_block_size and prefix_cache')
        key = tuple(int(t) for t in ids)
        chunk_blocks = max(1, int(chunk_blocks))

        def gather(gen):
            del gen
            blocks = self._prefix_entries.get(key)
            if not isinstance(blocks, list) or not blocks:
                raise ValueError(
                    'prefix not cached on this replica (evicted or '
                    'never prefilled); retry or fall back monolithic')
            if self._cache is None:
                raise ValueError('engine pool not initialized')
            leaves, _treedef = jax.tree.flatten(self._cache)
            groups = [blocks[i:i + chunk_blocks]
                      for i in range(0, len(blocks), chunk_blocks)]
            out = []
            for grp in groups:
                idx = _upload(list(grp), jnp.int32, self._repl)
                parts = []
                for leaf in leaves:
                    axis = self._block_axis(leaf)
                    sub = jnp.moveaxis(
                        jnp.take(leaf, idx, axis=axis), axis, 0)
                    parts.append(_land(sub).tobytes())
                out.append((len(grp), b''.join(parts)))
            return out, len(blocks)

        payloads, total = self._run_in_tick(gather)
        meta = self._expected_leaf_meta()
        chunks: List[bytes] = []
        start = 0
        for seq, (nblk, payload) in enumerate(payloads):
            final = seq == len(payloads) - 1
            chunks.append(kv_cache_lib.pack_kv_chunk(
                stream_id, seq, start, self.paged_block_size, meta,
                payload, nblk, final=final,
                key=list(key) if final else None,
                total_blocks=total if final else None,
                trace=trace_header))
            start += nblk
            _HANDOFF_EXPORT_CHUNKS.inc()
            _HANDOFF_EXPORT_BYTES.inc(len(payload))
        return chunks

    def _release_session_blocks(self, session: '_IngestSession') -> None:
        try:
            session.pool.release(session.blocks)
        except ValueError:
            # The pool was reset wholesale since these blocks were
            # allocated (wedge recovery / tick-failure reset) — the
            # whole old pool is garbage, nothing to roll back.
            pass
        session.blocks = []
        session.staged_idx = [[] for _ in session.staged_idx]
        session.staged_arr = [[] for _ in session.staged_arr]

    def _rollback_session_locked(self, stream_id: str,
                                 outcome: str) -> None:
        """Drop a session and return its blocks to refcount-0 (the
        pool `check()` invariant the chaos tests pin). Caller holds
        _ingest_lock."""
        session = self._ingest_sessions.pop(stream_id, None)
        if session is None:
            return
        self._release_session_blocks(session)
        key = {'aborted': 'streams_aborted',
               'expired': 'streams_expired'}.get(outcome,
                                                 'streams_aborted')
        self.ingest_stats[key] += 1
        _HANDOFF_INGEST_STREAMS.labels(outcome=outcome).inc()

    def _expire_ingest_sessions_locked(self, now: float) -> None:
        stale = [sid for sid, s in self._ingest_sessions.items()
                 if now - s.touched > self._ingest_ttl]
        for sid in stale:
            logger.warning('ingest stream %s expired after %.0fs '
                           'without a final chunk; rolling back', sid,
                           self._ingest_ttl)
            self._rollback_session_locked(sid, 'expired')

    def abort_ingest(self, stream_id: str) -> bool:
        """Roll a partial handoff stream back to refcount-0 (the LB
        aborts after a prefill replica died mid-stream; the TTL sweep
        catches streams nobody aborts). Idempotent; True iff a session
        existed."""
        with self._ingest_lock:
            present = stream_id in self._ingest_sessions
            self._rollback_session_locked(stream_id, 'aborted')
        return present

    def ingest_chunk(self, data: bytes) -> Dict[str, Any]:
        """Apply one framed handoff chunk to this (decode-tier) engine.

        Robustness contract (unit-pinned in tests/test_disagg.py):
        corrupt chunks raise kv_cache.ChunkError and mutate NOTHING;
        out-of-order chunks raise kv_cache.ChunkSequenceError carrying
        the expected seq; a retried already-applied seq (including the
        final chunk of an already-published stream) is acknowledged
        idempotently without double-allocating; pool pressure sheds
        (EngineOverloadedError → the server's 503 + Retry-After) with
        the partial stream rolled back to refcount-0. The final chunk's
        batched scatter + index publish run in the engine tick thread.
        """
        _refuse_recurrent(self.cfg, 'ingest_chunk', _NO_STATE_IN_STREAM)
        _refuse_blocks(self.cfg, 'ingest_chunk', _NO_BLOCK_STREAM)
        fault_injection.point('engine.ingest')
        if not (self.paged_block_size and self.prefix_cache):
            raise ValueError('KV ingest requires paged_block_size and '
                             'prefix_cache')
        if self._draining:
            with self._ingest_lock:
                self.ingest_stats['chunks_shed'] += 1
            _INGEST_SHED.inc()
            raise exceptions.EngineDrainingError(
                'engine is draining; not accepting KV ingest')
        try:
            header, payload = kv_cache_lib.unpack_kv_chunk(data)
        except kv_cache_lib.ChunkError:
            with self._ingest_lock:
                self.ingest_stats['chunks_rejected'] += 1
            _INGEST_REJECTED.inc()
            raise
        meta = self._expected_leaf_meta()
        if header['block_size'] != self.paged_block_size or \
                kv_cache_lib.leaf_sig(header['leaves']) != \
                kv_cache_lib.leaf_sig(meta):
            with self._ingest_lock:
                self.ingest_stats['chunks_rejected'] += 1
            _INGEST_REJECTED.inc()
            raise kv_cache_lib.ChunkError(
                'chunk layout does not match this engine (block_size / '
                'model config / dtype / kv-quant mismatch)')
        sid, seq = header['stream_id'], int(header['seq'])
        final = bool(header.get('final'))
        # The chunk header carries the SENDER's trace context, so this
        # replica's ingest spans join the same trace as the prefill
        # that produced the blocks (docs/observability.md "Tracing").
        trace_ctx = (tracing.parse_header(header.get('trace'))
                     if tracing.enabled() else None)
        t_chunk = tracing.now() if trace_ctx is not None else 0.0
        now = time_lib.monotonic()
        key: Optional[tuple] = None
        with self._ingest_lock:
            self._expire_ingest_sessions_locked(now)
            session = self._ingest_sessions.get(sid)
            if session is not None and session.pool is not self._pool:
                # A recovery replaced the pool since this stream
                # started; its blocks died with the old pool. Drop the
                # session — the sender's retry restarts from seq 0.
                del self._ingest_sessions[sid]
                session = None
            if session is None:
                if final and tuple(header['key']) in \
                        self._prefix_entries:
                    # Retried final chunk of an already-published
                    # stream: the publish won, ack idempotently.
                    self.ingest_stats['chunks_duplicate'] += 1
                    _INGEST_DUP.inc()
                    return {'ok': True, 'duplicate': True, 'seq': seq}
                if seq != 0:
                    self.ingest_stats['chunks_rejected'] += 1
                    _INGEST_REJECTED.inc()
                    raise kv_cache_lib.ChunkSequenceError(0, seq)
                # Decode-side admission gate: a NEW stream must leave
                # headroom for at least one full-depth request beyond
                # itself — shed (the server maps this to 503 +
                # Retry-After) rather than let ingest starve live
                # decode slots and corrupt under pressure.
                floor = self._blocks_per_seq
                if self._pool.free < int(header['num_blocks']) + floor:
                    self.ingest_stats['chunks_shed'] += 1
                    _INGEST_SHED.inc()
                    raise exceptions.EngineOverloadedError(
                        f'KV pool pressure: {self._pool.free} free '
                        f'blocks cannot admit a new handoff stream '
                        f'(need chunk + {floor} headroom)')
                session = _IngestSession(sid, self._pool, now,
                                         len(meta))
                self._ingest_sessions[sid] = session
            if seq < session.next_seq:
                session.touched = now
                self.ingest_stats['chunks_duplicate'] += 1
                _INGEST_DUP.inc()
                return {'ok': True, 'duplicate': True, 'seq': seq}
            if seq > session.next_seq:
                self.ingest_stats['chunks_rejected'] += 1
                _INGEST_REJECTED.inc()
                raise kv_cache_lib.ChunkSequenceError(session.next_seq,
                                                      seq)
            if int(header['start_block']) != len(session.blocks):
                # seq matches but the block offset does not: the stream
                # is incoherent — abort it wholesale.
                self._rollback_session_locked(sid, 'aborted')
                self.ingest_stats['chunks_rejected'] += 1
                _INGEST_REJECTED.inc()
                raise kv_cache_lib.ChunkError(
                    f'chunk start_block {header["start_block"]} does '
                    f'not match the {len(session.blocks)} blocks '
                    f'assembled so far')
            blocks: list = []
            try:
                for _ in range(int(header['num_blocks'])):
                    blocks.append(self._pool.alloc())
            except kv_cache_lib.PoolExhaustedError as e:
                self._pool.release(blocks)
                self._rollback_session_locked(sid, 'aborted')
                self.ingest_stats['chunks_shed'] += 1
                _INGEST_SHED.inc()
                raise exceptions.EngineOverloadedError(
                    f'KV pool exhausted mid-ingest: {e}') from e
            idx = np.asarray(blocks, np.int32)
            off = 0
            try:
                for i in range(len(meta)):
                    dt = np.dtype(meta[i]['dtype'])
                    count = len(blocks) * self._ingest_elems[i]
                    arr = np.frombuffer(
                        payload, dtype=dt, count=count,
                        offset=off).reshape(
                            (len(blocks),) + tuple(meta[i]['shape']))
                    session.staged_idx[i].append(idx)
                    session.staged_arr[i].append(arr)
                    off += count * dt.itemsize
            except ValueError as e:
                # CRC passed but the payload length disagrees with the
                # declared num_blocks — incoherent, abort the stream.
                self._pool.release(blocks)
                self._rollback_session_locked(sid, 'aborted')
                self.ingest_stats['chunks_rejected'] += 1
                _INGEST_REJECTED.inc()
                raise kv_cache_lib.ChunkError(
                    f'chunk payload does not match the pool layout: '
                    f'{e}') from e
            session.blocks.extend(blocks)
            session.next_seq = seq + 1
            session.chunks += 1
            session.bytes += len(payload)
            session.touched = now
            if final:
                if int(header['total_blocks']) != len(session.blocks):
                    self._rollback_session_locked(sid, 'aborted')
                    self.ingest_stats['chunks_rejected'] += 1
                    _INGEST_REJECTED.inc()
                    raise kv_cache_lib.ChunkError(
                        f'stream assembled {len(session.blocks)} '
                        f'blocks but the final chunk declares '
                        f'{header["total_blocks"]}')
                del self._ingest_sessions[sid]
                key = tuple(int(t) for t in header['key'])
            self.ingest_stats['chunks_ok'] += 1
        _INGEST_OK.inc()
        if trace_ctx is not None:
            tracing.record_span(
                'engine.ingest_chunk', t_chunk, tracing.now(),
                parent=trace_ctx,
                attrs={'stream': sid, 'seq': seq,
                       'blocks': int(header['num_blocks'])})
        if not final:
            return {'ok': True, 'seq': seq}

        # Final chunk: ONE batched scatter per leaf + index publish,
        # in the engine tick thread (exclusive pool access; the
        # import_prefixes staging pattern applied per stream).
        def apply(gen):
            if self._cache is None:
                self._cache = self._init_cache_for_mode()
            leaves, treedef = jax.tree.flatten(self._cache)
            for i in range(len(leaves)):
                axis = self._block_axis(leaves[i])
                bidx = np.concatenate(session.staged_idx[i])
                arr = np.concatenate(session.staged_arr[i], axis=0)
                arr = np.moveaxis(arr, 0, axis)
                sel = (slice(None),) * axis + \
                    (_upload(bidx, sharding=self._repl),)
                leaves[i] = leaves[i].at[sel].set(
                    _upload(np.ascontiguousarray(arr),
                            sharding=self._repl))
            cache = jax.tree.unflatten(treedef, leaves)

            def commit():
                if session.pool is not self._pool:
                    # The pool was reset between assembly and apply
                    # (tick-failure path keeps the generation): these
                    # blocks no longer exist — publishing would poison
                    # the successor pool.
                    raise exceptions.EngineWedgedError(
                        'engine recovered mid-ingest; stream lost')
                self._cache = cache
                displaced = self._prefix_entries.put(
                    key, list(session.blocks))
                for old_key, old_blocks in displaced:
                    self._pool.release(old_blocks)
                    self._prewarmed_keys.discard(old_key)
                # Hits on an ingested entry count toward the prewarm
                # metric: same semantics — TTFT served from KV this
                # replica never computed.
                self._prewarmed_keys.add(key)

            self._commit_gen(gen, commit)
            return True

        import concurrent.futures
        t_pub = tracing.now() if trace_ctx is not None else 0.0
        try:
            self._run_in_tick(apply)
        except BaseException as e:
            with self._ingest_lock:
                if not isinstance(e, (TimeoutError,
                                      concurrent.futures.TimeoutError)):
                    # Definitive failure: the apply never committed —
                    # roll the blocks back to refcount-0. A TIMEOUT is
                    # different: the apply may still be queued/running
                    # and could yet publish these blocks, so releasing
                    # them here would corrupt the pool; the watchdog's
                    # wholesale pool reset is the recovery path for a
                    # genuinely stalled tick thread.
                    self._release_session_blocks(session)
                self.ingest_stats['streams_aborted'] += 1
            _HANDOFF_INGEST_STREAMS.labels(outcome='aborted').inc()
            raise
        imported = len(session.blocks)
        with self._ingest_lock:
            self.ingest_stats['streams_completed'] += 1
            self.ingest_stats['blocks_ingested'] += imported
        _HANDOFF_INGEST_STREAMS.labels(outcome='completed').inc()
        _HANDOFF_INGEST_BLOCKS.inc(imported)
        if trace_ctx is not None:
            tracing.record_span(
                'engine.ingest_publish', t_pub, tracing.now(),
                parent=trace_ctx,
                attrs={'stream': sid, 'blocks': imported,
                       'key_tokens': len(key)})
        return {'ok': True, 'seq': seq, 'final': True,
                'imported_blocks': imported,
                'key_tokens': len(key)}

    def _admit(self, slot: int, req: '_Request', gen: int = -1) -> None:
        req.admit_mono = time_lib.monotonic()
        self._trace_admitted(req)
        if self.paged_block_size:
            self._admit_paged(slot, req, gen)
            return
        # `context` == ids, except for a preemption continuation where
        # the already-generated tokens fold in (prefill resumes the
        # stream exactly where the preempted slot stopped).
        context = req.context
        true_len = len(context)
        # Adapter requests bypass the prefix cache: cached KV was
        # computed under SOME adapter's k/v projections (v is a default
        # LoRA target), so sharing it across adapter identities would
        # silently break per-adapter bit-identity. Base-model requests
        # (slot 0) keep the full prefix-cache behavior.
        use_prefix = self.prefix_cache and req.adapter_slot == 0
        plen, pcache = (self._longest_cached_prefix(context)
                        if use_prefix else (0, None))
        if plen >= self._MIN_PREFIX and \
                plen + self._bucket(true_len - plen) <= \
                self.cfg.max_seq_len:
            # Continue from the cached prefix: only the suffix prefills.
            suffix = context[plen:]
            bucket = self._bucket(len(suffix))
            tokens = _upload([suffix + [0] * (bucket - len(suffix))],
                             jnp.int32, self._repl)
            logits, cache1, *counts = self._prefill_continue(
                self.params, pcache, tokens,
                _upload(plen, jnp.int32, self._repl),
                _upload(len(suffix), jnp.int32, self._repl),
                self._adapters, self._aids_single(req))
            self.prefix_stats['hits'] += 1
            self.prefix_stats['tokens_reused'] += plen
            _PREFIX_HIT.inc()
            _PREFIX_TOKENS.inc(plen)
        else:
            bucket = self._bucket(true_len)
            padded = context + [0] * (bucket - true_len)
            tokens = _upload([padded], jnp.int32, self._repl)
            logits, cache1, *counts = self._prefill(
                self.params, tokens,
                _upload(true_len, jnp.int32, self._repl),
                self._adapters, self._aids_single(req))
            if use_prefix:
                self.prefix_stats['misses'] += 1
                _PREFIX_MISS.inc()
        self._queue_counts('chunk', 1, counts)
        if gen >= 0:
            self._check_gen(gen)
        if use_prefix:
            # The full prompt's KV is the entry future prompts extend
            # (chat turns append); cache1 is not donated anywhere, so
            # holding it is safe.
            self._store_prefix(context, cache1)
        req.prefill_chunks += 1
        cache = self._insert(self._cache, cache1,
                             _upload(slot, jnp.int32, self._repl))

        def _commit():
            self._cache = cache
            self._slots[slot] = req

        if gen >= 0:
            self._commit_gen(gen, _commit)
        else:
            _commit()
        self._first_token(self._slots, slot, req, logits, true_len)

    @staticmethod
    def _notify(req: '_Request', token) -> None:
        """Streaming callback, guarded: a consumer error (closed HTTP
        connection) must not kill the engine loop."""
        if req.on_token is None:
            return
        try:
            req.on_token(token)
        except Exception:  # pylint: disable=broad-except
            logger.exception('on_token callback failed')
            req.on_token = None

    def _finish(self, slots, slot: int) -> None:
        req = slots[slot]
        slots[slot] = None
        # Paged: return block refs; blocks shared with a prefix entry
        # stay alive (refcount > 0), private suffix blocks free now.
        self._release_blocks(req)
        # The adapter pin drops with the request: a refcount-0 resident
        # becomes an eviction candidate again.
        self._release_adapter(req)
        now = time_lib.monotonic()
        stats = {
            'ttft_s': req.first_token_time - req.submit_time,
            'total_s': now - req.submit_time,
            'new_tokens': len(req.tokens),
            'prompt_tokens': len(req.ids),
        }
        if self._block:
            # the pass of its block that unmasked each token, and what
            # max_new_tokens (or EOS) cut off the last block: a replay
            # of a pass needs the whole block
            n = len(req.tokens)
            over = len(req.unmask_pass) - n
            stats.update(
                passes=req.passes_done,
                unmask_pass=req.unmask_pass[:n],
                overshoot_tokens=req.last_cols[len(req.last_cols) - over:]
                if over else [],
                overshoot_pass=req.unmask_pass[n:])
        # Decode span BEFORE the future resolves: a caller that
        # snapshots the ring the moment generate() returns must see
        # the request's complete span set.
        self._trace_finished(req, slot, now)
        if not req.future.done():
            # done() here means the caller cancelled (shed a partially
            # submitted batch) — the result has no reader, so it must
            # not count as a delivered 'ok' either.
            _REQ_OK.inc()
            if len(req.tokens) > 1:
                # Per-request mean inter-token latency: decode span
                # over tokens after the first (chunked/speculative
                # ticks emit several tokens per dispatch, so per-token
                # deltas within a tick would read as ~0 and distort
                # the histogram).
                _TPOT_HIST.observe((now - req.first_token_time) /
                                   (len(req.tokens) - 1),
                                   exemplar=req.trace.trace_id
                                   if req.trace is not None else None)
            req.future.set_result((list(req.tokens), stats))
        self._notify(req, None)  # stream end (after the future resolves)

    def _loop(self) -> None:
        import contextlib
        gen = self._generation
        ctx = self.mesh if self.mesh is not None else \
            contextlib.nullcontext()
        with ctx:
            while not self._stop.is_set():
                if self._generation != gen:
                    return  # abandoned by the watchdog: a successor owns
                            # the slots/queue/cache now
                # The cache is built HERE, under the handler below: at
                # thread start, and again after a failed tick dropped
                # it. A loop that cannot build its cache must fail its
                # requests with the reason, not die holding them.
                starting = self._cache is None
                try:
                    if starting:
                        cache = self._init_cache_for_mode()
                        self._commit_gen(
                            gen, lambda: setattr(self, '_cache', cache))
                        starting = False
                    with tracing.phase('engine.tick'):
                        self._tick(gen)
                except _StaleEngineError:
                    return
                except Exception as e:  # pylint: disable=broad-except
                    # Fail every in-flight/queued request rather than
                    # hang their futures, then keep serving. The
                    # slot/queue extraction runs under _thread_lock
                    # with a generation check so a concurrent watchdog
                    # recovery can never be interleaved — a stale
                    # thread must not drain its SUCCESSOR's requests.
                    logger.exception(
                        'engine cache init failed: %s' if starting
                        else 'decode tick failed: %s', e)
                    if tracing.active():
                        # Flight-recorder trigger: dump BEFORE the
                        # state reset below wipes the evidence (the
                        # step_log survives, but slots/queue do not).
                        t_fail = tracing.now()
                        tracing.record_span(
                            'engine.tick_failure', t_fail, t_fail,
                            attrs={'error': f'{type(e).__name__}: {e}'})
                        tracing.flight_record(
                            'tick_failure',
                            extra=self._flight_extra(
                                f'{type(e).__name__}: {e}'))
                    failed = []
                    with self._thread_lock:
                        if self._generation != gen:
                            return
                        for slot in range(self.num_slots):
                            req = self._slots[slot]
                            if req is not None:
                                self._slots[slot] = None
                                failed.append(req)
                        while not self._queue.empty():
                            try:
                                failed.append(self._queue.get_nowait())
                            except Exception:  # pylint: disable=broad-except
                                break
                        if starting:
                            # This loop cannot run. Retire it under
                            # the lock submit() enqueues under: a
                            # request put after this sees no thread
                            # and starts one, which fails it the same
                            # way, at once and with this exception.
                            self._thread = None
                    for req in failed:
                        self._fail_request(req, e)
                    if starting:
                        return

                    def _reset_state():
                        # Rebuilt at the top of the loop, inside the
                        # handler.
                        self._cache = None
                        # The failed tick's pipeline state is untrusted:
                        # every pending lookahead dispatch in the ring
                        # (and the device feed chained off it) must
                        # never be emitted — its requests were just
                        # failed above.
                        self._ring.clear()
                        self._joins = []
                        _DISPATCH_AHEAD.set(0)
                        self._feed = None
                        self._aids_sig = None
                        self._aids_cache = None
                        if self.max_adapters:
                            # Same wholesale reset as wedge recovery:
                            # the failed tick's residency bookkeeping
                            # is untrusted.
                            self._adapter_pool = \
                                self._adapter_pool.fresh()
                            self._adapters = _zeros_from_shapes(
                                self._adapter_boxed,
                                self.mesh if self._tp > 1 else None)
                            _ADAPTER_RESIDENT.set(0)
                        if self.paged_block_size:
                            # Fresh pool + prefix index: the failed
                            # tick's block bookkeeping is untrusted.
                            self._pool = kv_cache_lib.BlockPool(
                                self.cfg.paged_num_blocks,
                                self.paged_block_size)
                            self._prefix_entries = \
                                self._new_prefix_index()
                            self._prewarmed_keys = set()

                    try:
                        self._commit_gen(gen, _reset_state)
                    except _StaleEngineError:
                        return
                if self._generation == gen:
                    self._heartbeat = time_lib.monotonic()
                    self._warm_tick = True

    def _housekeep(self, slots, queue, gen: int) -> Tuple[float, float]:
        """The tick's chores before admission (`engine.tick.housekeep`):
        engine-thread work, ingest expiry, the deadline and cancellation
        scans of slots and queue, SLO preemption. Returns the wall and
        monotonic instants the deadlines were judged at, which the
        admission loop judges by too."""
        # Engine-thread work (handoff gathers, ingest finalizes) runs
        # FIRST: these items need the pool tree while no dispatch is in
        # flight, and a decode-tier replica must finalize an ingest
        # promptly even when it has no active slots.
        if self._engine_work:
            self._drain_engine_work(gen)
        # Orphaned ingest streams (sender died mid-handoff AND the LB's
        # best-effort /kv/abort never arrived) are reclaimed HERE, every
        # tick — not only when the next chunk happens to arrive. A
        # quiet decode replica must not hold a dead stream's blocks
        # until new ingest traffic shows up.
        if self._ingest_sessions:
            with self._ingest_lock:
                self._expire_ingest_sessions_locked(time_lib.monotonic())
        now = time_lib.time()        # wall: deadlines are absolute epoch
        mono_now = time_lib.monotonic()  # durations in error messages
        # Per-request deadlines: an expired (or caller-cancelled)
        # in-flight request frees its slot with a clean error instead
        # of burning decode steps.
        for slot in range(self.num_slots):
            req = slots[slot]
            if req is None:
                continue
            if req.future.cancelled():
                slots[slot] = None
                self._release_blocks(req)
                self._release_adapter(req)
                self._notify(req, None)
            elif req.deadline is not None and now > req.deadline:
                slots[slot] = None
                self._release_blocks(req)
                self._fail_request(
                    req,
                    exceptions.RequestDeadlineExceededError(
                        f'request exceeded its deadline after '
                        f'{mono_now - req.submit_time:.1f}s '
                        f'({len(req.tokens)} tokens generated)'))
        # Expired/cancelled entries must leave the QUEUE every tick
        # too, even when no slot frees for minutes — submit()'s
        # contract is that a deadline fires whether the request is
        # queued or mid-decode, and a dead entry must not hold
        # admission-queue capacity.
        if not queue.empty():
            # One pass under the mutex: partition into kept/dead and
            # swap the deque contents in place. (The old loop called
            # deque.remove(req) inside a scan over a snapshot — O(n²)
            # on a deep backlog, all while holding the mutex.)
            dead = []
            with queue.mutex:
                kept = collections.deque()
                for req in queue.queue:
                    if req.future.cancelled() or (
                            req.deadline is not None and
                            now > req.deadline):
                        dead.append(req)
                    else:
                        kept.append(req)
                if dead:
                    queue.queue.clear()
                    queue.queue.extend(kept)
            for req in dead:
                if req.future.cancelled():
                    self._release_adapter(req)
                    self._notify(req, None)
                else:
                    self._fail_request(
                        req,
                        exceptions.RequestDeadlineExceededError(
                            f'request expired in the admission queue '
                            f'after {mono_now - req.submit_time:.1f}s'))
        # SLO preemption (docs/serving.md "Multi-tenant serving"): an
        # interactive arrival that would otherwise wait takes a
        # batch-tier slot NOW. The batch request re-queues RETRYABLY at
        # the head of its tier — blocks released, context folded to
        # ids+tokens — and CONTINUES from its generated tokens on
        # re-admission, so greedy output is bit-identical to the
        # uninterrupted stream and nothing is lost non-retryably.
        if not queue.empty():
            waiting = queue.tier_depths().get('interactive', 0)
            if waiting:
                free = sum(1 for r in slots if r is None)
                need = waiting - free
                for slot in range(self.num_slots - 1, -1, -1):
                    if need <= 0:
                        break
                    req = slots[slot]
                    if req is None or req.tier != 'batch':
                        continue
                    # Chaos seam: an armed fault here is the preemption
                    # path itself failing — the tick-failure handler
                    # fails in-flight work cleanly (docs/resilience.md).
                    fault_injection.point('engine.slot_preempt')
                    t_pre = (tracing.now() if req.trace is not None
                             else 0.0)
                    slots[slot] = None
                    self._release_blocks(req)
                    # Its pending columns are shed: the same request
                    # object comes back, maybe to this very slot, so
                    # identity alone would not tell them stale.
                    for entry in self._ring:
                        entry.reqs[slot] = None
                    req.inflight = 0
                    req.prefilling = False
                    req.prefill_pos = 0
                    req.next_pos = 0
                    req.preemptions += 1
                    req.context = req.ids + req.tokens
                    self.tenancy_stats['slot_preempts'] += 1
                    _SLOT_PREEMPTS.inc()
                    if req.trace is not None:
                        tracing.record_span(
                            'engine.slot_preempt', t_pre, tracing.now(),
                            parent=req.trace,
                            attrs={'slot': slot,
                                   'tokens_done': len(req.tokens)})
                    queue.requeue_front(req)
                    need -= 1
        return now, mono_now

    def _admit_waiting(self, slots, queue, gen: int, now: float,
                       mono_now: float) -> None:
        """The admission loop (`engine.tick.admit`)."""
        # Admit new requests into free slots (between ticks — this is
        # the "continuous" in continuous batching). Requests that
        # expired or were cancelled while queued are dropped, not
        # admitted.
        for slot in range(self.num_slots):
            while slots[slot] is None and not queue.empty():
                try:
                    req = queue.get_nowait()
                except Exception:  # pylint: disable=broad-except
                    break
                if req.future.cancelled():
                    self._release_adapter(req)
                    self._notify(req, None)
                    continue
                if req.deadline is not None and now > req.deadline:
                    self._fail_request(
                        req,
                        exceptions.RequestDeadlineExceededError(
                            f'request expired in the admission queue '
                            f'after {mono_now - req.submit_time:.1f}s'))
                    continue
                # Prefill of a fresh prompt bucket may JIT-compile:
                # widen the watchdog allowance for the dispatch. (Paged
                # admission is cheap — block attach + CoW — but keeps
                # the same flag for its CoW-copy first compile.)
                self._admitting_tick = True
                try:
                    self._admit(slot, req, gen)
                except kv_cache_lib.PoolExhaustedError as e:
                    # Shed THIS request; in-flight slots keep their
                    # blocks and keep decoding.
                    self._fail_request(
                        req, exceptions.EngineOverloadedError(
                            f'KV block pool exhausted at admission: '
                            f'{e}'))
                    continue
                except BaseException as e:
                    # The request is "in hand" — in neither the queue
                    # nor a slot — so no recovery/cleanup path would
                    # ever resolve its future: fail it here before
                    # propagating. Paged blocks it acquired are
                    # returned — except on stale abandonment, where
                    # the pool object belongs to a successor now and
                    # this thread must not touch it.
                    if not isinstance(e, _StaleEngineError):
                        self._release_blocks(req)
                    self._fail_request(
                        req,
                        exceptions.EngineWedgedError(
                            'engine recovery interrupted admission; '
                            'request aborted')
                        if isinstance(e, _StaleEngineError) else e)
                    raise

    def _tick(self, gen: int) -> None:
        self._check_gen(gen)
        # Snapshot the slot table AND the queue: every read/write in
        # this tick goes to THESE objects. If the watchdog abandons the
        # thread mid-tick it swaps both for fresh ones, so a stale
        # thread resuming here mutates only its own abandoned state —
        # it can neither corrupt the successor's slots nor steal
        # requests from the successor's queue.
        slots = self._slots
        queue = self._queue
        # Each stretch of the tick sits in exactly one `engine.tick.*`
        # phase (docs/observability.md), so what no phase covers is the
        # tick's self time. Tracing off, a phase is one boolean test.
        with tracing.phase('engine.tick.housekeep'):
            now, mono_now = self._housekeep(slots, queue, gen)
        with tracing.phase('engine.tick.admit'):
            self._admit_waiting(slots, queue, gen, now, mono_now)
        # Chunked prefill (paged mode): every mid-prefill slot advances
        # ONE fixed-shape chunk, then the decode below still runs for
        # the slots already past prefill — the interleaving that keeps
        # TPOT flat while a long prompt lands. First chunk may
        # JIT-compile (once per engine), hence inside the widened
        # watchdog allowance.
        prefilling = [i for i, r in enumerate(slots)
                      if r is not None and r.prefilling]
        if prefilling:
            self._admitting_tick = True
            with tracing.phase('engine.tick.prefill'):
                self._prefill_tick(slots, prefilling, gen)
            prefilling = [i for i, r in enumerate(slots)
                          if r is not None and r.prefilling]
        # Admission (and its possible compile) is over; refresh the
        # heartbeat BEFORE dropping the widened allowance, or a
        # longer-than-timeout (but legitimate) admission would read as
        # stalled the instant the flag clears. Steady-state decode then
        # gets the normal allowance. Gen-guarded: a stale thread must
        # not freshen the heartbeat and mask a successor's wedge.
        if self._generation == gen:
            self._heartbeat = time_lib.monotonic()
        self._admitting_tick = False
        # Saturation signals, refreshed once per tick (cheap: gauge sets
        # behind the enabled-check).
        _ACTIVE_SLOTS.set(sum(r is not None and not r.prefilling
                              for r in slots))
        _QUEUE_DEPTH.set(queue.qsize())
        if obs.enabled():
            # Per-tier ADMISSION-QUEUE depth (matching the global
            # skytpu_engine_queue_depth semantics — slotted requests
            # are _ACTIVE_SLOTS' business); costs a queue scan, so
            # behind the exporter check.
            for tier_name, depth in \
                    self._queue.tier_depths().items():
                _TIER_QUEUE_DEPTH.labels(tier=tier_name).set(depth)
        # Re-set every tick, not only at construction/probe: the
        # exporter typically enables AFTER warmup, and a gauge set
        # while recording is disabled is a no-op. Unconditional so a
        # single-chip engine reads the documented 1, not an unset 0.
        _TP_SIZE.set(self._tp)
        _DECODE_KERNEL.set(_DECODE_KERNEL_CODE[self.decode_kernel])
        if self.decode_kernel != 'xla' and self._pool is not None:
            # Per-step fused-bytes gauge, recomputed per tick from live
            # pool occupancy (re-set here, not only at construction —
            # exporters usually enable after warmup, the PR-5 lesson).
            _DECODE_FUSED_BYTES.set(self.fused_bytes_per_step())
        if self._tp > 1 and self._hlo_probe_cache is not None:
            _TP_COLLECTIVES.set(self._hlo_probe_cache['total'])
            _TP_ALLREDUCE_BYTES.set(
                self._hlo_probe_cache['all_reduce_bytes'])
        if self._pool is not None:
            # Capacity re-set here (not only at __init__): the exporter
            # usually enables AFTER engine construction, and a gauge set
            # while recording is disabled is a no-op.
            _PAGED_CAPACITY.set(self._pool.num_blocks)
            _PAGED_USED.set(self._pool.used)
            if self.paged_int8_bytes_saved:
                _PAGED_INT8_SAVED.set(self.paged_int8_bytes_saved)
            if self._per_dev_gauges:
                # tp>1: per-device view of the pool. Bytes are static
                # per engine (pool leaves / tp), computed once the
                # cache exists; used-blocks match across devices while
                # the block tables are replicated.
                if self._pool_dev_bytes is None and \
                        self._cache is not None:
                    self._pool_dev_bytes = _tree_bytes(self._cache)[1]
                for g_used, g_bytes in self._per_dev_gauges:
                    g_used.set(self._pool.used)
                    if self._pool_dev_bytes is not None:
                        g_bytes.set(self._pool_dev_bytes)
        ring = self._ring
        if ring and ring[0].gen != gen:
            # A recovery swapped engine state since those dispatches
            # were issued: their requests were already failed —
            # nothing from the ring may ever be emitted.
            ring.clear()
            _DISPATCH_AHEAD.set(0)
        # A slot whose last step is already queued (_spent) decodes no
        # further: it rides inert until that step is consumed.
        active = self._decodable(slots)
        if not active:
            if ring:
                # Steps still pending for slots that are spent (or
                # overshoot for requests that finished or were killed):
                # land the oldest, discarding by identity.
                self._consume_oldest(slots, gen)
            elif not prefilling:
                with tracing.phase('engine.tick.wait'):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
            _DISPATCH_AHEAD.set(len(ring))
            return
        # Chaos harness: tests/SKYTPU_FAULTS can fail or wedge the
        # decode step here; disarmed this is a single boolean check.
        fault_injection.point('engine.decode')
        self._check_gen(gen)
        # Speculation only pays when a greedy slot can accept drafts;
        # an all-sampling active set would pay (K+1)x forward cost to
        # emit one token per slot — use the plain/chunked path instead.
        any_greedy = any(slots[i].temperature <= 0 for i in active)
        if self.speculative > 0 and any_greedy:
            if ring:
                # Spec ticks sample and emit in the same tick: every
                # pending lookahead's tokens must land first or the
                # per-request stream would reorder.
                self._flush_ring(slots, gen)
                self.tick_stats['flushes'] += 1
                active = self._decodable(slots)
                if not active:
                    return
            with tracing.phase('engine.tick.dispatch'):
                spec = self._spec_tick(slots, active, gen)
            if spec is not None:
                out, valid = spec
                self._decode_steps += 1
                self.step_log.append((self._decode_steps,
                                      frozenset(active)))
                with tracing.phase('engine.tick.emit'):
                    self._emit(slots, active, out, valid)
                if self.paged_block_size:
                    # Rejected drafts: hand the over-reserved tail
                    # blocks back instead of holding them to
                    # completion.
                    for i in active:
                        if slots[i] is not None:
                            self._trim_blocks(slots[i])
                return
            # else: a slot is near the cache window — single-step tick.
        if ring and not self._feed_fits(slots, active):
            # The device feed does not hold what this dispatch needs
            # (a first token that was sampled on the host: a
            # speculative engine's join): drain the whole pipeline,
            # then dispatch off host state.
            self._land_joins(gen)
            self._flush_ring(slots, gen)
            self.tick_stats['flushes'] += 1
            active = self._decodable(slots)
            if not active:
                return
        # Queue the next step BEFORE waiting on any result: off the
        # newest pending step's in-graph feed when there is one, and on
        # up to async_depth ahead of the oldest — the device runs them
        # back to back while every line of host work below (landing,
        # emit, metrics, and the next tick's deadline/queue/admission
        # scan) overlaps its compute. The first dispatch carries the
        # slots that joined this tick; their first tokens are landed
        # right behind it, so every later count of a request's tokens
        # is whole.
        with tracing.phase('engine.tick.dispatch'):
            while active and len(ring) <= self.async_depth:
                self._dispatch(
                    slots, active, self._step_count(slots, active,
                                                    prefilling),
                    gen, chain=ring[-1] if ring else None)
                if self._joins:
                    break
                active = self._decodable(slots)
        self._land_joins(gen)
        # A synchronous engine (async_depth=0) lands the step it just
        # queued; with the ring up the oldest pending one is landed,
        # while the device runs the rest.
        if len(ring) > self.async_depth:
            self._consume_oldest(slots, gen)
        _DISPATCH_AHEAD.set(len(ring))

    def _spent(self, req: '_Request') -> bool:
        """True iff the steps already queued for `req` are known to
        finish it (max_new_tokens, or the window's end): its tokens are
        emitted and the slot freed when the last of them is consumed,
        and no further step is dispatched for it meanwhile. EOS cannot
        be foreseen and costs up to async_depth discarded steps."""
        ahead = req.inflight
        if self._block:
            # block mode counts passes: the plan is exact (_join_block)
            return ahead > 0 and \
                req.passes_done + ahead >= req.passes_total
        return ahead > 0 and (
            len(req.tokens) + ahead >= req.max_new_tokens or
            req.next_pos + ahead + 1 >= self.cfg.max_seq_len)

    def _decodable(self, slots) -> list:
        """Slots the next decode dispatch carries: past prefill and not
        yet provided with their last step."""
        return [i for i, r in enumerate(slots)
                if r is not None and not r.prefilling
                and not self._spent(r)]

    def _step_count(self, slots, active, prefilling) -> int:
        """Steps the next dispatch scans: decode_chunk of them when
        nothing is waiting to be admitted (admission latency stays
        bounded by one chunk), a single step otherwise. Full chunks
        only: k ∈ {1, decode_chunk} so serving never JIT-compiles a new
        scan length mid-stream. Slots whose cache window can't absorb a
        full chunk finish on single steps; a mid-prefill slot also
        forces single steps so its next chunk isn't delayed by a whole
        decode scan."""
        if self.decode_chunk > 1 and self._queue.empty() \
                and not prefilling and all(
                    self.cfg.max_seq_len - slots[i].next_pos
                    - slots[i].inflight >= self.decode_chunk
                    for i in active):
            return self.decode_chunk
        return 1

    @staticmethod
    def _feed_key(req: '_Request') -> tuple:
        """What a row of the decode feed is keyed by: the request and
        the position its next dispatched step writes (in block mode
        the number of its next pass, next_pos moving in lumps there)."""
        if req.passes_total:
            return (req.seq, req.passes_done + req.inflight)
        return (req.seq, req.next_pos + req.inflight)

    def _feed_fits(self, slots, active) -> bool:
        """True iff the device feed holds token and position of every
        slot in `active` (rows of other slots may hold anything)."""
        feed = self._feed
        return feed is not None and all(
            feed[2][i] == self._feed_key(slots[i]) for i in active)

    def _dispatch(self, slots, active, k, gen,
                  chain: 'Optional[_Inflight]' = None):
        """Issue one k-step decode dispatch for `active` slots and
        return its device output columns (num_slots, k).

        Inputs are device-resident whenever possible: the feed the
        newest dispatch returned in-graph, with the rows of joining
        slots laid over it on the device (`_join_feed`), is used when
        it holds every active row — zero uploads; otherwise it is
        rebuilt from host lists (a synchronous engine's slot churn).
        `chain` is the newest still-unconsumed dispatch in the ring,
        whose tokens the host has not seen: the tick gives it only
        once it knows that the feed fits. The temps array caches under
        a value signature the same way. The result is appended to the
        lookahead ring with its host copy started."""
        active_set = set(active)
        tables = None
        if self.paged_block_size:
            # Cover every position this dispatch writes (k steps past
            # the request's own pending columns — ahead of the deepest
            # lookahead position) so the table stays fixed across the
            # scanned chunk and across every chained step.
            try:
                for i in active:
                    self._ensure_blocks(req=slots[i],
                                        upto_pos=self._write_end(
                                            slots[i], k))
            except kv_cache_lib.PoolExhaustedError as e:
                # Can only happen with an undersized explicit pool:
                # surface it through the tick-failure path (fails and
                # clears in-flight requests) rather than wedging.
                raise exceptions.EngineOverloadedError(
                    f'KV block pool exhausted mid-decode: {e}') from e
            # Tables only change at admission/finish/block-growth, so
            # steady-state ticks reuse the cached device array instead
            # of rebuilding + re-uploading it (per-tick host work is
            # the tick-latency budget). The fingerprint is the block
            # ids themselves — a few dozen ints, far cheaper than a
            # numpy build + host-to-device transfer, and immune to
            # id()-recycling across request objects.
            tables = self._tables_for(slots, active_set)
        if self._block:
            # Block mode: the feed lives on the device from a slot's
            # join to its finish (the host never sees a block between
            # its passes), so it always fits, ring or no ring.
            if not self._feed_fits(slots, active):
                raise RuntimeError(
                    'block mode: the device feed does not hold every '
                    'active slot\'s block')
            if chain is not None:
                self.tick_stats['chained'] += 1
            out_cols, state, cache, *counts = self._decode_block(
                self.params, self._cache, self._feed[0], tables,
                self._valid_for(active_set))
            return self._queued(slots, active, active_set, 1, gen,
                                out_cols, (state, None), cache, counts)
        tsig = tuple(slots[i].temperature if i in active_set else 0.0
                     for i in range(self.num_slots))
        if tsig != self._temps_sig:
            self._temps_cache = _upload(list(tsig), jnp.float32,
                                        self._repl)
            self._temps_sig = tsig
        temps = self._temps_cache
        if chain is not None:
            tok_dev, pos_dev = self._feed[0], self._feed[1]
            self.tick_stats['chained'] += 1
        elif self._feed_fits(slots, active):
            tok_dev, pos_dev = self._feed[0], self._feed[1]
        else:
            # Slot churn on a synchronous engine, or a start from host
            # state: every value here is host-resident (the ring is
            # empty), so this costs two small uploads, never a device
            # sync.
            tok_dev = _upload([(slots[i].tokens[-1]
                                if i in active_set else 0)
                               for i in range(self.num_slots)],
                              jnp.int32, self._repl)
            pos_dev = _upload([(slots[i].next_pos
                                if i in active_set else 0)
                               for i in range(self.num_slots)],
                              jnp.int32, self._repl)
        aids = self._aids_for(slots, active_set)
        valid = self._valid_for(active_set)
        self._rng, rng = jax.random.split(self._rng)
        if k == 1:
            out_cols, feed_next, cache, *counts = self._decode(
                self.params, self._cache, tok_dev, pos_dev, temps, rng,
                tables, self._adapters, aids, valid)
        else:
            rngs = jax.random.split(rng, k)
            out_cols, feed_next, cache, *counts = self._decode_multi(
                self.params, self._cache, tok_dev, pos_dev, temps,
                rngs, tables, self._adapters, aids, valid)
        return self._queued(slots, active, active_set, k, gen, out_cols,
                            feed_next, cache, counts)

    def _write_end(self, req: '_Request', k: int) -> int:
        """One past the last position the request's next dispatch of k
        steps writes: its blocks must cover it. In block mode the end
        of the block that its next pass works on."""
        if self._block:
            blk = self._block_of(req, req.passes_done + req.inflight)
            return min(req.block0 + (blk + 1) * self._block,
                       self.cfg.max_seq_len)
        return min(req.next_pos + req.inflight + k, self.cfg.max_seq_len)

    def _queued(self, slots, active, active_set, k, gen, out_cols,
                feed_next, cache, counts):
        """A dispatch has been issued: commit its cache, log it, key
        the feed it returned and put it on the lookahead ring with its
        host copy started."""
        self._queue_counts('decode', k, counts)
        self._commit_gen(gen, lambda: setattr(self, '_cache', cache))
        self._decode_steps += k
        self.step_log.append((self._decode_steps, frozenset(active)))
        for i in active:
            slots[i].inflight += k
        # The feed predicts host state AFTER every pending emit lands:
        # (seq, next_pos + inflight) per active slot.
        self._feed = (feed_next[0], feed_next[1], tuple(
            self._feed_key(slots[i]) if i in active_set else None
            for i in range(self.num_slots)))
        self.tick_stats['dispatches'] += 1
        out_cols.copy_to_host_async()
        # Every program queued so far ends before this step does: its
        # counts land with this step's tokens.
        riding, self._counts_pending = self._counts_pending, []
        self._ring.append(_Inflight(out_cols, list(slots), list(active),
                                    k, gen, riding))
        if self.async_depth:
            depth = len(self._ring)
            _DISPATCH_AHEAD.set(depth)
            _DISPATCH_AHEAD_DEPTH.observe(depth)
        return out_cols

    @property
    def _inflight(self) -> 'Optional[_Inflight]':
        """Newest in-flight lookahead dispatch, or None — the
        compatibility view of the ring (depth-1 callers and tests
        predate async_depth=N)."""
        return self._ring[-1] if self._ring else None

    def _consume_oldest(self, slots, gen: int) -> None:
        """Land the OLDEST pending dispatch's tokens (its host copy
        started at dispatch) and emit them. Columns whose slot changed
        hands since dispatch — EOS overshoot after a finish, a
        deadline kill, a preemption — are discarded by request
        IDENTITY, never by position arithmetic; a request that
        finishes while deeper entries are still pending sheds their
        columns the same way, up to async_depth steps late."""
        infl = self._ring.popleft()
        with tracing.phase('engine.tick.land'):
            out_cols = _land(infl.out)   # waits on the copy the
                                         # dispatch already started async
        # The wait above may span a watchdog recovery: never emit into
        # a successor's world.
        self._check_gen(gen)
        for kind, calls, counts in infl.counts:
            self._note_routing(kind, calls, _land(counts))
        live = [i for i in infl.active if slots[i] is infl.reqs[i]]
        for i in live:
            slots[i].inflight -= infl.k
        if live:
            with tracing.phase('engine.tick.emit'):
                if self._block:
                    self._emit(slots, live,
                               *self._landed_pass(slots, live, out_cols))
                else:
                    self._emit(slots, live, out_cols, None)

    def _landed_pass(self, slots, live, out):
        """A block pass has landed (`_decode_block_impl`'s `out`): count
        it, note the unmask pass of every token it emits, and hand
        `_emit` the columns and how many of each row are valid (none on
        most passes, the block's new tokens on the one that cleared its
        last mask; `_emit` cuts them at max_new_tokens)."""
        b = self._block
        valid, masks = out[:, 2 * b], out[live, 2 * b + 1]
        stats = self.block_stats
        stats['block_passes'] += len(live)
        stats['block_masked_positions'] += int(masks.sum())
        stats['block_commits'] += int((masks == 0).sum())
        for i in live:
            req, n = slots[i], int(valid[i])
            req.passes_done += 1
            if n:
                req.unmask_pass.extend(out[i, b:b + n].tolist())
                req.last_cols = out[i, :n].tolist()
                if req.first_token_time is None:
                    self._note_first_token(req, i)
        return out[:, :b], valid

    def _queue_counts(self, kind: str, calls: int, counts: list) -> None:
        """A routed program's counts (`counts` holds the program's one
        more output, or nothing): their host copy starts now and they
        ride in with the next decode step's tokens."""
        for arr in counts:
            arr.copy_to_host_async()
            self._counts_pending.append((kind, calls, arr))

    def _note_routing(self, kind: str, calls: int, counts) -> None:
        total = self.route_stats[kind]
        total[0] += calls
        for i, c in enumerate(counts):
            total[1 + i] += int(c)

    def _flush_ring(self, slots, gen: int) -> None:
        """Drain the whole pipeline oldest-first (spec ticks, a feed
        the device does not hold): after this the ring is empty and
        every surviving request's host state reflects every dispatched
        token."""
        while self._ring:
            self._consume_oldest(slots, gen)
        _DISPATCH_AHEAD.set(0)

    def _emit(self, slots, active, out_cols, valid) -> None:
        """Append per-slot output columns (up to valid[slot] of them —
        None ⇒ all) with EOS/max/window termination. `slots` is the
        emitting tick's snapshot (see _tick)."""
        for slot in active:
            req = slots[slot]
            limit = (out_cols.shape[1] if valid is None
                     else int(valid[slot]))
            emitted = 0
            for c in range(limit):
                req.next_pos += 1
                token = int(out_cols[slot, c])
                req.tokens.append(token)
                emitted += 1
                self._notify(req, token)
                done = (len(req.tokens) >= req.max_new_tokens or
                        (req.eos_id is not None
                         and token == req.eos_id) or
                        req.next_pos + 1 >= self.cfg.max_seq_len)
                if done:
                    # Overshoot columns for this slot are discarded; the
                    # stale cache entries sit beyond every future query
                    # position (causal-masked) or get overwritten by the
                    # next admitted request's _insert.
                    self._finish(slots, slot)
                    break
            # Coalesced per-slot-per-tick (was one inc() per token —
            # even the disabled-path boolean check adds up in the
            # hottest loop in the codebase).
            _TOKENS_TOTAL.inc(emitted)
            if self._block:
                self.block_stats['block_tokens'] += emitted

    # ---------------- public api ----------------

    def submit(self, prompt_ids, max_new_tokens: int = 32,
               temperature: float = 0.0,
               eos_id: Optional[int] = None,
               on_token=None,
               deadline: Optional[float] = None,
               adapter: Optional[str] = None,
               priority: str = 'standard'):
        """Enqueue one request; returns a concurrent.futures.Future that
        resolves to (token_ids, stats). `on_token` (optional) is called
        from the engine thread with each token as it lands and once with
        None when the request finishes — the streaming hook. `deadline`
        (absolute time.time() seconds) fails the request with
        RequestDeadlineExceededError once passed, whether it is still
        queued or mid-decode.

        Multi-tenant serving (docs/serving.md): `adapter` names a
        registered LoRA adapter — the request decodes through that
        adapter's slot IN THE SAME dispatch as other adapters' and
        base-model requests; the adapter is pinned (never evicted)
        until the request resolves. `priority` is the SLO tier
        ('interactive'/'standard'/'batch'): interactive admits first
        and may preempt batch slots; with a `deadline` the request is
        shed AT SUBMIT (TierDeadlineUnmeetableError → 429+Retry-After)
        when the current queue depth makes the deadline unmeetable.

        Admission control: while draining, or with max_queue_depth
        exceeded, raises EngineDrainingError/EngineOverloadedError
        instead of queueing — callers shed load at the edge."""
        import concurrent.futures
        tier = tenancy.validate_tier(priority)
        if self._draining:
            _REJECT_DRAINING.inc()
            raise exceptions.EngineDrainingError(
                'engine is draining for shutdown; not accepting new '
                'requests')
        if self.max_queue_depth:
            # Backlog = queued beyond what free slots will absorb at
            # the next tick: an idle engine must accept a burst of
            # num_slots + cap, not shed at cap while slots sit empty.
            free = sum(1 for r in self._slots if r is None)
            backlog = self._queue.qsize() - free
            if backlog >= self.max_queue_depth:
                _REJECT_OVERLOADED.inc()
                raise exceptions.EngineOverloadedError(
                    f'engine admission queue is full ({backlog} '
                    f'queued beyond free capacity, cap '
                    f'{self.max_queue_depth})')
        if temperature > 0:
            _refuse_blocks(
                self.cfg, f'temperature={temperature}',
                'a pass takes x0 = argmax and its probability as the '
                'confidence that orders the unmasking; sampling x0 is '
                'not built')
        ids = [int(t) for t in prompt_ids]
        if not ids:
            raise ValueError('empty prompt')
        if len(ids) + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f'{len(ids)}+{max_new_tokens} exceeds max_seq_len '
                f'{self.cfg.max_seq_len}')
        # Deadline-aware admission (per tier): shed NOW when the queue
        # ahead of this request makes its deadline unmeetable — a
        # retryable 429 at submit beats occupying queue capacity only
        # to be killed mid-wait. Estimate = waves of same-or-higher-
        # priority backlog × the admission→first-token service EWMA
        # (None until the first completion: never shed on a guess).
        if deadline is not None and self.ttft_estimate:
            ahead = self._queue.depth_at_or_above(tier)
            free = sum(1 for r in self._slots if r is None)
            backlog = ahead - free
            # Only a real backlog sheds: an unmeetable deadline on an
            # IDLE engine is the client's problem, not a load
            # condition — it admits and fails 504 through the normal
            # deadline machinery (pre-existing contract).
            projected = (tenancy.projected_wait(
                backlog, self.num_slots, self.ttft_estimate)
                if backlog > 0 else 0.0)
            if backlog > 0 and time_lib.time() + projected > deadline:
                _TIER_DEADLINE_SHED.labels(tier=tier).inc()
                self.tenancy_stats['deadline_sheds'] += 1
                raise exceptions.TierDeadlineUnmeetableError(
                    f'{tier} deadline unmeetable at current queue '
                    f'depth ({ahead} ahead, projected '
                    f'{projected:.2f}s); retry later')
        if tier != 'standard':
            # Flips the server's X-SkyTPU-Tier-Load header on: the
            # per-response tier scan is only worth paying once tiered
            # traffic actually exists (see server._fleet_intel_headers).
            self._tiers_active = True
        adapter_slot, pinned_pool = 0, None
        if adapter is not None:
            try:
                adapter_slot = self._ensure_resident(adapter, pin=True)
            except exceptions.AdapterPoolExhaustedError:
                _ADAPTER_SHED.inc()
                self.tenancy_stats['adapter_sheds'] += 1
                raise
            pinned_pool = self._adapter_pool
        _TIER_REQUESTS.labels(tier=tier).inc()
        future: 'concurrent.futures.Future' = concurrent.futures.Future()
        req = _Request(ids, max_new_tokens, temperature, eos_id, future,
                       on_token=on_token, deadline=deadline, tier=tier,
                       adapter=adapter, adapter_slot=adapter_slot,
                       adapter_pool=pinned_pool)
        if tracing.enabled():
            # One enabled-check; the ambient context (the server's
            # request span, or an activate()d handoff context) becomes
            # this request's trace — every engine span parents to it.
            req.trace = tracing.current()
        # Enqueue under _thread_lock: watchdog recovery swaps the queue
        # object under the same lock, so this put lands either in the
        # old queue BEFORE the swap (and is failed by the recovery
        # drain) or in the successor queue — never in an abandoned
        # queue nobody will ever read (a future that hangs forever).
        # Re-checking _draining under the same lock closes the
        # drain/submit race the same way: either this request is
        # visible to drain's wait loop, or it is refused here.
        with self._thread_lock:
            if self._draining:
                _REJECT_DRAINING.inc()
                self._release_adapter(req)
                raise exceptions.EngineDrainingError(
                    'engine is draining for shutdown; not accepting '
                    'new requests')
            self._queue.put(req)
        _QUEUE_DEPTH.set(self._queue.qsize())
        self._ensure_thread()
        self._wake.set()
        return future

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = 300.0,
                 adapter: Optional[str] = None,
                 priority: str = 'standard'):
        """Blocking convenience wrapper around submit()."""
        return self.submit(prompt_ids, max_new_tokens, temperature,
                           eos_id, adapter=adapter,
                           priority=priority).result(timeout=timeout)

    def measure_ttft(self, num_requests: int, prompt,
                     max_new_tokens: int = 16,
                     return_stats: bool = False):
        """Submit `num_requests` concurrently; returns their TTFTs (s)
        (or the full per-request stats dicts with return_stats)."""
        futures = [self.submit(prompt, max_new_tokens=max_new_tokens)
                   for _ in range(num_requests)]
        stats = [f.result(timeout=600.0)[1] for f in futures]
        if return_stats:
            return stats
        return [st['ttft_s'] for st in stats]

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admitting (submit raises
        EngineDrainingError), let in-flight AND already-queued requests
        finish, then stop the engine thread. Returns True when
        everything finished before `timeout` (None = wait forever).
        Requests still pending when the drain gives up are FAILED with
        EngineDrainingError — a drain must never leave a caller blocked
        on a future nobody will resolve."""
        import queue as queue_lib
        with self._thread_lock:
            self._draining = True
        deadline = (time_lib.monotonic() + timeout
                    if timeout is not None else None)
        while self._busy():
            thread = self._thread
            if thread is None or not thread.is_alive():
                break  # no engine thread will ever finish them
            if deadline is not None and time_lib.monotonic() > deadline:
                break
            time_lib.sleep(0.02)
        finished = not self._busy()
        self.stop()
        if not finished:
            leftovers = []
            with self._thread_lock:
                for slot in range(self.num_slots):
                    req = self._slots[slot]
                    if req is not None:
                        self._slots[slot] = None
                        leftovers.append(req)
                while True:
                    try:
                        leftovers.append(self._queue.get_nowait())
                    except queue_lib.Empty:
                        break
            err = exceptions.EngineDrainingError(
                'engine drain timed out; request aborted during '
                'shutdown')
            for req in leftovers:
                self._release_blocks(req)
                self._fail_request(req, err)
        return finished

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        thread = self._thread  # a loop that cannot start clears it
        if thread is not None:
            thread.join(timeout=5.0)


def load_params_from_checkpoint(cfg: ModelConfig,
                                checkpoint_dir: str,
                                mesh: Optional[Any] = None) -> Any:
    """Restore trained params from an Orbax checkpoint written by
    train/run.py. Params-only partial restore: the fp32 AdamW moments
    (~5x the bf16 param bytes) never materialize — the difference
    between a serving replica that fits and one that OOMs for 8B+.

    `mesh` (a serving mesh from parallel.decode_mesh) makes orbax
    deserialize each leaf DIRECTLY into its tree_shardings placement —
    a tp>1 engine's weights arrive on device already sharded on the tp
    axis, and the later _place_params device_put is an identity. The
    whole-tree-on-device-0 materialization this avoids was the gap
    between serving a too-big-for-one-chip checkpoint and OOMing at
    restore (the PR-7 named follow-up). Without a mesh the historical
    behavior stands: restore over the local training-style mesh.

    LoRA checkpoints (train runs with --lora-rank write a lora.json
    sidecar) restore with the adapter structure recorded there and are
    merged on-device into plain base weights — `serve.server
    --checkpoint-dir <lora run>` just works, no HF export detour."""
    import dataclasses as _dc
    import json as _json
    import os as _os

    from skypilot_tpu.train.checkpoints import restore_params_only
    sidecar = _os.path.join(_os.path.expanduser(checkpoint_dir),
                            'lora.json')
    if _os.path.exists(sidecar):
        with open(sidecar, encoding='utf-8') as f:
            meta = _json.load(f)
        from skypilot_tpu.models.lora import merge_lora
        lora_cfg = _dc.replace(cfg, **meta)
        logger.info('LoRA checkpoint (%s): merging adapters into base '
                    'weights for serving', meta)
        return merge_lora(restore_params_only(lora_cfg, checkpoint_dir,
                                              mesh=mesh),
                          lora_cfg)
    return restore_params_only(cfg, checkpoint_dir, mesh=mesh)


@functools.lru_cache(maxsize=2)
def get_engine(model_name: str, batch_size: int = 1,
               max_seq_len: Optional[int] = None,
               checkpoint_dir: Optional[str] = None,
               tp: Optional[int] = None) -> InferenceEngine:
    """Process-wide engine cache (the serve server's accessor).

    `tp=None` (the default) picks the tensor-parallel degree from the
    LOCAL device count: the largest tp dividing both the device count
    and every tp-sharded model dim (infer_serving_tp) — a model too
    big for one chip serves over all local chips with no flag. tp=1
    forces the single-chip engine; tp>1 shards over the first tp
    devices (parallel.decode_mesh)."""
    cfg = get_config(model_name)
    if tp is None:
        tp = infer_serving_tp(cfg, len(jax.devices()))
    mesh = None
    if tp > 1:
        from skypilot_tpu.parallel import decode_mesh
        mesh = decode_mesh(tp)
    params = None
    if checkpoint_dir:
        # Mesh-first: orbax deserializes straight into the serving
        # shardings, never materializing the tree whole on device 0.
        params = load_params_from_checkpoint(cfg, checkpoint_dir,
                                             mesh=mesh)
    return InferenceEngine(model_name, params=params,
                           batch_size=batch_size, max_seq_len=max_seq_len,
                           mesh=mesh)
