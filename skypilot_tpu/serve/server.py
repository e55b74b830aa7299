"""HTTP inference server: the in-tree engine behind serve replicas.

Reference analogue: the vLLM/TGI servers the reference's llm/ recipes
launch (SURVEY §2.9); TPU-native it is first-party, wrapping
models/inference.InferenceEngine in aiohttp.

Endpoints:
  GET  /health              → 200 once the engine is warm
  GET  /metrics             → Prometheus text exposition (engine
                              TTFT/TPOT histograms, queue depth, shed
                              counters — docs/observability.md)
  POST /generate            → {"prompt_ids": [[...]] | "prompt": "text",
                              "max_new_tokens": N, "temperature": T}
                              ⇒ {"token_ids": [[...]], "text": [...],
                                 "stats": {...}}
  POST /v1/completions      → OpenAI-compatible text completions
  POST /v1/chat/completions → OpenAI-compatible chat (generic template)
  GET  /v1/models           → the served model id
(OpenAI scope: streaming SSE + non-streaming, n=1, stop strings, usage accounting —
existing OpenAI-client code points base_url here unchanged.)

Tokenization: accepts raw token ids (any external tokenizer), or text via
the built-in byte-level tokenizer (ids 0-255 = bytes — honest and
dependency-free; swap in a real tokenizer via --tokenizer hf:<path> when
the model has one).

Concurrency: the engine continuous-batches — each request's prompt drops
into a free decode slot between ticks (prompt lengths bucket to powers of
two inside the engine), so concurrent requests interleave on-chip instead
of queueing behind one another.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import tempfile
import threading
import time
from typing import List, Optional

from aiohttp import web

from skypilot_tpu import exceptions
from skypilot_tpu.serve import constants as serve_constants
from skypilot_tpu.observability import exposition
from skypilot_tpu.observability import metrics as obs
from skypilot_tpu.observability import tracing
from skypilot_tpu.utils import fault_injection

logger = logging.getLogger(__name__)

# Server metrics (docs/observability.md). Request latency/status are
# recorded by a middleware so every route (including /metrics itself)
# is covered without per-handler boilerplate.
_REQ_LATENCY = obs.histogram(
    'skytpu_server_request_seconds',
    'HTTP request latency by route', ('route',))
_REQ_TOTAL = obs.counter(
    'skytpu_server_requests_total',
    'HTTP requests by route and status', ('route', 'status'))
_SHED_TOTAL = obs.counter(
    'skytpu_server_shed_total',
    'Requests shed with 429/503 + Retry-After', ('reason',))
_DRAINING_GAUGE = obs.gauge(
    'skytpu_server_draining',
    '1 while the server drains for shutdown, else 0')
_PREEMPT_DRAIN_HIST = obs.histogram(
    'skytpu_server_preempt_drain_seconds',
    'Preemption notice → in-flight work drained: how much of the '
    'notice budget the drain consumed (the remainder funds the '
    'prefix export)',
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 30.0, 60.0))
_PREEMPT_NOTICES = obs.counter(
    'skytpu_server_preempt_notices_total',
    'Preemption notices handled (POST /preempt or SIGTERM-with-'
    'deadline)')


@web.middleware
async def _metrics_middleware(request: web.Request, handler):
    """Times every request and counts (route, status) — including
    exceptions mapped to HTTP errors by aiohttp."""
    start = time.monotonic()
    status = 500
    try:
        response = await handler(request)
        status = response.status
        return response
    except web.HTTPException as e:
        status = e.status
        raise
    finally:
        resource = request.match_info.route.resource
        # Unmatched requests (404s) share ONE bucket: using the raw
        # path would let a scanner mint unbounded label cardinality in
        # the process-wide registry.
        route = (resource.canonical if resource is not None
                 else 'unmatched')
        _REQ_LATENCY.labels(route=route).observe(
            time.monotonic() - start)
        _REQ_TOTAL.labels(route=route, status=str(status)).inc()


@web.middleware
async def _tracing_middleware(request: web.Request, handler):
    """Continues (or mints) the request's trace (docs/observability.md
    "Tracing"): an inbound X-SkyTPU-Trace header — the LB's, or the
    prefill tier's on /kv/ingest — parents a 'server.request' span;
    header-less POSTs mint a fresh trace so direct-to-replica traffic
    is traceable too. GETs without a header (health probes, scrapes)
    stay untraced — probe noise must not churn the span ring. The span
    is the ambient context for the whole handler task (contextvars:
    concurrent requests on one event loop cannot cross-contaminate),
    so engine.submit() captures it. Disabled tracing costs one boolean
    check per request."""
    if not tracing.enabled():
        return await handler(request)
    ctx = tracing.parse_header(request.headers.get(tracing.TRACE_HEADER))
    if ctx is None and request.method != 'POST':
        return await handler(request)
    with tracing.span('server.request', parent=ctx,
                      attrs={'route': request.path,
                             'method': request.method}) as sp:
        response = await handler(request)
        sp.set_attr('status', response.status)
        return response


def byte_encode(text: str) -> List[int]:
    return list(text.encode('utf-8'))


def byte_decode(ids: List[int]) -> str:
    return bytes(i for i in ids if 0 <= i < 256).decode(
        'utf-8', errors='replace')


class _HandoffPushError(Exception):
    """A chunk push to the decode replica failed past its retry budget.
    `pushed` counts chunks the receiver acknowledged before the failure
    (the partial stream the LB must abort)."""

    def __init__(self, message: str, pushed: int,
                 status: Optional[int] = None) -> None:
        super().__init__(message)
        self.pushed = pushed
        self.status = status


class InferenceServer:

    # Class-level defaults so a bare instance (tests wrap an existing
    # engine via __new__) still has sane serving-state flags.
    ready = False
    draining = False
    request_timeout = 0.0
    # Preemption lifecycle (docs/resilience.md): where prefix artifacts
    # go on notice / come from at pre-warm, the default notice budget,
    # and the last pre-warm outcome (surfaced via /health → serve
    # status).
    prefix_store: Optional[str] = None
    preempt_drain_timeout = 10.0
    last_prewarm: Optional[dict] = None
    # Disaggregated serving (docs/serving.md): which tier this replica
    # serves — 'prefill' computes KV and streams it out (/kv/prefill),
    # 'decode' assembles incoming streams (/kv/ingest), 'monolithic'
    # (default) runs both phases locally.
    tier = 'monolithic'

    def __init__(self, model: str, max_seq_len: Optional[int] = None,
                 tokenizer: str = 'byte',
                 checkpoint_dir: Optional[str] = None,
                 hf_model_path: Optional[str] = None,
                 num_slots: int = 4,
                 quantize: Optional[str] = None,
                 decode_chunk: int = 1,
                 kv_quant: Optional[str] = None,
                 top_k: int = 0,
                 top_p: float = 0.0,
                 speculative: int = 0,
                 prefix_cache: int = 0,
                 max_queue_depth: int = 0,
                 request_timeout: float = 0.0,
                 watchdog_timeout: float = 0.0,
                 paged_block_size: int = 0,
                 paged_num_blocks: Optional[int] = None,
                 prefill_chunk: int = 0,
                 async_depth: int = 1,
                 prefix_store: Optional[str] = None,
                 preempt_drain_timeout: float = 10.0,
                 tp: int = 1,
                 tier: str = 'monolithic',
                 max_adapters: int = 0,
                 adapter_rank: int = 0,
                 adapter_alpha: float = 16.0,
                 adapter_targets: str = '',
                 decode_kernel: str = 'xla') -> None:
        from skypilot_tpu.models.inference import (
            ContinuousBatchingEngine, load_params_from_checkpoint)
        from skypilot_tpu.models import get_config
        if checkpoint_dir and hf_model_path:
            raise ValueError('--checkpoint-dir and --hf-model-path are '
                             'mutually exclusive')
        # Tensor-parallel serving: ONE endpoint over an engine whose
        # weights + KV pool shard across the first `tp` local devices
        # (parallel.decode_mesh; the per-layer all-reduce rides ICI).
        # Request/response surface is unchanged — sharding is invisible
        # to clients.
        mesh = None
        if tp and tp > 1:
            from skypilot_tpu.parallel import decode_mesh
            mesh = decode_mesh(tp)
        params = None
        if checkpoint_dir:
            # Mesh-first restore: with tp>1 orbax deserializes each
            # leaf straight into its serving-mesh sharding
            # (tree_shardings out-shardings), so the weights never
            # materialize whole on device 0 before _place_params.
            params = load_params_from_checkpoint(get_config(model),
                                                 checkpoint_dir,
                                                 mesh=mesh)
        elif hf_model_path:
            # A local HF checkpoint dir (safetensors): convert into the
            # mesh-first tree. The cfg carries the max_seq_len override
            # so the converter validates position tables against what
            # the engine will actually run with.
            from skypilot_tpu.models.convert import load_hf_checkpoint
            cfg = get_config(model)
            if max_seq_len is not None:
                import dataclasses
                cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
            params = load_hf_checkpoint(hf_model_path, cfg)
        # Continuous batching: requests stream into free decode slots, so
        # concurrent requests interleave instead of queueing behind each
        # other (the old engine serialized behind an asyncio lock).
        self.engine = ContinuousBatchingEngine(model, params=params,
                                               num_slots=num_slots,
                                               max_seq_len=max_seq_len,
                                               quantize=quantize,
                                               decode_chunk=decode_chunk,
                                               kv_quant=kv_quant,
                                               top_k=top_k, top_p=top_p,
                                               speculative=speculative,
                                               prefix_cache=prefix_cache,
                                               max_queue_depth=max_queue_depth,
                                               watchdog_timeout=(
                                                   watchdog_timeout or None),
                                               paged_block_size=paged_block_size,
                                               paged_num_blocks=paged_num_blocks,
                                               prefill_chunk=prefill_chunk,
                                               async_depth=async_depth,
                                               mesh=mesh,
                                               tier=tier,
                                               ingest_ttl=serve_constants
                                               .ingest_session_ttl_seconds(),
                                               max_adapters=max_adapters,
                                               adapter_rank=adapter_rank,
                                               adapter_alpha=adapter_alpha,
                                               adapter_targets=adapter_targets,
                                               decode_kernel=decode_kernel)
        self.tier = tier
        self.tokenizer_kind = tokenizer
        self._hf_tokenizer = None
        if tokenizer.startswith('hf:'):
            from transformers import AutoTokenizer
            self._hf_tokenizer = AutoTokenizer.from_pretrained(
                tokenizer[3:])
        self.ready = False
        # Server-wide per-request deadline cap (seconds; 0 = none). A
        # request's own `timeout_s` can only tighten it.
        self.request_timeout = request_timeout
        # Graceful drain: once set (SIGTERM), new requests get 503 +
        # Retry-After while in-flight ones finish; /health flips to 503
        # so LBs pull this replica from their ready set.
        self.draining = False
        self.prefix_store = prefix_store
        self.preempt_drain_timeout = preempt_drain_timeout
        self.last_prewarm = None
        # The notice body (_drain_and_export) runs EXACTLY ONCE, under
        # this lock, and caches its outcome: a SIGTERM that lands
        # while a notice is mid-flight waits for it; one that lands in
        # the gap between `draining = True` and the executor starting
        # the body runs the body itself; one that lands after a
        # completed POST /preempt gets the cached outcome and exits.
        self._notice_lock = threading.Lock()
        self._notice_result: Optional[dict] = None

    # -- tokenizer --

    def encode(self, text: str) -> List[int]:
        if self._hf_tokenizer is not None:
            return self._hf_tokenizer.encode(text)
        return byte_encode(text)

    def decode(self, ids: List[int]) -> str:
        if self._hf_tokenizer is not None:
            return self._hf_tokenizer.decode(ids)
        return byte_decode(ids)

    # -- handlers --

    async def handle_health(self, request: web.Request) -> web.Response:
        del request
        if self.draining:
            return web.json_response(
                {'status': 'draining'}, status=503,
                headers={'Retry-After': '5',
                         'X-SkyTPU-Draining': '1'})
        if not self.ready:
            return web.json_response({'status': 'warming'}, status=503)
        payload = {'status': 'ok', 'tier': self.tier}
        engine = getattr(self, 'engine', None)
        if engine is not None and getattr(engine, 'max_adapters', 0):
            # Multi-tenant surface for the replica manager's probe →
            # serve status ADAPTERS / TIER-MIX columns.
            info = engine.adapters_info()
            payload['adapters'] = {'capacity': info['capacity'],
                                   'resident': info['resident']}
        if engine is not None and hasattr(engine, 'tier_load'):
            try:
                payload['tier_load'] = engine.tier_load()
            except Exception:  # pylint: disable=broad-except
                pass
        if self.last_prewarm is not None:
            # Surfaced to the replica manager's readiness probe, which
            # records it on the ReplicaInfo (serve status shows it).
            payload['prewarm'] = self.last_prewarm
        return web.json_response(payload)

    # -- graceful degradation helpers --

    @staticmethod
    def _unavailable(message: str, status: int = 503,
                     retry_after: int = 1,
                     reason: str = 'overloaded') -> web.Response:
        """Load-shedding response: overload/drain return 429/503 WITH
        Retry-After instead of piling onto the batch queue. Draining
        responses carry X-SkyTPU-Draining so the LB replays idempotent
        requests on another replica immediately instead of charging
        this (healthy, just departing) replica's circuit breaker."""
        _SHED_TOTAL.labels(reason=reason).inc()
        headers = {'Retry-After': str(retry_after)}
        if reason == 'draining':
            headers['X-SkyTPU-Draining'] = '1'
        return web.json_response({'error': message}, status=status,
                                 headers=headers)

    def _check_admission(self) -> Optional[web.Response]:
        if self.draining:
            return self._unavailable(
                'server is draining for shutdown', retry_after=5,
                reason='draining')
        return None

    def _batch_capacity_error(self, n_prompts: int) -> Optional[str]:
        """A single batch larger than slots + queue cap can NEVER be
        admitted: shedding it with a retryable 429/503 would send the
        client into an infinite backoff loop — it must be a terminal
        400 instead."""
        cap = self.engine.max_queue_depth
        if not cap:
            return None
        limit = cap + self.engine.num_slots
        if n_prompts > limit:
            return (f'batch of {n_prompts} prompts exceeds this '
                    f'server\'s capacity ({limit}); split the request')
        return None

    def _deadline_for(self, data: dict) -> Optional[float]:
        """Per-request deadline: the request's own timeout_s, capped by
        the server-wide --request-timeout. None = no deadline."""
        timeout = data.get('timeout_s')
        timeout = float(timeout) if timeout is not None else None
        if timeout is not None and timeout <= 0:
            raise ValueError('timeout_s must be > 0')
        if self.request_timeout:
            timeout = (min(timeout, self.request_timeout)
                       if timeout is not None else self.request_timeout)
        return time.time() + timeout if timeout is not None else None

    async def handle_generate(self, request: web.Request) -> web.Response:
        busy = self._check_admission()
        if busy is not None:
            return busy
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            return web.json_response({'error': 'body must be JSON'},
                                     status=400)
        if 'prompt_ids' in data:
            prompts = data['prompt_ids']
            if not isinstance(prompts, (list, tuple)):
                return web.json_response(
                    {'error': 'prompt_ids must be a list of token '
                              'lists'}, status=400)
        elif 'prompt' in data:
            prompt = data['prompt']
            try:
                prompts = [self.encode(p) for p in
                           (prompt if isinstance(prompt, list)
                            else [prompt])]
            except (TypeError, AttributeError) as e:
                return web.json_response(
                    {'error': f'prompt must be text: {e}'}, status=400)
        else:
            return web.json_response(
                {'error': 'need prompt or prompt_ids'}, status=400)

        if data.get('stream'):
            if len(prompts) != 1:
                return web.json_response(
                    {'error': 'stream=true takes exactly one prompt'},
                    status=400)
            # Invalid input must fail as a 400 BEFORE the stream opens,
            # exactly like the non-streaming path — not as an aiohttp
            # 500 after submit exploded.
            try:
                max_new = int(data.get('max_new_tokens', 32))
                temperature = float(data.get('temperature', 0.0))
                deadline = self._deadline_for(data)
                adapter, priority = self._tenant_fields(data)
                tokens, future = self._token_stream(prompts[0], max_new,
                                                    temperature,
                                                    deadline=deadline,
                                                    adapter=adapter,
                                                    priority=priority)
            except (TypeError, ValueError,
                    exceptions.UnknownAdapterError) as e:
                return web.json_response({'error': str(e)}, status=400)
            except exceptions.TierDeadlineUnmeetableError as e:
                # Deadline-aware admission: shed with 429 BEFORE
                # queueing (docs/serving.md "Multi-tenant serving").
                return self._unavailable(str(e), status=429,
                                         reason='deadline')
            except exceptions.EngineOverloadedError as e:
                return self._unavailable(str(e))
            push, flush = self._delta_decoder()
            try:
                resp = await self._sse_prepare(request)
                async for tok in tokens:
                    await self._sse_send(resp, {'token_id': tok,
                                                'text_delta': push(tok)})
                exc = future.exception()
                if exc is not None:
                    await self._sse_send(resp, {'error': str(exc)})
                else:
                    _, stats = future.result()
                    await self._sse_send(resp, {'done': True,
                                                'text_delta': flush(),
                                                'stats': stats})
                await resp.write_eof()
            finally:
                # A disconnected client cancels this handler mid-relay;
                # without cancelling the engine future the generation
                # keeps burning a decode slot for no reader (no-op if
                # the future already resolved).
                future.cancel()
            return resp

        # All prompts go straight into the engine queue; awaiting the
        # futures concurrently lets this request's prompts AND other
        # in-flight HTTP requests share decode ticks.
        too_big = self._batch_capacity_error(len(prompts))
        if too_big is not None:
            return web.json_response({'error': too_big}, status=400)
        futures = []
        try:
            max_new = int(data.get('max_new_tokens', 32))
            temperature = float(data.get('temperature', 0.0))
            deadline = self._deadline_for(data)
            adapter, priority = self._tenant_fields(data)
            for ids in prompts:
                futures.append(self._submit_one(ids, max_new,
                                                temperature,
                                                deadline=deadline,
                                                adapter=adapter,
                                                priority=priority))
        except (TypeError, ValueError,
                exceptions.UnknownAdapterError) as e:
            self._cancel_all(futures)
            return web.json_response({'error': str(e)}, status=400)
        except exceptions.TierDeadlineUnmeetableError as e:
            self._cancel_all(futures)
            return self._unavailable(str(e), status=429,
                                     reason='deadline')
        except exceptions.EngineOverloadedError as e:
            # Shedding a PARTIALLY submitted batch must release the
            # queue slots its head already took, or the orphans keep
            # decoding for no reader and deepen the overload.
            self._cancel_all(futures)
            return self._unavailable(str(e))
        try:
            gathered = await asyncio.gather(
                *[asyncio.wrap_future(f) for f in futures])
        except exceptions.RequestDeadlineExceededError as e:
            return web.json_response({'error': str(e)}, status=504)
        except exceptions.EngineWedgedError as e:
            return self._unavailable(str(e), retry_after=2,
                                     reason='wedged')
        results = [out for out, _ in gathered]
        stats = [st for _, st in gathered]
        return web.json_response({
            'token_ids': results,
            'text': [self.decode(r) for r in results],
            'stats': stats,
        })

    @staticmethod
    def _cancel_all(futures) -> None:
        """Release engine work for a batch the handler is abandoning
        (queued entries are dropped at admission; a request already in
        a slot is swept at the next tick)."""
        for future in futures:
            future.cancel()

    def _submit_one(self, ids: List[int], max_new: int,
                    temperature: float, on_token=None,
                    deadline: Optional[float] = None,
                    adapter: Optional[str] = None,
                    priority: str = 'standard'):
        max_seq = self.engine.cfg.max_seq_len
        if len(ids) + max_new > max_seq:
            ids = ids[-(max_seq - max_new):]
        return self.engine.submit(ids, max_new_tokens=max_new,
                                  temperature=temperature,
                                  on_token=on_token,
                                  deadline=deadline,
                                  adapter=adapter,
                                  priority=priority)

    @staticmethod
    def _tenant_fields(data: dict) -> tuple:
        """(adapter, priority) from a request body — shared by
        /generate and the OpenAI routes. Raises ValueError (→ 400) on
        malformed values; unknown-adapter/unmeetable-deadline
        verdicts come from the engine at submit."""
        adapter = data.get('adapter')
        if adapter is not None and not isinstance(adapter, str):
            raise ValueError('adapter must be a string name')
        priority = data.get('priority') or 'standard'
        if not isinstance(priority, str):
            raise ValueError('priority must be a string')
        from skypilot_tpu.serve import tenancy
        tenancy.validate_tier(priority)
        return adapter, priority

    # -- streaming plumbing --

    def _token_stream(self, ids: List[int], max_new: int,
                      temperature: float,
                      deadline: Optional[float] = None,
                      adapter: Optional[str] = None,
                      priority: str = 'standard'):
        """(async-iterable of tokens, future): engine-thread tokens
        bridged onto this event loop; the iterable ends at the engine's
        None sentinel (sent after the future resolves)."""
        loop = asyncio.get_event_loop()
        queue: 'asyncio.Queue' = asyncio.Queue()

        def on_token(tok):
            loop.call_soon_threadsafe(queue.put_nowait, tok)

        future = self._submit_one(ids, max_new, temperature,
                                  on_token=on_token, deadline=deadline,
                                  adapter=adapter, priority=priority)

        async def tokens():
            while True:
                tok = await queue.get()
                if tok is None:
                    return
                yield tok

        return tokens(), future

    def _delta_decoder(self):
        """Incremental text decoding: feed tokens one at a time via
        `push` for the NEW text since the last call; `flush` at stream
        end for whatever was held back. Cumulative decode with a
        trailing-replacement-char holdback: an in-progress multi-byte
        sequence decodes as U+FFFD and would CHANGE retroactively when
        its continuation bytes arrive, so it is withheld until complete
        (or until flush, where a genuine U+FFFD is emitted as-is).

        `sent['text']` tracks what the CLIENT actually received. On a
        retroactive prefix change (pathological byte soup, tokenizer
        cleanup), push withholds output — it must NOT adopt the new
        decode as its baseline, or every later delta would be computed
        against text the client never saw (dropping or duplicating the
        corrected span). flush() then emits the corrected tail — the
        diff against what was actually sent — so the client's
        accumulated stream equals the canonical decode whenever the
        final decode extends it."""
        toks: List[int] = []
        sent = {'text': ''}

        def _stable(full: str) -> str:
            return full[:-1] if full.endswith('�') else full

        def push(tok: int) -> str:
            toks.append(tok)
            full = _stable(self.decode(toks))
            if not full.startswith(sent['text']):
                # Retroactive change despite holdback: withhold until
                # the decode re-extends what was already emitted (the
                # corrected tail lands in a later push or in flush).
                return ''
            delta = full[len(sent['text']):]
            if delta:
                sent['text'] = full
            return delta

        def flush() -> str:
            full = self.decode(toks)
            if full.startswith(sent['text']):
                return full[len(sent['text']):]
            # The canonical decode no longer extends what was sent.
            # When everything already on the wire past the common
            # prefix is U+FFFD placeholders (a stale '�' that got
            # emitted before its replacement bytes arrived), the
            # corrected text was WITHHELD by push — emit it now, as
            # the diff against what was actually sent, instead of
            # dropping it: the stale marker cannot be retracted, but
            # the replacement must not be lost with it
            # (regression-pinned, tests/test_fleet_routing.py).
            already = sent['text']
            common = 0
            for a, b in zip(already, full):
                if a != b:
                    break
                common += 1
            stale_tail = already[common:]
            if stale_tail and set(stale_tail) <= {'�'}:
                return full[common:]
            # Genuinely divergent non-placeholder text is on the wire;
            # emitted bytes cannot be retracted — log loudly rather
            # than silently diverge.
            logger.warning(
                'streamed text diverged from canonical decode '
                '(sent %r... vs canonical %r...)', sent['text'][:40],
                full[:40])
            return ''

        return push, flush

    @staticmethod
    async def _sse_prepare(request: web.Request) -> web.StreamResponse:
        resp = web.StreamResponse(
            headers={'Content-Type': 'text/event-stream',
                     'Cache-Control': 'no-cache'})
        await resp.prepare(request)
        return resp

    @staticmethod
    async def _sse_send(resp: web.StreamResponse, payload) -> None:
        data = payload if isinstance(payload, str) else json.dumps(
            payload)
        await resp.write(f'data: {data}\n\n'.encode())

    def _generate_one(self, ids: List[int], max_new: int,
                      temperature: float):
        out, st = self._submit_one(ids, max_new, temperature).result(
            timeout=600.0)
        return out, st

    def warmup(self) -> None:
        t0 = time.monotonic()
        self._generate_one([1, 2, 3], 4, 0.0)
        if getattr(self.engine, '_tp', 1) > 1:
            # Publish the tp collective gauges from the compiled-HLO
            # probe. This pays one extra AOT compile of the decode
            # step (the probe cannot reuse the warmup request's jit
            # cache) — deliberately spent HERE, before ready=True,
            # so it never lands on the serving path.
            stats = self.engine.decode_hlo_stats()
            logger.info('tp=%d decode step: %d collectives, '
                        '%d all-reduce bytes/tick',
                        stats['tp'], stats['total'],
                        stats['all_reduce_bytes'])
        self.ready = True
        logger.info('engine warm in %.1fs', time.monotonic() - t0)

    # -- preemption lifecycle (docs/resilience.md) --
    #
    # Notice paths: POST /preempt (the replica manager / tests) and
    # SIGTERM-with-deadline (the cloud). Both stop admission, drain
    # in-flight work under the existing graceful-drain machinery
    # (which flushes the async ring and fails anything left with a
    # RETRYABLE error — request identity is never silently lost), then
    # export hot prefixes to the configured store within what remains
    # of the notice budget. A replacement replica pre-warms from the
    # newest artifact BEFORE flipping /health to ready.

    def _can_export_prefixes(self) -> bool:
        return bool(self.prefix_store and
                    getattr(self.engine, 'paged_block_size', 0) and
                    getattr(self.engine, 'prefix_cache', 0))

    def _artifact_prefix(self) -> str:
        service = os.environ.get('SKYTPU_SERVICE_NAME', '')
        return f'{service}/' if service else ''

    def _artifact_key(self) -> str:
        rid = os.environ.get('SKYTPU_REPLICA_ID', '0')
        # Zero-padded nanosecond stamp: "newest" == lexicographically
        # last under list_keys' ascending sort.
        return (f'{self._artifact_prefix()}'
                f'prefix-{time.time_ns():020d}-r{rid}.skypfx')

    def _export_to_store(self, budget_s: Optional[float]) -> dict:
        """Export hot prefixes to the prefix store; returns the export
        stats (+ 'key' when an artifact was published)."""
        from skypilot_tpu.data import storage as storage_lib
        store = storage_lib.artifact_store_from_url(self.prefix_store)
        with tempfile.TemporaryDirectory(prefix='skytpu-pfx-') as tmp:
            path = os.path.join(tmp, 'artifact.skypfx')
            stats = self.engine.export_prefixes(path, budget_s=budget_s)
            if stats.get('exported'):
                key = self._artifact_key()
                store.put_file(path, key)
                stats['key'] = key
                # Bound the store under preemption churn: pre-warm
                # only ever walks the newest 3 artifacts, so anything
                # older than the newest 5 is dead weight growing the
                # bucket (and every replacement's listing) forever.
                # Best-effort — a prune failure must not fail the
                # export.
                try:
                    keys = store.list_keys(self._artifact_prefix())
                    for old in keys[:-5]:
                        store.delete_key(old)
                    if len(keys) > 5:
                        stats['pruned'] = len(keys) - 5
                except Exception:  # pylint: disable=broad-except
                    logger.warning('prefix-artifact prune failed',
                                   exc_info=True)
        return stats

    def _drain_and_export(self, budget_s: float) -> dict:
        """The synchronous notice body (runs off the event loop):
        drain within most of the budget, then export with whatever
        remains. Partial export under deadline is fine; a kill landing
        mid-export publishes nothing (the artifact rename is atomic)."""
        with self._notice_lock:
            if self._notice_result is None:
                self._notice_result = self._drain_and_export_impl(
                    budget_s)
            return dict(self._notice_result)

    def _drain_and_export_impl(self, budget_s: float) -> dict:
        _PREEMPT_NOTICES.inc()
        # Flight-recorder trigger (docs/observability.md "Tracing"):
        # dump BEFORE the drain so the record shows what the engine
        # was doing when the notice landed, not an already-quiesced
        # engine.
        tracing.flight_record(
            'preempt_notice',
            extra={'budget_s': budget_s, 'tier': self.tier,
                   'queue_load': getattr(self.engine, 'queue_load',
                                         lambda: 0)()})
        t0 = time.monotonic()
        deadline = t0 + budget_s
        # Reserve a slice of the budget for the export itself.
        export_reserve = min(2.0, budget_s * 0.3) \
            if self._can_export_prefixes() else 0.0
        # The notice span covers the whole drain + export window (the
        # engine.preempt_export child lands inside export_prefixes).
        with tracing.span('server.preempt_notice',
                          attrs={'budget_s': budget_s}) as sp:
            drained = self.engine.drain(
                timeout=max(0.1, budget_s - export_reserve))
            sp.set_attr('drained', drained)
            _PREEMPT_DRAIN_HIST.observe(time.monotonic() - t0)
            result: dict = {'drained': drained, 'export': None}
            if not self._can_export_prefixes():
                return result
            if not drained:
                # A timed-out drain can leave the engine thread
                # mid-tick; export_prefixes requires a quiesced
                # engine, and a snapshot raced by a live tick could
                # publish a CRC-valid artifact holding stale KV.
                # Losing the artifact is fine — the replacement just
                # comes up cold; poisoning it is not.
                result['error'] = 'drain timed out; export skipped'
                return result
            try:
                # Chaos seam: the kill landing between drain and export.
                fault_injection.point('replica.preempt_kill')
                result['export'] = self._export_to_store(
                    budget_s=max(0.1, deadline - time.monotonic()))
            except fault_injection.InjectedFault as e:
                result['error'] = f'killed mid-export: {e}'
            except Exception as e:  # pylint: disable=broad-except
                logger.warning('prefix export failed: %s', e)
                result['error'] = str(e)
            return result

    async def handle_preempt(self, request: web.Request) -> web.Response:
        """POST /preempt — the preemption-notice hook: stop admission
        NOW, drain + export within the notice budget, answer with the
        outcome. The process stays up (the actual kill comes from the
        cloud); /health keeps answering 503-draining so the fleet
        routes away."""
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            data = {}
        if not isinstance(data, dict):
            return web.json_response(
                {'error': 'body must be a JSON object'}, status=400)
        raw = data.get('deadline_s')
        try:
            # None → default; 0/negative/non-numeric → 400, never
            # silently swapped for the default.
            budget = (self.preempt_drain_timeout if raw is None
                      else float(raw))
            if budget <= 0:
                raise ValueError('deadline_s must be > 0')
        except (TypeError, ValueError) as e:
            return web.json_response({'error': str(e)}, status=400)
        if self.draining:
            return web.json_response({'status': 'already-draining'})
        self.draining = True
        _DRAINING_GAUGE.set(1)
        loop = asyncio.get_event_loop()
        result = await loop.run_in_executor(
            None, self._drain_and_export, budget)
        result['status'] = 'drained'
        return web.json_response(result)

    def prewarm_from_store(self) -> Optional[dict]:
        """Pre-warm the engine's PrefixIndex from the newest artifact
        in the prefix store (walking back across up to 3 artifacts when
        the newest is rejected wholesale). Failures never block
        serving — the replica just comes up cold. Returns (and records
        in self.last_prewarm) the outcome dict."""
        if not self._can_export_prefixes():
            return None
        from skypilot_tpu.data import storage as storage_lib
        from skypilot_tpu.models import kv_cache as kv_cache_lib
        try:
            store = storage_lib.artifact_store_from_url(self.prefix_store)
            keys = store.list_keys(self._artifact_prefix())
        except Exception as e:  # pylint: disable=broad-except
            self.last_prewarm = {'status': 'failed', 'error': str(e)}
            return self.last_prewarm
        if not keys:
            self.last_prewarm = {'status': 'no-artifact'}
            return self.last_prewarm
        for key in list(reversed(keys))[:3]:
            try:
                with tempfile.TemporaryDirectory(
                        prefix='skytpu-pfx-') as tmp:
                    path = os.path.join(tmp, 'artifact.skypfx')
                    store.get_file(key, path)
                    stats = self.engine.import_prefixes(path)
                self.last_prewarm = {
                    'status': 'ok', 'key': key,
                    'imported': stats['imported'],
                    'blocks': stats['blocks'],
                    'skipped_corrupt': stats['skipped_corrupt'],
                    'partial': stats['stopped_pool_full'],
                }
                return self.last_prewarm
            except kv_cache_lib.ArtifactError as e:
                # Whole artifact untrusted: try the next-newest.
                logger.warning('pre-warm artifact %s rejected: %s',
                               key, e)
                self.last_prewarm = {'status': 'rejected',
                                     'key': key, 'error': str(e)}
            except Exception as e:  # pylint: disable=broad-except
                logger.warning('pre-warm from %s failed: %s', key, e)
                self.last_prewarm = {'status': 'failed',
                                     'key': key, 'error': str(e)}
        return self.last_prewarm

    # -- disaggregated prefill/decode handoff (docs/serving.md) --
    #
    # The prefill tier computes a prompt's KV and pushes it engine →
    # engine, block-granularly, to the decode replica the LB picked:
    #   POST /kv/prefill  (prefill tier; body {prompt_ids, target,
    #                      stream_id}) — prefill + chunked push
    #   POST /kv/ingest   (decode tier; body = one framed chunk) —
    #                      CRC+sequence-validated assembly
    #   POST /kv/abort    (decode tier; body {stream_id}) — roll a
    #                      partial stream back to refcount-0
    # Failure semantics: a shed ingest answers 503 + Retry-After (the
    # decode pool must never corrupt under pressure), an out-of-order
    # chunk answers 409 with the expected seq (the pusher resumes
    # there), a corrupt chunk answers 400 (the pusher may retry the
    # same seq — ingest is idempotent per (stream, seq)).

    def _push_stream(self, target: str, chunks, stream_id: str,
                     trace: Optional['tracing.SpanContext'] = None
                     ) -> dict:
        """Push framed chunks to `target`'s /kv/ingest sequentially.
        One transport retry per CHUNK (receiver dedups by seq — a
        stream of many chunks survives one transient hiccup per chunk,
        not two total) plus up to two 409-guided resumes per stream;
        anything else raises _HandoffPushError. `trace` (the kv_push
        span's context) rides each POST as X-SkyTPU-Trace so the
        decode replica's server.request span joins the handoff
        trace (the chunk headers carry it too, for the engine-level
        ingest spans)."""
        import requests as requests_lib
        headers = {'Content-Type': 'application/octet-stream'}
        trace_header = tracing.header_value(trace)
        if trace_header:
            headers[tracing.TRACE_HEADER] = trace_header
        pushed = 0
        bytes_total = 0
        retries = 0        # total across the stream (reported)
        chunk_retries = 0  # transport retries for the CURRENT seq
        resumes = 0        # 409-guided resumes (whole stream)
        i = 0
        while i < len(chunks):
            # Chaos seam: an armed 'kv.stream' fault is the prefill
            # replica dying mid-stream (or the wire tearing) — the LB
            # must re-dispatch or fall back, the decode side must roll
            # the partial stream back to refcount-0.
            fault_injection.point('kv.stream')
            try:
                resp = requests_lib.post(
                    target + '/kv/ingest', data=chunks[i],
                    headers=headers,
                    timeout=30.0)
            except requests_lib.RequestException as e:
                if chunk_retries >= 1:
                    raise _HandoffPushError(
                        f'push to {target} failed: {e}', pushed) from e
                chunk_retries += 1
                retries += 1
                continue           # retry the same seq — idempotent
            if resp.status_code == 200:
                pushed += 1
                bytes_total += len(chunks[i])
                i += 1
                chunk_retries = 0
                continue
            if resp.status_code == 409 and resumes < 2:
                # Out-of-order verdict carries the seq the receiver
                # expects: resume exactly there.
                try:
                    expected = int(resp.json().get('expected', -1))
                except (ValueError, AttributeError):
                    expected = -1
                if 0 <= expected < len(chunks):
                    resumes += 1
                    retries += 1
                    i = expected
                    chunk_retries = 0
                    continue
            raise _HandoffPushError(
                f'push to {target} answered {resp.status_code}: '
                f'{resp.text[:200]}', pushed,
                status=resp.status_code)
        return {'chunks': pushed, 'bytes': bytes_total,
                'retries': retries}

    def _prefill_and_push(self, ids, target: str, stream_id: str,
                          chunk_blocks: int,
                          trace: Optional['tracing.SpanContext'] = None
                          ) -> dict:
        t0 = time.monotonic()
        # Runs on an executor thread: the handler's request span is
        # adopted explicitly (activate) so the prefill engine's
        # queue-wait/prefill spans — and the push below — join the
        # handoff trace.
        with tracing.activate(trace):
            pstats = self.engine.prefill_prefix(ids)
            with tracing.span('server.kv_push',
                              attrs={'target': target,
                                     'stream': stream_id}) as sp:
                chunks = self.engine.export_prefix_chunks(
                    ids, stream_id, chunk_blocks=chunk_blocks,
                    trace_header=tracing.header_value(sp.ctx))
                push = self._push_stream(target, chunks, stream_id,
                                         trace=sp.ctx)
                sp.set_attr('chunks', push['chunks'])
                sp.set_attr('bytes', push['bytes'])
        return {'ok': True, 'stream_id': stream_id,
                'chunks': push['chunks'], 'bytes': push['bytes'],
                'push_retries': push['retries'],
                'blocks': -(-len(ids) //
                            self.engine.paged_block_size),
                'prefill_ttft_s': pstats['ttft_s'],
                'handoff_s': time.monotonic() - t0}

    async def handle_kv_prefill(self,
                                request: web.Request) -> web.Response:
        """POST /kv/prefill — the prefill-tier half of a handoff: chunk-
        prefill the prompt into pool blocks, then stream them to the
        decode replica named in `target`."""
        if self.draining:
            return self._unavailable('server is draining for shutdown',
                                     retry_after=5, reason='draining')
        if self.tier == 'decode':
            return web.json_response(
                {'error': 'this replica is decode-tier; /kv/prefill is '
                          'a prefill-tier route'}, status=400)
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            return web.json_response({'error': 'body must be JSON'},
                                     status=400)
        prompt_ids = data.get('prompt_ids')
        target = data.get('target')
        if not isinstance(prompt_ids, (list, tuple)) or not prompt_ids \
                or not all(isinstance(t, int) for t in prompt_ids):
            return web.json_response(
                {'error': 'prompt_ids must be a non-empty token list'},
                status=400)
        if not isinstance(target, str) or not target.startswith('http'):
            return web.json_response(
                {'error': 'target must be the decode replica URL'},
                status=400)
        stream_id = str(data.get('stream_id') or
                        f'h-{time.time_ns():x}')
        try:
            chunk_blocks = int(data.get('chunk_blocks') or
                               serve_constants.handoff_chunk_blocks())
        except (TypeError, ValueError):
            return web.json_response(
                {'error': 'chunk_blocks must be an int'}, status=400)
        loop = asyncio.get_event_loop()
        try:
            result = await loop.run_in_executor(
                None, self._prefill_and_push,
                [int(t) for t in prompt_ids], target.rstrip('/'),
                stream_id, chunk_blocks, tracing.current())
        except exceptions.EngineOverloadedError as e:
            return self._unavailable(str(e))
        except _HandoffPushError as e:
            # Mid-stream push failure: the LB aborts the partial
            # ingest and re-dispatches / falls back. 502 = upstream
            # (decode-side or wire) trouble, retryable by contract.
            # push_status relays the DECODE side's verdict so the LB
            # can tell a shed ingest (503: re-dispatching to another
            # prefill replica just recomputes the prefill into the
            # same wall) from a dead wire (retryable elsewhere).
            return web.json_response(
                {'error': str(e), 'stream_id': stream_id,
                 'pushed_chunks': e.pushed,
                 'push_status': e.status}, status=502)
        except fault_injection.InjectedFault as e:
            return web.json_response(
                {'error': f'handoff stream fault: {e}',
                 'stream_id': stream_id}, status=500)
        except ValueError as e:
            # Prefix evicted between prefill and export (storm
            # pressure), or an unservable prompt: retryable conflict —
            # the LB re-dispatches or falls back monolithic.
            return web.json_response(
                {'error': str(e), 'stream_id': stream_id}, status=409)
        return web.json_response(result)

    async def handle_kv_ingest(self,
                               request: web.Request) -> web.Response:
        """POST /kv/ingest — apply one framed handoff chunk to this
        decode replica's pool (see engine.ingest_chunk for the
        idempotency/rollback contract)."""
        from skypilot_tpu.models import kv_cache as kv_cache_lib
        if self.tier == 'prefill':
            return web.json_response(
                {'error': 'this replica is prefill-tier; /kv/ingest is '
                          'a decode-tier route'}, status=400)
        data = await request.read()
        if not data:
            return web.json_response({'error': 'empty chunk'},
                                     status=400)
        loop = asyncio.get_event_loop()
        try:
            result = await loop.run_in_executor(
                None, self.engine.ingest_chunk, data)
        except kv_cache_lib.ChunkSequenceError as e:
            return web.json_response(
                {'error': str(e), 'expected': e.expected}, status=409)
        except kv_cache_lib.ChunkError as e:
            return web.json_response({'error': str(e)}, status=400)
        except exceptions.EngineDrainingError as e:
            return self._unavailable(str(e), retry_after=5,
                                     reason='draining')
        except exceptions.EngineOverloadedError as e:
            # The decode-side admission gate: shed, never corrupt.
            return self._unavailable(str(e), retry_after=1,
                                     reason='ingest-pressure')
        except fault_injection.InjectedFault as e:
            return web.json_response(
                {'error': f'ingest fault: {e}'}, status=500)
        return web.json_response(result)

    async def handle_kv_abort(self,
                              request: web.Request) -> web.Response:
        """POST /kv/abort — roll a partial handoff stream back to
        refcount-0 (idempotent)."""
        try:
            data = await request.json()
            stream_id = str(data['stream_id'])
        except Exception:  # pylint: disable=broad-except
            return web.json_response(
                {'error': 'body must be JSON with stream_id'},
                status=400)
        aborted = self.engine.abort_ingest(stream_id)
        return web.json_response({'ok': True, 'aborted': aborted})

    # -- multi-tenant adapter registry (docs/serving.md) --
    #
    # POST /adapters/load   {"name": n, "path": p}  — register the npz
    #   adapter archive at `p` (tenancy.save_adapter_npz format) and
    #   make it RESIDENT in the device-side pool (the device write runs
    #   in the engine tick thread, off the steady decode path).
    # DELETE /adapters/{name} — unregister; 409 while in-flight
    #   requests pin it, 404 when unknown.
    # GET /adapters — registry/residency/refcount snapshot.

    async def handle_adapter_load(self,
                                  request: web.Request) -> web.Response:
        if self.draining:
            return self._unavailable('server is draining for shutdown',
                                     retry_after=5, reason='draining')
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            return web.json_response({'error': 'body must be JSON'},
                                     status=400)
        name = data.get('name')
        path = data.get('path')
        if not isinstance(name, str) or not isinstance(path, str):
            return web.json_response(
                {'error': 'need name and path (npz adapter archive, '
                          'tenancy.save_adapter_npz format)'},
                status=400)
        from skypilot_tpu.serve import tenancy
        loop = asyncio.get_event_loop()

        def load():
            tree = tenancy.load_adapter_npz(os.path.expanduser(path))
            return self.engine.load_adapter(name, tree)

        try:
            slot = await loop.run_in_executor(None, load)
        except exceptions.AdapterPoolExhaustedError as e:
            return self._unavailable(str(e), retry_after=2,
                                     reason='adapter-pool')
        except exceptions.UnknownAdapterError as e:
            return web.json_response({'error': str(e)}, status=400)
        except (ValueError, OSError) as e:
            return web.json_response({'error': str(e)}, status=400)
        except fault_injection.InjectedFault as e:
            return web.json_response(
                {'error': f'adapter load fault: {e}'}, status=500)
        return web.json_response({'ok': True, 'name': name,
                                  'slot': slot})

    async def handle_adapter_delete(self,
                                    request: web.Request) -> web.Response:
        name = request.match_info['name']
        loop = asyncio.get_event_loop()
        try:
            await loop.run_in_executor(
                None, self.engine.unload_adapter, name)
        except exceptions.AdapterInUseError as e:
            return web.json_response({'error': str(e)}, status=409)
        except exceptions.UnknownAdapterError as e:
            return web.json_response({'error': str(e)}, status=404)
        except fault_injection.InjectedFault as e:
            return web.json_response(
                {'error': f'adapter evict fault: {e}'}, status=500)
        return web.json_response({'ok': True, 'name': name})

    async def handle_adapters(self,
                              request: web.Request) -> web.Response:
        del request
        return web.json_response(self.engine.adapters_info())

    async def handle_traces(self, request: web.Request) -> web.Response:
        """GET /traces — this process's span ring as JSON (the
        `skytpu trace --url` feed), plus the histogram exemplars that
        link metrics to trace ids (docs/observability.md "Tracing").
        `?window_s=N` restricts to recent spans."""
        window: Optional[float] = None
        raw = request.query.get('window_s')
        if raw:
            try:
                window = float(raw)
            except ValueError:
                return web.json_response(
                    {'error': 'window_s must be a number'}, status=400)
        return web.json_response({
            'schema': 'skytpu-traces/1',
            'enabled': tracing.enabled(),
            'spans': tracing.snapshot(window_s=window),
            'exemplars': exposition.collect_exemplars(),
        })

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition of the process-wide registry:
        engine TTFT/TPOT histograms, queue depth, shed counters, and
        whatever else this process recorded (docs/observability.md)."""
        del request
        _DRAINING_GAUGE.set(1 if self.draining else 0)
        return web.Response(text=exposition.generate_latest(),
                            content_type='text/plain',
                            charset='utf-8')

    # -- OpenAI-compatible surface --
    #
    # The reference's serving recipes expose the OpenAI API via vLLM;
    # existing OpenAI-client code points its base_url here unchanged.
    # Scope: text + chat completions with `stream: true` SSE (chunk
    # objects + [DONE], deltas from the engine's per-token callback),
    # temperature, max_tokens, stop strings (post-hoc truncation;
    # stop+stream rejected — partial-match holdback is out of scope),
    # and usage accounting. One choice per request (`n` > 1 → 400).
    # top_k/top_p are ENGINE-level (--top-k/--top-p: jit-static, one
    # compile); a request's own top_p is rejected with 400 unless it is
    # the no-op client default (top_p=1) — silently sampling from a
    # different distribution than asked would be worse than failing.

    def _truncate_at_stop(self, text: str, stop) -> tuple:
        """Earliest occurrence of ANY stop sequence wins (OpenAI
        semantics — list order is irrelevant)."""
        if not stop:
            return text, 'length'
        hits = [idx for s in
                ([stop] if isinstance(stop, str) else list(stop))
                if (idx := text.find(s)) >= 0]
        if hits:
            return text[:min(hits)], 'stop'
        return text, 'length'

    @staticmethod
    def _openai_error(message: str, status: int = 400,
                      retry_after: Optional[int] = None,
                      shed_reason: Optional[str] = None) -> web.Response:
        """`shed_reason` (overloaded/draining/wedged) feeds the same
        shed counter as /generate — passed explicitly by the call site
        that caught the exception, never inferred from message text."""
        err_type = ('invalid_request_error' if status == 400 else
                    'server_error')
        headers = ({'Retry-After': str(retry_after)}
                   if retry_after is not None else None)
        if shed_reason == 'draining':
            headers = dict(headers or {})
            headers['X-SkyTPU-Draining'] = '1'
        if shed_reason is not None:
            _SHED_TOTAL.labels(reason=shed_reason).inc()
        return web.json_response(
            {'error': {'message': message, 'type': err_type}},
            status=status, headers=headers)

    def _validate_openai(self, data: dict):
        if data.get('stream') and data.get('stop'):
            # Streaming + stop strings would need partial-match
            # holdback to avoid emitting text past the stop; refusing
            # beats silently streaming wrong output.
            return self._openai_error(
                'stream=true with stop strings is not supported; '
                'drop stop or stream=false')
        if int(data.get('n') or 1) != 1:
            return self._openai_error('only n=1 is supported')
        req_top_p = data.get('top_p')
        if req_top_p is not None and float(req_top_p) != 1.0:
            return self._openai_error(
                'per-request top_p is not supported (filters are '
                'engine-level: serve with --top-p/--top-k); send '
                'top_p=1 or omit it')
        max_new = int(data.get('max_tokens') or 16)
        if not 0 < max_new < self.engine.cfg.max_seq_len:
            return self._openai_error(
                f'max_tokens must be in (0, '
                f'{self.engine.cfg.max_seq_len}) for this model')
        return None

    @staticmethod
    def _prompts_to_lists(prompt):
        """OpenAI's four prompt shapes: str, [str, ...], [int, ...]
        (ONE tokenized prompt), [[int, ...], ...]."""
        if isinstance(prompt, str):
            return [prompt]
        if isinstance(prompt, list):
            if prompt and all(isinstance(t, int) for t in prompt):
                return [prompt]
            return prompt
        raise ValueError('prompt must be a string, list of strings, or '
                         'token array(s)')

    async def handle_v1_completions(self,
                                    request: web.Request) -> web.Response:
        if self.draining:
            return self._openai_error('server is draining for shutdown',
                                      status=503, retry_after=5,
                                      shed_reason='draining')
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            return self._openai_error('body must be JSON')
        err = self._validate_openai(data)
        if err is not None:
            return err
        prompt = data.get('prompt')
        if prompt is None:
            return self._openai_error('prompt is required')
        futures = []
        try:
            prompts = self._prompts_to_lists(prompt)
            prompt_ids = [self.encode(p) if isinstance(p, str) else
                          [int(t) for t in p] for p in prompts]
            max_new = int(data.get('max_tokens') or 16)
            temperature = float(data.get('temperature') or 0.0)
            deadline = self._deadline_for(data)
            adapter, priority = self._tenant_fields(data)
            if data.get('stream'):
                if len(prompt_ids) != 1:
                    return self._openai_error(
                        'stream=true takes exactly one prompt')
                return await self._stream_completions(
                    request, data, prompt_ids[0], max_new, temperature,
                    deadline=deadline, adapter=adapter,
                    priority=priority)
            too_big = self._batch_capacity_error(len(prompt_ids))
            if too_big is not None:
                return self._openai_error(too_big)
            for ids in prompt_ids:
                futures.append(self._submit_one(ids, max_new,
                                                temperature,
                                                deadline=deadline,
                                                adapter=adapter,
                                                priority=priority))
        except (TypeError, ValueError,
                exceptions.UnknownAdapterError) as e:
            # Bad shapes/values (empty prompt, non-numeric fields,
            # unregistered adapter, ...) surface as OpenAI-format 400s,
            # not aiohttp 500s.
            self._cancel_all(futures)
            return self._openai_error(str(e))
        except exceptions.TierDeadlineUnmeetableError as e:
            self._cancel_all(futures)
            return self._openai_error(str(e), status=429, retry_after=1,
                                      shed_reason='deadline')
        except exceptions.EngineOverloadedError as e:
            # OpenAI clients back off on 429 (rate limit semantics);
            # cancel the already-submitted head of the batch so shed
            # work does not keep consuming queue depth.
            self._cancel_all(futures)
            return self._openai_error(str(e), status=429, retry_after=1,
                                      shed_reason='overloaded')
        try:
            gathered = await asyncio.gather(
                *[asyncio.wrap_future(f) for f in futures])
        except exceptions.RequestDeadlineExceededError as e:
            return self._openai_error(str(e), status=504)
        except exceptions.EngineWedgedError as e:
            return self._openai_error(str(e), status=503, retry_after=2,
                                      shed_reason='wedged')
        choices = []
        completion_tokens = 0
        for i, (out, _st) in enumerate(gathered):
            text, finish = self._truncate_at_stop(self.decode(out),
                                                  data.get('stop'))
            completion_tokens += len(out)
            choices.append({'index': i, 'text': text, 'logprobs': None,
                            'finish_reason': finish})
        prompt_tokens = sum(len(p) for p in prompt_ids)
        return web.json_response({
            'id': f'cmpl-{int(time.time() * 1e3):x}',
            'object': 'text_completion',
            'created': int(time.time()),
            'model': data.get('model') or self.engine.cfg.name,
            'choices': choices,
            'usage': {'prompt_tokens': prompt_tokens,
                      'completion_tokens': completion_tokens,
                      'total_tokens': prompt_tokens + completion_tokens},
        })

    async def _stream_completions(self, request, data, ids, max_new,
                                  temperature, deadline=None,
                                  adapter=None, priority='standard'
                                  ) -> web.StreamResponse:
        """OpenAI text-completion SSE chunks, closed by `data: [DONE]`."""
        cmpl_id = f'cmpl-{int(time.time() * 1e3):x}'
        created = int(time.time())
        model = data.get('model') or self.engine.cfg.name

        def chunk(text, finish=None):
            return {'id': cmpl_id, 'object': 'text_completion',
                    'created': created, 'model': model,
                    'choices': [{'index': 0, 'text': text,
                                 'logprobs': None,
                                 'finish_reason': finish}]}

        tokens, future = self._token_stream(ids, max_new, temperature,
                                            deadline=deadline,
                                            adapter=adapter,
                                            priority=priority)
        push, flush = self._delta_decoder()
        try:
            # Inside the try: a client that disconnects during prepare
            # must still cancel the already-submitted generation.
            resp = await self._sse_prepare(request)
            async for tok in tokens:
                delta = push(tok)
                if delta:
                    await self._sse_send(resp, chunk(delta))
            exc = future.exception()
            if exc is not None:
                # Mid-stream engine failure: an error event and NO
                # [DONE] — a truncated stream must not parse as a clean
                # completion.
                await self._sse_send(resp, {'error': {
                    'message': str(exc), 'type': 'server_error'}})
                await resp.write_eof()
                return resp
            await self._sse_send(resp, chunk(flush(), finish='length'))
            await self._sse_send(resp, '[DONE]')
            await resp.write_eof()
        finally:
            future.cancel()  # free the decode slot if the client left
        return resp

    async def _stream_chat(self, request, data, ids, max_new,
                           temperature, deadline=None, adapter=None,
                           priority='standard') -> web.StreamResponse:
        """OpenAI chat-completion SSE chunks (delta objects), closed by
        `data: [DONE]`."""
        chat_id = f'chatcmpl-{int(time.time() * 1e3):x}'
        created = int(time.time())
        model = data.get('model') or self.engine.cfg.name

        def chunk(delta, finish=None):
            return {'id': chat_id, 'object': 'chat.completion.chunk',
                    'created': created, 'model': model,
                    'choices': [{'index': 0, 'delta': delta,
                                 'finish_reason': finish}]}

        tokens, future = self._token_stream(ids, max_new, temperature,
                                            deadline=deadline,
                                            adapter=adapter,
                                            priority=priority)
        try:
            resp = await self._sse_prepare(request)
            await self._sse_send(resp, chunk({'role': 'assistant'}))
            push, flush = self._delta_decoder()
            async for tok in tokens:
                delta = push(tok)
                if delta:
                    await self._sse_send(resp, chunk({'content': delta}))
            exc = future.exception()
            if exc is not None:
                await self._sse_send(resp, {'error': {
                    'message': str(exc), 'type': 'server_error'}})
                await resp.write_eof()
                return resp
            tail = flush()
            if tail:
                await self._sse_send(resp, chunk({'content': tail}))
            await self._sse_send(resp, chunk({}, finish='length'))
            await self._sse_send(resp, '[DONE]')
            await resp.write_eof()
        finally:
            future.cancel()  # free the decode slot if the client left
        return resp

    async def handle_v1_chat(self, request: web.Request) -> web.Response:
        if self.draining:
            return self._openai_error('server is draining for shutdown',
                                      status=503, retry_after=5,
                                      shed_reason='draining')
        try:
            data = await request.json()
        except Exception:  # pylint: disable=broad-except
            return self._openai_error('body must be JSON')
        err = self._validate_openai(data)
        if err is not None:
            return err
        messages = data.get('messages')
        if not messages:
            return self._openai_error('messages is required')
        # Model-fidelity first: when serving with --tokenizer hf:<path>
        # and the tokenizer ships a chat template, use it. Otherwise a
        # generic role-tagged template.
        try:
            ids = None
            if (self._hf_tokenizer is not None and
                    getattr(self._hf_tokenizer, 'chat_template', None)):
                ids = self._hf_tokenizer.apply_chat_template(
                    messages, add_generation_prompt=True)
            if ids is None:
                parts = [
                    f'{m.get("role", "user")}: {m.get("content", "")}'
                    for m in messages
                ]
                ids = self.encode('\n'.join(parts) + '\nassistant:')
            max_new = int(data.get('max_tokens') or 16)
            temperature = float(data.get('temperature') or 0.0)
            deadline = self._deadline_for(data)
            adapter, priority = self._tenant_fields(data)
            if data.get('stream'):
                return await self._stream_chat(request, data, ids,
                                               max_new, temperature,
                                               deadline=deadline,
                                               adapter=adapter,
                                               priority=priority)
            future = self._submit_one(ids, max_new, temperature,
                                      deadline=deadline,
                                      adapter=adapter,
                                      priority=priority)
        except (TypeError, ValueError, AttributeError,
                exceptions.UnknownAdapterError) as e:
            return self._openai_error(str(e))
        except exceptions.TierDeadlineUnmeetableError as e:
            return self._openai_error(str(e), status=429, retry_after=1,
                                      shed_reason='deadline')
        except exceptions.EngineOverloadedError as e:
            return self._openai_error(str(e), status=429, retry_after=1,
                                      shed_reason='overloaded')
        try:
            out, _st = await asyncio.wrap_future(future)
        except exceptions.RequestDeadlineExceededError as e:
            return self._openai_error(str(e), status=504)
        except exceptions.EngineWedgedError as e:
            return self._openai_error(str(e), status=503, retry_after=2,
                                      shed_reason='wedged')
        text, finish = self._truncate_at_stop(self.decode(out),
                                              data.get('stop'))
        prompt_tokens, completion_tokens = len(ids), len(out)
        return web.json_response({
            'id': f'chatcmpl-{int(time.time() * 1e3):x}',
            'object': 'chat.completion',
            'created': int(time.time()),
            'model': data.get('model') or self.engine.cfg.name,
            'choices': [{'index': 0,
                         'message': {'role': 'assistant',
                                     'content': text},
                         'finish_reason': finish}],
            'usage': {'prompt_tokens': prompt_tokens,
                      'completion_tokens': completion_tokens,
                      'total_tokens': prompt_tokens + completion_tokens},
        })

    async def handle_v1_models(self, request: web.Request) -> web.Response:
        del request
        return web.json_response({
            'object': 'list',
            'data': [{'id': self.engine.cfg.name, 'object': 'model',
                      'owned_by': 'skypilot_tpu'}],
        })

    def _fleet_intel_headers(self) -> dict:
        """Routing intel piggybacked on every response (the
        X-SkyTPU-Draining pattern): current queue load and the prefix-
        cache digest, read by the load balancer's cache-aware /
        least-loaded policy (docs/serving.md "Fleet routing").
        Best-effort by contract — a failure here must never fail a
        response the engine already produced."""
        headers = {}
        engine = getattr(self, 'engine', None)
        if engine is None:
            return headers
        try:
            headers['X-SkyTPU-Queue-Depth'] = str(engine.queue_load())
            headers['X-SkyTPU-Tier'] = getattr(self, 'tier',
                                               'monolithic')
            # The LB's handoff gate needs to know whether its
            # byte-encoded text/chat hints match this replica's own
            # tokenization (docs/serving.md "Disaggregated serving").
            headers['X-SkyTPU-Tokenizer'] = (
                'hf' if getattr(self, '_hf_tokenizer', None) is not None
                else 'byte')
            digest = engine.prefix_digest()
            if digest:
                headers['X-SkyTPU-Prefix-Digest'] = digest
            # Multi-tenant intel: per-tier backlog for tier-aware
            # least-loaded routing, and the resident adapter set for
            # adapter-affinity routing (docs/serving.md). The tier
            # header costs an O(queue) scan under the admission mutex,
            # so it only turns on once tiered traffic (or an adapter
            # pool) actually exists — the LB degrades gracefully
            # without it.
            if hasattr(engine, 'tier_load') and (
                    getattr(engine, 'max_adapters', 0) or
                    getattr(engine, '_tiers_active', False)):
                from skypilot_tpu.serve import tenancy
                headers['X-SkyTPU-Tier-Load'] = \
                    tenancy.render_tier_load_header(engine.tier_load())
            if getattr(engine, 'max_adapters', 0):
                # Sent even when EMPTY: an eviction-to-none must clear
                # the LB's stale affinity for this replica.
                resident = engine._adapter_pool.resident_names()  # pylint: disable=protected-access
                headers['X-SkyTPU-Adapters'] = ','.join(resident)
        except Exception:  # pylint: disable=broad-except
            logger.debug('fleet-intel headers unavailable', exc_info=True)
        return headers

    def make_app(self) -> web.Application:
        # Serving a /metrics route IS attaching an exporter: recording
        # flips on here, never at import (tests pin the import path
        # side-effect-free).
        obs.enable()

        @web.middleware
        async def fleet_headers_middleware(request, handler):
            response = await handler(request)
            # Streaming responses (SSE) are already on the wire by the
            # time the middleware sees them — headers are immutable.
            if not response.prepared:
                for key, value in self._fleet_intel_headers().items():
                    response.headers[key] = value
            return response

        app = web.Application(middlewares=[_metrics_middleware,
                                           _tracing_middleware,
                                           fleet_headers_middleware])
        app.router.add_get('/health', self.handle_health)
        app.router.add_get('/metrics', self.handle_metrics)
        app.router.add_get('/traces', self.handle_traces)
        app.router.add_post('/preempt', self.handle_preempt)
        app.router.add_post('/adapters/load', self.handle_adapter_load)
        app.router.add_delete('/adapters/{name}',
                              self.handle_adapter_delete)
        app.router.add_get('/adapters', self.handle_adapters)
        app.router.add_post('/kv/prefill', self.handle_kv_prefill)
        app.router.add_post('/kv/ingest', self.handle_kv_ingest)
        app.router.add_post('/kv/abort', self.handle_kv_abort)
        app.router.add_post('/generate', self.handle_generate)
        app.router.add_post('/v1/completions', self.handle_v1_completions)
        app.router.add_post('/v1/chat/completions', self.handle_v1_chat)
        app.router.add_get('/v1/models', self.handle_v1_models)
        return app


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--model', default='llama3-1b')
    parser.add_argument('--port', type=int, default=8080)
    parser.add_argument('--max-seq-len', type=int, default=None)
    parser.add_argument('--tokenizer', default='byte')
    parser.add_argument('--checkpoint-dir', default=None,
                        help='Orbax checkpoint dir (train/run.py output).')
    parser.add_argument('--hf-model-path', default=None,
                        help='local HuggingFace checkpoint dir; '
                        'converted at load (models/convert.py)')
    parser.add_argument('--num-slots', type=int, default=4,
                        help='concurrent decode slots (continuous '
                             'batching width)')
    parser.add_argument('--tp', type=int, default=1,
                        help='tensor-parallel serving: shard the '
                             'weights, activations and KV cache/pool '
                             'over the first N local devices (kv heads '
                             '/ attention heads / MLP hidden / vocab '
                             'split per parallel/sharding.py; XLA '
                             'inserts the per-layer all-reduce over '
                             'ICI). One endpoint, same API; greedy '
                             'output is bit-identical to tp=1. N must '
                             'divide the model\'s head/kv-head/mlp/'
                             'vocab dims (see docs/performance.md '
                             '"Sharded serving"). 1 = single-chip')
    def _top_k_arg(v):
        k = int(v)
        if k < 0:
            raise argparse.ArgumentTypeError('--top-k must be >= 0')
        return k

    def _top_p_arg(v):
        f = float(v)
        if not 0.0 <= f < 1.0:
            raise argparse.ArgumentTypeError(
                '--top-p must be in [0, 1) (0 = off; 1.0 would be a '
                'no-op — omit the flag instead)')
        return f

    parser.add_argument('--top-k', type=_top_k_arg, default=0,
                        help='sampling: keep only the K highest-logit '
                             'tokens (0 = off; engine-level, one '
                             'compile)')
    parser.add_argument('--top-p', type=_top_p_arg, default=0.0,
                        help='sampling: nucleus filter mass, in [0, 1) '
                             '(0 = off)')
    parser.add_argument('--kv-quant', default=None, choices=['int8'],
                        help='int8 KV cache (per-token scales): halves '
                             'the cache HBM streaming that dominates '
                             'long-context decode')
    parser.add_argument('--quantize', default=None, choices=['int8'],
                        help='weight-only int8 serving: halves the HBM '
                             'weight traffic that bounds decode')
    parser.add_argument('--speculative', type=int, default=0,
                        help='prompt-lookup speculative decoding: draft '
                             'K tokens per tick by n-gram lookup in the '
                             'request context, verify in one forward — '
                             'accepted drafts save decode dispatches; '
                             'greedy output is unchanged (exact). '
                             'Takes precedence over --decode-chunk.')
    parser.add_argument('--decode-chunk', type=int, default=1,
                        help='decode steps per device dispatch when no '
                             'request awaits admission (>1 cuts host '
                             'round trips; admission latency bounded by '
                             'one chunk)')
    parser.add_argument('--prefix-cache', type=int, default=0,
                        help='keep the last N prompts\' prefilled KV; a '
                             'new prompt sharing a cached prefix (chat '
                             'history, shared system prompt) prefills '
                             'only the suffix. Each entry holds a full '
                             'batch-1 KV cache in HBM — size to spare '
                             'memory.')
    parser.add_argument('--paged-block-size', type=int, default=0,
                        help='paged KV cache: pool KV in fixed blocks '
                             'of N tokens with ref-counted block-'
                             'granular prefix sharing and chunked '
                             'prefill (0 = contiguous per-slot cache; '
                             'see docs/performance.md)')
    parser.add_argument('--paged-num-blocks', type=int, default=None,
                        help='paged pool capacity in blocks (default: '
                             '(num_slots + prefix_cache) x max_seq_len '
                             '/ block_size + 1)')
    parser.add_argument('--prefill-chunk', type=int, default=0,
                        help='paged mode: prompt tokens one prefill '
                             'dispatch carries — ONE compiled prefill '
                             'shape, long prompts interleave with decode '
                             '(default 0: the engine\'s rule, what one '
                             'pass over the weights carries before the '
                             'chunk outgrows a decode step — 256 tokens '
                             'in bf16, 128 with int8 weights, in whole '
                             'blocks, at most the context)')
    parser.add_argument('--async-depth', type=int, default=1,
                        help='async decode pipeline: a ring of N '
                             'in-flight decode dispatches, each '
                             'queued off the previous one\'s device '
                             'output before the host waits on any '
                             'result, so host scheduling overlaps '
                             'device compute. The ring rides through '
                             'finishes by length and through joins; '
                             'EOS is detected up to N steps late, '
                             'overshoot discarded — greedy token '
                             'streams stay bit-identical; composes '
                             'with --paged-block-size, --kv-quant and '
                             '--speculative (which still flushes), '
                             'see docs/performance.md. Default 1; '
                             '0 = synchronous ticks')
    parser.add_argument('--max-queue', type=int, default=64,
                        help='admission control: queued-request cap; '
                             'beyond it requests are shed with 429/503 '
                             '+ Retry-After instead of growing the '
                             'batch queue unboundedly (0 = unbounded)')
    parser.add_argument('--request-timeout', type=float, default=0.0,
                        help='per-request deadline cap in seconds '
                             '(0 = none); a request\'s own timeout_s '
                             'can only tighten it')
    parser.add_argument('--watchdog-timeout', type=float, default=120.0,
                        help='engine watchdog: fail in-flight requests '
                             'cleanly when the decode thread makes no '
                             'progress for this long (0 = off); must '
                             'exceed the worst-case decode tick')
    parser.add_argument('--drain-timeout', type=float, default=30.0,
                        help='graceful shutdown (SIGTERM): stop '
                             'admitting, wait up to this long for '
                             'in-flight requests, then exit')
    parser.add_argument('--prefix-store',
                        default=os.environ.get('SKYTPU_PREFIX_STORE'),
                        help='preemption-native serving: store URL for '
                             'hot-prefix artifacts (gs://bucket, '
                             'local://bucket, or a directory). On a '
                             'preemption notice (POST /preempt or '
                             'SIGTERM) cached prefixes export here; at '
                             'startup the newest artifact pre-warms '
                             'the prefix index BEFORE /health goes '
                             'ready. Requires --paged-block-size and '
                             '--prefix-cache. Default: '
                             '$SKYTPU_PREFIX_STORE')
    parser.add_argument('--tier',
                        default=os.environ.get('SKYTPU_REPLICA_TIER',
                                               'monolithic'),
                        choices=['monolithic', 'prefill', 'decode'],
                        help='disaggregated serving tier '
                             '(docs/serving.md): prefill replicas '
                             'compute KV and stream it block-'
                             'granularly to decode replicas '
                             '(/kv/prefill → /kv/ingest); decode '
                             'replicas serve handed-off requests from '
                             'the ingested prefix. Requires '
                             '--paged-block-size and --prefix-cache '
                             'for the specialized tiers. Default: '
                             '$SKYTPU_REPLICA_TIER or monolithic')
    parser.add_argument('--max-adapters', type=int, default=0,
                        help='multi-tenant serving: hold up to N LoRA '
                             'adapters resident in a device-side pool '
                             'and batch requests for DIFFERENT '
                             'adapters (and the base model) into one '
                             'decode dispatch. Adapters register via '
                             'POST /adapters/load; requests pick one '
                             'with the `adapter` field. 0 = off '
                             '(docs/serving.md "Multi-tenant serving")')
    parser.add_argument('--adapter-rank', type=int, default=0,
                        help='uniform LoRA rank every resident adapter '
                             'must share (required with --max-adapters)')
    parser.add_argument('--adapter-alpha', type=float, default=16.0,
                        help='LoRA alpha for the resident adapters')
    parser.add_argument('--adapter-targets', default='',
                        help='comma list of adapted projections from '
                             '{q,k,v,o,gate,up,down} (default: the '
                             "model config's lora_targets)")
    parser.add_argument('--decode-kernel', default='xla',
                        choices=['xla', 'pallas', 'pallas_interpret'],
                        help='paged decode attention kernel: xla '
                             '(default; gather + einsum) or pallas '
                             '(fused VMEM block-table walk — dequant, '
                             'score, softmax and weighted sum in one '
                             'pass; also fuses resident multi-LoRA '
                             'gather+dot). Requires --paged-block-size '
                             'and a TPU (it refuses to start without '
                             'one, and under --tp > 1); '
                             'pallas_interpret runs the same kernel '
                             'under the Pallas interpreter on the CPU '
                             '(docs/performance.md "Fused decode '
                             'kernel")')
    parser.add_argument('--preempt-drain-timeout', type=float,
                        default=serve_constants
                        .preempt_notice_budget_seconds(),
                        help='default notice budget (seconds) for '
                             'POST /preempt when the notice does not '
                             'carry its own deadline_s (same env knob '
                             'and default the replica manager uses: '
                             '$SKYTPU_SERVE_PREEMPT_NOTICE_BUDGET, '
                             'docs/resilience.md)')
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    from skypilot_tpu.parallel import distributed
    distributed.initialize()
    server = InferenceServer(args.model, max_seq_len=args.max_seq_len,
                             tokenizer=args.tokenizer,
                             checkpoint_dir=args.checkpoint_dir,
                             hf_model_path=args.hf_model_path,
                             num_slots=args.num_slots,
                             quantize=args.quantize,
                             decode_chunk=args.decode_chunk,
                             kv_quant=args.kv_quant,
                             top_k=args.top_k, top_p=args.top_p,
                             speculative=args.speculative,
                             prefix_cache=args.prefix_cache,
                             max_queue_depth=args.max_queue,
                             request_timeout=args.request_timeout,
                             watchdog_timeout=args.watchdog_timeout,
                             paged_block_size=args.paged_block_size,
                             paged_num_blocks=args.paged_num_blocks,
                             prefill_chunk=args.prefill_chunk,
                             async_depth=args.async_depth,
                             prefix_store=args.prefix_store,
                             preempt_drain_timeout=args.preempt_drain_timeout,
                             tp=args.tp,
                             tier=args.tier,
                             max_adapters=args.max_adapters,
                             adapter_rank=args.adapter_rank,
                             adapter_alpha=args.adapter_alpha,
                             adapter_targets=args.adapter_targets,
                             decode_kernel=args.decode_kernel)
    logger.info('sampling filters: top_k=%s top_p=%s (0 = off)',
                args.top_k, args.top_p)
    distributed.log_device_memory('after weights placed')
    # Preemption pre-warm BEFORE ready: a replacement replica restores
    # the fleet's hot prefixes so its first shared-prefix request is a
    # cache hit, not a TTFT cliff.
    prewarm = server.prewarm_from_store()
    if prewarm is not None:
        logger.info('prefix pre-warm: %s', prewarm)
    server.warmup()

    # Graceful drain on SIGTERM: stop admitting (health flips to 503 so
    # the LB pulls this replica), finish in-flight requests, then exit.
    import signal
    import threading

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)

    def _graceful_exit():
        raise web.GracefulExit()

    def _drain_and_exit():
        # SIGTERM-with-deadline IS a preemption notice: same drain +
        # prefix-export body as POST /preempt, then exit.
        logger.info('SIGTERM: draining (finishing in-flight requests, '
                    'budget %.0fs)...', args.drain_timeout)
        result = server._drain_and_export(args.drain_timeout)  # pylint: disable=protected-access
        logger.info('drain %s; export: %s; shutting down.',
                    'complete' if result['drained'] else 'timed out',
                    result.get('export') or result.get('error'))
        _schedule_exit()

    exit_scheduled = threading.Event()

    def _schedule_exit():
        if not exit_scheduled.is_set():
            exit_scheduled.set()
            loop.call_soon_threadsafe(_graceful_exit)

    def _await_notice_then_exit():
        # Already draining when the kill signal landed. The notice
        # body is run-once-and-cached, so this call covers every
        # interleaving: a POST /preempt that finished earlier returns
        # its cached outcome immediately; one mid-flight is waited
        # for; one scheduled but not yet started loses the race and
        # THIS thread performs the drain+export instead. Then ALWAYS
        # exit: swallowing the SIGTERM here used to leave the process
        # running until SIGKILL.
        server._drain_and_export(args.drain_timeout)  # pylint: disable=protected-access
        _schedule_exit()

    def _on_sigterm(signum, frame):
        del signum, frame
        if server.draining:
            threading.Thread(target=_await_notice_then_exit,
                             daemon=True, name='drain-exit').start()
            return
        server.draining = True
        _DRAINING_GAUGE.set(1)
        threading.Thread(target=_drain_and_exit, daemon=True,
                         name='drain').start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    web.run_app(server.make_app(), host='0.0.0.0', port=args.port,
                handle_signals=False, loop=loop)
    return 0


if __name__ == '__main__':
    sys.exit(main())
