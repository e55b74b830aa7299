"""Benchmark: flagship-model training throughput on the local TPU chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Extra keys carry the sequence-length sweep (seq 2048/4096 MFU+tps) and
the serving TTFT rows so one line records the whole perf surface.

- Model: llama3-1b (the flagship Llama-3-style architecture at a size that
  trains on a single 16 GB v5e chip; same code path as the 8B/70B configs).
- Measures steady-state step time of the full jitted train step (fwd + bwd +
  adamw) on synthetic data, reports tokens/sec/chip.
- vs_baseline = achieved MFU ÷ 0.45, the north-star MFU bar from
  BASELINE.md (the reference publishes no throughput numbers of its own —
  SURVEY §6 — so the MFU target is the tracking metric).
- The default run ALSO sweeps seq 2048/4096 and measures serving p50/p99
  TTFT (continuous-batching engine, decode_chunk=8); --serve/--quantize
  measure a single serving config explicitly.

One process: `main()` runs the measurement in the process that holds the
chip (a chip belongs to one process at a time, so nothing probes it from
a child first). Without a TPU the measurement rows refuse to run;
`--quick` is the tiny CPU smoke of the same code path, and the
`--dryrun-*` rows are CPU correctness rows that run in a child with
eight fake devices. A row that throws ends the run non-zero.

Param dtype is bf16 here: fp32 master weights + Adam moments for a ~1B
model would exceed a single v5e's HBM; throughput/MFU are unaffected.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_TIMEOUT_S = float(os.environ.get('SKYTPU_BENCH_TIMEOUT', '1200'))


def _emit_skip(reason: str, **extra) -> None:
    """The bench contract is ONE machine-parseable JSON line on stdout.
    A row that cannot run (an engine the constructor refuses, too few
    devices) honors it too: {"skipped": true, ...}."""
    print(json.dumps({'skipped': True, 'reason': reason, **extra}))


def _parse_args(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='llama3-1b')
    parser.add_argument('--steps', type=int, default=10)
    parser.add_argument('--warmup', type=int, default=2)
    parser.add_argument('--batch', type=int, default=8)
    parser.add_argument('--seq', type=int, default=1024)
    parser.add_argument('--sweep-seq', default='2048,4096',
                        help='extra sequence lengths for the default '
                             'TPU sweep ("" disables)')
    parser.add_argument('--quick', action='store_true',
                        help='tiny model, few steps (smoke)')
    parser.add_argument('--serve', action='store_true',
                        help='measure ONLY inference p50 TTFT')
    parser.add_argument('--tp', type=int, default=0,
                        help='serve row: tensor-parallel degree — '
                             'shard the engine (weights + KV pool on '
                             'the kv-head axis) over the first N local '
                             'devices via parallel.decode_mesh; the '
                             'row reports per-device weight/pool HBM '
                             'and the compiled-HLO all-reduce count '
                             '(0/1 = single-chip, the historical row)')
    parser.add_argument('--dryrun-serve-sharded', action='store_true',
                        help='emit the MULTICHIP_serve proxy row on 8 '
                             'fake CPU devices (no chip needed): tp=N '
                             '(--tp, default 2) sharded engine vs its '
                             'single-chip twin — bit-identical greedy, '
                             'per-device weights+pool <= (1/N + eps), '
                             'collective count from the compiled-HLO '
                             'probe (parallel/hlo_probe)')
    parser.add_argument('--dryrun-train-zero1', action='store_true',
                        help='emit the MULTICHIP_train_zero1 proxy row '
                             'on 8 fake CPU devices (no chip needed): '
                             'ZeRO-1 weight-update sharding on a dp=8 '
                             'mesh vs the unsharded trainer — '
                             'bit-identical loss+grad_norm over 3 '
                             'steps (with and without grad_accum), '
                             'per-device optimizer-state bytes <= '
                             '(1/dp + eps), and reduce-scatter + '
                             'all-gather counts from the compiled-HLO '
                             'probe (parallel/hlo_probe)')
    parser.add_argument('--dryrun-train-elastic', action='store_true',
                        help='emit the MULTICHIP_train_elastic proxy '
                             'row on 8 fake CPU devices (no chip '
                             'needed): a 2-notice preemption storm '
                             'over the elastic train loop — dp=4 → '
                             'surviving dp=2 → grown-back dp=4 — '
                             'reporting steps-lost-per-preemption '
                             '(pinned 0 beyond the in-flight step), '
                             'per-incarnation resume latency, and '
                             'loss bit-parity vs an unpreempted run '
                             'over the same data order')
    parser.add_argument('--dryrun-serve-fleet', action='store_true',
                        help='emit the FLEET_serve proxy row on CPU (no '
                             'chip needed): a 3-replica fleet of real '
                             'engines routed by the prefix-aware policy '
                             'vs round-robin on a shared-prefix '
                             'workload — reports prefix-hit ratio, '
                             'retry amplification past a dead replica, '
                             'p50/p99 routed TTFT per policy, and pins '
                             'that miss/stale/corrupt-digest routing '
                             'falls back instead of erroring')
    parser.add_argument('--dryrun-serve-disagg', action='store_true',
                        help='emit the DISAGG_serve proxy row on CPU '
                             '(no chip needed): a tiered fleet of real '
                             'engines (1 prefill + 2 decode) runs a '
                             'long-prompt storm through the two-stage '
                             'KV handoff while a phase-aware '
                             'monolithic 3-replica fleet runs the same '
                             'storm — reports short-prompt (decode-'
                             'tier) TTFT under the storm for both, '
                             'measured handoff chunk/byte counters '
                             'pinned against the expected block math, '
                             'and greedy bit-identity vs a monolithic '
                             'oracle for every request')
    parser.add_argument('--dryrun-serve-multitenant', action='store_true',
                        help='emit the MULTITENANT_serve proxy row on '
                             'CPU (no chip needed): ONE real engine '
                             'holds 3 resident LoRA adapters and '
                             'serves a 3-adapter × 3-tier mix — pins '
                             'per-adapter greedy BIT-IDENTITY vs '
                             'three dedicated single-adapter engines, '
                             'one-decode-dispatch batching (compile '
                             'count == 1 + shared step_log rows), and '
                             'interactive p50 TTFT under a batch-tier '
                             'flood vs the same flood untiered '
                             '(docs/serving.md "Multi-tenant '
                             'serving")')
    parser.add_argument('--dryrun-trace', action='store_true',
                        help='emit the TRACE proxy row on CPU (no chip '
                             'needed): a real 2-hop disaggregated '
                             'handoff (1 prefill + 1 decode server '
                             'behind the real LB, live HTTP) with '
                             'tracing ON — pins ONE trace with '
                             'LB→prefill→ingest→decode parentage '
                             'intact (≥4 hops) and the '
                             'queue-wait/prefill/decode span shape, '
                             'and reports the measured enabled-vs-'
                             'disabled decode-tick overhead ratio '
                             '(docs/observability.md "Tracing")')
    parser.add_argument('--dryrun-lint', action='store_true',
                        help='emit the SKYLINT proxy row (no chip, no '
                             'jax): run the AST correctness analyzer '
                             '(skytpu lint, docs/static-analysis.md) '
                             'over skypilot_tpu/ and report unwaived '
                             'findings — 0 is the pinned bar, so the '
                             'dryrun supervisor surfaces lint '
                             'regressions next to the perf proxies')
    parser.add_argument('--no-serve-row', action='store_true',
                        help='skip the serve row in the default sweep')
    parser.add_argument('--quantize', default=None, choices=['int8'],
                        help='serving engine int8 weight-only variant')
    parser.add_argument('--kv-quant', default=None, choices=['int8'],
                        help='serving engine int8 KV cache variant')
    parser.add_argument('--int8-kv', action='store_true',
                        help='shorthand for --kv-quant int8; composes '
                             'with --paged-block-size (int8 block '
                             'pool: the serve row reports the pool '
                             'bytes saved) and --async-depth N')
    parser.add_argument('--decode-chunk', type=int, default=8,
                        help='decode steps per dispatch for the serve '
                             'row (amortizes host round-trips)')
    parser.add_argument('--speculative', type=int, default=0,
                        help='serve row: prompt-lookup speculative '
                             'decoding draft length')
    parser.add_argument('--prefix-cache', type=int, default=0,
                        help='serve row: LRU of N prefilled prompts; '
                             'shared-prefix requests prefill only the '
                             'suffix')
    parser.add_argument('--paged-block-size', type=int, default=0,
                        help='serve row: paged KV cache with N-token '
                             'blocks (block-granular prefix sharing + '
                             'chunked prefill); the row reports pool '
                             'occupancy')
    parser.add_argument('--async-depth', type=int, default=0,
                        help='serve row: async decode pipeline — a '
                             'ring of N in-flight decode dispatches '
                             'chained off each other\'s device output; '
                             'the row reports the host-gap fraction '
                             'the pipeline removes and the chained-'
                             'dispatch count (0 = synchronous ticks)')
    parser.add_argument('--decode-kernel', default='xla',
                        choices=['xla', 'pallas', 'pallas_interpret'],
                        help='serve row: paged decode attention kernel '
                             '— xla (gather + einsum) or pallas (fused '
                             'VMEM block-table walk; requires '
                             '--paged-block-size). The kernel-vs-XLA '
                             'tok/s + MFU diff on a real chip is the '
                             'standing BASELINE.md action')
    parser.add_argument('--dryrun-serve-kernel', action='store_true',
                        help='emit the KERNEL_serve proxy row on CPU '
                             '(no chip needed): the fused pallas '
                             'decode kernel (interpreter mode) next '
                             'to its XLA twin — greedy streams across '
                             'the composition cells, the compiled-'
                             'HLO gather-count diff (the pool-window '
                             'gather the in-kernel table walk '
                             'deletes), the fused HBM bytes-per-step '
                             'accounting, and the fused multi-LoRA '
                             'pays/does-not-pay verdict '
                             '(docs/performance.md "Fused decode '
                             'kernel")')
    parser.add_argument('--tune-attn', action='store_true',
                        help='sweep flash-attention block sizes per '
                             'sequence length (fwd+bwd wall time) and '
                             'report the best; use to pick '
                             'attn_block_q/attn_block_k defaults')
    parser.add_argument('--worker', action='store_true',
                        help='the --dryrun-* supervisor\'s child: run '
                             'the row in this process')
    args = parser.parse_args(argv)
    return args


class _UnsupportedServeCombo(Exception):
    """Engine CONSTRUCTION rejected the flag combination — a
    deterministic verdict worth a structured skip. Errors raised after
    construction are real failures and propagate as themselves."""


def _measure_ttft(cfg, mesh, quantize=None, decode_chunk=1,
                  kv_quant=None, speculative=0, prefix_cache=0,
                  paged_block_size=0, async_depth=0,
                  decode_kernel='xla') -> dict:
    """p50/p99 time-to-first-token + aggregate decode throughput under
    concurrent requests on the local chip(s) via the continuous-batching
    engine (models/inference.py) — the BASELINE.md serving row.

    `mesh` with tp>1 (parallel.decode_mesh) measures the SHARDED
    engine: the row gains per-device weight/pool HBM and the
    compiled-HLO all-reduce proxy next to the usual TTFT numbers."""
    import time as time_lib

    from skypilot_tpu.models import inference as inference_lib
    try:
        engine = inference_lib.ContinuousBatchingEngine(
            cfg, num_slots=4, mesh=mesh, quantize=quantize,
            decode_chunk=decode_chunk, kv_quant=kv_quant,
            speculative=speculative, prefix_cache=prefix_cache,
            paged_block_size=paged_block_size, async_depth=async_depth,
            decode_kernel=decode_kernel)
    except (ValueError, NotImplementedError) as e:
        raise _UnsupportedServeCombo(str(e)) from e
    prompt = list(range(1, 33))
    # Warmup: compile prefill + decode (and the verify step, if on).
    engine.generate(prompt, max_new_tokens=4)
    if paged_block_size and prefix_cache:
        # Second warmup HITS the prefix the first one stored, compiling
        # the copy-on-write clone too — otherwise the first measured
        # request pays that jit and pollutes the p99 TTFT this row
        # exists to benchmark.
        engine.generate(prompt, max_new_tokens=4)
    # Host-gap deltas from engine.tick_stats — the exact quantity the
    # skytpu_engine_tick_host_gap_seconds histogram records, read
    # WITHOUT obs.enable(): turning recording on would add per-observe
    # locking inside the very loop being measured.
    gap0 = engine.tick_stats['host_gap_s']
    chained0 = engine.tick_stats['chained']
    t0 = time_lib.time()
    stats = engine.measure_ttft(num_requests=16, prompt=prompt,
                                max_new_tokens=16, return_stats=True)
    wall = time_lib.time() - t0
    occupancy = engine.paged_occupancy()
    tick_stats = dict(engine.tick_stats)
    host_gap_s = tick_stats['host_gap_s'] - gap0
    tp_row = {}
    if getattr(engine, '_tp', 1) > 1:
        mem = engine.memory_footprint()
        hlo = engine.decode_hlo_stats()
        tp_row = {
            'tp': mem['tp'],
            'per_device_weight_mb': round(
                mem['weight_bytes_per_device'] / 2**20, 2),
            'per_device_kv_mb': round(
                mem['kv_bytes_per_device'] / 2**20, 2),
            'tp_collectives': hlo['total'],
            'tp_allreduce_bytes_per_step': hlo['all_reduce_bytes'],
        }
    engine.stop()
    ttfts = sorted(st['ttft_s'] for st in stats)
    total_new = sum(st['new_tokens'] for st in stats)
    # Two throughput views: e2e = all tokens / wall (includes prefill +
    # queue wait through the 4 slots — the user-visible number), and the
    # median per-request DECODE rate (post-first-token), which is the
    # number the decode levers (chunk/speculative/kv-quant) move.
    decode_rates = sorted(
        (st['new_tokens'] - 1) / max(st['total_s'] - st['ttft_s'], 1e-9)
        for st in stats if st['new_tokens'] > 1)
    import math
    n = len(ttfts)
    p99_idx = min(n - 1, math.ceil(n * 0.99) - 1)  # nearest-rank
    row = {
        'p50_ttft_ms': round(ttfts[n // 2] * 1e3, 2),
        'p99_ttft_ms': round(ttfts[p99_idx] * 1e3, 2),
        'e2e_tok_per_s': round(total_new / wall, 1),
        'decode_tok_per_s_per_req': round(
            decode_rates[len(decode_rates) // 2], 1)
        if decode_rates else 0.0,
    }
    # Host-gap fraction: host time in which the device had no queued
    # decode work, over the measured wall — the dispatch-bound overhead
    # the async pipeline (--async-depth 1) exists to remove.
    row['host_gap_frac'] = round(min(1.0, host_gap_s / max(wall, 1e-9)),
                                 4)
    row.update(tp_row)
    row['async_depth'] = async_depth
    row['chained_dispatches'] = tick_stats['chained'] - chained0
    if speculative:
        drafted = max(1, engine.spec_stats['drafted'])
        row['spec_accept_rate'] = round(
            engine.spec_stats['accepted'] / drafted, 3)
    if prefix_cache:
        # All 16 requests share one prompt: after the first admit, every
        # prefill is a (near-total) prefix hit — the lever's upper bound.
        row['prefix_hit_rate'] = round(
            engine.prefix_stats['hits'] /
            max(1, engine.prefix_stats['hits'] +
                engine.prefix_stats['misses']), 3)
        row['prefix_tokens_reused'] = engine.prefix_stats['tokens_reused']
    if occupancy:
        # Pool accounting: peak blocks touched vs capacity — the HBM
        # the paged layout actually used (vs slots x max_seq_len).
        row['paged_block_size'] = occupancy['block_size']
        row['paged_blocks_capacity'] = occupancy['blocks_capacity']
        row['paged_peak_blocks_used'] = occupancy['peak_blocks_used']
        row['paged_pool_occupancy'] = round(
            occupancy['peak_blocks_used'] /
            max(1, occupancy['blocks_capacity']), 3)
        row['paged_blocks_reused'] = occupancy['blocks_reused']
        row['paged_cow_copies'] = occupancy['cow_copies']
        row['paged_prefill_chunks'] = occupancy['prefill_chunks']
        if engine.paged_int8_bytes_saved:
            # int8 block pool: HBM the quantized pool saves vs the
            # float pool (models/kv_cache.int8_pool_bytes_saved).
            row['paged_int8_bytes_saved'] = engine.paged_int8_bytes_saved
            row['paged_int8_mb_saved'] = round(
                engine.paged_int8_bytes_saved / 2**20, 1)
        if 'pool_bytes_per_device' in occupancy:
            # tp>1: every device holds its kv-head shard of EVERY
            # block — bytes, not block counts, divide by tp.
            row['paged_pool_bytes_per_device'] = \
                occupancy['pool_bytes_per_device']
    return row


def _dryrun_serve_sharded(args) -> int:
    """MULTICHIP_serve: the sharded-serving proxy row on 8 fake CPU
    devices (a correctness row: compile and transfer counts, not
    speed, so it needs no chip).

    Builds a tp=N ContinuousBatchingEngine (paged + int8 pool — the
    full composed substrate) next to a single-chip twin and pins:
    bit-identical greedy output, per-device weights+pool bytes
    <= (1/N + eps) of the single-chip footprint, and >0 all-reduces in
    the compiled decode step (parallel/hlo_probe). Emits ONE JSON row
    mirroring the MULTICHIP_r0x dryrun contract."""
    from __graft_entry__ import _force_cpu_devices
    _force_cpu_devices(8)
    import dataclasses

    import jax

    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import inference as inference_lib
    from skypilot_tpu.parallel import decode_mesh

    tp = args.tp if args.tp and args.tp > 1 else 2
    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    prompt = list(range(1, 17))
    kw = dict(num_slots=4, paged_block_size=8, kv_quant='int8')

    base = inference_lib.ContinuousBatchingEngine(cfg, **kw)
    ref, _ = base.generate(prompt, max_new_tokens=12)
    mem0 = base.memory_footprint()
    base.stop()

    engine = inference_lib.ContinuousBatchingEngine(
        cfg, mesh=decode_mesh(tp), **kw)
    got, _ = engine.generate(prompt, max_new_tokens=12)
    mem = engine.memory_footprint()
    hlo = engine.decode_hlo_stats()
    occupancy = engine.paged_occupancy()
    engine.stop()

    eps = 0.05
    frac = mem['total_bytes_per_device'] / max(1, mem0['total_bytes'])
    ok = bool(got == ref and frac <= 1.0 / tp + eps
              and hlo['all_reduce'] > 0)
    row = {
        'metric': 'MULTICHIP_serve dryrun',
        'value': float(tp),
        'unit': 'tp',
        'vs_baseline': 1.0,
        'n_devices': len(jax.devices()),
        'tp': tp,
        'ok': ok,
        'skipped': False,
        'greedy_bit_identical': got == ref,
        'per_device_weight_bytes': mem['weight_bytes_per_device'],
        'per_device_pool_bytes': mem['kv_bytes_per_device'],
        'per_device_bytes': mem['total_bytes_per_device'],
        'single_chip_bytes': mem0['total_bytes'],
        'per_device_frac': round(frac, 4),
        'max_frac': round(1.0 / tp + eps, 4),
        'collectives': hlo['total'],
        'allreduce_count': hlo['all_reduce'],
        'allreduce_bytes_per_step': hlo['all_reduce_bytes'],
        'pool_blocks_capacity': occupancy['blocks_capacity'],
        'pool_bytes_per_device': occupancy.get('pool_bytes_per_device'),
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_serve_kernel(args) -> int:  # pylint: disable=unused-argument
    """KERNEL_serve: the fused pallas paged-decode proxy row on CPU
    (interpreter mode: counts and equality, not speed).

    Pins, against the XLA twin sharing every knob: greedy streams
    across the composition cells (paged / +int8 / +spec / +async3),
    the compiled-HLO gather-count diff (the pool-window gather the
    in-kernel block-table walk deletes — pinned on 'gather'
    specifically, since interpreter emulation inflates dynamic-slice
    counts on CPU), the fused HBM bytes-per-step accounting, and the
    fused multi-LoRA kernel's bit-exactness. The kernel-vs-XLA
    measurement on the chip is ROADMAP S5; this row says nothing about
    speed. Single-chip by design (no fake-device forcing — the
    DISAGG/MULTITENANT pattern); the supervisor pins
    JAX_PLATFORMS=cpu."""
    import dataclasses

    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import inference as inference_lib

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    prompt = list(range(1, 17))
    cells = [
        ('paged', dict(paged_block_size=8)),
        ('paged-int8', dict(paged_block_size=8, kv_quant='int8')),
        ('paged-spec', dict(paged_block_size=8, speculative=3)),
        ('paged-int8-async3', dict(paged_block_size=8, kv_quant='int8',
                                   async_depth=3)),
    ]

    def _engine(**kw):
        return inference_lib.ContinuousBatchingEngine(cfg, num_slots=2,
                                                      **kw)

    cell_rows = {}
    try:
        for name, kw in cells:
            xla = _engine(**kw)
            ref, _ = xla.generate(prompt, max_new_tokens=12)
            xla.stop()
            pal = _engine(decode_kernel='pallas_interpret', **kw)
            got, _ = pal.generate(prompt, max_new_tokens=12)
            cell_rows[name] = {'match': got == ref,
                               'decode_kernel': pal.decode_kernel}
            pal.stop()

        xla = _engine(paged_block_size=8)
        xla_stats = xla.decode_kernel_hlo_stats()
        xla.stop()
        pal = _engine(paged_block_size=8,
                      decode_kernel='pallas_interpret')
        pal_stats = pal.decode_kernel_hlo_stats()
        pal.stop()
    except (ValueError, NotImplementedError) as e:
        _emit_skip(f'unsupported serve-kernel combination: {e}',
                   combo={'decode_kernel': 'pallas_interpret',
                          'paged_block_size': 8})
        return 3

    # Fused multi-LoRA leg: the kernel is bit-exact vs the XLA
    # take+dot path (same accumulation order), so the row checks
    # exactness and counts the per-step B*(in*r + r*out) adapter-gather
    # HBM round trip it removes. Whether that pays is a chip
    # measurement (ROADMAP S5), not something this row can say.
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.ops.fused_lora import fused_multi_lora
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (4, 1, cfg.d_model), jnp.float32)
    a_stack = jax.random.normal(keys[1], (3, cfg.d_model, 4),
                                jnp.float32)
    b_stack = jax.random.normal(keys[2], (3, 4, cfg.d_model),
                                jnp.float32)
    ids = jnp.asarray([0, 2, 1, 0], jnp.int32)
    fused = fused_multi_lora(x, a_stack, b_stack, ids, interpret=True)
    ref_lora = jnp.einsum(
        'bsr,bro->bso', jnp.einsum('bsi,bir->bsr', x, a_stack[ids]),
        b_stack[ids])
    lora_exact = bool(jnp.max(jnp.abs(fused - ref_lora)) == 0.0)
    gather_bytes = int(ids.shape[0] * 4 *
                       (cfg.d_model * 4 + 4 * cfg.d_model))

    gathers_removed = xla_stats['gather'] - pal_stats['gather']
    ok = bool(all(c['match'] for c in cell_rows.values())
              and all(c['decode_kernel'] == 'pallas_interpret'
                      for c in cell_rows.values())
              and gathers_removed > 0
              and pal_stats['fused_bytes_per_step'] > 0
              and xla_stats['fused_bytes_per_step'] == 0
              and lora_exact)
    row = {
        'metric': 'KERNEL_serve dryrun fused paged-decode',
        'value': float(gathers_removed),
        'unit': 'gathers_removed_per_decode_step',
        'vs_baseline': (pal_stats['gather'] /
                        max(1, xla_stats['gather'])),
        'ok': ok,
        'skipped': False,
        'cells': cell_rows,
        'xla_gather': xla_stats['gather'],
        'pallas_gather': pal_stats['gather'],
        'xla_hlo': {k: v for k, v in xla_stats.items()
                    if isinstance(v, int)},
        'pallas_hlo': {k: v for k, v in pal_stats.items()
                      if isinstance(v, int)},
        'fused_bytes_per_step': pal_stats['fused_bytes_per_step'],
        'lora_fusion': {
            'bit_exact': lora_exact,
            'adapter_gather_bytes_removed_per_step': gather_bytes,
            'verdict': 'not measured on the chip (ROADMAP S5)',
        },
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_serve_fleet(args) -> int:
    """FLEET_serve: the fleet-routing proxy row on CPU (a correctness
    row: counts, not speed, so it needs no chip). A FakeReplicaFleet of 3 REAL engines (paged + prefix
    cache) is routed at the policy level — digests and queue depths
    fed back exactly as the LB learns them in-band — through the same
    shared-prefix workload under round-robin and prefix-aware
    policies, plus one dead replica advertising an attractive digest
    (the retry-amplification path) and one corrupt digest on the wire
    (the fallback path). Pins: prefix-aware hit ratio STRICTLY above
    round-robin, greedy output bit-identical to a single healthy
    replica under both policies, bounded retry amplification, and
    zero routing errors. Emits ONE JSON row."""
    del args
    import dataclasses
    import math as math_lib
    import time as time_lib

    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import inference as inference_lib
    from skypilot_tpu.models.kv_cache import prefix_route_hash
    from skypilot_tpu.serve.load_balancing_policies import (
        PrefixAwarePolicy, RoundRobinPolicy)

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    groups = [list(range(s, s + 24)) for s in (1, 60, 120, 180, 240)]
    rounds = 3

    def prompts():
        for round_i in range(rounds):
            for gi, group in enumerate(groups):
                yield gi, round_i, group + [400 + round_i]

    # Bit-identity oracle: one healthy single replica.
    ref_engine = inference_lib.ContinuousBatchingEngine(
        cfg, num_slots=2, paged_block_size=8, prefix_cache=6)
    reference = {(gi, ri): ref_engine.generate(ids, max_new_tokens=4,
                                               timeout=600)[0]
                 for gi, ri, ids in prompts()}
    ref_engine.stop()

    def run_policy(policy) -> dict:
        engines = [
            inference_lib.ContinuousBatchingEngine(
                cfg, num_slots=2, paged_block_size=8, prefix_cache=6)
            for _ in range(3)
        ]
        urls = [f'replica://{i}' for i in range(3)]
        dead_url = 'replica://zombie'
        policy.set_ready_replicas(urls + [dead_url])
        # The dead replica advertises the most attractive digest for
        # group 4 — a replica that died mid-advertisement. Routing
        # must absorb it as ONE wasted attempt per request at most.
        policy.observe_response(dead_url, {
            'X-SkyTPU-Queue-Depth': '0',
            'X-SkyTPU-Prefix-Digest': 'v1:8:1:' + ','.join(
                prefix_route_hash(groups[4][:k * 8])
                for k in range(1, 4)),
        })
        attempts = served = rejected = mismatches = 0
        ttfts = []
        t0 = time_lib.time()
        for gi, round_i, ids in prompts():
            tried = set()
            while True:
                attempts += 1
                url, _info = policy.select(
                    exclude=tried,
                    hint={'token_ids': ids, 'prompt_len': len(ids)})
                assert url is not None, 'routing failed closed'
                if url == dead_url:
                    # Simulated transport error → client-level retry
                    # on another replica (the LB breaker path).
                    tried.add(url)
                    continue
                engine = engines[urls.index(url)]
                policy.note_routed(url)
                toks, stats = engine.generate(ids, max_new_tokens=4,
                                              timeout=600)
                policy.note_done(url)
                ttfts.append(stats['ttft_s'])
                headers = {
                    'X-SkyTPU-Queue-Depth': str(engine.queue_load()),
                }
                digest = engine.prefix_digest()
                if digest:
                    headers['X-SkyTPU-Prefix-Digest'] = digest
                if gi == 0 and round_i == 1:
                    # Corrupt digest on the wire: must be dropped and
                    # counted, never raised.
                    headers['X-SkyTPU-Prefix-Digest'] = 'garbage!!'
                if policy.observe_response(url, headers) == 'rejected':
                    rejected += 1
                if toks != reference[(gi, round_i)]:
                    mismatches += 1
                served += 1
                break
        wall = time_lib.time() - t0
        hits = sum(e.prefix_stats['hits'] for e in engines)
        misses = sum(e.prefix_stats['misses'] for e in engines)
        for engine in engines:
            engine.stop()
        ttfts.sort()
        n = len(ttfts)
        p99_idx = min(n - 1, math_lib.ceil(n * 0.99) - 1)
        return {
            'prefix_hit_ratio': round(hits / max(1, hits + misses), 4),
            'prefix_hits': hits,
            'prefix_misses': misses,
            'retry_amplification': round(attempts / max(1, served), 4),
            'attempts': attempts,
            'served': served,
            'output_mismatches': mismatches,
            'digests_rejected': rejected,
            'p50_routed_ttft_ms': round(ttfts[n // 2] * 1e3, 2),
            'p99_routed_ttft_ms': round(ttfts[p99_idx] * 1e3, 2),
            'wall_s': round(wall, 1),
        }

    rr = run_policy(RoundRobinPolicy())
    pa = run_policy(PrefixAwarePolicy())
    ok = bool(
        pa['prefix_hit_ratio'] > rr['prefix_hit_ratio'] and
        pa['output_mismatches'] == 0 and rr['output_mismatches'] == 0
        and pa['digests_rejected'] >= 1 and
        pa['retry_amplification'] <= 2.0 and
        rr['retry_amplification'] <= 2.0)
    row = {
        'metric': 'FLEET_serve dryrun prefix-hit ratio',
        'value': pa['prefix_hit_ratio'],
        'unit': 'hit_ratio',
        'vs_baseline': round(
            pa['prefix_hit_ratio'] / max(1e-9, rr['prefix_hit_ratio']),
            2) if rr['prefix_hit_ratio'] else float(
                pa['prefix_hits'] or 1),
        'ok': ok,
        'skipped': False,
        'replicas': 3,
        'groups': len(groups),
        'rounds': rounds,
        'round_robin': rr,
        'prefix_aware': pa,
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_serve_disagg(args) -> int:
    """DISAGG_serve: the disaggregated prefill/decode proxy row on CPU
    (the FLEET_serve pattern applied to the two-stage KV handoff;
    docs/serving.md "Disaggregated serving").

    Two fleets of REAL engines run the same long-prompt storm plus
    short interactive traffic:

    - disaggregated: 1 prefill-tier + 2 decode-tier engines. Long
      prompts route through the policy's two-stage handoff — the
      prefill engine chunk-prefills, serializes CRC'd chunks, the
      decode engine ingests them — then decode as ASYNC in-flight work
      on the decode tier while short-prompt TTFT is measured.
    - monolithic: 3 engines behind the phase-aware policy at its
      DEFAULT knobs (fleet of 3 < the specialization floor of 4, so
      routing is uniform — the honest PR-8 baseline at this size).
      The same longs scatter as in-flight work, so shorts compete
      with long-prompt CHUNKED PREFILL instead of mere decode.

    Pins: every output (longs and shorts, both fleets) bit-identical
    to a monolithic oracle; measured handoff chunks == longs ×
    ceil(blocks/chunk_blocks) and payload bytes == blocks × the
    per-block leaf math; zero chunks rejected; short-prompt p50 TTFT
    on the disaggregated decode tier STRICTLY below the monolithic
    fleet's. Emits ONE JSON row."""
    del args
    import dataclasses
    import math as math_lib
    import time as time_lib

    import numpy as np

    os.environ.setdefault('SKYTPU_SERVE_LB_DISAGG_THRESHOLD', '32')
    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import inference as inference_lib
    from skypilot_tpu.models import kv_cache as kv_cache_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAwarePolicy

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    block_size = 8
    chunk_blocks = 2
    longs = [list(range(s, s + 48)) for s in (1, 60, 120, 180)]
    shorts = [[7, 8, 9 + i] for i in range(6)]
    long_new, short_new = 16, 4

    def make_engine(tier='monolithic'):
        return inference_lib.ContinuousBatchingEngine(
            cfg, num_slots=4, paged_block_size=block_size,
            prefix_cache=8, tier=tier)

    try:
        oracle = make_engine()
    except ValueError as e:
        # An unconstructable engine combination is a deterministic
        # verdict — the structured skip, never the retry ladder.
        _emit_skip(f'unsupported disagg combination: {e}',
                   combo={'paged_block_size': block_size,
                          'prefix_cache': 8})
        return 3
    ref_long = {i: oracle.generate(ids, max_new_tokens=long_new,
                                   timeout=600)[0]
                for i, ids in enumerate(longs)}
    ref_short = {i: oracle.generate(ids, max_new_tokens=short_new,
                                    timeout=600)[0]
                 for i, ids in enumerate(shorts)}
    oracle.stop()

    def p50(values):
        ordered = sorted(values)
        return ordered[len(ordered) // 2]

    def run_storm(engines, route_long):
        """Submit every long ASYNC via `route_long` (returns the
        engine that will decode it), then measure each short's TTFT
        while the longs are in flight. Returns (short ttfts,
        long-output mismatches)."""
        futures = [(i, route_long(i, ids).submit(
            ids, max_new_tokens=long_new)) for i, ids in
            enumerate(longs)]
        ttfts = []
        mismatches = 0
        for i, ids in enumerate(shorts):
            engine = engines[i % len(engines)]
            out, stats = engine.generate(ids, max_new_tokens=short_new,
                                         timeout=600)
            ttfts.append(stats['ttft_s'])
            if out != ref_short[i]:
                mismatches += 1
        for i, future in futures:
            out, _stats = future.result(timeout=600)
            if out != ref_long[i]:
                mismatches += 1
        return ttfts, mismatches

    # ---- disaggregated fleet: 1 prefill + 2 decode ----
    pre = make_engine('prefill')
    decs = [make_engine('decode') for _ in range(2)]
    policy = PrefixAwarePolicy()
    urls = ['replica://pre', 'replica://d0', 'replica://d1']
    policy.set_ready_replicas(urls)
    policy.set_replica_tiers({'replica://pre': 'prefill',
                              'replica://d0': 'decode',
                              'replica://d1': 'decode'})
    by_url = {'replica://d0': decs[0], 'replica://d1': decs[1]}
    handoff_chunks = 0
    handoff_payload_bytes = 0
    handoff_blocks = 0
    handoffs = 0

    def route_long_disagg(i, ids):
        nonlocal handoff_chunks, handoff_payload_bytes, handoffs, \
            handoff_blocks
        url, info = policy.select(hint={'token_ids': ids,
                                        'prompt_len': len(ids)})
        assert info['result'] == 'handoff', info
        pre.prefill_prefix(ids, timeout=600)
        chunks = pre.export_prefix_chunks(ids, f'dry-{i}',
                                          chunk_blocks=chunk_blocks)
        dec = by_url[url]
        for chunk in chunks:
            result = dec.ingest_chunk(chunk)
            _header, payload = kv_cache_lib.unpack_kv_chunk(chunk)
            handoff_payload_bytes += len(payload)
        handoff_chunks += len(chunks)
        handoff_blocks += result['imported_blocks']
        handoffs += 1
        policy.note_routed(url)
        return dec

    t0 = time_lib.time()
    disagg_ttfts, disagg_mismatch = run_storm(decs, route_long_disagg)
    disagg_wall = time_lib.time() - t0
    ingest_rejected = sum(e.ingest_stats['chunks_rejected']
                          for e in decs)
    prewarm_hits = sum(e.prefix_stats['prewarm_hits'] for e in decs)
    for engine in decs:
        engine._pool.check()  # pylint: disable=protected-access
    meta = pre._expected_leaf_meta()  # pylint: disable=protected-access
    per_block_bytes = sum(
        int(np.prod(m['shape'], dtype=np.int64)) *
        np.dtype(m['dtype']).itemsize for m in meta)
    for engine in [pre] + decs:
        engine.stop()

    # ---- monolithic phase-aware fleet (PR-8 baseline, default knobs:
    # a 3-replica fleet sits below the phase floor → uniform) ----
    monos = [make_engine() for _ in range(3)]
    mono_policy = PrefixAwarePolicy()
    mono_urls = [f'replica://m{i}' for i in range(3)]
    mono_policy.set_ready_replicas(mono_urls)
    mono_by_url = dict(zip(mono_urls, monos))

    def route_long_mono(_i, ids):
        url, _info = mono_policy.select(hint={'token_ids': ids,
                                              'prompt_len': len(ids)})
        mono_policy.note_routed(url)
        return mono_by_url[url]

    t0 = time_lib.time()
    mono_ttfts, mono_mismatch = run_storm(monos, route_long_mono)
    mono_wall = time_lib.time() - t0
    for engine in monos:
        engine.stop()

    blocks_per_long = -(-len(longs[0]) // block_size)
    expect_blocks = len(longs) * blocks_per_long
    expect_chunks = len(longs) * math_lib.ceil(
        blocks_per_long / chunk_blocks)
    expect_bytes = expect_blocks * per_block_bytes
    disagg_p50 = p50(disagg_ttfts)
    mono_p50 = p50(mono_ttfts)
    ok = bool(
        disagg_mismatch == 0 and mono_mismatch == 0
        and handoffs == len(longs)
        and handoff_chunks == expect_chunks
        and handoff_blocks == expect_blocks
        and handoff_payload_bytes == expect_bytes
        and ingest_rejected == 0
        and prewarm_hits >= len(longs)
        and disagg_p50 < mono_p50)
    row = {
        'metric': 'DISAGG_serve dryrun storm short-prompt TTFT',
        'value': round(disagg_p50 * 1e3, 2),
        'unit': 'ms',
        'vs_baseline': round(mono_p50 / max(1e-9, disagg_p50), 2),
        'ok': ok,
        'skipped': False,
        'prefill_replicas': 1,
        'decode_replicas': 2,
        'long_prompts': len(longs),
        'long_prompt_tokens': len(longs[0]),
        'short_prompts': len(shorts),
        'handoffs': handoffs,
        'handoff_chunks': handoff_chunks,
        'expected_chunks': expect_chunks,
        'handoff_payload_bytes': handoff_payload_bytes,
        'expected_payload_bytes': expect_bytes,
        'per_block_bytes': per_block_bytes,
        'blocks_per_long': blocks_per_long,
        'ingest_chunks_rejected': ingest_rejected,
        'prewarm_hits': prewarm_hits,
        'output_mismatches': disagg_mismatch + mono_mismatch,
        'disagg_short_ttft_p50_ms': round(disagg_p50 * 1e3, 2),
        'mono_short_ttft_p50_ms': round(mono_p50 * 1e3, 2),
        'disagg_wall_s': round(disagg_wall, 1),
        'mono_wall_s': round(mono_wall, 1),
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_serve_multitenant(args) -> int:
    """MULTITENANT_serve: the multi-LoRA + SLO-tier proxy row on CPU
    (docs/serving.md "Multi-tenant serving"; the DISAGG_serve pattern
    applied to tenancy).

    One REAL multi-adapter engine (3 resident adapters, paged pool)
    serves a 3-adapter × 3-tier request mix; three dedicated
    single-adapter engines (unmerged LoRADenseGeneral) plus a plain
    base engine are the bit-identity oracles. Then the SLO leg: a
    batch-tier flood with interactive arrivals, tiered vs the SAME
    flood with every request untiered ('standard').

    Pins: per-request greedy bit-identity (mixed batch vs dedicated
    engines, every tier cell); ONE compiled decode program + ≥1
    all-four-slots step_log row (the one-dispatch batching proof);
    interactive p50 TTFT under the flood strictly below the untiered
    baseline; zero non-retryable losses with ≥1 slot preemption.
    Emits ONE JSON row; unconstructable combos emit the structured
    {"skipped": true} line with rc=3."""
    del args
    import dataclasses
    import time as time_lib

    from flax import linen as nn
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import inference as inference_lib
    from skypilot_tpu.models.transformer import Transformer
    from skypilot_tpu.serve import tenancy

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)
    lora_kw = dict(adapter_rank=4, adapter_alpha=8.0,
                   adapter_targets='q,v')
    lora_cfg = dataclasses.replace(cfg, lora_rank=4, lora_alpha=8.0,
                                   lora_targets='q,v')
    prompt = list(range(1, 11))
    n_adapters, new_tokens = 3, 8

    try:
        engine = inference_lib.ContinuousBatchingEngine(
            cfg, num_slots=4, max_adapters=n_adapters,
            paged_block_size=8, prefix_cache=4, **lora_kw)
    except (ValueError, NotImplementedError) as e:
        # An unconstructable combination is a deterministic verdict —
        # the structured skip, never the retry ladder.
        _emit_skip(f'unsupported multitenant combination: {e}',
                   combo={'max_adapters': n_adapters,
                          'paged_block_size': 8, **lora_kw})
        return 3
    base_params = engine.params

    # ---- adapter weights + dedicated oracles ----
    template_model = Transformer(dataclasses.replace(lora_cfg,
                                                     decode=True))
    template_vars = nn.unbox(template_model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32),
        jnp.zeros((1, 8), jnp.int32)))
    template = tenancy.adapter_tree_from_lora_params(
        template_vars['params'])
    leaves, treedef = jax.tree.flatten(template)

    def rand_tree(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            jax.random.normal(k, leaf.shape, jnp.float32) * 0.05
            for k, leaf in zip(keys, leaves)])

    def overlay(params, sub):
        out = dict(params)
        for key, value in sub.items():
            out[key] = (overlay(params[key], value)
                        if isinstance(value, dict) else value)
        return out

    trees = {f'tenant-{i}': rand_tree(100 + i)
             for i in range(n_adapters)}
    refs = {}
    plain = inference_lib.ContinuousBatchingEngine(
        cfg, params=base_params, num_slots=4)
    refs['base'] = plain.generate(prompt,
                                  max_new_tokens=new_tokens)[0]
    plain.stop()
    for name, tree in trees.items():
        dedicated = inference_lib.ContinuousBatchingEngine(
            lora_cfg, params=overlay(base_params, tree), num_slots=4)
        refs[name] = dedicated.generate(
            prompt, max_new_tokens=new_tokens)[0]
        dedicated.stop()

    # ---- leg (a): mixed 3-adapter × 3-tier batch on ONE engine ----
    for name, tree in trees.items():
        engine.load_adapter(name, tree)
    tiers = ['interactive', 'standard', 'batch']
    futures = [('base', engine.submit(prompt,
                                      max_new_tokens=new_tokens))]
    for i, name in enumerate(trees):
        futures.append((name, engine.submit(
            prompt, max_new_tokens=new_tokens, adapter=name,
            priority=tiers[i % len(tiers)])))
    mismatches = 0
    for name, future in futures:
        out, _stats = future.result(timeout=600)
        if out != refs[name]:
            mismatches += 1
    decode_compiles = engine._decode._cache_size()  # pylint: disable=protected-access
    shared_steps = sum(1 for entry in engine.step_log
                       if entry[0] != 'prefill' and len(entry[1]) == 4)
    adapter_stats = dict(engine._adapter_pool.stats)  # pylint: disable=protected-access
    engine.stop()

    # ---- leg (b): interactive p50 TTFT under a batch flood ----
    def p50(values):
        ordered = sorted(values)
        return ordered[len(ordered) // 2]

    def run_flood(tiered: bool):
        """8 batch-tier floods + 3 interactive arrivals on a WARM
        2-slot engine; returns (interactive ttfts, batch failures,
        preempts). The warmup request compiles prefill+decode first so
        the TTFT comparison measures SCHEDULING, not JIT noise."""
        flood_engine = inference_lib.ContinuousBatchingEngine(
            cfg, params=base_params, num_slots=2,
            max_adapters=n_adapters, paged_block_size=8,
            prefix_cache=4, **lora_kw)
        flood_engine.generate([1, 2, 3], max_new_tokens=2,
                              timeout=600)
        flood_priority = 'batch' if tiered else 'standard'
        int_priority = 'interactive' if tiered else 'standard'
        flood = [flood_engine.submit(list(range(1, 9)),
                                     max_new_tokens=48,
                                     priority=flood_priority)
                 for _ in range(8)]
        time_lib.sleep(0.15)
        arrivals = [flood_engine.submit([40 + i, 41, 42],
                                        max_new_tokens=4,
                                        priority=int_priority)
                    for i in range(3)]
        ttfts = [f.result(timeout=600)[1]['ttft_s'] for f in arrivals]
        failures = sum(1 for f in flood
                       if f.exception(timeout=600) is not None)
        preempts = flood_engine.tenancy_stats['slot_preempts']
        flood_engine.stop()
        return ttfts, failures, preempts

    tiered_ttfts, tiered_failures, preempts = run_flood(tiered=True)
    untiered_ttfts, untiered_failures, _ = run_flood(tiered=False)
    tiered_p50 = p50(tiered_ttfts)
    untiered_p50 = p50(untiered_ttfts)

    ok = bool(
        mismatches == 0
        and decode_compiles == 1
        and shared_steps >= 1
        and tiered_failures == 0 and untiered_failures == 0
        and preempts >= 1
        and tiered_p50 < untiered_p50)
    row = {
        'metric': 'MULTITENANT_serve dryrun interactive TTFT under '
                  'batch flood',
        'value': round(tiered_p50 * 1e3, 2),
        'unit': 'ms',
        'vs_baseline': round(untiered_p50 / max(1e-9, tiered_p50), 2),
        'ok': ok,
        'skipped': False,
        'adapters': n_adapters,
        'tiers': tiers,
        'output_mismatches': mismatches,
        'decode_compiles': decode_compiles,
        'shared_4slot_steps': shared_steps,
        'adapter_loads': adapter_stats.get('loads', 0),
        'slot_preempts': preempts,
        'batch_failures_tiered': tiered_failures,
        'batch_failures_untiered': untiered_failures,
        'interactive_ttft_p50_ms_tiered': round(tiered_p50 * 1e3, 2),
        'interactive_ttft_p50_ms_untiered': round(
            untiered_p50 * 1e3, 2),
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_trace(args) -> int:
    """TRACE: the end-to-end tracing proxy row on CPU (the
    DISAGG_serve pattern applied to the span layer;
    docs/observability.md "Tracing").

    A real 2-hop disaggregated handoff over LIVE HTTP — 1 prefill + 1
    decode server behind the real LB, tracing ON — must produce ONE
    trace whose span tree keeps the full parentage:

        lb.request → lb.handoff → lb.handoff_attempt →
        server.request[/kv/prefill] → server.kv_push →
        engine.ingest_publish (decode side)

    (≥4 hops LB→prefill→ingest→decode) with queue-wait / prefill /
    decode spans present for the served request. Separately, a steady
    decode run measures the ENABLED-vs-DISABLED per-tick overhead
    ratio — the disabled path is pinned elsewhere at one enabled-check
    (tests/test_tracing.py); here the enabled cost is REPORTED so the
    row catches a regression that makes tracing unaffordable."""
    del args
    import asyncio
    import dataclasses
    import socket
    import threading
    import time as time_lib

    import requests as requests_lib

    os.environ['SKYTPU_SERVE_LB_DISAGG_THRESHOLD'] = '16'
    os.environ['SKYTPU_SERVE_HANDOFF_CHUNK_BLOCKS'] = '1'
    from skypilot_tpu.models import get_config
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    from skypilot_tpu.observability import tracing
    from skypilot_tpu.serve.load_balancer import SkyServeLoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAwarePolicy
    from skypilot_tpu.serve.server import InferenceServer

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32',
        max_seq_len=64, remat=False)

    def free_port():
        with socket.socket() as sock:
            sock.bind(('', 0))
            return sock.getsockname()[1]

    def serve_app(app):
        from aiohttp import web
        port = free_port()

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            runner = web.AppRunner(app)
            loop.run_until_complete(runner.setup())
            loop.run_until_complete(
                web.TCPSite(runner, '127.0.0.1', port).start())
            loop.run_forever()

        threading.Thread(target=run, daemon=True).start()
        deadline = time_lib.time() + 30
        while time_lib.time() < deadline:
            with socket.socket() as sock:
                sock.settimeout(0.5)
                try:
                    sock.connect(('127.0.0.1', port))
                    return port
                except OSError:
                    time_lib.sleep(0.1)
        raise RuntimeError('server thread never bound its port')

    def wrap(engine, tier):
        server = InferenceServer.__new__(InferenceServer)
        server.engine = engine
        server.tokenizer_kind = 'byte'
        server._hf_tokenizer = None  # pylint: disable=protected-access
        server.ready = True
        server.request_timeout = 0.0
        server.draining = False
        server.tier = tier
        return server

    try:
        engines = {
            tier: ContinuousBatchingEngine(
                cfg, num_slots=2, paged_block_size=8, prefix_cache=6,
                tier=tier)
            for tier in ('prefill', 'decode')
        }
    except ValueError as e:
        _emit_skip(f'unsupported trace-dryrun engine combination: {e}',
                   combo={'paged_block_size': 8, 'prefix_cache': 6})
        return 3
    urls, tiers = [], {}
    for tier, engine in engines.items():
        engine.generate([1, 2, 3], max_new_tokens=2,
                        timeout=600)  # compile
        port = serve_app(wrap(engine, tier).make_app())
        url = f'http://127.0.0.1:{port}'
        urls.append(url)
        tiers[url] = tier
    policy = PrefixAwarePolicy()
    lb_port = free_port()
    lb = SkyServeLoadBalancer('http://127.0.0.1:1', lb_port,
                              policy_name='prefix_aware')
    lb.policy = policy
    policy.set_ready_replicas(list(urls))
    policy.set_replica_tiers(tiers)
    lb.start_in_thread()
    lb_url = f'http://127.0.0.1:{lb_port}'
    deadline = time_lib.time() + 30
    while time_lib.time() < deadline:
        try:
            requests_lib.get(lb_url + '/metrics', timeout=2)
            break
        except requests_lib.RequestException:
            time_lib.sleep(0.1)

    tracing.enable()
    tracing.reset()
    ids = list(range(1, 25))  # 24 tokens ≥ threshold ⇒ handoff
    resp = requests_lib.post(
        lb_url + '/generate',
        json={'prompt_ids': [ids], 'max_new_tokens': 4}, timeout=600)
    handoff_ok = resp.status_code == 200
    spans = tracing.snapshot()
    names = sorted(s['name'] for s in spans)
    traces = {s['trace_id'] for s in spans}
    by_id = {s['span_id']: s for s in spans}

    def chain_of(span):
        out = [span['name']]
        while span.get('parent_id') in by_id:
            span = by_id[span['parent_id']]
            out.append(span['name'])
        return list(reversed(out))

    publishes = [s for s in spans if s['name'] == 'engine.ingest_publish']
    publish_chain = chain_of(publishes[0]) if publishes else []
    decodes = [s for s in spans if s['name'] == 'engine.decode']
    decode_chain = max((chain_of(s) for s in decodes),
                       key=len, default=[])
    required = {'lb.request', 'lb.route', 'lb.handoff',
                'server.request', 'server.kv_push',
                'engine.queue_wait', 'engine.prefill', 'engine.decode',
                'engine.ingest_chunk', 'engine.ingest_publish'}
    shape_ok = (handoff_ok and len(traces) == 1 and
                required <= set(names) and
                len(publish_chain) >= 5 and
                publish_chain[0] == 'lb.request' and
                len(decode_chain) >= 3)

    # ---- enabled-vs-disabled decode-tick overhead ----
    # One single-slot steady decode per mode on a fresh monolithic
    # engine (same compile cache within this process): per-token wall
    # with tracing disabled vs enabled. The engine records NO per-tick
    # spans (coalescing is per request), so the ratio should sit near
    # 1.0; it is REPORTED, and only a gross regression (>2x) fails the
    # row — CI wall clocks are noisy.
    bench_engine = ContinuousBatchingEngine(cfg, num_slots=1)
    bench_engine.generate([5, 6, 7], max_new_tokens=8,
                          timeout=600)  # warm the jit caches
    steps = 48

    def per_token_s() -> float:
        best = float('inf')
        for rep in range(3):
            # The enabled runs must exercise REAL span recording
            # (queue-wait/prefill/decode per request): an ambient
            # context makes submit() capture a trace exactly like a
            # traced serving request — otherwise req.trace stays None
            # and the "enabled" measurement differs from disabled by
            # one boolean, making the regression guard vacuous.
            # activate(None)/NULL_SPAN keep the disabled runs no-ops.
            root = tracing.start_span('lb.request')
            t0 = time_lib.monotonic()
            with tracing.activate(root.ctx):
                bench_engine.generate([5, 6, 7 + rep],
                                      max_new_tokens=steps, timeout=600)
            best = min(best, (time_lib.monotonic() - t0) / steps)
            root.end()
        return best

    tracing.disable()
    disabled_s = per_token_s()
    tracing.enable()
    enabled_s = per_token_s()
    tracing.disable()
    overhead_ratio = enabled_s / max(1e-9, disabled_s)

    for engine in list(engines.values()) + [bench_engine]:
        engine.stop()
    ok = bool(shape_ok and overhead_ratio < 2.0)
    row = {
        'metric': 'TRACE dryrun 2-hop handoff span tree',
        'value': len(publish_chain),
        'unit': 'hops',
        'ok': ok,
        'skipped': False,
        'traces': len(traces),
        'spans': len(spans),
        'span_names': sorted(set(names)),
        'publish_chain': publish_chain,
        'decode_chain': decode_chain,
        'handoff_http_200': handoff_ok,
        'tick_overhead_ratio': round(overhead_ratio, 3),
        'tick_disabled_us': round(disabled_s * 1e6, 1),
        'tick_enabled_us': round(enabled_s * 1e6, 1),
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_train_zero1(args) -> int:
    """MULTICHIP_train_zero1: the ZeRO-1 weight-update-sharding proxy
    row on 8 fake CPU devices (a correctness row: bit identity, byte
    and collective counts, not speed; arxiv 2004.13336).

    Trains the tiny model 3 steps on a pure dp=8 mesh twice — once
    plain, once with zero_sharding — for grad_accum 1 AND 2, with
    clipping ACTIVE (the hard case: the clip scale is where sharded
    reduction order would leak into the update), and pins:

    - loss AND grad_norm bit-identical between the two trainers;
    - per-device optimizer-state bytes <= (1/dp + eps) x unsharded;
    - the compiled zero1 step scatters gradients (reduce-scatter, or
      the CPU pipeline's unfused all-reduce + partition-slice form)
      and all-gathers the updated params, while the plain step has
      NO scatter and NO gather.

    Emits ONE JSON row mirroring the MULTICHIP_r0x dryrun contract."""
    del args
    from __graft_entry__ import _force_cpu_devices
    _force_cpu_devices(8)
    import jax

    dp = 8
    n = len(jax.devices())
    if n < dp:
        # Deterministic verdict, not a flaky device: the structured
        # skip (never the retry ladder), emitted BEFORE the training
        # stack even imports.
        _emit_skip(f'train-zero1 dryrun needs {dp} devices, have {n}',
                   combo={'dp': dp, 'n_devices': n})
        return 3
    import dataclasses

    from skypilot_tpu.models import get_config
    from skypilot_tpu.parallel import train_mesh
    from skypilot_tpu.train import (TrainConfig, create_sharded_state,
                                    make_train_step, synthetic_batch)
    from skypilot_tpu.train import metrics as metrics_lib
    from skypilot_tpu.train.trainer import compiled_step_collectives

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32')
    tc = TrainConfig(warmup_steps=1, total_steps=10,
                     learning_rate=3e-2, grad_clip_norm=0.5)
    mesh = train_mesh(dp)
    rng = jax.random.PRNGKey(0)
    batches = [synthetic_batch(jax.random.PRNGKey(i), 16, 64,
                               cfg.vocab_size) for i in range(3)]

    def run(zero, accum, probe=True):
        state, sh = create_sharded_state(cfg, mesh, rng, tc,
                                         zero_sharding=zero)
        step = make_train_step(cfg, mesh, sh, grad_accum=accum)
        # The probe is an honest second AOT compile — skip it for the
        # runs whose stats nothing reads.
        hlo = compiled_step_collectives(step, state, batches[0],
                                        dp=dp) if probe else None
        series = []
        with mesh:
            for b in batches:
                state, m = step(state, b)
                series.append((float(m['loss']),
                               float(m['grad_norm'])))
        return series, hlo, metrics_lib.opt_state_bytes(state)

    base1, base_hlo, (base_bytes, base_per_dev) = run(False, 1)
    zero1, zero_hlo, (_, zero_per_dev) = run(True, 1)
    base2, _, _ = run(False, 2, probe=False)
    zero2, zero_hlo2, _ = run(True, 2)

    eps = 0.05
    frac = zero_per_dev / max(1, base_bytes)
    rs = zero_hlo['reduce_scatter_effective']
    ok = bool(
        base1 == zero1 and base2 == zero2
        and frac <= 1.0 / dp + eps
        and rs > 0 and zero_hlo['all_gather'] > 0
        and zero_hlo2['reduce_scatter_effective'] > 0
        and base_hlo['reduce_scatter_effective'] == 0
        and base_hlo['all_gather'] == 0)
    row = {
        'metric': 'MULTICHIP_train_zero1 dryrun',
        'value': float(dp),
        'unit': 'dp',
        'vs_baseline': 1.0,
        'n_devices': n,
        'dp': dp,
        'ok': ok,
        'skipped': False,
        'steps': len(batches),
        'loss_grad_norm_bit_identical': base1 == zero1,
        'accum2_bit_identical': base2 == zero2,
        'losses': [loss for loss, _ in zero1],
        'opt_state_bytes': base_bytes,
        'opt_state_bytes_per_device': zero_per_dev,
        'unsharded_bytes_per_device': base_per_dev,
        'per_device_frac': round(frac, 4),
        'max_frac': round(1.0 / dp + eps, 4),
        'reduce_scatter_effective': rs,
        'reduce_scatter_native': zero_hlo['reduce_scatter'],
        'partition_scatter': zero_hlo['partition_scatter'],
        'all_gather': zero_hlo['all_gather'],
        'all_reduce': zero_hlo['all_reduce'],
        'accum2_reduce_scatter_effective':
            zero_hlo2['reduce_scatter_effective'],
        'accum2_all_gather': zero_hlo2['all_gather'],
        'baseline_reduce_scatter_effective':
            base_hlo['reduce_scatter_effective'],
        'baseline_all_gather': base_hlo['all_gather'],
        'baseline_all_reduce': base_hlo['all_reduce'],
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _dryrun_train_elastic(args) -> int:
    """MULTICHIP_train_elastic: the preemption-native elastic-training
    proxy row on 8 fake CPU devices (a correctness row: bit parity
    and step counts across live dp resharding, not speed;
    arxiv 2004.13336 + 2011.03641).

    Trains the tiny model 6 steps at a canonical extent of dp=4 twice —
    once unpreempted, once through a 2-notice storm (notice at dp=4 →
    relaunch at the surviving dp=2 → notice → grow back to dp=4) using
    the PR-9 reshard restore between incarnations — and pins:

    - ZERO completed steps re-trained per preemption (only the
      in-flight step is at risk, by construction);
    - the merged storm loss series bit-identical to the unpreempted
      run over the same data order (the extent-invariant elastic step);
    - resume latency per incarnation (mesh + init + reshard restore),
      the number a real spot fleet pays per relaunch.

    Emits ONE JSON row mirroring the MULTICHIP_r0x dryrun contract."""
    del args
    from __graft_entry__ import _force_cpu_devices
    _force_cpu_devices(8)
    import jax

    need = 8
    n = len(jax.devices())
    if n < need:
        # Deterministic verdict, not a flaky device: the structured
        # skip (never the retry ladder), emitted BEFORE the training
        # stack even imports.
        _emit_skip(f'train-elastic dryrun needs {need} devices, '
                   f'have {n}', combo={'canonical_dp': 4,
                                       'n_devices': n})
        return 3
    import dataclasses
    import tempfile

    from skypilot_tpu.models import get_config
    from skypilot_tpu.train import TrainConfig, synthetic_batch
    from skypilot_tpu.train.elastic import (ElasticTrainLoop,
                                            PreemptionNotice,
                                            surviving_extent)

    cfg = dataclasses.replace(
        get_config('test-tiny'), dtype='float32', param_dtype='float32')
    tc = TrainConfig(warmup_steps=1, total_steps=6,
                     learning_rate=3e-2, grad_clip_norm=0.5)
    total_steps = 6
    batches = [synthetic_batch(jax.random.PRNGKey(i), 16, 64,
                               cfg.vocab_size)
               for i in range(total_steps)]

    base_loop = ElasticTrainLoop(cfg, tc,
                                 tempfile.mkdtemp(prefix='skytpu-ela-b-'),
                                 canonical_dp=4)
    base = base_loop.run(4, lambda s: batches[s], total_steps)

    storm_loop = ElasticTrainLoop(cfg, tc,
                                  tempfile.mkdtemp(prefix='skytpu-ela-s-'),
                                  canonical_dp=4)
    notice = PreemptionNotice()
    dp2 = surviving_extent(4, 2)

    def trigger(step):
        def f(s):
            if s == step:
                notice.deliver()
            return batches[s]
        return f

    series = {}
    incs = []
    prev_next = 0
    steps_lost = []
    plan = [(4, trigger(1)), (dp2, trigger(3)), (4, lambda s: batches[s])]
    for dp, bf in plan:
        notice.clear()
        r = storm_loop.run(dp, bf, total_steps, notice=notice)
        start = r.next_step - len(r.series)
        steps_lost.append(max(0, prev_next - start))
        for i, v in enumerate(r.series):
            series[start + i] = v
        prev_next = r.next_step
        incs.append({'dp': r.dp, 'start': start, 'next': r.next_step,
                     'preempted': r.preempted,
                     'committed': r.checkpoint_committed,
                     'resume_latency_s': round(r.resume_latency_s, 3)})

    parity = [series.get(s) == base.series[s]
              for s in range(total_steps)]
    lost_per_preemption = (sum(steps_lost[1:]) /
                           max(1, len(steps_lost) - 1))
    resume_latencies = [inc['resume_latency_s'] for inc in incs]
    ok = bool(
        all(parity)
        and all(l == 0 for l in steps_lost)
        and [inc['dp'] for inc in incs] == [4, dp2, 4]
        and all(inc['committed'] for inc in incs)
        and incs[0]['preempted'] and incs[1]['preempted']
        and not incs[2]['preempted'])
    row = {
        'metric': 'MULTICHIP_train_elastic dryrun',
        'value': lost_per_preemption,
        'unit': 'steps_lost/preemption',
        'vs_baseline': 1.0,
        'n_devices': n,
        'canonical_dp': 4,
        'surviving_dp': dp2,
        'ok': ok,
        'skipped': False,
        'steps': total_steps,
        'preemptions': 2,
        'steps_lost': steps_lost,
        'loss_bit_identical': all(parity),
        'losses': [loss for loss, _ in
                   (series[s] for s in sorted(series))],
        'incarnations': incs,
        'resume_latency_s': resume_latencies,
        'resume_latency_mean_s': round(
            sum(resume_latencies) / len(resume_latencies), 3),
    }
    print(json.dumps(row))
    return 0 if ok else 1


def _supervise_dryrun(argv) -> int:
    """Run a CPU-only dryrun (sharded serving / fleet routing) in a
    subprocess with the fake 8-CPU-device environment. These rows
    check correctness and counts, never the chip, so the child is
    pinned to the CPU whatever this process would get."""
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    flags = env.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()
    cmd = [sys.executable, '-u', os.path.abspath(__file__),
           '--worker'] + argv
    try:
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                              timeout=_TIMEOUT_S, env=env, check=False)
    except subprocess.TimeoutExpired:
        _emit_skip(f'sharded serve dryrun timed out after '
                   f'{_TIMEOUT_S:.0f}s')
        return 1
    for line in reversed((proc.stdout or '').splitlines()):
        try:
            parsed = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(parsed, dict) and (
                'metric' in parsed or parsed.get('skipped')):
            print(line)
            return proc.returncode
    _emit_skip(f'sharded serve dryrun worker rc={proc.returncode} '
               f'printed no JSON row')
    return 1


def _measure_train(cfg, mesh, n, batch, seq, steps, warmup,
                   on_tpu) -> dict:
    """One training row. `mfu` is None off the TPU: the CPU has no
    published peak to be a fraction of (train/metrics.py raises for
    it), and --quick exists to smoke the code path, not to rate it."""
    import jax

    from skypilot_tpu.parallel import sharding as sharding_lib
    from skypilot_tpu.train import (TrainConfig, create_sharded_state,
                                    make_train_step, synthetic_batch)
    from skypilot_tpu.train import metrics as metrics_lib

    rng = jax.random.PRNGKey(0)
    state, shardings = create_sharded_state(
        cfg, mesh, rng, TrainConfig(warmup_steps=2, total_steps=1000))
    step_fn = make_train_step(cfg, mesh, shardings)
    # Cycle a few distinct batches so the loss stays an honest LM loss
    # instead of memorizing one batch.
    batches = [
        synthetic_batch(jax.random.PRNGKey(i), batch, seq,
                        cfg.unpadded_vocab_size or cfg.vocab_size)
        for i in range(4)
    ]
    timer = metrics_lib.StepTimer(warmup_steps=warmup)
    loss = None
    with sharding_lib.use_mesh(mesh):
        for i in range(steps + warmup):
            timer.start()
            state, m = step_fn(state, batches[i % len(batches)])
            loss = float(m['loss'])  # sync: forces the step to finish
            timer.stop()
    step_time = timer.mean_step_time()
    if on_tpu:
        # publish_throughput lands the same numbers in the metrics
        # registry (skytpu_train_tokens_per_sec / skytpu_train_mfu) so
        # a scraper sees exactly what this table prints.
        tps_all, mfu = metrics_lib.publish_throughput(
            cfg, batch, seq, step_time, num_chips=n)
    else:
        tps_all = metrics_lib.tokens_per_sec(batch, seq, step_time)
        mfu = None
    tps = tps_all / n
    print(f'model={cfg.name} chips={n} batch={batch} seq={seq} '
          f'steps={steps} step_time={step_time*1e3:.1f}ms '
          f'loss={loss:.3f} MFU='
          + ('not measured' if mfu is None else f'{mfu*100:.1f}%'),
          file=sys.stderr)
    # Free before the next row: state + moments of two seq-lengths
    # need not co-reside.
    del state, batches, step_fn
    return {'tps': round(tps, 1), 'mfu': mfu,
            'step_ms': round(step_time * 1e3, 1)}


def _tune_attn(args) -> dict:
    """Per-seq (block_q, block_k) sweep of the flash fwd+bwd pair on
    bench-like shapes, on the TPU (the caller refuses other platforms:
    a time taken under the interpreter ranks nothing). Prints a table;
    returns {seq: best_cfg}. A block pair the compiler refuses raises."""
    import itertools

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.ops.flash_attention import flash_attention

    b, h, d = 4, 16, 128
    seqs = [args.seq] + [int(s) for s in args.sweep_seq.split(',') if s]
    blocks = [128, 256, 512, 1024]
    best = {}
    for seq in seqs:
        q, k, v, g = (jax.random.normal(jax.random.PRNGKey(i),
                                        (b, seq, h, d), jnp.bfloat16)
                      for i in range(4))
        rows = []
        for bq, bk in itertools.product(blocks, blocks):
            if seq % bq or seq % bk:
                continue

            def f(q, k, v, bq=bq, bk=bk):
                return flash_attention(q, k, v, impl='pallas',
                                       block_q=bq, block_k=bk)

            fwd_bwd = jax.jit(lambda q, k, v, g: jax.vjp(
                f, q, k, v)[1](g))
            # Compile + smoke, SYNCED — async bleed into the timed
            # window would inflate every measurement ~20%.
            jax.block_until_ready(fwd_bwd(q, k, v, g))
            t0 = time.time()
            for _ in range(5):
                out = fwd_bwd(q, k, v, g)
            jax.block_until_ready(out)
            dt_ms = (time.time() - t0) / 5 * 1e3
            rows.append((dt_ms, bq, bk))
            print(f'[tune] seq={seq} bq={bq} bk={bk}: {dt_ms:.2f} ms',
                  file=sys.stderr)
        if rows:
            rows.sort()
            t, bq, bk = rows[0]
            best[seq] = {'block_q': bq, 'block_k': bk,
                         'ms': round(t, 2)}
            print(f'[tune] BEST seq={seq}: bq={bq} bk={bk} '
                  f'({t:.2f} ms fwd+bwd)', file=sys.stderr)
    return best


def _dryrun_lint(args) -> int:  # pylint: disable=unused-argument
    """SKYLINT: the static-analysis proxy row (pure CPU stdlib — no
    jax, no devices, no fake-device env). Mirrors the MULTICHIP_r0x
    dryrun contract: ONE JSON row, ok == zero unwaived findings, the
    per-checker breakdown as extra keys so a regression names the
    checker that caught it."""
    from skypilot_tpu import analysis
    try:
        result = analysis.run_lint()
    except analysis.LintError as e:
        _emit_skip(f'skylint internal error: {e}')
        return 2
    summary = result.to_dict()['summary']
    row = {
        'metric': 'SKYLINT dryrun',
        'value': float(summary['unwaived']),
        'unit': 'unwaived findings',
        'vs_baseline': 0.0,            # the pinned bar IS zero
        'ok': result.ok,
        'skipped': False,
        'checkers': len(result.selected),
        'waived': summary['waived'],
        'by_checker': summary['by_checker'],
        'duration_s': summary['duration_s'],
    }
    print(json.dumps(row))
    return 0 if result.ok else 1


def _worker(args) -> int:
    if args.dryrun_lint:
        return _dryrun_lint(args)
    if args.dryrun_serve_sharded:
        # CPU-only by design; forces its own fake-device backend
        # BEFORE any jax.devices() call.
        return _dryrun_serve_sharded(args)
    if args.dryrun_serve_kernel:
        return _dryrun_serve_kernel(args)
    if args.dryrun_serve_fleet:
        return _dryrun_serve_fleet(args)
    if args.dryrun_serve_disagg:
        return _dryrun_serve_disagg(args)
    if args.dryrun_serve_multitenant:
        return _dryrun_serve_multitenant(args)
    if args.dryrun_trace:
        return _dryrun_trace(args)
    if args.dryrun_train_zero1:
        # CPU-only by design; forces its own fake-device backend
        # BEFORE any jax.devices() call.
        return _dryrun_train_zero1(args)
    if args.dryrun_train_elastic:
        return _dryrun_train_elastic(args)

    import jax

    from skypilot_tpu.models import get_config
    from skypilot_tpu.parallel import (build_mesh, distributed,
                                       infer_mesh_config)

    distributed.enable_compile_cache()
    if args.int8_kv:
        args.kv_quant = 'int8'   # --int8-kv is shorthand for this
    init_start = time.time()
    devices = jax.devices()
    n = len(devices)
    on_tpu = devices[0].platform == 'tpu'
    print(f'[bench] backend up in {time.time() - init_start:.1f}s: '
          f'{n} x {devices[0].device_kind} ({devices[0].platform})',
          file=sys.stderr)
    if not on_tpu and (args.tune_attn or not args.quick):
        # A measurement row off the chip measures XLA's CPU backend or
        # the Pallas interpreter. Say so and stop; never a smaller
        # model under the same metric name.
        print(f'[bench] no TPU: jax found platform '
              f'{devices[0].platform!r} ({devices[0].device_kind}). '
              f'The measurement rows need the chip; --quick runs the '
              f'test-tiny smoke of the train and serve paths, and the '
              f'--dryrun-* rows are CPU rows.', file=sys.stderr)
        return 2
    if args.quick:
        model_name = 'test-tiny'
        batch, seq, steps = 8, 128, 4
        sweep = []
    else:
        model_name, batch, seq, steps = (args.model, args.batch, args.seq,
                                         args.steps)
        sweep = [int(s) for s in args.sweep_seq.split(',') if s]
    mesh = build_mesh(infer_mesh_config(n))  # fsdp over all local chips

    if args.tune_attn:
        best = _tune_attn(args)
        result = {'metric': 'flash-attn block tune',
                  'value': float(len(best)), 'unit': 'seqs',
                  'vs_baseline': 1.0, 'best': best}
        print(json.dumps(result))
        return 0

    if args.serve:
        serve_cfg = get_config(model_name, param_dtype='bfloat16')
        if args.tp and args.tp > 1:
            # Tensor-parallel serve row: tp innermost over the first N
            # local chips (parallel.decode_mesh) instead of the
            # training default (fsdp over everything). A tp exceeding
            # the local device count is as deterministic a verdict as
            # an engine-construction rejection — same structured skip.
            from skypilot_tpu.parallel import decode_mesh
            try:
                mesh = decode_mesh(args.tp)
            except ValueError as e:
                _emit_skip(f'unsupported serve combination: {e}',
                           combo={'tp': args.tp, 'n_devices': n})
                return 3
        try:
            ttft = _measure_ttft(serve_cfg, mesh, quantize=args.quantize,
                                 decode_chunk=args.decode_chunk,
                                 kv_quant=args.kv_quant,
                                 speculative=args.speculative,
                                 prefix_cache=args.prefix_cache,
                                 paged_block_size=args.paged_block_size,
                                 async_depth=args.async_depth,
                                 decode_kernel=args.decode_kernel)
        except _UnsupportedServeCombo as e:
            # An unrunnable flag combination (block size not dividing
            # the window, an unknown quant mode, ...) must still honor
            # the one-JSON-line contract: a structured skip naming the
            # combo, not a stack trace with nothing to parse. Only
            # CONSTRUCTION failures qualify — a ValueError raised
            # mid-measurement is a real failure and must propagate,
            # not masquerade as a deterministic skip.
            _emit_skip(
                f'unsupported serve combination: {e}',
                combo={'kv_quant': args.kv_quant or 'none',
                       'speculative': args.speculative,
                       'paged_block_size': args.paged_block_size,
                       'async_depth': args.async_depth,
                       'decode_kernel': args.decode_kernel})
            return 3
        print(f'serve: {ttft}', file=sys.stderr)
        tags = [t for t in (args.quantize,
                            f'tp-{args.tp}'
                            if args.tp and args.tp > 1 else None,
                            f'kv-{args.kv_quant}' if args.kv_quant
                            else None,
                            f'spec-{args.speculative}'
                            if args.speculative else None,
                            f'pfx-{args.prefix_cache}'
                            if args.prefix_cache else None,
                            f'paged-{args.paged_block_size}'
                            if args.paged_block_size else None,
                            f'async-{args.async_depth}'
                            if args.async_depth else None,
                            f'kernel-{args.decode_kernel}'
                            if args.decode_kernel != 'xla'
                            else None) if t]
        result = {
            'metric': f'{serve_cfg.name} serve p50 TTFT'
                      + (f' ({"+".join(tags)})' if tags else ''),
            'value': ttft['p50_ttft_ms'],
            'unit': 'ms',
            'vs_baseline': 1.0,  # tracking metric: no reference number
            'decode_chunk': args.decode_chunk,
            'quantize': args.quantize or 'none',
            'kv_quant': args.kv_quant or 'none',
            'speculative': args.speculative,
            'prefix_cache': args.prefix_cache,
            'paged_block_size': args.paged_block_size,
            'decode_kernel': args.decode_kernel,
            **ttft,
        }
        print(json.dumps(result))
        return 0

    cfg = get_config(model_name, param_dtype='bfloat16')
    row = _measure_train(cfg, mesh, n, batch, seq, steps, args.warmup,
                         on_tpu)
    mfu = row['mfu']
    result = {
        'metric': f'{cfg.name} train tokens/sec/chip',
        'value': row['tps'],
        'unit': 'tokens/s/chip',
        'vs_baseline': None if mfu is None else round(mfu / 0.45, 4),
        'mfu': None if mfu is None else round(mfu, 4),
        'seq': seq,
    }

    # A sweep row or the serve row that throws ends the run non-zero:
    # a result line missing a row it was asked for is not a result.
    for extra_seq in sweep:
        srow = _measure_train(cfg, mesh, n, batch, extra_seq, steps,
                              args.warmup, on_tpu)
        result.update({
            f'seq{extra_seq}_tps': srow['tps'],
            f'seq{extra_seq}_mfu': round(srow['mfu'], 4),
        })

    if on_tpu and not args.quick and not args.no_serve_row:
        serve_cfg = get_config(model_name, param_dtype='bfloat16')
        ttft = _measure_ttft(serve_cfg, mesh,
                             quantize=args.quantize,
                             decode_chunk=args.decode_chunk,
                             kv_quant=args.kv_quant)
        print(f'serve: {ttft}', file=sys.stderr)
        result.update({'serve_p50_ttft_ms': ttft['p50_ttft_ms'],
                       'serve_p99_ttft_ms': ttft['p99_ttft_ms'],
                       'serve_decode_chunk': args.decode_chunk,
                       'serve_quantize': args.quantize or 'none',
                       'serve_kv_quant': args.kv_quant or 'none'})

    print(json.dumps(result))
    return 0


def main() -> int:
    args = _parse_args()
    if args.worker or args.dryrun_lint:
        # --dryrun-lint: the analyzer is stdlib-only and deterministic,
        # so it needs neither a child nor fake devices.
        return _worker(args)
    if (args.dryrun_serve_sharded or args.dryrun_serve_fleet or
            args.dryrun_serve_disagg or args.dryrun_serve_multitenant or
            args.dryrun_trace or args.dryrun_serve_kernel or
            args.dryrun_train_zero1 or args.dryrun_train_elastic):
        return _supervise_dryrun(sys.argv[1:])
    # The measurement runs HERE: the one process that holds the chip.
    return _worker(args)


if __name__ == '__main__':
    sys.exit(main())
