"""Sharded-serving test driver: runs the tp=2 composition matrix on 8
fake CPU devices and prints ONE JSON line with every result.

Run by tests/test_composition_matrix.py through the `sharded_subprocess`
conftest fixture — in a SUBPROCESS so the main pytest process keeps its
single-device jit caches (the satellite's isolation requirement) and one
driver run feeds every sharded test's assertions.

Covers:
- tp=2 × {contiguous, paged, int8, speculative, async_depth=3, chunked
  prefill}: greedy token streams BIT-IDENTICAL to the single-chip
  engine with the same knobs (the acceptance-criteria pin);
- a PR-6 prefix-artifact round-trip THROUGH a sharded pool (export from
  one tp=2 engine, pre-warm another, prewarm-hit + bit-identity);
- per-device weight+KV footprint ≤ (1/tp + ε) of single-chip;
- the compiled-HLO collective probe (all-reduces > 0 under tp=2).
"""
import dataclasses
import json
import os
import sys
import tempfile


def _force_devices() -> None:
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()


PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]
LONG_PROMPT = list(range(1, 33))      # ≥ _MIN_PREFIX: prefix-cacheable

# Mirrors test_composition_matrix._CELLS, restricted to the sharded
# acceptance set: every composition must survive the layout change.
CELLS = [
    ('contig', {}),
    ('paged', dict(paged_block_size=8)),
    ('int8', dict(kv_quant='int8')),
    ('paged-int8', dict(paged_block_size=8, kv_quant='int8')),
    ('spec', dict(paged_block_size=8, speculative=3)),
    ('async3', dict(paged_block_size=8, kv_quant='int8',
                    async_depth=3)),
    ('chunkedprefill', dict(paged_block_size=8, prefill_chunk=4)),
    # Fused pallas decode kernel under tp (ISSUE 18): GSPMD runs the
    # interpreter kernel over gathered inputs on fake devices (the
    # replication note in docs/performance.md), so correctness — the
    # greedy stream vs the single-chip pallas engine — is what the tp
    # cell pins.
    ('pallas-paged', dict(paged_block_size=8,
                          decode_kernel='pallas_interpret')),
]


def _cfg(**kw):
    from skypilot_tpu.models import get_config
    cfg = get_config('test-tiny')
    return dataclasses.replace(cfg, dtype='float32',
                               param_dtype='float32', max_seq_len=64,
                               remat=False, **kw)


def _engine(mesh=None, **kw):
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    return ContinuousBatchingEngine(_cfg(), num_slots=2, mesh=mesh,
                                    **kw)


def run(tp: int = 2) -> dict:
    _force_devices()
    import jax
    from skypilot_tpu.parallel import decode_mesh

    out = {'tp': tp, 'n_devices': len(jax.devices()), 'cells': {}}
    assert out['n_devices'] >= tp, jax.devices()
    mesh = decode_mesh(tp)

    for name, kw in CELLS:
        base = _engine(**kw)
        ref, _ = base.generate(PROMPT, max_new_tokens=16)
        base.stop()
        shard = _engine(mesh=mesh, **kw)
        got, stats = shard.generate(PROMPT, max_new_tokens=16)
        cell = {'match': got == ref, 'ref': ref, 'got': got,
                'new_tokens': stats['new_tokens']}
        if kw.get('async_depth'):
            cell['chained'] = shard.tick_stats['chained']
        if kw.get('paged_block_size'):
            shard._pool.check()  # pylint: disable=protected-access
        if name == 'async3':
            # One cell also carries the footprint + HLO probes (every
            # sharded engine shares the placement path).
            mem = shard.memory_footprint()
            base2 = _engine(**kw)
            mem0 = base2.memory_footprint()
            base2.stop()
            out['memory'] = {
                'per_device_bytes': mem['total_bytes_per_device'],
                'single_chip_bytes': mem0['total_bytes'],
                'frac': (mem['total_bytes_per_device'] /
                         mem0['total_bytes']),
            }
            out['hlo'] = shard.decode_hlo_stats()
            # Late-exporter pin (the PR-5 int8-gauge lesson): enable
            # recording only NOW — after construction, warmup and the
            # probe — and the next ticks must still publish the tp
            # gauges (the engine re-sets them per tick).
            from skypilot_tpu import observability as obs_pkg
            obs_pkg.enable()
            shard.generate(PROMPT, max_new_tokens=4)
            metrics = obs_pkg.parse_prometheus_text(
                obs_pkg.generate_latest())
            obs_pkg.disable()

            def _gauge(name_):
                series = metrics.get(name_, {}).get('samples', {})
                vals = list(series.values())
                return vals[0] if vals else None

            out['late_exporter_gauges'] = {
                'tp_size': _gauge('skytpu_engine_tp_size'),
                'tp_collectives': _gauge('skytpu_engine_tp_collectives'),
                'tp_allreduce_bytes': _gauge(
                    'skytpu_engine_tp_allreduce_bytes'),
            }
        shard.stop()
        out['cells'][name] = cell

    # PR-6 artifact round-trip through a SHARDED pool: export from one
    # tp engine, pre-warm a fresh one, and the warmed engine both
    # credits the import (prewarm hit) and stays bit-identical.
    kw = dict(paged_block_size=8, prefix_cache=4)
    src = _engine(mesh=mesh, **kw)
    ref, _ = src.generate(LONG_PROMPT, max_new_tokens=12)
    path = os.path.join(tempfile.mkdtemp(prefix='skytpu-shard-'),
                        'prefixes.bin')
    export = src.export_prefixes(path)
    src.stop()
    dst = _engine(mesh=mesh, **kw)
    imported = dst.import_prefixes(path)
    got, _ = dst.generate(LONG_PROMPT, max_new_tokens=12)
    out['roundtrip'] = {
        'exported': export['exported'],
        'imported': imported['imported'],
        'blocks': imported['blocks'],
        'prewarm_hits': dst.prefix_stats['prewarm_hits'],
        'match': got == ref,
    }
    dst._pool.check()  # pylint: disable=protected-access
    dst.stop()
    # Artifacts are tp-PORTABLE (gather/scatter trade in global block
    # bytes): the same tp=2 export pre-warms a single-chip engine.
    xdst = _engine(**kw)
    ximported = xdst.import_prefixes(path)
    xgot, _ = xdst.generate(LONG_PROMPT, max_new_tokens=12)
    out['roundtrip']['cross_tp_imported'] = ximported['imported']
    out['roundtrip']['cross_tp_match'] = xgot == ref
    xdst.stop()

    # get_engine's documented auto-tp: on 8 local devices with
    # test-tiny (2 kv heads) it must pick tp=2 and serve end-to-end
    # through the sharded InferenceEngine path.
    import jax.numpy as jnp

    from skypilot_tpu.models.inference import get_engine
    auto = get_engine('test-tiny', max_seq_len=64)
    toks, _ = auto.generate(jnp.ones((1, 4), jnp.int32),
                            max_new_tokens=4)
    out['get_engine'] = {
        'tp': auto._tp,  # pylint: disable=protected-access
        'new_tokens': int(toks.shape[1]),
    }

    gauges = out['late_exporter_gauges']
    out['ok'] = (all(c['match'] for c in out['cells'].values())
                 and out['roundtrip']['match']
                 and out['roundtrip']['cross_tp_match']
                 and out['roundtrip']['prewarm_hits'] >= 1
                 and out['memory']['frac'] <= 1.0 / tp + 0.05
                 and out['hlo']['all_reduce'] > 0
                 and gauges['tp_size'] == tp
                 and (gauges['tp_allreduce_bytes'] or 0) > 0
                 and out['get_engine'] == {'tp': 2, 'new_tokens': 4})
    return out


if __name__ == '__main__':
    result = run(tp=int(sys.argv[1]) if len(sys.argv) > 1 else 2)
    print(json.dumps(result))
    sys.exit(0 if result['ok'] else 1)
