"""What every driver shares: finding a cell's files by name, the device
gate, the compile cache, the table of peaks and the one result line.

Nothing here touches jax until `require_tpu` is called, so `run.py` can
refuse a directory that lacks the program before any import of it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
PROCESS_START = time.monotonic()


class HarnessError(Exception):
    """The run cannot be measured: no result line is printed."""


def load_json(path: str) -> dict:
    with open(path, 'r', encoding='utf-8') as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, 'BENCHMARK.json'))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench['workloads']:
        if cell['name'] == name:
            return cell
    raise HarnessError(f'no workload {name!r} in BENCHMARK.json; have '
                       f'{[c["name"] for c in bench["workloads"]]}')


def load_config(name: str) -> dict:
    return load_json(os.path.join(PERF_DIR, 'configs', f'{name}.json'))


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(PERF_DIR, 'traffic', f'{name}.json'))


def load_module(kind: str, name: str):
    """perf/<kind>/<name>.py as a module, found by name alone (names may
    hold dots, so this goes by path and not by import)."""
    path = os.path.join(PERF_DIR, kind, f'{name}.py')
    if not os.path.exists(path):
        raise HarnessError(f'{kind} {name!r}: no file {path}')
    spec = importlib.util.spec_from_file_location(
        f'perf_{kind}_{name.replace(".", "_").replace("-", "_")}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: dict, group: str) -> list:
    """The metrics of `group` ('end_to_end' | 'per_layer') this cell
    reports: those that list it, or list no cells at all."""
    out = []
    for m in bench[group]:
        cells = m.get('workloads')
        if cells is None or cell['name'] in cells:
            out.append(m)
    return out


def run_context(workload: str, seed: int, seconds: float,
                trace: bool = False) -> dict:
    """What a driver is handed: the cell with its files, found by name,
    and the device, which has to be a TPU with the cell's chips."""
    require_program()
    cell = find_cell(load_benchmark(), workload)
    limits = load_json(os.path.join(
        PERF_DIR, 'cells', f'{cell["name"]}.json'))['limits']
    device = require_tpu(int(cell['chips']))
    enable_compile_cache()
    return {'cell': cell, 'config': load_config(cell['config']),
            'mix': load_traffic(cell['traffic']), 'limits': limits,
            'seed': seed, 'seconds': seconds, 'trace': trace,
            'device': device, 'peaks': peaks_for(device['kind'])}


def require_program() -> None:
    """A directory that holds only BENCHMARK.json and perf/ has no
    system to test."""
    if not os.path.isdir(os.path.join(ROOT, 'skypilot_tpu')):
        raise HarnessError('skypilot_tpu/ is not in this checkout: there '
                           'is no system under test')
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), every program stored."""
    import jax
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not path:
        path = os.path.join(ROOT, '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return path


def require_tpu(chips: int) -> dict:
    """The device as jax reports it, or HarnessError when it is not a
    TPU with at least `chips` chips. Never falls back."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu':
        raise HarnessError(f'jax found platform {platform!r}, not a TPU: '
                           f'nothing is measured')
    if len(devices) < chips:
        raise HarnessError(f'cell needs {chips} chip(s), jax found '
                           f'{len(devices)}')
    return {'platform': platform, 'kind': devices[0].device_kind,
            'count': chips}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(PERF_DIR, 'peaks.json'))
    if kind not in table:
        raise HarnessError(f'device kind {kind!r} is not in perf/peaks.json'
                           f' (known: {sorted(table)})')
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax
    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0)))
    return peak


def seed_key(seed: int, stream: int = 0):
    """A jax key from any whole number up to 2**32 and beyond: the low
    31 bits seed it, the rest and the stream number are folded in. The
    key is of the `rbg` kind: drawing 4 billion weights with the default
    threefry takes the chip 13 s, with its own generator under one."""
    import jax
    key = jax.random.key(seed & 0x7FFFFFFF, impl='rbg')
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all values (q in 0..100)."""
    vals = sorted(values)
    if not vals:
        raise ValueError('percentile of nothing')
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f'check {name}: value={c["value"]!r} limit={c["limit"]!r} '
              f'ok={c["ok"]}', file=sys.stderr)
    sys.stderr.flush()


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, breakdown=None,
                checks=None) -> str:
    out = {'correct': bool(correct), 'attempted': int(attempted),
           'failed': int(failed), 'metrics': metrics, 'device': device}
    if breakdown is not None:
        out['breakdown'] = breakdown
    out['checks'] = checks or {}
    return json.dumps(out)
