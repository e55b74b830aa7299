"""perf/run.py refuses to measure, and prints no result, when it finds
no TPU, and in a directory that holds only BENCHMARK.json and perf/."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cell():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)['workloads'][0]['name']


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, 'perf/run.py', '--workload', _cell(), '--seed',
         '1', '--seconds', '1', '--trace', '0'],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines():
        if line.startswith('{') and '"metrics"' in line:
            return False
    return True


def test_no_tpu_no_metric():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout), p.stdout
    assert 'not a TPU' in p.stderr


def test_bare_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(os.path.join(ROOT, 'perf'), tmp_path / 'perf',
                    ignore=shutil.ignore_patterns('__pycache__'))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout), p.stdout
    assert 'no system under test' in p.stderr
