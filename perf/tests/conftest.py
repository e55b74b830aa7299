"""The benchmark's own tests run on the CPU: `python -m pytest perf/tests -q`."""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)
for p in (PERF_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
