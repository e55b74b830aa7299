#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (perf/configs/<config>.json), its traffic mix
(perf/traffic/<mix>.json), its limits (perf/cells/<cell>.json) and every
per-layer metric (perf/metrics/<name>.py) are found by name. The last
line of standard output is one JSON object; a run that cannot be
measured (no TPU, too few chips, no program) exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import os
import sys

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
if PERF_DIR not in sys.path:
    sys.path.insert(0, PERF_DIR)

import common  # noqa: E402


def collect_metrics(bench: dict, cell: dict, trace: bool, e2e: dict,
                    reader_ctx) -> dict:
    out = {}
    if not trace:
        for m in common.cell_metrics(bench, cell, 'end_to_end'):
            if m['name'] not in e2e:
                raise common.HarnessError(
                    f'the run has no {m["name"]} for {cell["name"]}')
            out[m['name']] = {'value': e2e[m['name']], 'unit': m['unit']}
        return out
    for m in common.cell_metrics(bench, cell, 'per_layer'):
        reader = common.load_module('metrics', m['name'])
        value = reader.read(reader_ctx)
        if value is None:
            continue      # nothing to read: the metric is left out
        out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        ctx = common.run_context(args.workload, args.seed, args.seconds,
                                 bool(args.trace))
        bench, cell = common.load_benchmark(), ctx['cell']
        driver = common.load_module('drivers', ctx['mix']['driver'])
        res = driver.run(ctx)
        metrics = collect_metrics(bench, cell, bool(args.trace),
                                  res['e2e'], res['reader_ctx'])
        breakdown = None
        if args.trace and res['reader_ctx'] is not None:
            tr = res['reader_ctx']['trace']
            breakdown = {'device_ops': tr.top_ops(10),
                         'idle_gaps': tr.idle_gaps(10)}
    except common.HarnessError as e:
        print(f'perf/run.py: {e}', file=sys.stderr)
        return 2
    for k, v in sorted(res['e2e'].items()):
        print(f'note {k}={v!r}')
    common.print_checks(res['checks'])
    print(common.result_line(
        correct=res['correct'], attempted=res['attempted'],
        failed=res['failed'], metrics=metrics, device=res['device'],
        breakdown=breakdown, checks=res['checks']))
    sys.stdout.flush()
    return 0


if __name__ == '__main__':
    sys.exit(main())
