#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python perf/limit_readings.py --workload <cell> --seeds 1,2,3 --seconds 20
        [--quantize int8]      serving: the program's own int8 path, the control
        [--also fp8,half]      training: controls and faults, read in the
                               reference put in the program's place and
                               judged by the checks that decide a run

Each seed builds the cell anew (weights, engine or train state), runs a
short window at the cell's own load and the comparison with the plain
reference, and prints one line with every number compared. Not part of a
benchmark run: the limits go into perf/cells/<cell>.json by hand, with
the readings into PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, PERF_DIR)

import common  # noqa: E402


def altering_emit(real):
    """The fault 'a token altered where it is produced': the engine's
    `_emit` with every 5th token a slot emits replaced by its neighbour
    in the vocabulary."""
    import numpy as np
    count = [0]

    def emit(self, slots, active, out_cols, valid):
        cols = np.array(out_cols)
        for slot in active:
            count[0] += 1
            if count[0] % 5 == 0:
                cols[slot, 0] = (int(cols[slot, 0]) + 1) % \
                    self.cfg.vocab_size
        return real(self, slots, active, cols, valid)

    return emit


def plant_altered_tokens() -> None:
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    ContinuousBatchingEngine._emit = altering_emit(
        ContinuousBatchingEngine._emit)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=20.0)
    ap.add_argument('--quantize', default='')
    ap.add_argument('--also', default='')
    ap.add_argument('--fault', default='',
                    help="serving: 'alter' replaces every 5th sampled "
                         'token before it is emitted')
    args = ap.parse_args()
    base = common.run_context(args.workload, 0, args.seconds)
    driver = common.load_module('drivers', base['mix']['driver'])
    if args.fault == 'alter':
        plant_altered_tokens()
    for seed in [int(s) for s in args.seeds.split(',')]:
        ctx = dict(base, seed=seed)
        if args.quantize:
            ctx['engine_overrides'] = {'quantize': args.quantize}
        if args.also:
            ctx['also'] = args.also.split(',')
        res = driver.run(ctx)
        values = {k: c['value'] for k, c in res['checks'].items()}
        print('reading ' + json.dumps(
            {'seed': seed, 'quantize': args.quantize, 'values': values,
             'also': res.get('also', {}), 'e2e': res['e2e'],
             'correct': res['correct'],
             'failed': res['failed']}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
