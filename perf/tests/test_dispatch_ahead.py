"""`dispatch_ahead_pct`: the reader over the engine's own counts, on a
program that has them and on one that does not."""
import pytest

import common
import tiny


def _read(ctx):
    return common.load_module('metrics', 'dispatch_ahead_pct').read(ctx)


def test_chained_over_dispatches():
    occ = {'decode_dispatches': 40, 'decode_chained': 38,
           'ring_flushes': 0}
    assert _read({'occupancy': occ}) == pytest.approx(95.0)


@pytest.mark.parametrize('occ', [None, {}, {'blocks_capacity': 9},
                                 {'decode_dispatches': 0,
                                  'decode_chained': 0}])
def test_nothing_to_read_without_the_counters(occ):
    """The parent's engine reports neither count; a run that
    dispatched nothing has no share."""
    assert _read({'occupancy': occ}) is None


@pytest.mark.parametrize('depth,low,high', [(0, 0.0, 0.0),
                                            (None, 80.0, 100.0)])
def test_a_traced_run_reports_what_the_engine_counted(depth, low, high):
    """Through the closed-loop driver at a tiny size: a synchronous
    engine queues nothing ahead, the program's default nearly every
    dispatch."""
    ctx = tiny.ctx(tiny.config(True, 0), tiny.serve_mix(),
                   tiny.SERVE_LIMITS, 2**31 + 7, 1.5, trace=True)
    if depth is not None:
        ctx['engine_overrides'] = {'async_depth': depth}
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct'], res['checks']
    got = _read(res['reader_ctx'])
    assert low <= got <= high, got
    assert res['reader_ctx']['occupancy']['ring_flushes'] == 0
