"""The trace reduction on a small recorded trace: 420 ms cut from the
first traced chip run of this benchmark (an open-loop chat mix on
mistral-7b-l16 that PR 24 tried and left out; TPU v5 lite), with the
harness's mark of the traced stretch in it."""
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), 'data',
                    'chat_small.xplane.pb')


@pytest.fixture(scope='module')
def trace():
    return trace_reduce.load(DATA)


def test_union_and_merge():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.merged([(5, 20), (0, 10), (30, 40)]) == [
        [0, 20], [30, 40]]
    assert trace_reduce.module_base('jit__decode_step_impl(157)') == \
        'jit__decode_step_impl'
    assert trace_reduce.op_base('%fusion.172 = bf16[16]') == 'fusion'


def test_stretch_and_busy(trace):
    assert trace.stretch_ns is not None
    assert abs(trace.window_s() - 2.939498762) < 1e-6
    assert 0 in trace.devices
    # the cut holds 420 ms of the stretch, nearly all of it busy
    assert 0.40 < trace.busy_s() < 0.43


def test_modules_found_by_name(trace):
    totals = trace.module_totals()
    prefill = sum(v for k, v in totals.items() if 'prefill' in k)
    decode = sum(v for k, v in totals.items() if 'decode' in k)
    assert 0.37 < prefill < 0.39 and 0.04 < decode < 0.05
    assert 0 < trace.ops_within_modules('prefill') <= prefill
    assert 0 < trace.ops_within_modules('decode') <= decode
    assert trace.ops_within_modules('no_such_program') == 0.0


def test_breakdown(trace):
    ops = trace.top_ops(10)
    assert 1 <= len(ops) <= 10 and all(s > 0 for _, s in ops)
    assert 'while' not in [n for n, _ in ops]
    gaps = trace.idle_gaps(10)
    assert len(gaps) <= 10


def test_a_trace_with_no_device_reads_nothing():
    empty = trace_reduce.Trace({}, [])
    assert empty.busy_s() == 0.0 and empty.window_s() is None
    assert empty.ops_within_modules('decode') == 0.0
    assert empty.top_ops() == [] and empty.idle_gaps() == []
