"""`moe_gmm_share_pct`: the reader over a device trace, on a program
whose grouped products are XLA's `ragged-dot`, on one whose are the
`moe_gmm` kernel, and on one that has neither."""
import json
import os

import pytest

import common
import trace_reduce


def _read(trace):
    return common.load_module('metrics', 'moe_gmm_share_pct').read(
        {'trace': trace})


def _trace(ops):
    dev = trace_reduce.DeviceTrace(0)
    dev.ops = ops
    return trace_reduce.Trace({0: dev}, [])


MS = 1_000_000
PARENT = '%ragged-dot-none.{} = bf16[512,3072]{{1,0}} custom-call(%a, %b)'
CHANGE = ('%moe_gmm.{} = f32[512,3072]{{1,0:T(8,128)}} custom-call('
          '%bitcast.2, %fusion.1), custom_call_target="tpu_custom_call"')
# a fusion that reads a product's output carries its name as an operand
READER = ('%multiply_fusion.{} = bf16[512,3072]{{1,0}} fusion('
          '%moe_gmm.3, %ragged-dot-none.4), kind=kLoop')


@pytest.mark.parametrize('product', [PARENT, CHANGE,
                                     'ragged-dot-none.{} = bf16[8]',
                                     '%moe_gmm = bf16[512,3072] custom'])
def test_the_products_seconds_over_the_busy_seconds(product):
    ops = []
    for i in range(4):      # 10 ms: 2 of a product, 6 of a reader, 2 idle
        t = 10 * MS * i
        ops.append((t, t + 2 * MS, product.format(i)))
        ops.append((t + 2 * MS, t + 8 * MS, READER.format(i)))
    assert _read(_trace(ops)) == pytest.approx(25.0)


def test_both_names_count_together():
    ops = [(0, MS, PARENT.format(1)), (MS, 2 * MS, CHANGE.format(2)),
           (2 * MS, 4 * MS, READER.format(3))]
    assert _read(_trace(ops)) == pytest.approx(50.0)


@pytest.mark.parametrize('ops', [
    [], [(0, MS, READER.format(1))],
    [(0, MS, '%fusion.7 = bf16[32,4096] fusion(%p)'),
     (MS, 2 * MS, '%attn.3 = bf16[1,4096,32,128] custom-call(%q)')]])
def test_nothing_to_read_without_a_grouped_product(ops):
    """A dense model's trace (and the flash kernels' `attn`) has
    neither name; a fusion that reads a product is not a product."""
    assert _read(_trace(ops)) is None
    assert _read(trace_reduce.Trace({}, [])) is None


def test_a_recorded_dense_trace_reads_nothing():
    trace = trace_reduce.load(os.path.join(
        os.path.dirname(__file__), 'data', 'chat_small.xplane.pb'))
    assert trace.busy_s() > 0 and _read(trace) is None


def test_the_benchmark_lists_it_for_the_expert_cell_alone():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        entries = json.load(f)['per_layer']
    assert [e for e in entries if e['name'] == 'moe_gmm_share_pct'] == [{
        'name': 'moe_gmm_share_pct', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'model step',
        'moves': 'tokens_per_s',
        'workloads': ['trinity-large-l5-ep8.chat-128']}]
