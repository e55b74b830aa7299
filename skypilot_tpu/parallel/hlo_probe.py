"""Compiled-HLO probe: what the compiler made of a sharded program.

Parses a compiled executable's optimized HLO text and counts the
collectives GSPMD inserted — how many all-reduces a tp-sharded decode
step pays per tick and how many bytes they move over ICI — and reads
the shapes each Mosaic custom call was handed, which is how a
multi-chip run shows that a Pallas kernel got its per-device shard and
not the gathered whole. These are counts and shapes, not times.

Consumed by the inference engines (`decode_hlo_stats`, which feeds the
`skytpu_engine_tp_allreduce_bytes` / `skytpu_engine_tp_collectives`
gauges), by the trainer (`compiled_step_collectives`, behind
`train.run --probe-hlo`) and by the fake-device drivers under `tests/`
(`sharded_driver.py`, `zero1_driver.py`). Pure text parsing — no jax
import, so it is testable without a device and adds nothing to engine
import time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

# Collective op mnemonics as they appear in optimized HLO. Order
# matters for longest-match ('all-reduce-start' before 'all-reduce' is
# handled by matching '-start'/'-done' suffixes explicitly).
_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter',
                'collective-permute', 'all-to-all')

_ITEMSIZE = {
    'pred': 1, 's8': 1, 'u8': 1, 'f8e4m3fn': 1, 'f8e5m2': 1,
    's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8, 'c64': 8, 'c128': 16,
}

# `f32[4,1,64]` / `bf16[8]` / `s32[]` result-shape tokens.
_SHAPE_RE = re.compile(r'\b([a-z]\w*)\[([0-9,]*)\]')


def _shape_elems(dtype: str, dims: str) -> int:
    if dtype not in _ITEMSIZE:
        return 0  # token/opaque types carry no payload we can count
    n = 1
    for d in dims.split(','):
        if d:
            n *= int(d)
    return n


def _shape_bytes(dtype: str, dims: str) -> int:
    return _shape_elems(dtype, dims) * _ITEMSIZE.get(dtype, 0)


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Count collective ops (and the bytes their results carry) in
    optimized HLO text (`compiled.as_text()`).

    Returns {'<op>': count, '<op>_bytes': bytes, ..., 'total',
    'total_bytes'} with op keys underscored (all_reduce, ...). Async
    pairs (all-reduce-start / all-reduce-done) count ONCE, via the
    -start op. Byte counts sum each collective's RESULT shapes (tuple
    results sum their elements) — for an all-reduce that is exactly the
    payload every participating device contributes/receives per step.
    """
    stats: Dict[str, Any] = {}
    for op in _COLLECTIVES:
        key = op.replace('-', '_')
        stats[key] = 0
        stats[key + '_bytes'] = 0
    for line in hlo_text.splitlines():
        if '=' not in line:
            continue
        lhs, _, rhs = line.partition('=')
        rhs = rhs.lstrip()
        for op in _COLLECTIVES:
            # Match the op at the head of the RHS (`f32[...] all-reduce(`
            # puts the result shape first on the lhs side of ' = ' only
            # for named instructions; optimized HLO prints
            # `%name = f32[..] all-reduce(...)`, so after '=' the shape
            # precedes the mnemonic).
            m = re.search(r'\b' + re.escape(op) + r'(-start)?\(', rhs)
            if m is None:
                continue
            if re.search(r'\b' + re.escape(op) + r'-done\(', rhs):
                continue  # the -start already counted this pair
            key = op.replace('-', '_')
            stats[key] += 1
            shape_src = rhs[:m.start()] or lhs
            shapes = _SHAPE_RE.findall(shape_src)
            size = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
            if m.group(1) and len(shapes) % 2 == 0 and \
                    shapes[:len(shapes) // 2] == shapes[len(shapes) // 2:]:
                # Async `-start` ops return an (operand-alias, result)
                # tuple whose halves mirror each other — summing both
                # would double-count the payload the collective moves.
                size //= 2
            stats[key + '_bytes'] += size
            break
    stats['total'] = sum(stats[op.replace('-', '_')]
                         for op in _COLLECTIVES)
    stats['total_bytes'] = sum(stats[op.replace('-', '_') + '_bytes']
                               for op in _COLLECTIVES)
    return stats


def custom_call_operands(hlo_text: str,
                         target: str = 'tpu_custom_call') -> list:
    """Operand shapes of every custom call to `target` in optimized HLO
    text, one list of 'dtype[dims]' strings per call, in program order.
    `tpu_custom_call` is how a Pallas TPU kernel appears; its
    `operand_layout_constraints={...}` attribute lists exactly the
    operands it was handed. Under a mesh those must be the per-device
    shard: a kernel handed the global shape runs the whole batch on
    every chip."""
    calls = []
    key = 'operand_layout_constraints={'
    for line in hlo_text.splitlines():
        if f'custom_call_target="{target}"' not in line:
            continue
        start = line.find(key)
        if start < 0:
            continue
        # The attribute's value nests braces (`bf16[8,128]{1,0}`).
        start += len(key)
        depth, end = 1, start
        while end < len(line) and depth:
            depth += {'{': 1, '}': -1}.get(line[end], 0)
            end += 1
        calls.append([f'{dt}[{dims}]' for dt, dims in
                      _SHAPE_RE.findall(line[start:end - 1])])
    return calls


# Memory-layout op mnemonics for gather_stats. Matched with a
# lookahead '(' and a (?<![\w-]) guard so collective names never
# alias in ('all-gather(' must not count as 'gather(', 'reduce-
# scatter(' not as 'scatter('); 'dynamic-update-slice(' never
# contains 'dynamic-slice(' so the pair needs no ordering.
_GATHER_OPS = ('gather', 'scatter', 'dynamic-slice',
               'dynamic-update-slice')


def gather_stats(hlo_text: str) -> Dict[str, Any]:
    """Count the scatter/gather op cluster in optimized HLO text —
    the ops the XLA paged decode path spends on materializing each
    row's gathered KV window (and scattering the chunk writes), which
    the fused pallas kernel replaces with in-kernel block-table walks.

    Returns {'gather': n, 'scatter': n, 'dynamic_slice': n,
    'dynamic_update_slice': n, 'total': n}. Counts instruction heads
    only (after the '=' like collective_stats), so fused-computation
    BODIES still count their ops — on CPU the interpreter-mode pallas
    program and the XLA program both print flat entry computations and
    the diff is what tests/test_composition_matrix.py pins."""
    stats: Dict[str, Any] = {op.replace('-', '_'): 0
                             for op in _GATHER_OPS}
    patterns = [(op, re.compile(r'(?<![\w-])' + re.escape(op) + r'\('))
                for op in _GATHER_OPS]
    for line in hlo_text.splitlines():
        if '=' not in line:
            continue
        rhs = line.partition('=')[2]
        for op, pat in patterns:
            if pat.search(rhs):
                stats[op.replace('-', '_')] += 1
    stats['total'] = sum(stats[op.replace('-', '_')]
                         for op in _GATHER_OPS)
    return stats


# `%name = f32[512,64]{1,0} op(`: an array-valued definition (a tuple's
# result starts with '(' and does not match).
_DEF_RE = re.compile(r'\s*(?:ROOT )?(%[\w.-]+) = ([a-z]\w*)\[([0-9,]*)\]')
_OPERAND_RE = re.compile(r'%[\w.-]+')


def partition_scatter_count(hlo_text: str,
                            shards: Optional[int] = None) -> int:
    """Count partition-addressed scatter slices: ops whose result is an
    exact 1/k fraction (k = `shards` when given, else any k >= 2) of one
    of their operands AND whose offset comes from `partition-id` — each
    device keeps only ITS shard of a cross-replica-reduced tensor.

    This is the reduce-scatter as the CPU backend spells it. The SPMD
    partitioner lowers "reduced tensor consumed at a sharded layout" to
    all-reduce + dynamic-slice(partition-id); TPU/GPU pipelines then run
    the ReduceScatterCreator rewrite that fuses the pair into a native
    `reduce-scatter` op, but the CPU pipeline (the 8-fake-device proxy
    environment) does not, so the ZeRO-1 pins count BOTH forms:
    `collective_stats()['reduce_scatter']` for the fused op and this
    pattern for the unfused one (`trainer.compiled_step_collectives`,
    tests/zero1_driver.py).

    Text heuristic, deliberately narrow: a line counts when it has a
    `%partition-id` operand and its largest array operand carries
    exactly `k x` the result's elements — gather-style index plumbing
    (embedding scatter-adds also consult partition-id under a dp-sharded
    batch) never slices a tensor down by the shard count, so it does not
    match. The CPU pipeline duplicates the slice into each fusion that
    reads the shard, so the count is of slicing ops, not of gradient
    leaves; the plain step has none."""
    # An older XLA printed each operand's shape in the consuming line;
    # the installed one prints the name alone, so operands with no
    # inline shape are looked up where they were defined (above their
    # use: a computation prints in dependency order).
    defined = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            defined[m.group(1)] = _shape_elems(m.group(2), m.group(3))
        if '%partition-id' not in line or '=' not in line:
            continue
        _lhs, _, rhs = line.partition('=')
        # `%name = f32[8,512]{1,0} fusion(f32[512,64] %op, u32[] %pid)`:
        # the first shape after '=' is the RESULT, the rest operands.
        shapes = _SHAPE_RE.findall(rhs)
        if not shapes:
            continue
        result = _shape_elems(*shapes[0])
        if result <= 0:
            continue
        operands = [_shape_elems(dt, dims) for dt, dims in shapes[1:]]
        if not operands:
            args = rhs.partition('(')[2].partition(')')[0]
            operands = [defined.get(name, 0)
                        for name in _OPERAND_RE.findall(args)]
        biggest = max(operands, default=0)
        if biggest <= result or biggest % result:
            continue
        k = biggest // result
        if shards is None:
            if k >= 2:
                count += 1
        elif k == shards:
            count += 1
    return count
