"""The layer loop of a decoding model carries the cache and writes it in
place (models/cache_carry.py); the training loop is the one it was.

Pinned here, on tiny configurations of both families (the Llama-shaped
decoder and the Falcon-H1 block with its two recurrent-state leaves),
paged and contiguous, float and int8 K/V:

(a) the compiled decode step and prefill chunk make no new buffer of a
    whole cache leaf's shape (no `copy`, no `AllocateBuffer`), slice no
    layer's whole pool or state out of a leaf, and need scratch under a
    quarter of the cache's bytes. Compiled for the CPU always, and for a
    described v5e where one can be described. The CPU backend widens a
    bfloat16 scatter's operand to float32, whole, in any program, so
    there float32 stands in for bfloat16; the v5e case runs bfloat16;
(b) logits and every cache leaf after two chunks and three decode steps
    are bit-identical to the same model with its cache scanned as it was
    before, and to the unrolled `scan_layers=False` model on the same
    weights (Falcon-H1: as close to it as the parent commit is);
(c) `_decode_multi_feed_impl` with K = 4 gives the tokens and the cache
    of four single steps (two nested loops carry the cache);
(d) with `cfg.decode` false `flax.linen.scan` is called once with the
    arguments it had before there was a carried loop, the carried loop
    is never entered, and a process that imports the trainer does not
    import its module;
(e) the cache tree is what it was: paths, shapes, types, logical axes;
(f) the paged-decode kernel (ops/paged_attention.py), which the decode
    programs take by default on a TPU, compiles for a described v5e at
    the widths that default admits, float and int8 pools, 1 to 8 kv
    heads (the other v5e compiles of the chip's kernels live here too:
    one file, one worker that loads the TPU compiler).
"""
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from skypilot_tpu.models import get_config
from skypilot_tpu.models import inference
from skypilot_tpu.models import transformer as transformer_lib
from skypilot_tpu.models.inference import ContinuousBatchingEngine
from skypilot_tpu.models.transformer import Transformer

FALCON_MULTIPLIERS = dict(
    embed_multiplier=4.0, attn_in_multiplier=1.0, key_multiplier=0.5,
    attn_out_multiplier=0.5, ssm_in_multiplier=0.5,
    ssm_multipliers=(0.7, 0.5, 0.6, 0.8, 0.7), ssm_out_multiplier=0.6,
    mlp_multipliers=(0.5, 0.5), lm_head_multiplier=0.5)

SLOTS = 4
BLOCKS = 257     # a pool far larger than anything else a tiny step holds
# Heads of the chip's own tile (8 kv heads of 128 lanes, as the serving
# cells have): a narrower head the TPU compiler relays whole on its way
# in and out of any program, loop or none, and the test would read that.
HEADS = dict(num_heads=8, num_kv_heads=8, head_dim_override=128)
F32 = dict(dtype='float32', param_dtype='float32')

# 'falcon-h1-kernel': the decode step's recurrence through its kernel
# (ops/ssd_state_step), which writes the scan state where it lies; the
# interpreter's on the CPU, the Mosaic kernel on a described v5e
LAYOUTS = ('paged', 'paged-int8', 'contiguous', 'contiguous-int8',
           'falcon-h1', 'falcon-h1-kernel')


def _layers(layout: str) -> int:
    """Deep enough that one layer's windows (a contiguous layer's K and
    V are read whole: they ARE the window) are a small part of the
    cache."""
    return 24 if layout.startswith('contiguous') else 6


def _cfg(layout: str, **kw):
    if layout.startswith('falcon-h1'):
        return get_config(
            'falcon-h1-34b', num_layers=_layers(layout), d_model=64, d_mlp=128,
            vocab_size=512, max_seq_len=128, ssm_heads=4, ssm_head_dim=8,
            ssm_state=16, ssm_groups=2, attention_impl='xla',
            **HEADS, **FALCON_MULTIPLIERS, **kw)
    if layout == 'afmoe':
        # a leading dense layer, then expert layers: two carried groups
        return get_config(
            'trinity-large-preview', num_layers=6, num_dense_layers=2,
            d_model=64, d_mlp=128, vocab_size=512, max_seq_len=128,
            num_experts=8, experts_held=4, first_expert=2,
            experts_per_token=2, d_expert=32, d_shared_expert=32,
            layer_kinds=((32, True), (0, False)) * 3, **HEADS, **kw)
    return get_config('test-tiny', num_layers=_layers(layout), **HEADS, **kw)


def _engine_kw(layout: str) -> dict:
    kw = dict(num_slots=SLOTS)
    if not layout.startswith('contiguous'):
        kw.update(paged_block_size=16, paged_num_blocks=BLOCKS)
    if layout.endswith('int8'):
        kw['kv_quant'] = 'int8'
    if layout.endswith('kernel'):
        kw['decode_kernel'] = 'pallas_interpret'
    return kw


def _engine(layout: str, **cfg_kw) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(_cfg(layout, **cfg_kw),
                                    **_engine_kw(layout))


def _boxed_cache(engine):
    batch = 1 if engine.paged_block_size else engine.num_slots
    return inference._abstract_init(  # pylint: disable=protected-access
        engine.model, engine.cfg, batch)['cache']


def _path(keys) -> str:
    return '/'.join(str(getattr(k, 'key', k)) for k in keys)


def _leaves(tree) -> dict:
    return {_path(p): a for p, a in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------
# (a) what the compiler makes of it
# ---------------------------------------------------------------------

_HLO_DTYPE = {'float32': 'f32', 'bfloat16': 'bf16', 'int8': 's8'}
_INSTR = re.compile(
    r'^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w\-]+)\(')
_HEADER = re.compile(r'^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$')
_CALLEE = re.compile(r'\b(calls|body|condition|to_apply)=%?([\w.\-]+)')
# ops that hand on a buffer they were given, or write into it
_IN_PLACE = ('parameter', 'get-tuple-element', 'bitcast',
             'dynamic-update-slice', 'scatter')


def _computations(text: str) -> dict:
    """{computation: [instruction]} of optimized HLO text, an
    instruction as a dict of name, root, dtype, dims, op, line."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            continue
        if line.strip() == '}':
            cur = None
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append({
                'name': m.group(2), 'root': bool(m.group(1)),
                'dtype': m.group(3),
                'dims': tuple(int(d) for d in m.group(4).split(',') if d),
                'op': m.group(5), 'line': line})
    return comps


def _made_by(instr: dict, comps: dict) -> str:
    """The op that makes an instruction's result: its own, or for a
    fusion the root of what it calls, seen through bitcasts."""
    if instr['op'] != 'fusion':
        if instr['op'] == 'custom-call':
            return ('AllocateBuffer' if 'AllocateBuffer' in instr['line']
                    else 'custom-call')
        return instr['op']
    callee = dict(_CALLEE.findall(instr['line'])).get('calls')
    body = comps.get(callee, [])
    by_name = {i['name']: i for i in body}
    root = next((i for i in body if i['root']), None)
    while root is not None and root['op'] in ('bitcast', 'reshape'):
        operand = re.search(r'\(\s*%?([\w.\-]+)', root['line'][
            root['line'].index(root['op'] + '('):])
        root = by_name.get(operand.group(1)) if operand else None
    return 'fusion' if root is None else root['op']


def _new_buffers(text: str, shapes: set) -> list:
    """(computation is a loop's body, instruction name, making op) of
    every instruction outside fused computations whose result has one of
    `shapes` ((hlo dtype, dims)) and is a NEW buffer: not a parameter,
    a tuple's element, a bitcast, or an update in place."""
    comps = _computations(text)
    callees = _CALLEE.findall(text)
    fused = {c for key, c in callees if key == 'calls'} & {
        dict(_CALLEE.findall(i['line'])).get('calls')
        for body in comps.values() for i in body if i['op'] == 'fusion'}
    bodies = {c for key, c in callees if key == 'body'}
    out = []
    for name, body in comps.items():
        if name in fused:
            continue
        for i in body:
            if (i['dtype'], i['dims']) not in shapes:
                continue
            made = _made_by(i, comps)
            if made not in _IN_PLACE:
                out.append((name in bodies, i['name'], made))
    return out


@pytest.fixture(scope='module')
def v5e():
    """One described v5e chip's sharding, or a skip where the TPU
    compiler can describe none."""
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ.setdefault('TPU_ACCELERATOR_TYPE', 'v5litepod-4')
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
        return SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # pylint: disable=broad-except
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


def _compile_programs(engine, one):
    """{'decode', 'chunk'}: the engine's two steady-state programs,
    compiled ahead of time from shapes alone with the cache donated, as
    the engine jits them (for the described chip `one`, or the CPU). A
    contiguous engine has no chunk program."""
    sds = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)
    params = sds(engine.params)
    cache = sds(nn.unbox(_boxed_cache(engine)))
    n = engine.num_slots
    # the programs that are told which rows are real: a recurrent
    # state's, a router's
    recurrent = engine._row_valid  # pylint: disable=protected-access
    progs = {}
    tables = None
    if engine.paged_block_size:
        width = engine.cfg.max_seq_len // engine.paged_block_size + 1
        tables = arr(jnp.int32, n, width)
        progs['chunk'] = (
            engine._prefill_chunk_impl,  # pylint: disable=protected-access
            (params, cache, arr(jnp.int32, 1, engine.prefill_chunk),
             arr(jnp.int32, 1, width), arr(jnp.int32), arr(jnp.int32)),
            dict(slot=arr(jnp.int32) if recurrent else None))
    progs['decode'] = (
        engine._decode_step_impl,  # pylint: disable=protected-access
        (params, cache, arr(jnp.int32, n), arr(jnp.int32, n),
         arr(jnp.float32, n), arr(jnp.uint32, 2), tables),
        dict(valid=arr(jnp.int32, n) if recurrent else None))
    kw = {} if one is None else {'lowering_platforms': ('tpu',)}
    return {name: jax.jit(fn, donate_argnames=('cache',)).trace(
        *args, **kwargs).lower(**kw).compile()
            for name, (fn, args, kwargs) in progs.items()}, cache


def _check_in_place(layout: str, one) -> None:
    # on the CPU float32 stands in for bfloat16 (the module's docstring)
    engine = _engine(layout, **(F32 if one is None else {}))
    if one is not None and engine.decode_kernel == 'pallas_interpret':
        # compiled for the chip: its kernels, not the interpreter's (the
        # process sees the CPU, where the engine refuses 'pallas')
        engine.cfg = dataclasses.replace(engine.cfg, decode_kernel='pallas')
        engine.model = Transformer(engine.cfg)
    try:
        compiled, cache = _compile_programs(engine, one)
    finally:
        engine.stop()
    leaves = _leaves(cache)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves.values())
    hlo = lambda a, dims: (_HLO_DTYPE[a.dtype.name], tuple(dims))
    whole = {hlo(a, a.shape) for a in leaves.values()}
    # K and V themselves, and on the chip the recurrent state: no new
    # buffer of their shape anywhere. The other leaves are held to the
    # loop, on the chip: nothing of their shape is made inside one (an
    # int8 pool's scale rows the TPU compiler lays out another way
    # inside a program than at its edge, so each is relaid on the way
    # in and on the way out, loop or none). The CPU backend copies small
    # loop-carried leaves and relays scale rows a trip, in the scanned
    # form as in this one: there K and V are what it tells apart.
    strict = {hlo(a, a.shape) for k, a in leaves.items()
              if k.endswith(('cached_key', 'cached_value', 'cached_latent'))
              or (one is not None and '/mixer/' in k)}
    # one layer's part of a leaf that no program needs whole: a paged
    # pool, of which a step reads the rows its tables name, and in the
    # chunk program a recurrent state, of which it reads one slot. (A
    # contiguous layer's K and V ARE its window, and a decode step's
    # recurrence reads every slot's state: those reads are the work.)
    assert set(compiled) == ({'decode'} if layout.startswith('contiguous')
                             else {'decode', 'chunk'})
    for name, prog in compiled.items():
        text = prog.as_text()
        assert _new_buffers(text, strict) == [], name
        if one is not None and layout.endswith('kernel'):
            # the decode step's recurrence is the kernel, the chunk's not
            state_kernel = re.search(
                r'%(ssd|kda)_state_step(\.\d+)? = .*custom-call\(', text)
            assert bool(state_kernel) == (name == 'decode'), name
        if one is not None:
            made = _new_buffers(text, whole)
            assert [m for m in made if m[0]] == [], (name, made)
        part = set()
        for key, a in leaves.items():
            pool = '/attn/' in key and not layout.startswith('contiguous')
            state = '/mixer/' in key and name == 'chunk'
            if pool or state:
                part |= {hlo(a, (1,) + a.shape[1:]), hlo(a, a.shape[1:])}
        sliced = [m for m in _new_buffers(text, part)
                  if m[2] in ('dynamic-slice', 'copy', 'AllocateBuffer')]
        assert sliced == [], (name, sliced)
        temp = prog.memory_analysis().temp_size_in_bytes
        assert temp < cache_bytes / 4, (name, temp, cache_bytes)


@pytest.mark.parametrize('layout', LAYOUTS)
def test_compiled_for_the_cpu_no_program_copies_or_slices_a_leaf(layout):
    _check_in_place(layout, None)


@pytest.mark.parametrize('layout', LAYOUTS)
def test_compiled_for_a_v5e_no_program_copies_or_slices_a_leaf(layout,
                                                               v5e):
    _check_in_place(layout, v5e)


def test_compiled_for_the_cpu_a_layer_pattern_carries_a_leaf_a_group():
    """Two groups of layers ('dense_layers', 'layers'), each with a
    loop of its own that carries its K and V: no copy, no slice."""
    _check_in_place('afmoe', None)


def test_compiled_for_a_v5e_a_layer_pattern_carries_a_leaf_a_group(v5e):
    _check_in_place('afmoe', v5e)


def test_compiled_for_a_v5e_the_hybrid_cell_at_published_widths(v5e):
    """Ling-3.0-flash's share as its cell serves it (7 layers, 256 slots,
    context 2560, weights as shapes alone): the decode step runs the
    delta rule of both KDA groups through ops/kda_state_step and the
    latent attention through ops/paged_attention; neither program makes
    a buffer of the KDA state's or the latent pool's shape, and inside
    no loop one of any cache leaf's; weights, cache and scratch fit the
    chip. (At tiny sizes the TPU compiler moves small leaves into
    VMEM and back, which this test would read: so the cell's own
    sizes. A group of one layer loops once, and its convolution inputs
    are then written whole as the step's new value.)"""
    cfg = get_config(
        'ling-3.0-flash', num_layers=7, num_dense_layers=1,
        experts_held=64, vocab_size=19648, max_seq_len=2560,
        mixer_kinds=('kda',) * 6 + ('mla',),
        expert_swiglu_limits=(0.0,) * 7, shared_swiglu_limits=(0.0,) * 7,
        param_dtype='bfloat16')
    abstract = nn.unbox(jax.eval_shape(lambda: Transformer(
        dataclasses.replace(cfg, decode=False)).init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))['params'])
    engine = ContinuousBatchingEngine(cfg, params=abstract, num_slots=256,
                                      paged_block_size=16,
                                      decode_kernel='xla')
    engine.cfg = dataclasses.replace(engine.cfg, decode_kernel='pallas')
    engine.model = Transformer(engine.cfg)
    try:
        compiled, cache = _compile_programs(engine, v5e)
    finally:
        engine.stop()
    leaves = _leaves(cache)
    hlo = lambda a, dims: (_HLO_DTYPE[a.dtype.name], tuple(dims))
    strict = {hlo(a, a.shape) for k, a in leaves.items()
              if k.endswith(('ssm_state', 'cached_latent'))}
    whole = {hlo(a, a.shape) for a in leaves.values()}
    assert len(strict) == 3
    for name, prog in compiled.items():
        text = prog.as_text()
        assert _new_buffers(text, strict) == [], name
        assert [m for m in _new_buffers(text, whole) if m[0]] == [], name
        kda = len(re.findall(r'%kda_state_step(\.\d+)? = ', text))
        latent = len(re.findall(r'%attn\._paged(\.\d+)? = ', text))
        assert (kda, latent) == ((2, 1) if name == 'decode' else (0, 0))
        mem = prog.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11e9


def test_compiled_for_a_v5e_the_kda_state_kernel_at_published_widths(v5e):
    """Ling-3.0-flash's delta rule over 256 slots, one Mosaic call on
    the stacked state of five layers (a traced layer): 2.7 GB written
    where it lies, never copied; the scratch is the per-head inputs."""
    from skypilot_tpu.ops import kda_state_step
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=v5e)
    layers, slots, heads, d = 5, 256, 32, 128
    leaf = (layers, slots, heads, d, d)
    vec = arr(jnp.float32, slots, heads, d)
    compiled = jax.jit(kda_state_step.kda_state_step,
                       donate_argnums=(0,)).trace(
        arr(jnp.float32, *leaf), arr(jnp.int32), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), vec, vec, vec, vec, vec).lower(
            lowering_platforms=('tpu',)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _new_buffers(text, {('f32', leaf), ('f32', leaf[1:])}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_compiled_for_a_v5e_the_latent_decode_at_published_widths(v5e):
    """Ling-3.0-flash's MLA decode step over 256 slots at context
    2560: the pool of 576 values a position laid out as rows of 640
    (five lane tiles), one kv head both key and value, 32 absorbed query
    heads; one Mosaic call, and the pool is neither copied nor relaid
    (its 4-d view for the kernel folds away)."""
    from skypilot_tpu.models.mla import cache_width
    from skypilot_tpu.ops import paged_attention
    cfg = get_config('ling-3.0-flash')
    width = cache_width(cfg)
    assert width == 640
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=v5e)
    slots, bs, context = 256, 16, 2560
    blocks = slots * context // bs + 1
    pool = (1, blocks, bs, width)

    def attend(q, pool, tables, positions, first):
        view = pool.reshape(-1, bs, 1, width)
        return paged_attention.paged_decode_attention(
            q, view, view, tables, positions, first_block=first,
            sm_scale=192 ** -0.5)[..., :512]
    compiled = jax.jit(attend).trace(
        arr(jnp.bfloat16, slots, 1, 32, width), arr(jnp.bfloat16, *pool),
        arr(jnp.int32, slots, context // bs), arr(jnp.int32, slots, 1),
        arr(jnp.int32)).lower(lowering_platforms=('tpu',)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _new_buffers(text, {('bf16', pool), ('bf16', pool[1:]),
                               ('bf16', (blocks, bs, 1, width))}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_compiled_for_a_v5e_the_grouped_products_at_published_widths(
        v5e, monkeypatch):
    """The dropless layer of Trinity-Large-Preview's share on one chip,
    a decode step's 128 tokens, its experts one layer of a stack of
    four: each grouped product is the repo's own Pallas kernel
    (`moe_gmm`, `ops/grouped_matmul.py`), three of them, handed the
    stack whole with the layer as a scalar: no `ragged-dot` is left,
    and nothing of one layer's experts' shape is sliced out of the
    stack (1.8 GB a layer, which a scanned stack cost: PERF.md section
    6, PR 33)."""
    from skypilot_tpu.models.moe import MoEBlock
    from skypilot_tpu.ops import grouped_matmul
    # the process sees the CPU; the program is compiled for the chip
    monkeypatch.setattr(grouped_matmul, '_on_tpu', lambda: True)
    cfg = get_config('trinity-large-preview', experts_held=32,
                     param_dtype='bfloat16')
    block = MoEBlock(cfg)
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=v5e)
    x = arr(jnp.bfloat16, 128, 1, 3072)
    own = nn.unbox(jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1, 3072), jnp.bfloat16))
    )['params'])
    assert own['w_gate'].shape == (1, 32, 3072, 3072)
    stacks = tuple(arr(jnp.bfloat16, 4, *own[n].shape[1:])
                   for n in ('w_gate', 'w_up', 'w_down'))
    params = {n: jax.tree.map(lambda a: arr(a.dtype, *a.shape), own[n])
              for n in ('router', 'expert_bias', 'shared')}
    compiled = jax.jit(lambda p, s, x, layer: block.apply(
        {'params': p}, x, None, (s, layer))).trace(
            params, stacks, x, arr(jnp.int32)).lower(
                lowering_platforms=('tpu',)).compile()
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if 'custom-call(' in l and 'tpu_custom_call' in l]
    assert len(calls) == 3, text[:2000]
    for l in calls:
        assert re.match(r'\s*(ROOT )?%moe_gmm(\.\d+)? = (f32|bf16)'
                        r'\[512,3072\]', l), l
        assert '[4,32,3072,3072]' in l, l
    assert 'ragged-dot' not in text and 'ragged_dot' not in text
    assert _new_buffers(text, {('bf16', (32, 3072, 3072)),
                               ('bf16', (1, 32, 3072, 3072))}) == []
    # scratch: the sorted rows and the products' outputs, a few MB
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize('pool,kv_heads,n_rep,rows', [
    ('bfloat16', 8, 4, 1),     # decode-heavy's heads, a decode step
    ('bfloat16', 8, 8, 4),     # the most query rows the default takes
    ('bfloat16', 1, 8, 4),
    ('int8', 8, 6, 1),
    ('int8', 1, 8, 4),         # 16-row int8 blocks, half a packed tile
    ('int8', 4, 8, 4),
])
def test_compiled_for_a_v5e_the_paged_decode_kernel(v5e, pool, kv_heads,
                                                      n_rep, rows):
    """One Mosaic call over every layer's pool (a traced first block and
    window), at the query widths `takes_rows` admits."""
    from skypilot_tpu.ops import paged_attention
    assert paged_attention.takes_rows(rows)
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=v5e)
    slots, bs, context = 16, 16, 1024
    blocks = 3 * slots * context // bs + 1
    int8 = pool == 'int8'
    kv = arr(jnp.dtype(pool), blocks, bs, kv_heads, 128)
    scale = arr(jnp.float32, blocks, bs, kv_heads, 1) if int8 else None

    def attend(q, k, v, ks, vs, tables, positions, first, window):
        return paged_attention.paged_decode_attention(
            q, k, v, tables, positions, window=window, first_block=first,
            k_scale=ks, v_scale=vs)
    compiled = jax.jit(attend).trace(
        arr(jnp.bfloat16, slots, rows, kv_heads * n_rep, 128), kv, kv,
        scale, scale, arr(jnp.int32, slots, context // bs),
        arr(jnp.int32, slots, rows), arr(jnp.int32),
        arr(jnp.int32)).lower(lowering_platforms=('tpu',)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # no gathered window: scratch is the queries' and the scale rows'
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_compiled_for_a_v5e_the_state_kernel_at_published_widths(v5e):
    """Falcon-H1-34B's recurrence over 64 slots, one Mosaic call on the
    stacked scan state of six layers (a traced layer): the leaf is
    written where it lies, never copied, and the scratch is the small
    per-head inputs and `y`."""
    from skypilot_tpu.ops import ssd_state_step
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=v5e)
    layers, slots, heads, p_dim, n, groups = 6, 64, 32, 128, 256, 2
    leaf = (layers, slots, heads, p_dim, n)
    compiled = jax.jit(ssd_state_step.ssd_state_step,
                       donate_argnums=(0,)).trace(
        arr(jnp.float32, *leaf), arr(jnp.int32), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), arr(jnp.float32, slots, heads),
        arr(jnp.float32, slots, heads, p_dim),
        arr(jnp.float32, slots, groups, n),
        arr(jnp.float32, slots, groups, n)).lower(
            lowering_platforms=('tpu',)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _new_buffers(text, {('f32', leaf), ('f32', leaf[1:])}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# What the checker reads in the scanned form, as the TPU compiler
# printed it for the parent commit (a toy of Mistral-l16's structure):
# each layer's slice out, a write back into a new stacked buffer, and
# that buffer copied whole onto the donated one after the loop.
SCANNED = '''
HloModule jit_step

%fused_computation.1 (param_0.1: bf16[16,1793,16,8,128], param_1.1: s32[]) -> bf16[1,1793,16,8,128] {
  %param_0.1 = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = s32[]{:T(128)} parameter(1)
  %constant.1 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.1 = bf16[1,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.1, %param_1.1, %constant.1, %constant.1, %constant.1, %constant.1), dynamic_slice_sizes={1,1793,16,8,128}
}

%fused_computation.2 (param_0.2: bf16[16,1793,16,8,128], param_1.2: bf16[1,1793,16,8,128], param_2.2: s32[]) -> bf16[16,1793,16,8,128] {
  %param_0.2 = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = bf16[1,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.2 = s32[]{:T(128)} parameter(2)
  %constant.2 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_update_slice.2 = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.2, %param_1.2, %param_2.2, %constant.2, %constant.2, /*index=5*/%constant.2, %constant.2)
}

%body (arg: (s32[], bf16[16,1793,16,8,128], bf16[16,1793,16,8,128])) -> (s32[], bf16[16,1793,16,8,128], bf16[16,1793,16,8,128]) {
  %arg = (s32[]{:T(128)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %old = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %new = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %constant_dynamic-slice_fusion = bf16[1,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%old, %i), kind=kLoop, calls=%fused_computation.1
  %scatter.1 = bf16[1,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} scatter(%constant_dynamic-slice_fusion, %i, %i), to_apply=%assign
  %constant_dynamic-update-slice_fusion = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%new, %scatter.1, %i), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple = (s32[]{:T(128)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}) tuple(%i, %old, %constant_dynamic-update-slice_fusion)
}

ENTRY %main (cache: bf16[16,1793,16,8,128]) -> bf16[16,1793,16,8,128] {
  %cache = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %zero = s32[]{:T(128)} constant(0)
  %custom-call = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(), custom_call_target="AllocateBuffer"
  %tuple.1 = (s32[]{:T(128)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}) tuple(%zero, %cache, %custom-call)
  %while = (s32[]{:T(128)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond, body=%body
  %get-tuple-element.9 = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%while), index=2
  ROOT %copy.1 = bf16[16,1793,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.9)
}
'''


def test_the_checker_reads_a_scanned_caches_copies():
    leaf, layer = (16, 1793, 16, 8, 128), (1, 1793, 16, 8, 128)
    assert sorted(_new_buffers(SCANNED, {('bf16', leaf)})) == [
        (False, 'copy.1', 'copy'), (False, 'custom-call', 'AllocateBuffer')]
    assert _new_buffers(SCANNED, {('bf16', layer)}) == [
        (True, 'constant_dynamic-slice_fusion', 'dynamic-slice')]
    # the write back is an update in place of ITS operand, the new buffer
    assert _new_buffers(SCANNED, {('s8', leaf), ('bf16', leaf[1:])}) == []


# ---------------------------------------------------------------------
# (b) bit for bit the unrolled model
# ---------------------------------------------------------------------


def _unrolled(tree, num_layers: int):
    """A scanned tree (weights, or a cache) under the unrolled model's
    names: layer i of every stacked leaf."""
    out = {k: v for k, v in tree.items() if k != 'layers'}
    for i in range(num_layers):
        out[f'layer_{i}'] = jax.tree.map(lambda a, i=i: a[i],
                                         tree['layers']['layer'])
    return out


def _two_chunks_three_steps(model, params, cache, paged: bool,
                            recurrent: bool):
    """[logits], cache: two 16-token chunks (one slot's on a paged pool,
    every row's on a contiguous cache) and three decode steps over all
    slots at their own depths, one of them naming its state rows."""
    apply = jax.jit(
        lambda cache, tokens, positions, tables, rows: model.apply(
            {'params': params, 'cache': cache}, tokens, positions,
            block_tables=tables, state_rows=rows, mutable=['cache']))
    rng = np.random.default_rng(3)
    width = 128 // 16 + 1
    tables = None
    if paged:
        tables = np.zeros((SLOTS, width), np.int32)
        for r in range(SLOTS):
            tables[r, :4] = 1 + 4 * r + np.arange(4)
        tables = jnp.asarray(tables)
    logits = []
    rows_b = 1 if paged else SLOTS
    for c in range(2):
        tokens = jnp.asarray(rng.integers(1, 500, (rows_b, 16)), jnp.int32)
        positions = jnp.broadcast_to(16 * c + jnp.arange(16)[None],
                                     (rows_b, 16)).astype(jnp.int32)
        rows = None
        if recurrent:
            # slot 2's chunk, its last position but three a pad
            rows = (jnp.asarray([2], jnp.int32) if paged else None,
                    jnp.full((rows_b,), 16 if c == 0 else 13, jnp.int32))
        out, mut = apply(cache, tokens, positions,
                         None if tables is None else tables[2:3], rows)
        cache = nn.unbox(mut['cache'])
        logits.append(out)
    depth = jnp.asarray([[3], [40], [29], [17]], jnp.int32)
    for step in range(3):
        tokens = jnp.asarray(rng.integers(1, 500, (SLOTS, 1)), jnp.int32)
        rows = None
        if recurrent:
            valid = jnp.asarray([0, 1, 1, 1], jnp.int32)
            slots = (jnp.arange(SLOTS, dtype=jnp.int32)
                     if step == 1 else None)
            rows = (slots, valid)
        out, mut = apply(cache, tokens, depth + step, tables, rows)
        cache = nn.unbox(mut['cache'])
        logits.append(out)
    return logits, cache


def _same_bits(got, want, got_cache, want_cache) -> None:
    for step, (a, b) in enumerate(zip(got, want)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), step
    got_leaves, want_leaves = _leaves(got_cache), _leaves(want_cache)
    assert got_leaves.keys() == want_leaves.keys()
    for key, a in got_leaves.items():
        b = want_leaves[key]
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), key
    # and something was written: no leaf is still all zeros
    assert all(np.asarray(a, np.float32).any() for a in got_leaves.values())


def _model_and_cache(layout: str, **cfg_kw):
    engine = _engine(layout, **cfg_kw)
    try:
        # pylint: disable=protected-access
        return engine.cfg, engine.params, engine._init_cache_for_mode()
    finally:
        engine.stop()


@pytest.mark.parametrize('layout', LAYOUTS)
def test_logits_and_cache_are_the_scanned_caches_bit_for_bit(layout,
                                                             monkeypatch):
    """Against the loop as it was: with `has_variable` blind to the
    cache, `Transformer.__call__` scans it as the parent commit did."""
    cfg, params, cache = _model_and_cache(layout)
    paged, recurrent = bool(cfg.paged_block_size), cfg.has_recurrent_state
    got, got_cache = _two_chunks_three_steps(
        Transformer(cfg), params, cache, paged, recurrent)
    from skypilot_tpu.models import cache_carry
    monkeypatch.setattr(Transformer, 'has_variable',
                        lambda self, col, name: False)
    monkeypatch.setattr(cache_carry, 'carry_layers', None)
    want, want_cache = _two_chunks_three_steps(
        Transformer(cfg), params, jax.tree.map(jnp.zeros_like, cache),
        paged, recurrent)
    _same_bits(got, want, got_cache, want_cache)


@pytest.mark.parametrize('layout', LAYOUTS)
def test_logits_and_cache_are_the_unrolled_models(layout):
    """Bit for bit for the Llama-shaped decoder, in float32 (int8 K/V
    stays int8): in bfloat16 the CPU compiler rounds a scanned and an
    unrolled layer at different places. Falcon-H1's mixer it rounds
    differently in float32 too, at the parent commit as here (1e-7):
    there to 2e-6 and the same tokens."""
    cfg, params, cache = _model_and_cache(layout, **F32)
    paged, recurrent = bool(cfg.paged_block_size), cfg.has_recurrent_state
    layers = cfg.num_layers
    got, got_cache = _two_chunks_three_steps(
        Transformer(cfg), params, cache, paged, recurrent)
    flat_cfg = dataclasses.replace(cfg, scan_layers=False)
    want, want_cache = _two_chunks_three_steps(
        Transformer(flat_cfg), _unrolled(params, layers),
        _unrolled(jax.tree.map(jnp.zeros_like, cache), layers), paged,
        recurrent)
    got_cache = _unrolled(got_cache, layers)
    if not layout.startswith('falcon-h1'):
        _same_bits(got, want, got_cache, want_cache)
        return
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
        assert np.array_equal(a.argmax(-1), b.argmax(-1))
    for a, b in zip(jax.tree.leaves(got_cache),
                    jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------
# (c) a loop over steps round the loop over layers
# ---------------------------------------------------------------------


@pytest.mark.parametrize('layout', LAYOUTS)
def test_four_steps_in_one_dispatch_are_four_single_steps(layout):
    engine = _engine(layout)
    try:
        n, recurrent = engine.num_slots, engine.cfg.has_recurrent_state
        tables = None
        if engine.paged_block_size:
            width = engine.cfg.max_seq_len // engine.paged_block_size + 1
            rows = np.zeros((n, width), np.int32)
            for r in range(n):
                rows[r, :4] = 1 + 4 * r + np.arange(4)
            tables = jnp.asarray(rows)
        tokens = jnp.asarray([5, 17, 101, 250], jnp.int32)
        positions = jnp.asarray([0, 9, 30, 47], jnp.int32)
        temps = jnp.asarray([0.0, 0.0, 0.7, 0.0], jnp.float32)
        valid = jnp.asarray([1, 0, 1, 1], jnp.int32) if recurrent else None
        rngs = jax.random.split(jax.random.PRNGKey(7), 4)
        kw = dict(valid=valid)
        # pylint: disable=protected-access
        multi = jax.jit(engine._decode_multi_feed_impl,
                        donate_argnames=('cache',))
        single = jax.jit(engine._decode_step_impl,
                         donate_argnames=('cache',))
        toks, feed, cache4 = multi(
            engine.params, engine._init_cache_for_mode(), tokens,
            positions, temps, rngs, tables, **kw)
        cache1 = engine._init_cache_for_mode()
        cols, f = [], (tokens, positions)
        for k in range(4):
            col, f, cache1 = single(engine.params, cache1, f[0], f[1],
                                    temps, rngs[k], tables, **kw)
            cols.append(col)
    finally:
        engine.stop()
    assert np.array_equal(np.asarray(toks),
                          np.concatenate([np.asarray(c) for c in cols], 1))
    assert np.array_equal(np.asarray(feed[0]), np.asarray(f[0]))
    assert np.array_equal(np.asarray(feed[1]), np.asarray(f[1]))
    for (key, a), b in zip(_leaves(cache4).items(),
                           _leaves(cache1).values()):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), key


# ---------------------------------------------------------------------
# (d) the trainer's loop is the one it was
# ---------------------------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize('remat', [False, True])
@pytest.mark.parametrize('what', ['forward', 'gradient'])
def test_training_scans_as_it_did_and_never_enters_the_carried_loop(
        remat, what, monkeypatch):
    from skypilot_tpu.models import cache_carry
    cfg = get_config('test-tiny', remat=remat)
    assert not cfg.decode and cfg.scan_layers and cfg.num_layers == 2
    calls = []
    real_scan = nn.scan

    def spy(target, *args, **kwargs):
        calls.append((target, args, kwargs))
        return real_scan(target, *args, **kwargs)

    def never(*_, **__):
        raise AssertionError('the carried loop was entered')

    monkeypatch.setattr(nn, 'scan', spy)
    monkeypatch.setattr(cache_carry, 'carry_layers', never)
    monkeypatch.setattr(cache_carry.CarriedLayer, '__call__', never)
    monkeypatch.setattr(cache_carry, 'current_layer', never)
    model = Transformer(cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    assert set(variables) == {'params'}
    params = nn.unbox(variables['params'])
    calls.clear()

    def loss(p, t):
        return jnp.mean(model.apply({'params': p}, t).astype(jnp.float32))

    fn = loss if what == 'forward' else jax.grad(loss)
    jaxpr = jax.make_jaxpr(fn)(params, tokens)
    # one call, with the arguments Transformer.__call__ gave it before
    # there was a carried loop (9d59384, models/transformer.py:1026)
    assert len(calls) == 1
    target, args, kwargs = calls[0]
    assert args == ()
    assert kwargs == dict(
        variable_axes={'params': 0, 'cache': 0},
        split_rngs={'params': True}, length=cfg.num_layers,
        metadata_params={nn.PARTITION_NAME: 'layers'})
    # pylint: disable=protected-access
    if remat:
        assert issubclass(target, transformer_lib._ScannedLayer)
        assert target is not transformer_lib._ScannedLayer
    else:
        assert target is transformer_lib._ScannedLayer
    # the forward loop carries the activations alone and scans over the
    # stacked weights and nothing else: no cache, no layer index
    scans = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == 'scan']
    assert len(scans) == (1 if what == 'forward' else 2)
    fwd = scans[0].params
    # (under remat the positions ride along as a second carry)
    assert fwd['num_carry'] == (2 if remat else 1)
    scanned = len(scans[0].invars) - fwd['num_consts'] - fwd['num_carry']
    assert scanned == len(jax.tree.leaves(params['layers']['layer']))
    text = jax.jit(fn).lower(params, tokens).as_text()
    assert 'cache' not in text


def test_a_process_that_trains_never_loads_the_carried_loop():
    code = (
        'import sys, jax, jax.numpy as jnp\n'
        'from flax import linen as nn\n'
        'import skypilot_tpu.train.trainer\n'
        'import skypilot_tpu.parallel.pipeline\n'
        'from skypilot_tpu.models import get_config\n'
        'from skypilot_tpu.models.transformer import Transformer\n'
        'cfg = get_config("test-tiny")\n'
        'model = Transformer(cfg)\n'
        'tok = jnp.ones((1, 8), jnp.int32)\n'
        'p = model.init(jax.random.PRNGKey(0), tok)\n'
        'jax.grad(lambda p: model.apply(p, tok).sum())(p)\n'
        'assert "skypilot_tpu.models.cache_carry" not in sys.modules\n'
        'import dataclasses\n'
        'dec = Transformer(dataclasses.replace(cfg, decode=True))\n'
        'v = dec.init(jax.random.PRNGKey(0), tok)\n'
        'dec.apply(v, tok[:, :1], jnp.full((1, 1), 8), mutable=["cache"])\n'
        'assert "skypilot_tpu.models.cache_carry" in sys.modules\n'
        'print("ok")\n')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=False)
    assert out.returncode == 0 and out.stdout.strip().endswith('ok'), (
        out.stdout[-2000:], out.stderr[-2000:])


# ---------------------------------------------------------------------
# (e) the cache tree is what it was
# ---------------------------------------------------------------------

POOL = (6, BLOCKS, 16, 8, 128)
POOL_AXES = ('layers', None, None, 'kv_heads', None)
ROWS = (24, SLOTS, 128, 8, 128)
ROWS_AXES = ('layers', 'batch', None, 'kv_heads', None)
ATTN = 'layers/layer/attn/'
MIXER = 'layers/layer/mixer/'
# path -> (shape, type, logical axes), as the parent commit's init gives
# them (9d59384: read there at these sizes, spelled out here)
TREES = {
    'paged': {
        ATTN + 'cached_key': (POOL, 'bfloat16', POOL_AXES),
        ATTN + 'cached_value': (POOL, 'bfloat16', POOL_AXES),
    },
    'paged-int8': {
        ATTN + 'cached_key': (POOL, 'int8', POOL_AXES),
        ATTN + 'cached_key_scale': (POOL[:4] + (1,), 'float32', POOL_AXES),
        ATTN + 'cached_value': (POOL, 'int8', POOL_AXES),
        ATTN + 'cached_value_scale': (POOL[:4] + (1,), 'float32',
                                      POOL_AXES),
    },
    'contiguous': {
        ATTN + 'cached_key': (ROWS, 'bfloat16', ROWS_AXES),
        ATTN + 'cached_value': (ROWS, 'bfloat16', ROWS_AXES),
    },
    'contiguous-int8': {
        ATTN + 'cached_key': (ROWS, 'int8', ROWS_AXES),
        ATTN + 'cached_key_scale': (ROWS[:4], 'float32', ROWS_AXES[:4]),
        ATTN + 'cached_value': (ROWS, 'int8', ROWS_AXES),
        ATTN + 'cached_value_scale': (ROWS[:4], 'float32', ROWS_AXES[:4]),
    },
    'falcon-h1': {
        ATTN + 'cached_key': (POOL, 'bfloat16', POOL_AXES),
        ATTN + 'cached_value': (POOL, 'bfloat16', POOL_AXES),
        MIXER + 'conv_state': ((6, SLOTS, 3, 96), 'bfloat16',
                               ('layers', 'batch', None, None)),
        MIXER + 'ssm_state': ((6, SLOTS, 4, 8, 16), 'float32',
                              ('layers', 'batch', None, None, None)),
    },
}

TREES['falcon-h1-kernel'] = TREES['falcon-h1']


@pytest.mark.parametrize('layout', LAYOUTS)
def test_the_cache_tree_is_the_parents(layout):
    engine = _engine(layout)
    try:
        flat = jax.tree_util.tree_flatten_with_path(
            _boxed_cache(engine),
            is_leaf=lambda x: isinstance(x, nn.Partitioned))[0]
        got = {_path(p): (tuple(box.value.shape), box.value.dtype.name,
                          tuple(box.names)) for p, box in flat}
        assert got == TREES[layout]
        # the live tree an apply hands back is the same one
        engine.generate(list(range(1, 20)), max_new_tokens=3)
        live = _leaves(engine._cache)  # pylint: disable=protected-access
        assert {k: (tuple(a.shape), a.dtype.name)
                for k, a in live.items()} == {
                    k: v[:2] for k, v in TREES[layout].items()}
    finally:
        engine.stop()
