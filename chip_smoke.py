#!/usr/bin/env python3
"""chip_smoke.py: does the system still start on the chip?

Runs the two compute entry points the way a user starts them, on one
model at its full width (llama3-1b: the only registry entry with
128-wide GQA heads whose whole depth fits one 16 GB v5e through the
entry points as they stand), with random weights made from a seed, and
checks what comes out. It measures nothing; it is the quickest proof
that the program compiles, runs and answers correctly on the TPU.

    python chip_smoke.py              # on a machine with a TPU
    python chip_smoke.py --rehearse   # CPU, test-tiny, interpreter
                                      # kernels: debugs THIS script

Phases, one child process each, one after another (a chip belongs to
one process at a time, so the parent never imports jax):

  kernels      every Pallas kernel the tree keeps, compiled
               (interpret=False), against its XLA twin at the attention
               shapes of llama3-1b (16 heads / 8 kv / 128) and
               mistral-7b (32 / 8 / 128, window 4096)
  train        python -m skypilot_tpu.train.run --model llama3-1b
               --batch 8 --seq 1024 --steps 6 --log-every 1
  serve        python -m skypilot_tpu.serve.server --model llama3-1b,
               default flags; eight requests; /metrics; SIGTERM drain
  serve-paged  the same with --paged-block-size 16 --prefix-cache 4
               --decode-kernel pallas --kv-quant int8
  serve-tp4    (four or more devices) --model mistral-7b --tp 4
               --max-seq-len 2048: the whole published model
  train-4      (four or more devices) train.run --model mistral-7b
               --lora-rank 16 --batch 4 --seq 2048 --steps 4 --probe-hlo

Every child names its device; one that does not see platform 'tpu'
fails the run. No phase is skipped and reported as passed: with fewer
than four devices the last two print `not run: N device(s)`.

On success the LAST line of stdout is one JSON object,
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and the exit code is 0. Any failure, a machine without a TPU, or a
directory that holds this file and nothing else of the repository:
another exit code and no such line. `--rehearse` and `--only` runs never
print it. Logs of every child land in chiprun_out/chip_smoke/.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, 'chiprun_out', 'chip_smoke')
PHASES = ('kernels', 'train', 'serve', 'serve-paged', 'serve-tp4',
          'train-4')
FOUR_CHIP = ('serve-tp4', 'train-4')
# The whole run must end inside the driver's 1200 s, compilation
# included; phases share what is left of this.
BUDGET_S = 1150.0

_DEVICE_RE = re.compile(
    r"jax devices: platform=(\S+) device_kind='([^']*)' count=(\d+)")
_STEP_RE = re.compile(
    r'step (\d+)/(\d+) loss=(\S+) grad_norm=(\S+) step_time=(\S+)s')
_MEM_RE = re.compile(r'device_memory [^:]*: device=(\d+) .*?bytes_in_use=(\d+)')


class PhaseFailed(Exception):
    pass


# ---------------------------------------------------------------------
# kernels: the one child that is this file (the others are the entry
# points themselves). Everything jax lives below this line's functions.
# ---------------------------------------------------------------------

# Tolerances, as max|kernel - twin| / max|twin| (error relative to the
# largest element, so near-zero entries cannot fail a kernel that is
# right):
#
# bf16 carries 8 significant bits (eps = 2**-8 = 3.9e-3). A kernel and
# its twin take the same bf16 inputs and both accumulate in float32, but
# round probabilities and partial sums to bf16 at different points (the
# kernels rescale block by block), so a few eps of the largest element
# is expected and a wrong mask, scale or block index is not: it shows as
# an error of order one. 2**-5 (8 eps) for outputs that are one rounding
# deep; 2**-4 for gradients and for the ring carry, which chain two or
# three such roundings. The CPU rehearsal (float32 inputs, interpreter)
# uses 1e-5: there only summation order differs.
TOL_BF16 = 2.0 ** -5
TOL_BF16_CHAINED = 2.0 ** -4
TOL_F32 = 1e-5

# (name, heads, kv_heads, head_dim, sliding window)
ATTENTION_SHAPES = (('llama3-1b', 16, 8, 128, 0),
                    ('mistral-7b', 32, 8, 128, 4096))
VOCAB = {'llama3-1b': 32768, 'mistral-7b': 32000, 'test-tiny': 512}


def _kernels_child(rehearse: bool) -> int:
    import dataclasses
    import functools
    import importlib
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, ROOT)
    from skypilot_tpu.models import get_config
    from skypilot_tpu.models import transformer as transformer_lib
    from skypilot_tpu.ops.flash_attention import flash_attention
    from skypilot_tpu.ops.fused_lora import fused_multi_lora
    from skypilot_tpu.ops.paged_attention import paged_decode_attention
    from skypilot_tpu.parallel import distributed

    # (skypilot_tpu.ops exports a function under the module's name.)
    ring_lib = importlib.import_module('skypilot_tpu.ops.ring_attention')
    # What the entry points do first: the compile cache, and the log
    # line that names the device.
    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format='%(message)s')
    distributed.initialize()
    dev = jax.devices()[0]
    if not rehearse and dev.platform != 'tpu':
        print(f'kernels: FAILED: need platform tpu, jax reports '
              f'{dev.platform!r}', flush=True)
        return 2

    interpret = rehearse
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    tol, tol_chained = ((TOL_F32, TOL_F32) if rehearse
                        else (TOL_BF16, TOL_BF16_CHAINED))
    failures = []

    def rel_err(got, want) -> float:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return float('inf')
        return float(np.max(np.abs(got - want)) /
                     max(float(np.max(np.abs(want))), 1e-30))

    def check(name: str, pairs, bound: float) -> None:
        """pairs: {part: (kernel output, twin output)}."""
        errs = {k: rel_err(a, b) for k, (a, b) in pairs.items()}
        ok = all(e <= bound for e in errs.values())
        shown = ' '.join(f'{k}={e:.2e}' for k, e in errs.items())
        print(f'kernel {name}: {"ok" if ok else "FAILED"} rel_err '
              f'{shown} (bound {bound:.2e})', flush=True)
        if not ok:
            failures.append(name)

    def normal(seed, shape, dt=None):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.float32).astype(dt or dtype)

    # ---- flash attention, forward and backward ----
    seqs = (128,) if rehearse else (1024, 4096)
    for model, heads, kv, hd, window in ATTENTION_SHAPES:
        if rehearse:
            heads, kv, hd, window = heads // 4, kv // 4, 64, window // 64
        for seq in seqs:
            q = normal(1, (1, seq, heads, hd))
            k = normal(2, (1, seq, kv, hd))
            v = normal(3, (1, seq, kv, hd))
            g = normal(4, (1, seq, heads, hd))

            def fwd_bwd(impl, window=window):
                def run(q, k, v, g):
                    out, vjp = jax.vjp(
                        lambda q, k, v: flash_attention(
                            q, k, v, impl=impl, window=window), q, k, v)
                    return (out,) + vjp(g)
                return jax.jit(run)(q, k, v, g)

            got = fwd_bwd('pallas_interpret' if interpret else 'pallas')
            want = fwd_bwd('xla')
            name = f'flash[{model} seq={seq} window={window}]'
            check(name + ' fwd', {'out': (got[0], want[0])}, tol)
            check(name + ' bwd', {n: (got[i], want[i]) for i, n in
                                  enumerate(('dq', 'dk', 'dv'), 1)},
                  tol_chained)

    # ---- paged decode attention: bf16 and int8 pools ----
    block, slots = 16, 4
    max_len = 128 if rehearse else 2048
    bps = max_len // block
    for model, heads, kv, hd, window in ATTENTION_SHAPES:
        if rehearse:
            heads, kv, hd, window = heads // 4, kv // 4, 64, window // 64
        # The XLA twin IS the engine's XLA path: gather each row's
        # window through its table, then models/transformer's
        # _attend_window (the single definition of the decode math).
        cfg = dataclasses.replace(
            get_config(model), num_heads=heads, num_kv_heads=kv,
            head_dim_override=hd, sliding_window=window,
            max_seq_len=max_len, dtype=jnp.dtype(dtype).name)
        nblocks = slots * bps + 1
        k_pool = normal(5, (nblocks, block, kv, hd))
        v_pool = normal(6, (nblocks, block, kv, hd))
        kq, ks = transformer_lib._int8_quantize(  # pylint: disable=protected-access
            k_pool)
        vq, vs = transformer_lib._int8_quantize(  # pylint: disable=protected-access
            v_pool)
        ks, vs = ks[..., None], vs[..., None]
        # Shuffled tables: logical order != physical order; block 0 is
        # the scratch block, as in the engine.
        perm = np.random.RandomState(0).permutation(nblocks - 1) + 1
        tables = jnp.asarray(perm.reshape(slots, bps), jnp.int32)

        def twin(q, pos, tables, kp, vp, kscale=None, vscale=None,
                 cfg=cfg):
            gidx = (tables[:, :, None] * block +
                    jnp.arange(block)[None, None, :]).reshape(slots, -1)
            flat = lambda p: p.reshape((-1,) + p.shape[2:])  # noqa: E731
            return transformer_lib._attend_window(  # pylint: disable=protected-access
                cfg, q, flat(kp)[gidx], flat(vp)[gidx],
                None if kscale is None else flat(kscale)[gidx][..., 0],
                None if vscale is None else flat(vscale)[gidx][..., 0],
                pos)

        def kernel(q, pos, tables, kp, vp, kscale=None, vscale=None,
                   window=window):
            return paged_decode_attention(
                q, kp, vp, tables, pos, k_scale=kscale, v_scale=vscale,
                window=window, interpret=interpret)

        for cache_len in (64, max_len):
            for cur in (1, 16):     # a decode step; a prefill chunk
                # Four slots at different depths near cache_len.
                last = np.array([cache_len - 1, cache_len - 2,
                                 cache_len - 17, cache_len // 2])
                pos = jnp.asarray(
                    last[:, None] - (cur - 1) + np.arange(cur)[None, :],
                    jnp.int32)
                q = normal(7, (slots, cur, heads, hd))
                for pool, operands in (
                        ('f32' if rehearse else 'bf16', (k_pool, v_pool)),
                        ('int8', (kq, vq, ks, vs))):
                    got = jax.jit(kernel)(q, pos, tables, *operands)
                    want = jax.jit(twin)(q, pos, tables, *operands)
                    check(f'paged_decode[{model} pool={pool} '
                          f'len={cache_len} cur={cur}]',
                          {'out': (got, want)}, tol)

    # ---- fused multi-LoRA delta ----
    d_model, rank = (64, 4) if rehearse else (2048, 16)
    x = normal(8, (4, 1, d_model))
    a_stack = normal(9, (5, d_model, rank)) * d_model ** -0.5
    b_stack = normal(10, (5, rank, d_model)) * rank ** -0.5
    ids = jnp.asarray([0, 3, 1, 3], jnp.int32)
    got = jax.jit(lambda x, a, b, ids: fused_multi_lora(
        x, a, b, ids, interpret=interpret))(x, a_stack, b_stack, ids)
    # The twin is MultiLoRADenseGeneral's XLA branch: take, two dots.
    want = jax.jit(lambda x, a, b, ids: jnp.einsum(
        'bsr,bro->bso', jnp.einsum('bsi,bir->bsr', x, a[ids]),
        b[ids]))(x, a_stack, b_stack, ids)
    check(f'fused_lora[d={d_model} r={rank}]', {'out': (got, want)}, tol)

    # ---- ring attention chunk update ----
    chunk = 64 if rehearse else 1024
    for model, heads, _, hd, _ in ATTENTION_SHAPES:
        if rehearse:
            heads, hd = heads // 4, 64
        arrays = [normal(11 + i, (1, chunk, heads, hd)) for i in range(5)]

        def two_hops(update, heads=heads, hd=hd):
            def run(q, k1, v1, k2, v2):
                o = jnp.zeros((1, chunk, heads, hd), jnp.float32)
                m = jnp.full((1, heads, chunk), -1e30, jnp.float32)
                l = jnp.zeros((1, heads, chunk), jnp.float32)
                # Hop 1: an earlier chunk (full attend); hop 2: the
                # diagonal chunk (causal), onto hop 1's carry.
                o, m, l = update(q, k1, v1, o, m, l,
                                 sm_scale=hd ** -0.5, mask_mode=0,
                                 q_offset=jnp.int32(chunk),
                                 k_offset=jnp.int32(0))
                return update(q, k2, v2, o, m, l, sm_scale=hd ** -0.5,
                              mask_mode=1, q_offset=jnp.int32(chunk),
                              k_offset=jnp.int32(chunk))
            return jax.jit(run)(*arrays)

        got = two_hops(functools.partial(
            ring_lib._chunk_update_pallas,  # pylint: disable=protected-access
            interpret=interpret))
        want = two_hops(
            ring_lib._chunk_update)  # pylint: disable=protected-access
        check(f'ring_chunk_update[{model} chunk={chunk}]',
              {n: (got[i], want[i]) for i, n in enumerate('oml')},
              tol_chained)

    if failures:
        print(f'kernels: FAILED: {failures}', flush=True)
        return 1
    print('kernels: every kernel matched its XLA twin', flush=True)
    return 0


# ---------------------------------------------------------------------
# the parent: no jax from here on
# ---------------------------------------------------------------------


def _child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    env['PYTHONPATH'] = ROOT + os.pathsep + env.get('PYTHONPATH', '')
    env['PYTHONUNBUFFERED'] = '1'
    if rehearse:
        env['JAX_PLATFORMS'] = 'cpu'
    return env


class _Child:
    """One child process in its own process group, its output teed to
    a log file; killed with its group when the phase is over."""

    def __init__(self, name: str, cmd, rehearse: bool):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.log_path = os.path.join(OUT_DIR, f'{name}.log')
        self._log = open(self.log_path, 'w', encoding='utf-8')
        self._log.write('$ ' + ' '.join(cmd) + '\n')
        self._log.flush()
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=_child_env(rehearse), stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def text(self) -> str:
        self._log.flush()
        with open(self.log_path, encoding='utf-8',
                  errors='replace') as f:
            return f.read()

    def wait(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(
                f'timed out after {timeout:.0f}s') from None

    def close(self) -> None:
        """Stop the child and whatever it started."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()


def _device_of(text: str, rehearse: bool) -> dict:
    m = _DEVICE_RE.search(text)
    if m is None:
        raise PhaseFailed('child never named its device')
    device = {'platform': m.group(1), 'kind': m.group(2),
              'count': int(m.group(3))}
    if not rehearse and device['platform'] != 'tpu':
        raise PhaseFailed(
            f"no accelerator: the child saw platform "
            f"{device['platform']!r}, need 'tpu'")
    return device


def _tail(text: str, lines: int = 25) -> str:
    return '\n'.join('    | ' + ln for ln in text.splitlines()[-lines:])


def _memory_balance(text: str, detail: list, sharded: bool) -> None:
    """bytes_in_use per local device once weights are placed. A phase
    that shards over every device (`sharded`) fails if one device holds
    more than twice another; a one-chip server on a four-chip host
    rightly fills one."""
    held = {int(d): int(b) for d, b in _MEM_RE.findall(text)}
    if not held:
        return
    detail.append('bytes_in_use ' + ' '.join(
        f'd{d}={b / 2**30:.2f}GiB' for d, b in sorted(held.items())))
    if sharded and max(held.values()) > 2 * min(held.values()):
        raise PhaseFailed(f'device memory unbalanced: {held}')


def _phase_kernels(rehearse: bool, timeout: float):
    cmd = [sys.executable, os.path.abspath(__file__), '--child',
           'kernels'] + (['--rehearse'] if rehearse else [])
    child = _Child('kernels', cmd, rehearse)
    try:
        rc = child.wait(timeout)
        text = child.text()
        device = _device_of(text, rehearse)
        results = [ln for ln in text.splitlines()
                   if ln.startswith('kernel ')]
        if rc != 0:
            raise PhaseFailed(f'rc={rc}\n{_tail(text)}')
        if not results or any(': ok ' not in ln for ln in results):
            raise PhaseFailed(f'kernel lines:\n{_tail(text)}')
        return device, [f'{len(results)} kernel checks'] + results
    finally:
        child.close()


def _phase_train(name: str, rehearse: bool, timeout: float):
    model, seq = ('llama3-1b', 1024) if name == 'train' else \
        ('mistral-7b', 2048)
    if rehearse:
        model, seq = 'test-tiny', 64
    if name == 'train':
        steps, flags = 6, ['--batch', '8']
    else:
        steps, flags = 4, ['--lora-rank', '16', '--batch', '4',
                           '--probe-hlo']
    cmd = [sys.executable, '-m', 'skypilot_tpu.train.run', '--model',
           model, '--seq', str(seq), '--steps', str(steps),
           '--log-every', '1'] + flags
    child = _Child(name, cmd, rehearse)
    try:
        rc = child.wait(timeout)
        text = child.text()
        device = _device_of(text, rehearse)
        if rc != 0:
            raise PhaseFailed(f'rc={rc}\n{_tail(text)}')
        rows = _STEP_RE.findall(text)
        losses = [float(r[2]) for r in rows]
        if [int(r[0]) for r in rows] != list(range(steps)):
            raise PhaseFailed(f'expected steps 0..{steps - 1}, log has '
                              f'{[r[0] for r in rows]}\n{_tail(text)}')
        if not all(math.isfinite(x) for x in losses):
            raise PhaseFailed(f'non-finite loss: {losses}')
        detail = ['loss ' + ' '.join(f'{x:.4f}' for x in losses),
                  'step_time ' + ' '.join(f'{r[4]}s' for r in rows)]
        if not rehearse:
            # A random model's loss: the final norm hands the head
            # unit-RMS activations and the head is lecun-normal, so the
            # logits are N(0, 1) and E[loss] = ln(vocab) + 1/2 (10.90
            # for llama3-1b; a CPU forward pass at this seed gives
            # 10.913). The band is 5% of ln(vocab) either side.
            ln_vocab = math.log(VOCAB[model])
            if abs(losses[0] - (ln_vocab + 0.5)) > 0.05 * ln_vocab:
                raise PhaseFailed(
                    f'step 0 loss {losses[0]:.4f} is not within 5% of '
                    f'ln(vocab) of a random model\'s '
                    f'{ln_vocab + 0.5:.3f}')
            if "resolved to 'pallas'" not in text:
                raise PhaseFailed(
                    'the log does not show attention resolved to the '
                    f'Pallas kernel\n{_tail(text)}')
        _memory_balance(text, detail, sharded=True)   # fsdp over all
        if name == 'train-4':
            m = re.search(r'compiled step kernel operands: (\[.*\])',
                          text)
            if m is None:
                raise PhaseFailed('no kernel operands in the log')
            detail.append('kernel operands ' + m.group(1))
            if not rehearse:
                # (batch * heads, seq, head_dim) folded by the kernel's
                # caller: one device's share is batch/4 * 32 heads = 32
                # rows under fsdp=4; the gathered whole would be 128.
                lead = {int(x) for x in re.findall(
                    r"'bf16\[(\d+),2048,128\]'", m.group(1))}
                if lead != {32}:
                    raise PhaseFailed(
                        f'flash kernel operands are not the per-device '
                        f'shard: leading dims {sorted(lead)}, want [32]')
        return device, detail
    finally:
        child.close()


def _http(method: str, url: str, body=None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _metric(text: str, name: str, must_contain: str = '') -> float:
    """Sum of a metric's samples in Prometheus text (optionally only
    the lines containing `must_contain`, e.g. a label pair)."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and must_contain in line and \
                line[len(name):len(name) + 1] in (' ', '{'):
            total += float(line.rsplit(' ', 1)[1])
            seen = True
    if not seen:
        raise PhaseFailed(f'/metrics has no {name} {must_contain}')
    return total


def _phase_serve(name: str, rehearse: bool, timeout: float):
    model, extra = 'llama3-1b', []
    long_prompt, want_kernel = 1500, 0
    if name == 'serve-paged':
        extra = ['--paged-block-size', '16', '--prefix-cache', '4',
                 '--decode-kernel', 'pallas', '--kv-quant', 'int8']
        want_kernel = 1
    elif name == 'serve-tp4':
        model = 'mistral-7b'
        extra = ['--tp', '4', '--max-seq-len', '2048']
    if rehearse:
        model, long_prompt = 'test-tiny', 80
        if want_kernel:
            extra[extra.index('pallas')] = 'pallas_interpret'
            want_kernel = 2
        if name == 'serve-tp4':     # test-tiny has two kv heads
            extra = ['--tp', '2', '--max-seq-len', '128']
    vocab = VOCAB[model]
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    base = f'http://127.0.0.1:{port}'
    cmd = [sys.executable, '-m', 'skypilot_tpu.serve.server', '--model',
           model, '--port', str(port)] + extra
    t_start = time.monotonic()
    child = _Child(name, cmd, rehearse)
    try:
        # ---- wait for /health (the server warms up before it listens)
        while True:
            if child.proc.poll() is not None:
                raise PhaseFailed(f'server exited rc={child.proc.returncode} '
                                  f'before /health\n{_tail(child.text())}')
            if time.monotonic() - t_start > timeout:
                raise PhaseFailed(f'no /health after {timeout:.0f}s\n'
                                  f'{_tail(child.text())}')
            try:
                if _http('GET', base + '/health', timeout=5)[0] == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)
        ready_s = time.monotonic() - t_start
        device = _device_of(child.text(), rehearse)
        detail = [f'time to ready {ready_s:.1f}s']
        finished_name = 'skytpu_engine_requests_finished_total'
        before = _http('GET', base + '/metrics')[1]

        # ---- eight requests
        def ids(n, salt):
            return [(7 * i + salt) % (vocab - 1) + 1 for i in range(n)]

        def check_ids(what, got, want_n):
            if len(got) != want_n or not all(
                    isinstance(t, int) and 0 <= t < vocab for t in got):
                raise PhaseFailed(
                    f'{what}: want {want_n} token ids in [0, {vocab}), '
                    f'got {len(got)}: {got[:8]}...')

        def generate(what, prompt, max_new=32, temperature=0.0):
            status, body = _http('POST', base + '/generate', {
                'prompt_ids': [prompt], 'max_new_tokens': max_new,
                'temperature': temperature})
            if status != 200:
                raise PhaseFailed(f'{what}: HTTP {status}: {body[:300]}')
            check_ids(what, json.loads(body)['token_ids'][0], max_new)

        def streamed():
            status, body = _http('POST', base + '/generate', {
                'prompt_ids': [ids(32, 5)], 'max_new_tokens': 32,
                'stream': True})
            if status != 200:
                raise PhaseFailed(f'stream: HTTP {status}')
            events = [json.loads(ln[6:]) for ln in body.splitlines()
                      if ln.startswith('data: ')]
            if not events or not events[-1].get('done'):
                raise PhaseFailed(f'stream did not end in done: '
                                  f'{events[-1:]}')
            check_ids('stream', [e['token_id'] for e in events
                                 if 'token_id' in e], 32)

        def completions():
            status, body = _http('POST', base + '/v1/completions', {
                'prompt': ids(32, 6), 'max_tokens': 32})
            if status != 200:
                raise PhaseFailed(f'/v1/completions: HTTP {status}: '
                                  f'{body[:300]}')
            out = json.loads(body)
            if out['usage']['completion_tokens'] != 32 or \
                    out['choices'][0]['finish_reason'] != 'length':
                raise PhaseFailed(f'/v1/completions: {out["usage"]} '
                                  f'{out["choices"][0]["finish_reason"]}')

        errors = []

        def run(fn, *args):
            try:
                fn(*args)
            except Exception as e:  # pylint: disable=broad-except
                errors.append(f'{type(e).__name__}: {e}')

        threads = [threading.Thread(target=run, args=(
            generate, f'concurrent {i}', ids(32, i))) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        run(streamed)
        run(completions)
        run(generate, f'{long_prompt}-token prompt',
            ids(long_prompt, 9), 16)
        run(generate, 'temperature 0.8', ids(32, 11), 32, 0.8)
        if errors:
            raise PhaseFailed('; '.join(errors) + '\n' +
                              _tail(child.text()))

        # ---- /metrics
        metrics = _http('GET', base + '/metrics')[1]
        ok = (_metric(metrics, finished_name, 'outcome="ok"') -
              _metric(before, finished_name, 'outcome="ok"'))
        finished = (_metric(metrics, finished_name) -
                    _metric(before, finished_name))
        wedges = _metric(metrics, 'skytpu_engine_wedge_recoveries_total')
        kernel = _metric(metrics, 'skytpu_engine_decode_kernel')
        detail.append(f'8 requests sent: finished={finished:.0f} '
                      f'ok={ok:.0f} wedge_recoveries={wedges:.0f} '
                      f'skytpu_engine_decode_kernel {kernel:.0f}')
        if finished != 8 or ok != 8:
            raise PhaseFailed(f'8 requests sent, {finished:.0f} '
                              f'finished, {ok:.0f} ok')
        if wedges != 0:
            raise PhaseFailed(f'wedge_recoveries_total = {wedges}')
        if kernel != want_kernel:
            raise PhaseFailed(f'skytpu_engine_decode_kernel reads '
                              f'{kernel:.0f}, want {want_kernel}')
        _memory_balance(child.text(), detail,
                        sharded=name == 'serve-tp4')

        # ---- SIGTERM: drain and exit 0
        child.proc.send_signal(signal.SIGTERM)
        rc = child.wait(60)
        if rc != 0:
            raise PhaseFailed(f'SIGTERM drain exited rc={rc}\n'
                              f'{_tail(child.text())}')
        return device, detail
    finally:
        child.close()


def _run_phase(name: str, rehearse: bool, timeout: float):
    if name == 'kernels':
        return _phase_kernels(rehearse, timeout)
    if name.startswith('train'):
        return _phase_train(name, rehearse, timeout)
    return _phase_serve(name, rehearse, timeout)


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument('--rehearse', action='store_true',
                        help='run the same phases on the CPU at '
                             'test-tiny with interpreter kernels, to '
                             'debug this script; never a chip result')
    parser.add_argument('--only', default='',
                        help='comma list of phases to run (debugging; '
                             'a partial run never prints the result '
                             'line)')
    parser.add_argument('--child', default='', help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child == 'kernels':
        return _kernels_child(args.rehearse)

    tag = '[REHEARSAL on the CPU, not a chip result] ' \
        if args.rehearse else ''
    if not os.path.isdir(os.path.join(ROOT, 'skypilot_tpu')):
        print(f'{tag}chip_smoke: FAILED: no skypilot_tpu/ next to '
              f'{os.path.abspath(__file__)}: this script drives the '
              f'repository it sits in.')
        return 2
    only = [p for p in args.only.split(',') if p]
    unknown = [p for p in only if p not in PHASES]
    if unknown:
        parser.error(f'unknown phase(s) {unknown}; phases: {PHASES}')

    t0 = time.monotonic()
    device = None
    failed = []
    for name in PHASES:
        if only and name not in only:
            continue
        if name in FOUR_CHIP and device is not None and \
                device['count'] < 4:
            print(f"{tag}phase {name}: not run: {device['count']} "
                  f'device(s)', flush=True)
            continue
        left = BUDGET_S - (time.monotonic() - t0)
        t_phase = time.monotonic()
        try:
            if left <= 0:
                raise PhaseFailed(f'the {BUDGET_S:.0f}s budget is spent')
            seen, detail = _run_phase(name, args.rehearse, left)
            device = device or seen
            status = 'ok'
        except PhaseFailed as e:
            status, detail = 'FAILED', str(e).splitlines()
            failed.append(name)
        print(f'{tag}phase {name}: {status} '
              f'({time.monotonic() - t_phase:.1f}s)', flush=True)
        for line in detail:
            print(f'{tag}    {line}', flush=True)
        if failed and device is None:
            break   # no device: every later child would fail the same

    total = time.monotonic() - t0
    if device is not None:
        print(f"{tag}platform {device['platform']}, device kind "
              f"{device['kind']!r}, {device['count']} device(s), "
              f'{total:.0f}s in all', flush=True)
    if failed:
        print(f'{tag}chip_smoke: FAILED: {", ".join(failed)} '
              f'(logs in {OUT_DIR})', flush=True)
        return 1
    if args.rehearse or only:
        print(f'{tag}chip_smoke: the phases that ran passed; a '
              f'rehearsal or partial run is not a result.', flush=True)
        return 0
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
