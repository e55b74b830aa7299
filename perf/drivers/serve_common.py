"""What the two serving drivers share: the engine built from a cell's
files, the window's bookkeeping, the traced stretch, and the comparison
with the plain reference that decides `correct`.

The timed path is `ContinuousBatchingEngine.submit`, in this process,
which holds the chip. Times are this harness's own, taken with the host's
clock in the streaming callback; nothing is read from the program's own
latency figures.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

import common
import flops_bytes
import weights as weights_lib

# program's parameter path -> the reference's name for it
LAYER_NAMES = {
    'layers/layer/attn_norm/scale': 'attn_norm',
    'layers/layer/attn/q_proj/kernel': 'wq',
    'layers/layer/attn/k_proj/kernel': 'wk',
    'layers/layer/attn/v_proj/kernel': 'wv',
    'layers/layer/attn/q_proj/bias': 'bq',
    'layers/layer/attn/k_proj/bias': 'bk',
    'layers/layer/attn/v_proj/bias': 'bv',
    'layers/layer/attn/o_proj/kernel': 'wo',
    'layers/layer/mlp_norm/scale': 'mlp_norm',
    'layers/layer/mlp/gate_proj/kernel': 'w_gate',
    'layers/layer/mlp/up_proj/kernel': 'w_up',
    'layers/layer/mlp/down_proj/kernel': 'w_down',
}
EMBED, FINAL_NORM, LM_HEAD = ('embed/embedding', 'final_norm/scale',
                              'lm_head/kernel')


def log(msg: str) -> None:
    print(f'[perf {time.monotonic() - common.PROCESS_START:7.2f}s] {msg}',
          flush=True)


def program_config(config: dict, mix: dict):
    """The program's ModelConfig for this configuration file and mix,
    checked against the file's own sizes."""
    from skypilot_tpu.models import get_config
    prog = config['program']
    overrides = dict(prog['overrides'])
    overrides['max_seq_len'] = mix['engine']['max_seq_len']
    cfg = get_config(prog['registry_name'], **overrides)
    want = flops_bytes.dims(config)
    got = {'d': cfg.d_model, 'h': cfg.num_heads, 'kv': cfg.num_kv_heads,
           'hd': cfg.head_dim, 'f': cfg.d_mlp, 'v': cfg.vocab_size,
           'layers': cfg.num_layers, 'window': cfg.sliding_window}
    if want != got:
        raise common.HarnessError(
            f'the program\'s {prog["registry_name"]} is {got}, the '
            f'configuration file says {want}')
    if abs(cfg.norm_eps - config['rms_norm_eps']) > 1e-12 or \
            abs(cfg.rope_theta - config['rope_theta']) > 1e-6 or \
            bool(cfg.qkv_bias) != bool(config.get('attention_bias')):
        raise common.HarnessError('norm eps, rope theta or bias differ '
                                  'between program and configuration')
    return cfg


def abstract_params(cfg):
    """Shapes of the program's parameter tree (nothing is made)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from skypilot_tpu.models.transformer import Transformer
    init_cfg = dataclasses.replace(cfg, decode=False, weight_quant='none',
                                   attention_impl='xla')
    model = Transformer(init_cfg)
    boxed = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 8), jnp.int32)))['params']
    return boxed, nn.unbox(boxed)


def make_params(seed: int, boxed, abstract, mesh=None):
    """The weights, on the device, in one jitted call from the seed, in
    the type they are served in."""
    import jax
    from flax import linen as nn
    out_shardings = None
    if mesh is not None:
        from skypilot_tpu.parallel import sharding as sharding_lib
        out_shardings = nn.unbox(sharding_lib.tree_shardings(mesh, boxed))
    fn = jax.jit(lambda base: weights_lib.make_tree(base, abstract),
                 out_shardings=out_shardings)
    return fn(weights_lib.base_key(seed))


class Serve:
    """One run of a serving cell."""

    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.cell, self.config, self.mix = (ctx['cell'], ctx['config'],
                                            ctx['mix'])
        self.seed = ctx['seed']
        self.seconds = float(ctx['seconds'])
        self.trace = bool(ctx['trace'])
        self.chips = int(self.cell['chips'])
        self.engine = None
        self.trace_t = None           # (t0, t1) monotonic, traced stretch
        self.trace_counters = {}
        self.trace_obj = None
        self.spans = []
        self.engine_overrides = ctx.get('engine_overrides') or {}

    # ---- set-up -------------------------------------------------------
    def build(self) -> None:
        import jax
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        self.cfg = program_config(self.config, self.mix)
        mesh = None
        if self.chips > 1:
            from skypilot_tpu.parallel.mesh import decode_mesh
            mesh = decode_mesh(self.chips)
        self.mesh = mesh
        boxed, abstract = abstract_params(self.cfg)
        self.abstract = abstract
        log('making weights')
        params = make_params(self.seed, boxed, abstract, mesh)
        jax.block_until_ready(params)
        log('weights on the device')
        eng = dict(self.mix['engine'])
        eng.pop('max_seq_len', None)
        eng.update(self.engine_overrides)
        self.engine = ContinuousBatchingEngine(
            self.cfg, params=params, rng_seed=self.seed & 0x7FFFFFFF,
            mesh=mesh, **eng)
        del params
        self.vocab = self.cfg.vocab_size
        # a lever of the engine, read and never set: tokens a prefill
        # chunk holds (today the paged block's size, by its default)
        self.prefill_chunk = int(self.engine.prefill_chunk)

    def compile_warmup(self) -> None:
        """Every program the cell's traffic uses, once: a prompt of two
        chunks and a few decode steps."""
        rng = np.random.default_rng(5)
        ids = rng.integers(0, self.vocab, size=40).tolist()
        futs = [self.engine.submit(ids, max_new_tokens=4)
                for _ in range(2)]
        for f in futs:
            f.result(timeout=1100)
        log('programs compiled or loaded')

    # ---- sending ------------------------------------------------------
    def send(self, req) -> None:
        """Submit one request; its tokens' landing times are stamped in
        the engine's streaming callback."""
        def on_token(tok, _t=req.token_times, _k=req.tokens, _r=req):
            if tok is None:
                _r.done_time = time.monotonic()
                self.finished(_r)
            else:
                _t.append(time.monotonic())
                _k.append(tok)

        req.sent = time.monotonic()
        try:
            if self.trace:
                import jax
                from skypilot_tpu.observability import tracing
                with jax.profiler.TraceAnnotation('perf.submit'), \
                        tracing.span('perf.request'):
                    fut = self.engine.submit(
                        req.prompt, max_new_tokens=req.max_new,
                        on_token=on_token)
            else:
                fut = self.engine.submit(req.prompt,
                                         max_new_tokens=req.max_new,
                                         on_token=on_token)
        except Exception as e:  # refused at the door: a failed request
            req.error = repr(e)
            req.done_time = time.monotonic()
            self.finished(req)
            return
        req.future = fut

    def finished(self, req) -> None:
        """Hook for the closed loop (called on the engine's thread)."""

    # ---- the window and its traced stretch ------------------------------
    def open_window(self, t_open: float) -> None:
        """The window's first instant. A traced run records the program's
        own spans from here to the close, and a thread of its own traces
        `trace_s` seconds of it with the profiler, `trace_after_s` in:
        starting the profiler takes seconds, which the generator must
        not spend waiting."""
        self.t_open = t_open
        if not self.trace:
            return
        import threading
        from skypilot_tpu.observability import tracing
        tracing.enable()
        self._closed = threading.Event()
        self._tracer = threading.Thread(target=self._trace_stretch)
        self._tracer.start()

    def close_window(self) -> None:
        if not self.trace:
            return
        from skypilot_tpu.observability import tracing
        self._closed.set()
        now = time.monotonic()
        self.spans = [s for s in tracing.snapshot()
                      if self.t_open <= s['mono'] <= now]
        tracing.disable()

    def _nap(self, seconds: float) -> bool:
        """Sleep in short naps (they show in the trace as `$time sleep`,
        which the reduction knows for the harness's own waiting); True
        if the window closed meanwhile."""
        until = time.monotonic() + seconds
        while time.monotonic() < until and not self._closed.is_set():
            time.sleep(0.02)
        return self._closed.is_set()

    def _trace_stretch(self) -> None:
        import jax
        if self._nap(float(self.mix['trace_after_s'])):
            return
        trace_dir = tempfile.mkdtemp(prefix='perf_trace_')
        jax.profiler.start_trace(trace_dir)
        chunks0, steps0 = self._prefill_chunks(), self._decode_steps()
        with jax.profiler.TraceAnnotation('perf.traced_stretch'):
            t0 = time.monotonic()
            self._nap(float(self.mix['trace_s']))
            t1 = time.monotonic()
        self.trace_counters = {
            'prefill_chunks': self._prefill_chunks() - chunks0}
        # The engine logs each decode dispatch with the count of decode
        # steps so far: a dispatch holds as many steps as the count rose
        # by (1 today; `decode_chunk` of them once that lever is pulled).
        self.dispatches, last = [], steps0
        for count, active in self._decode_log():
            if count > steps0:
                self.dispatches.append((count - last, len(active)))
                last = count
        jax.profiler.stop_trace()
        self.trace_t, self.trace_dir = (t0, t1), trace_dir
        log(f'traced {t1 - t0:.2f}s from {t0 - self.t_open:.1f}s into '
            f'the window')

    def _step_log(self) -> list:
        for _ in range(100):     # the engine's thread appends meanwhile
            try:
                return list(self.engine.step_log)
            except RuntimeError:
                continue
        return []

    def _decode_log(self) -> list:
        return [e for e in self._step_log() if e[0] != 'prefill']

    def _decode_steps(self) -> int:
        log_ = self._decode_log()
        return log_[-1][0] if log_ else 0

    def _prefill_chunks(self) -> int:
        return self.engine.paged_stats['prefill_chunks']

    def read_trace(self) -> None:
        import trace_reduce
        self._tracer.join()
        if self.trace_t is None:
            raise common.HarnessError(
                'the window closed before its traced stretch began: '
                'nothing to read the per-layer metrics from')
        path = trace_reduce.find_xplane(self.trace_dir)
        self.trace_obj = trace_reduce.load(path)
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    # ---- after the window ---------------------------------------------
    def occupancy(self) -> dict:
        return dict(self.engine.paged_occupancy())

    def free_program(self) -> None:
        self.engine.stop()
        self.engine.params = None
        self.engine._cache = None
        self.engine = None
        gc.collect()

    def served_tokens_ok(self, req) -> bool:
        return (req.error is None and len(req.tokens) == req.max_new
                and all(0 <= t < self.vocab for t in req.tokens))

    def sample_for_check(self, done: list) -> list:
        """A sample of the finished requests, drawn from the seed, with
        the longest in it."""
        n = int(self.mix['check_requests'])
        if not done:
            return []
        longest = max(done, key=lambda r: len(r.prompt) + r.max_new)
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 23])
        rng.shuffle(rest)
        return [longest] + rest[:n - 1]


def reference_gaps(config: dict, cfg, abstract, seed: int, sample: list,
                   pad_to: int, control: str = '') -> dict:
    """For every served token of every sampled request: reference's best
    logit at that position minus the reference's logit of the served
    token. `control` ('int8' | 'fp8') instead reads the gap of the token
    that the reference in that lower precision puts first."""
    import jax
    import jax.numpy as jnp
    ref = common.load_module('references', config['family'])
    config = dict(config, sliding_window=flops_bytes.window(config))
    cat = weights_lib.Catalog(seed, abstract)
    n = len(sample)
    toks = np.zeros((n, pad_to), np.int32)
    rows, cols, served = [], [], []
    for i, r in enumerate(sample):
        seq = list(r.prompt) + list(r.tokens)
        toks[i, :len(seq)] = seq
        for j, t in enumerate(r.tokens):
            rows.append(i)
            cols.append(len(r.prompt) + j - 1)
            served.append(t)
    # One shape whatever was served, so that one program is compiled:
    # pad the list of positions to the most the sample could hold.
    count = len(served)
    cap = -(-max(count, 1) // 1024) * 1024
    rows += [0] * (cap - count)
    cols += [0] * (cap - count)
    served += [0] * (cap - count)
    f32 = lambda a: a.astype(jnp.float32)

    def run(how: str):
        def layer_weights(l):
            w = {ref_name: f32(cat.layer(path, l))
                 for path, ref_name in LAYER_NAMES.items()
                 if cat.has(path)}
            return ref.lower_precision(w, how) if how else w
        hidden = ref.hidden_states(jnp.asarray(toks), f32(cat.whole(EMBED)),
                                   layer_weights, cfg.num_layers, config)
        picked = hidden[jnp.asarray(rows), jnp.asarray(cols)]
        head = {'lm_head': f32(cat.whole(LM_HEAD))}
        if how:
            head = ref.lower_precision(head, how)
        return ref.logits_at(picked, f32(cat.whole(FINAL_NORM)),
                             head['lm_head'], config)

    logits = run('')
    best = jnp.max(logits, axis=-1)
    if control:
        low = run(control)
        chosen = jnp.argmax(low, axis=-1)
    else:
        chosen = jnp.asarray(served, jnp.int32)
    got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    gaps = np.asarray(jax.device_get(best - got), np.float64)[:count]
    return {'gap_max': float(gaps.max()), 'gap_mean': float(gaps.mean()),
            'tokens': int(gaps.size),
            'not_best': int((gaps > 0).sum())}


def window_metrics(window_reqs: list, all_reqs: list, t_open: float,
                   seconds: float) -> dict:
    """The end-to-end numbers of a serving window, from the stamps:
    tokens completed inside it by all requests, and the time per output
    token of every request judged (`window_reqs`)."""
    t_close = t_open + seconds
    out = {}
    tpot = [(r.token_times[-1] - r.token_times[0]) * 1e3
            / (len(r.token_times) - 1)
            for r in window_reqs if len(r.token_times) > 1]
    if tpot:
        out['tpot_p90_ms'] = common.percentile(tpot, 90)
        out['tpot_p50_ms'] = common.percentile(tpot, 50)
    prompt_tokens = output_tokens = 0
    for r in all_reqs:
        if r.token_times and t_open <= r.token_times[0] < t_close:
            prompt_tokens += len(r.prompt)
        output_tokens += sum(1 for t in r.token_times
                             if t_open <= t < t_close)
    out['tokens_per_s'] = (prompt_tokens + output_tokens) / seconds
    out['output_tokens_per_s'] = output_tokens / seconds
    out['requests_timed'] = len(tpot)
    return out


def traced_work(serve: Serve, all_reqs: list) -> dict:
    """Counts inside the traced stretch, for the per-layer readers:
    generated tokens with the position each was fed at, prompt tokens
    prefilled, first tokens."""
    t0, t1 = serve.trace_t
    decode_positions, first_tokens, prompt_done = [], 0, []
    for r in all_reqs:
        for j, t in enumerate(r.token_times):
            if t0 <= t < t1:
                if j == 0:
                    first_tokens += 1
                    prompt_done.append(len(r.prompt))
                else:
                    decode_positions.append(len(r.prompt) + j - 1)
    chunk = serve.prefill_chunk
    chunks = serve.trace_counters.get('prefill_chunks', 0)
    pad = sum((-p) % chunk for p in prompt_done)
    return {'decode_positions': decode_positions,
            'first_tokens': first_tokens,
            'prompt_tokens_prefilled': max(0, chunks * chunk - pad),
            'prompts_finished': prompt_done,
            'prefill_chunks': chunks, 'chunk': chunk,
            'decode_steps': sum(k for k, _ in serve.dispatches),
            'window_s': t1 - t0}


def finish(serve: Serve, window_reqs: list, all_reqs: list,
           t_open: float, attempted: int, failed: int) -> dict:
    """Everything after the window has closed and the answers are in:
    memory, metrics, the reference, the result. `window_reqs` are the
    requests judged: for `correct`, and for the time per token."""
    ctx = serve.ctx
    seconds = serve.seconds
    e2e = window_metrics(window_reqs, all_reqs, t_open, seconds)
    e2e['setup_s'] = t_open - common.PROCESS_START
    occ = serve.occupancy()
    peak = common.memory_peak_bytes(serve.chips)
    device = dict(ctx['device'], memory_peak_bytes=peak)
    reader_ctx = None
    if serve.trace:
        serve.read_trace()
        work = traced_work(serve, all_reqs)
        work['window_s'] = serve.trace_obj.window_s() or work['window_s']
        log(f'device seconds by program in the traced stretch: '
            f'{serve.trace_obj.module_totals()}')
        device['busy_s'] = serve.trace_obj.busy_s()
        device['window_s'] = work['window_s']
        reader_ctx = {
            'trace': serve.trace_obj, 'work': work, 'spans': serve.spans,
            'dispatches': serve.dispatches, 'occupancy': occ,
            'requests': window_reqs, 'e2e': e2e,
            'config': serve.config,
            'mix': serve.mix, 'cell': serve.cell, 'peaks': ctx['peaks'],
            'chips': serve.chips,
            'num_slots': int(serve.mix['engine']['num_slots']),
            't_open': t_open,
        }
    done = [r for r in window_reqs if serve.served_tokens_ok(r)]
    log(f'window closed: {len(window_reqs)} requests, {len(done)} whole; '
        f'{e2e}')
    sample = serve.sample_for_check(done)
    serve.free_program()
    limits = ctx['limits']
    checks = {}
    if sample:
        t = time.monotonic()
        gaps = reference_gaps(serve.config, serve.cfg, serve.abstract,
                              serve.seed, sample,
                              int(serve.mix['engine']['max_seq_len']),
                              control=ctx.get('control', ''))
        log(f'reference over {len(sample)} requests, {gaps["tokens"]} '
            f'tokens in {time.monotonic() - t:.1f}s: {gaps}')
        for name in ('gap_max', 'gap_mean'):
            checks[name] = {'value': gaps[name], 'limit': limits[name],
                            'ok': gaps[name] <= limits[name]}
        checks['tokens_compared'] = {
            'value': gaps['tokens'], 'limit': limits['min_tokens'],
            'ok': gaps['tokens'] >= limits['min_tokens']}
    else:
        checks['tokens_compared'] = {'value': 0,
                                     'limit': limits['min_tokens'],
                                     'ok': False}
    checks['failed_requests'] = {'value': failed, 'limit': 0,
                                 'ok': failed == 0}
    correct = all(c['ok'] for c in checks.values())
    return {'e2e': e2e, 'device': device, 'reader_ctx': reader_ctx,
            'checks': checks, 'correct': correct,
            'attempted': attempted, 'failed': failed}
