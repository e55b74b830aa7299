"""Training: the compiled step of `trainer.make_train_step` with its
state, built once in set-up, driven from the seed through its first
steps (which the plain reference follows) and handed, the same object,
to the window. The measure is tokens of the steps that ended in the
window, over the window's whole length, over the chips.

LoRA on a frozen bf16 base. The adapters are held in float32 (their
gradients and Adam moments with them): the benchmark makes the weights,
so it chooses; see PERF.md for why bf16 adapters are not benchmarked.
The optimizer's settings are all the mix's (`optimizer`).
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time

import numpy as np

import common
import flops_bytes
import traffic as traffic_lib
import weights as weights_lib
from drivers import serve_common
from drivers.serve_common import log

LORA_NAMES = {
    'layers/layer/attn/q_proj/lora_a': 'q_a',
    'layers/layer/attn/q_proj/lora_b': 'q_b',
    'layers/layer/attn/v_proj/lora_a': 'v_a',
    'layers/layer/attn/v_proj/lora_b': 'v_b',
}
CHECK_STEPS = 3
ADAPTER_DTYPE = 'float32'


def train_config(mix: dict, config: dict):
    from skypilot_tpu.models import get_config
    prog = config['program']
    lora = mix['lora']
    over = dict(prog['overrides'], max_seq_len=int(mix['seq']),
                lora_rank=int(lora['rank']),
                lora_alpha=float(lora['alpha']),
                lora_targets=lora['targets'])
    return get_config(prog['registry_name'], **over)


class Train:
    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.cell, self.config, self.mix = (ctx['cell'], ctx['config'],
                                            ctx['mix'])
        self.seed = ctx['seed']
        self.chips = int(self.cell['chips'])
        self.rows, self.seq = int(self.mix['batch']), int(self.mix['seq'])
        self.hp = dict(self.mix['optimizer'])

    def build(self) -> None:
        import dataclasses
        import jax
        import jax.numpy as jnp
        from flax import linen as nn
        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.parallel import mesh as mesh_lib
        from skypilot_tpu.parallel import sharding as sharding_lib
        from skypilot_tpu.train import trainer
        self.cfg = cfg = train_config(self.mix, self.config)
        serve_common.program_config(  # the same check of sizes
            self.config, {'engine': {'max_seq_len': self.seq}})
        self.mesh = mesh_lib.build_mesh(
            mesh_lib.infer_mesh_config(self.chips),
            list(jax.devices())[:self.chips])
        tc = trainer.TrainConfig(**self.hp)
        model = Transformer(cfg)
        init_model = Transformer(dataclasses.replace(
            cfg, attention_impl='xla'))
        tx = trainer.make_optimizer(tc, lora_only=True)
        dummy = jnp.ones((1, 128), jnp.int32)
        boxed = jax.eval_shape(
            lambda: init_model.init(jax.random.PRNGKey(0), dummy))['params']
        self.abstract = nn.unbox(boxed)

        def abstract_state():
            return trainer.TrainState.create(
                apply_fn=model.apply,
                params=init_model.init(jax.random.PRNGKey(0),
                                       dummy)['params'], tx=tx)

        shardings = sharding_lib.tree_shardings(
            self.mesh, jax.eval_shape(abstract_state))
        self.shardings = shardings

        def make_state(base):
            params = weights_lib.make_tree(
                base, self.abstract, adapter_dtype=ADAPTER_DTYPE)
            return trainer.TrainState.create(apply_fn=model.apply,
                                             params=params, tx=tx)

        log('making the state')
        with self.mesh:
            self.state = jax.jit(
                make_state, out_shardings=nn.unbox(shardings))(
                    weights_lib.base_key(self.seed))
        jax.block_until_ready(self.state.params)
        log('state on the device')
        self.step_fn = trainer.make_train_step(cfg, self.mesh, shardings)
        self.use_mesh = lambda: sharding_lib.use_mesh(self.mesh)
        self.batch_shardings = trainer.batch_sharding(self.mesh)

    def make_batches(self) -> None:
        """A pool of batches on the device, every row different; step n
        takes batch n of the pool, round and round."""
        import jax
        n = int(self.mix['batch_pool'])
        toks = traffic_lib.train_tokens(self.cfg.vocab_size, self.seed,
                                        n * self.rows, self.seq)
        self.host_tokens = toks.reshape(n, self.rows, self.seq + 1)
        self.batches = []
        for b in self.host_tokens:
            batch = {'inputs': b[:, :-1].astype(np.int32),
                     'targets': b[:, 1:].astype(np.int32),
                     'mask': np.ones((self.rows, self.seq), np.float32)}
            self.batches.append({
                k: jax.device_put(v, self.batch_shardings[k])
                for k, v in batch.items()})

    def step(self, n: int):
        """The window's own call and feed."""
        self.state, metrics = self.step_fn(
            self.state, self.batches[n % len(self.batches)])
        return metrics

    def lora_leaves(self, tree) -> dict:
        """{(layer, name): float32 numpy array} of the adapters in a
        tree keyed like the parameters."""
        import jax
        out = {}
        for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            path = '/'.join(weights_lib.path_of(kp))
            for prog, name in LORA_NAMES.items():
                if path.endswith(prog):
                    arr = np.asarray(jax.device_get(leaf), np.float32)
                    for l in range(arr.shape[0]):
                        out[(l, name)] = arr[l]
        return out

    def adam_mu(self) -> dict:
        """Adam's first moment of the adapters, found in the optimizer's
        state by its field name."""
        import jax
        out = {}
        flat = jax.tree_util.tree_flatten_with_path(self.state.opt_state)[0]
        for kp, leaf in flat:
            keys = [str(getattr(k, 'name', getattr(k, 'key', k)))
                    for k in kp]
            if 'mu' not in keys:
                continue
            for prog, name in LORA_NAMES.items():
                tail = prog.split('/')
                if keys[-len(tail):] == tail:
                    arr = np.asarray(jax.device_get(leaf), np.float32)
                    for l in range(arr.shape[0]):
                        out[(l, name)] = arr[l]
        return out


def first_steps(tr: Train) -> dict:
    """Steps 1..3 through the window's own call; what the reference is
    compared with."""
    import jax
    rec = {'loss': [], 'step_s': []}
    rec['p0'] = tr.lora_leaves(tr.state.params)
    with tr.use_mesh():
        for n in range(CHECK_STEPS):
            t = time.monotonic()
            m = tr.step(n)
            rec['loss'].append(float(jax.device_get(m['loss'])))
            rec['step_s'].append(time.monotonic() - t)
            if n == 0:
                mu = tr.adam_mu()
                rec['g1'] = {k: v / (1.0 - tr.hp['b1'])
                             for k, v in mu.items()}
    rec['p3'] = tr.lora_leaves(tr.state.params)
    return rec


def reference_steps(tr: Train, p0: dict, control: str = '',
                    fault: str = '') -> dict:
    """The plain reference through the same first steps from the same
    weights and rows. `control` ('int8' | 'fp8') holds the matmul weights
    in that lower precision; `fault` 'half' leaves the second half of
    every batch's tokens out and takes the mean over the rest."""
    import jax
    import jax.numpy as jnp
    config = dict(tr.config,
                  sliding_window=flops_bytes.window(tr.config))
    ref = common.load_module('references', config['family'])
    cat = weights_lib.Catalog(tr.seed, tr.abstract,
                              adapter_dtype=ADAPTER_DTYPE)
    f32 = lambda a: a.astype(jnp.float32)
    layers = tr.cfg.num_layers
    scale = tr.cfg.lora_alpha / tr.cfg.lora_rank

    def layer_weights(l):
        w = {name: f32(cat.layer(path, l))
             for path, name in serve_common.LAYER_NAMES.items()
             if cat.has(path)}
        return ref.lower_precision(w, control) if control else w

    params = {l: {n: jnp.asarray(p0[(l, n)]) for n in LORA_NAMES.values()}
              for l in range(layers)}
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    embed = f32(cat.whole(serve_common.EMBED))
    final = f32(cat.whole(serve_common.FINAL_NORM))
    head = {'lm_head': f32(cat.whole(serve_common.LM_HEAD))}
    if control:
        head = ref.lower_precision(head, control)
    out = {'loss': []}
    for n in range(CHECK_STEPS):
        b = tr.host_tokens[n % len(tr.host_tokens)]
        mask = None
        if fault == 'half':
            flat = np.arange(b.shape[0] * tr.seq).reshape(b.shape[0],
                                                          tr.seq)
            mask = jnp.asarray(flat < flat.size // 2, jnp.float32)
        loss, grads = ref.lora_loss_and_grads(
            jnp.asarray(b[:, :-1], jnp.int32),
            jnp.asarray(b[:, 1:], jnp.int32), embed, layer_weights,
            lambda l: params[l], final, head['lm_head'], layers, config,
            scale, mask=mask)
        out['loss'].append(float(loss))
        params, mu, nu, clipped = ref.clip_adamw_step(
            params, grads, mu, nu, n, tr.hp)
        if n == 0:
            out['g1'] = {(l, k): np.asarray(v) for l, d in clipped.items()
                         for k, v in d.items()}
    out['p_end'] = {(l, k): np.asarray(v) for l, d in params.items()
                    for k, v in d.items()}
    return out


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def compare(rec: dict, ref: dict, p0: dict) -> dict:
    """The numbers compared: each step's loss; by the worst leaf, the gap
    between the program's and the reference's norm of the first gradient
    and of the parameters' change, against the reference's norm of that
    leaf or of the median leaf, whichever is larger. Leaves whose first
    gradient in the reference is under a thousandth of the median leaf's
    are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(rec['loss'], ref['loss'])):
        out[f'loss_gap_step{i + 1}'] = abs(a - b) / abs(b)
    keys = sorted(ref['g1'])
    g_ref = {k: _norm(ref['g1'][k]) for k in keys}
    g_med = statistics.median(g_ref.values())
    out['grad_norm_gap'] = max(
        abs(_norm(rec['g1'][k]) - g_ref[k]) / max(g_ref[k], g_med)
        for k in keys)
    live = [k for k in keys if g_ref[k] >= 1e-3 * g_med]
    if 'p_end' in ref and 'p3' in rec and live:
        c_ref = {k: _norm(ref['p_end'][k] - p0[k]) for k in live}
        c_med = statistics.median(c_ref.values())
        out['change_norm_gap'] = max(
            abs(_norm(rec['p3'][k] - p0[k]) - c_ref[k])
            / max(c_ref[k], c_med) for k in live)
    out['leaves_left_out'] = len(keys) - len(live)
    return out


def judge(numbers: dict, limits: dict, steps_in_window: int) -> dict:
    """Each number compared beside its limit: what decides `correct`."""
    checks = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        checks[name] = {'value': value, 'limit': limit,
                        'ok': value is not None and value <= limit}
    checks['steps_in_window'] = {'value': steps_in_window, 'limit': 1,
                                 'ok': steps_in_window >= 1}
    return checks


def run(ctx: dict) -> dict:
    import jax
    tr = Train(ctx)
    tr.build()
    tr.make_batches()
    rec = first_steps(tr)
    log(f'first steps: losses {rec["loss"]} times {rec["step_s"]}')
    with tr.use_mesh():
        jax.block_until_ready(tr.step(CHECK_STEPS)['loss'])  # step 4, warm
        seconds = float(ctx['seconds'])
        trace = bool(ctx['trace'])
        state = {}
        step_times = []
        n = CHECK_STEPS + 1
        t_open = time.monotonic()
        t_close = t_open + seconds
        traced = None
        while True:
            t0 = time.monotonic()
            if t0 >= t_close:
                break
            if trace and 'begun' not in state and \
                    t0 - t_open >= float(tr.mix['trace_after_s']):
                state['begun'] = (len(step_times), begin_trace())
            jax.block_until_ready(tr.step(n)['loss'])
            t1 = time.monotonic()
            n += 1
            if t1 <= t_close:
                step_times.append(t1 - t0)
            if trace and 'begun' in state and 'ended' not in state and \
                    len(step_times) - state['begun'][0] >= \
                    int(tr.mix['trace_steps']):
                state['ended'] = True
                traced = end_trace(state['begun'][1],
                                   len(step_times) - state['begun'][0])
        if trace and 'begun' in state and 'ended' not in state:
            traced = end_trace(state['begun'][1],
                               len(step_times) - state['begun'][0])
    tokens = len(step_times) * tr.rows * tr.seq
    e2e = {'train_tokens_per_s_chip': tokens / seconds / tr.chips,
           'setup_s': t_open - common.PROCESS_START,
           'steps_in_window': len(step_times)}
    peak = common.memory_peak_bytes(tr.chips)
    device = dict(ctx['device'], memory_peak_bytes=peak)
    log(f'window closed: {len(step_times)} steps; {e2e}')
    reader_ctx = None
    if trace and traced is not None:
        import trace_reduce
        xplane = trace_reduce.find_xplane(traced['dir'])
        tobj = trace_reduce.load(xplane)
        shutil.rmtree(traced['dir'], ignore_errors=True)
        window_s = tobj.window_s() or traced['seconds']
        log(f'device seconds by program in the traced stretch: '
            f'{tobj.module_totals()}')
        device['busy_s'] = tobj.busy_s()
        device['window_s'] = window_s
        reader_ctx = {
            'trace': tobj, 'config': tr.config, 'mix': tr.mix,
            'cell': tr.cell, 'peaks': ctx['peaks'], 'chips': tr.chips,
            'step_times': step_times,
            'work': {'window_s': window_s, 'steps': traced['steps'],
                     'rows': tr.rows, 'seq': tr.seq,
                     'lora_rank': tr.cfg.lora_rank}}
    # free the program's state, then the reference
    p0 = rec.pop('p0')
    tr.state = None
    tr.batches = None
    tr.step_fn = None
    gc.collect()
    t = time.monotonic()
    ref = reference_steps(tr, p0, control=ctx.get('control', ''))
    numbers = compare(rec, ref, p0)
    log(f'reference {time.monotonic() - t:.1f}s: ref losses {ref["loss"]} '
        f'numbers {numbers}')
    checks = judge(numbers, ctx['limits'], len(step_times))
    also = {}
    for what in ctx.get('also', ()):
        # a control or a fault, read in the reference put in the
        # program's place and judged as a run is
        # (perf/limit_readings.py asks for these)
        alt = reference_steps(
            tr, p0, control=what if what in ('int8', 'fp8') else '',
            fault=what if what == 'half' else '')
        alt['p3'] = alt.pop('p_end')
        alt_numbers = compare(alt, ref, p0)
        alt_checks = judge(alt_numbers, ctx['limits'], len(step_times))
        also[what] = {'numbers': alt_numbers,
                      'correct': all(c['ok'] for c in alt_checks.values())}
        log(f'{what} in the program\'s place: {also[what]}')
    e2e['train_step_p50_ms'] = (statistics.median(step_times) * 1e3
                                if step_times else None)
    return {'e2e': e2e, 'device': device, 'reader_ctx': reader_ctx,
            'checks': checks, 'correct': all(c['ok']
                                             for c in checks.values()),
            'attempted': len(step_times), 'failed': 0,
            'numbers': numbers, 'also': also}


def begin_trace() -> dict:
    import jax
    d = tempfile.mkdtemp(prefix='perf_trace_')
    jax.profiler.start_trace(d)
    ann = jax.profiler.TraceAnnotation('perf.traced_stretch')
    ann.__enter__()
    return {'dir': d, 'ann': ann, 't0': time.monotonic()}


def end_trace(begun: dict, steps: int) -> dict:
    import jax
    t1 = time.monotonic()
    begun['ann'].__exit__(None, None, None)
    jax.profiler.stop_trace()
    return {'dir': begun['dir'], 'seconds': t1 - begun['t0'],
            'steps': steps}
