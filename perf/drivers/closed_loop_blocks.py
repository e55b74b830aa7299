"""Closed loop over a model that generates by diffusion over blocks: the
loop, the window, the stamps, `window_metrics` and the sample are
`closed_loop.py`'s and `serve_common.py`'s; what differs is what a
generated token is. A step of the engine is a PASS over every slot's
block of B positions; it yields no token, or a block's new tokens at
once. So the counts of the traced stretch are this file's
(`traced_work`), and so is the comparison that decides `correct`
(`block_gaps`): no served token's logit is at column `len(prompt) + j -
1` of one causal pass.

The comparison replays what the program says it did. A finished
request's future carries, beside its tokens, the pass of its block that
unmasked each (`unmask_pass`) and what `max_new_tokens` cut off its last
block. The clean stream is prompt + served tokens (+ the cut-off ones);
for pass number s = 0 .. steps - 1 the noisy stream holds, in each
block, the tokens unmasked before pass s and masks elsewhere. The
reference's `block_hidden_states(clean, noisy)` gives every block's
state at pass s in one forward. Read there, for every served token: the
gap between the reference's best logit and its logit of the served
token, at the token's own position in the pass that unmasked it
(`gap_mean`, `gap_max`, as in every serving cell); and `choice_gap`, the
reference's largest confidence among the positions still masked less
its confidence at the position the program chose (logged; it has no
limit: PERF.md section 2).

Copied from `closed_loop.run` and `serve_common.finish` because both
call `serve_common.traced_work` / `reference_gaps` by name (PERF.md
section 7 lists the copies for the next `benchmark` issue to fold): the
bodies of `run` and `finish` below, changed only where marked `blocks:`.

`ctx['fault']` plants one of the faults the cell's limits were set
against (`FAULTS`); not part of a benchmark run.
"""
from __future__ import annotations

import contextlib
import queue
import time

import numpy as np

import common
import traffic as traffic_lib
import weights as weights_lib
from drivers import serve_common
from drivers.closed_loop import ClosedServe
from drivers.serve_common import log

ROWS = 1024          # logits rows reduced at a time
LENGTHS = 512        # sampled requests are padded to a multiple of this


def run(ctx: dict) -> dict:
    with planted(ctx.get('fault', '')):
        return _run(ctx)


def _run(ctx: dict) -> dict:
    serve = ClosedServe(ctx)
    serve.build()
    pool = traffic_lib.closed_loop_pool(serve.mix, serve.vocab, serve.seed)
    serve.compile_warmup()
    clients = int(serve.mix['clients'])
    warm_s = float(serve.mix['warmup_s'])
    sent = []
    cursor = 0

    def send_next(client: int) -> None:
        nonlocal cursor
        src = pool[cursor % len(pool)]
        cursor += 1
        r = traffic_lib.Request(len(sent), src.prompt, src.max_new)
        r.client = client
        sent.append(r)
        serve.send(r)

    t_start = time.monotonic()
    for c in range(clients):
        send_next(c)
    t_open = t_start + warm_s
    t_close = t_open + serve.seconds
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t_open:
            opened = True
            serve.open_window(t_open)
            log(f'window opens; {clients} clients')
        if now >= t_close:
            break
        try:
            c = serve.free_clients.get(timeout=0.02)
        except queue.Empty:
            continue
        send_next(c)
    serve.close_window()
    for r in sent:
        if r.done_time is None and r.future is not None:
            r.future.cancel()
    in_window = [r for r in sent
                 if r.done_time is not None
                 and t_open <= r.done_time < t_close]
    failed = 0
    for r in in_window:
        if not serve.served_tokens_ok(r):
            failed += 1
            if r.future is not None and r.future.done() and \
                    not r.future.cancelled():
                r.error = r.error or repr(r.future.exception())
            log(f'request {r.index} failed: {r.error} '
                f'({len(r.tokens)}/{r.max_new} tokens)')
    time.sleep(0.3)   # let the engine see the cancellations
    return finish(serve, in_window, sent, t_open,
                  attempted=len(in_window), failed=failed)


def traced_work(serve, all_reqs: list) -> dict:
    """Counts inside the traced stretch, for the per-layer readers. A
    generated token counts at its own position, when it landed. Prefill
    covers a prompt's whole blocks, P0 = (len // B) * B, and ends with
    no token, so a prompt counts as prefilled in the stretch where its
    first block's tokens landed there (its P0 for its length). A decode
    step is a pass, from the engine's `step_log`."""
    t0, t1 = serve.trace_t
    b = int(serve.cfg.block_length)
    decode_positions, first_tokens, prompt_done = [], 0, []
    for r in all_reqs:
        for j, t in enumerate(r.token_times):
            if t0 <= t < t1:
                if j == 0:
                    first_tokens += 1
                    prompt_done.append(len(r.prompt) // b * b)
                decode_positions.append(len(r.prompt) + j)
    chunk = serve.prefill_chunk
    chunks = serve.trace_counters.get('prefill_chunks', 0)
    pad = sum((-p) % chunk for p in prompt_done)
    return {'decode_positions': decode_positions,
            'first_tokens': first_tokens,
            'prompt_tokens_prefilled': max(0, chunks * chunk - pad),
            'prompts_finished': [p for p in prompt_done if p],
            'prefill_chunks': chunks, 'chunk': chunk,
            'decode_steps': sum(k for k, _ in serve.dispatches),
            'window_s': t1 - t0}


def finish(serve, window_reqs: list, all_reqs: list, t_open: float,
           attempted: int, failed: int) -> dict:
    ctx = serve.ctx
    seconds = serve.seconds
    e2e = serve_common.window_metrics(window_reqs, all_reqs, t_open,
                                      seconds)
    e2e['setup_s'] = t_open - common.PROCESS_START
    occ = serve.occupancy()
    peak = common.memory_peak_bytes(serve.chips)
    device = dict(ctx['device'], memory_peak_bytes=peak)
    reader_ctx = None
    if serve.trace:
        serve.read_trace()
        work = traced_work(serve, all_reqs)             # blocks: its own
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
        gaps = block_gaps(serve.config, serve.cfg, serve.abstract,
                          serve.seed, sample,
                          int(serve.mix['engine']['max_seq_len']),
                          control=ctx.get('control', ''),
                          fault=ctx.get('fault', ''))    # blocks: its own
        log(f'reference over {len(sample)} requests, {gaps["tokens"]} '
            f'tokens in {time.monotonic() - t:.1f}s: {gaps}')
        for name in ('gap_max', 'gap_mean'):
            checks[name] = {'value': gaps[name], 'limit': limits[name],
                            'ok': gaps[name] <= limits[name]}
        # blocks: logged beside them, compared with nothing
        for name in ('choice_gap_mean', 'choice_gap_max',
                     'choices_not_best'):
            checks[name] = {'value': gaps[name], 'limit': None, 'ok': True}
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


# ---- the comparison --------------------------------------------------------

def replay_record(r, b: int, steps: int, fault: str = ''):
    """(clean ids, unmask pass a position: -1 for a prompt's, one past
    the last served position) of a finished request, from its future:
    the clean stream is the prompt, the served tokens and what was cut
    off the last block, a whole number of blocks."""
    _tokens, stats = r.future.result()
    passes = list(stats['unmask_pass']) + list(stats['overshoot_pass'])
    if fault == 'shift_record':
        passes = [(p + 1) % steps for p in passes]
    clean = (list(r.prompt) + list(r.tokens)
             + list(stats['overshoot_tokens']))
    if len(clean) % b or len(passes) != len(clean) - len(r.prompt):
        raise common.HarnessError(
            f'request {r.index}: {len(clean)} positions and '
            f'{len(passes)} unmask passes do not make whole blocks '
            f'of {b}')
    return (clean, [-1] * len(r.prompt) + passes,
            len(r.prompt) + len(r.tokens))


def block_gaps(config: dict, cfg, abstract, seed: int, sample: list,
               pad_to: int, control: str = '', fault: str = '') -> dict:
    """For every served token of every sampled request, at its own
    position in the pass that unmasked it: the reference's best logit
    minus its logit of the served token; and for every pass of every
    block, the reference's largest confidence among the positions still
    masked minus its confidence at the position the program unmasked.
    `control` ('int8' | 'fp8') instead reads the gap of the token that
    the reference in that lower precision puts first."""
    import jax
    import jax.numpy as jnp
    ref = common.load_reference(config)
    family = common.load_family(config)
    rcfg = family.reference_config(config)
    b, steps = rcfg['block_length'], rcfg['denoising_steps']
    mask_id = rcfg['mask_token_id']
    cat = weights_lib.Catalog(seed, family, abstract)
    records = [replay_record(r, b, steps, fault) for r in sample]
    # Requests of one padded length go through the reference together,
    # every pass number a row: one program a length.
    groups, quantum = {}, min(LENGTHS, pad_to)
    for i, (clean, _, _) in enumerate(records):
        groups.setdefault(-(-len(clean) // quantum) * quantum, []).append(i)
    clean_rows, noisy_rows, picks = {}, {}, []
    for t, members in groups.items():
        clean_arr = np.zeros((len(members) * steps, t), np.int32)
        noisy_arr = np.zeros_like(clean_arr)
        for m, i in enumerate(members):
            clean, upass, served_end = records[i]
            n = len(clean)
            ids, up = np.asarray(clean), np.asarray(upass)
            for s in range(steps):
                row = m * steps + s
                clean_arr[row, :n] = ids
                noisy_arr[row, :n] = np.where(up >= s, mask_id, ids)
                # the positions pass s saw masked: (group, row, position,
                # request, block, pass, unmasked by it?, served?, token)
                for p in np.nonzero(up >= s)[0]:
                    picks.append((t, row, int(p), i, int(p) // b, s,
                                  bool(up[p] == s), bool(p < served_end),
                                  int(ids[p])))
        clean_rows[t], noisy_rows[t] = clean_arr, noisy_arr
    count = len(picks)
    cap = -(-max(count, 1) // ROWS) * ROWS

    @jax.jit
    def reduce_rows(logits, token):
        best = jnp.max(logits, axis=-1)
        return (best, jax.nn.logsumexp(logits, axis=-1),
                jnp.argmax(logits, axis=-1).astype(jnp.int32),
                jnp.take_along_axis(logits, token[:, None], axis=-1)[:, 0])

    def run(how: str, token):
        """(best, lse, argmax, logit of `token`) a picked row."""
        layer_weights, whole = cat.reference_weights(
            (lambda w: ref.lower_precision(w, how)) if how else None)
        picked = []
        for t in groups:
            hidden = ref.block_hidden_states(
                jnp.asarray(clean_rows[t]), jnp.asarray(noisy_rows[t]),
                whole, layer_weights, cfg.num_layers, rcfg)
            mine = [(row, p) for g, row, p, *_ in picks if g == t]
            picked.append(hidden[jnp.asarray([r for r, _ in mine]),
                                 jnp.asarray([p for _, p in mine])])
            del hidden
        # `picks` is ordered by group, as `groups` is
        rows = jnp.concatenate(picked)
        rows = jnp.pad(rows, ((0, cap - count), (0, 0)))
        out = []
        for i in range(0, cap, ROWS):
            logits = ref.logits_at(rows[i:i + ROWS], whole, rcfg)
            out.append(reduce_rows(logits, token[i:i + ROWS]))
        return [np.asarray(jax.device_get(jnp.concatenate(c)))[:count]
                for c in zip(*out)]

    token = jnp.asarray([p[-1] for p in picks] + [0] * (cap - count),
                        jnp.int32)
    if control:
        _, _, low_first, _ = run(control, token)
        token = jnp.asarray(list(low_first) + [0] * (cap - count),
                            jnp.int32)
    best, lse, _first, at_token = run('', token)
    conf = np.exp(np.asarray(best, np.float64) - lse)
    chosen = np.asarray([p[6] for p in picks])
    served = chosen & np.asarray([p[7] for p in picks])
    gaps = (np.asarray(best, np.float64) - at_token)[served]
    # a pass of a block: what it could have chosen against what it chose
    passes = {}
    for k, (_, _, _, req, blk, s, is_chosen, _, _) in enumerate(picks):
        top, took = passes.get((req, blk, s), (0.0, 1.0))
        passes[(req, blk, s)] = (max(top, conf[k]),
                                 min(took, conf[k]) if is_chosen else took)
    choice = np.asarray([top - took for top, took in passes.values()])
    return {'gap_max': float(gaps.max()), 'gap_mean': float(gaps.mean()),
            'tokens': int(gaps.size), 'not_best': int((gaps > 0).sum()),
            'choice_gap_mean': float(choice.mean()),
            'choice_gap_max': float(choice.max()),
            'choices_not_best': int((choice > 0).sum()),
            'rows': count}


# ---- faults the limits were set against --------------------------------------

FAULTS = ('causal', 'no_commit', 'shift_record', 'alter')


@contextlib.contextmanager
def planted(fault: str):
    """One fault in the program (or, `shift_record`, in its record) for
    the length of a run: the causal mask in the block-causal one's
    place; the commit pass spoiled (the last position of a clean block
    forwarded as a mask, so the next block reads noisy K/V); the unmask
    record shifted by one pass (in `replay_record`); every 5th token
    altered where it is emitted."""
    if not fault or fault == 'shift_record':
        yield
        return
    if fault not in FAULTS:
        raise common.HarnessError(f'no fault {fault!r}; have {FAULTS}')
    import jax.numpy as jnp
    from skypilot_tpu.models.configs import ModelConfig
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    owner, name = {
        'causal': (ModelConfig, 'last_key_seen'),
        'no_commit': (ContinuousBatchingEngine, '_block_fed'),
        'alter': (ContinuousBatchingEngine, '_emit')}[fault]
    real = getattr(owner, name)

    def causal(self, q_pos):
        return q_pos

    def no_commit(self, tokens, is_masked):
        clean = ~jnp.any(is_masked, axis=-1, keepdims=True)
        last = jnp.arange(tokens.shape[1]) == tokens.shape[1] - 1
        return real(self, tokens, is_masked | (clean & last[None, :]))

    count = [0]

    def alter(self, slots, active, out_cols, valid):
        cols = np.array(out_cols)
        for slot in active:
            for c in range(int(valid[slot])):
                count[0] += 1
                if count[0] % 5 == 0:
                    cols[slot, c] = (int(cols[slot, c]) + 1) % \
                        self.cfg.vocab_size
        return real(self, slots, active, cols, valid)

    setattr(owner, name, {'causal': causal, 'no_commit': no_commit,
                          'alter': alter}[fault])
    try:
        yield
    finally:
        setattr(owner, name, real)
