"""Each driver run at a tiny size on the CPU from the test itself (a
rehearsal, not an option of the measurement path): the rest of a run
after the harness's look for a chip. Sound runs come out correct; the
control and each fault a cell can have come out not correct."""
import pytest

import common
import tiny


def _run(driver, cfg, mix, limits, seed=2**31 + 5, seconds=1.5, trace=False,
         control=''):
    ctx = tiny.ctx(cfg, mix, limits, seed, seconds, trace)
    if control:
        ctx['control'] = control
    return common.load_module('drivers', driver).run(ctx)


@pytest.mark.parametrize('bias,window', [(False, 40), (True, 0)])
def test_serving_driver_is_correct(bias, window):
    res = _run('closed_loop', tiny.config(bias, window), tiny.serve_mix(),
               tiny.SERVE_LIMITS)
    assert res['correct'], res['checks']
    assert res['attempted'] > 0 and res['failed'] == 0
    e2e = res['e2e']
    assert e2e['setup_s'] > 0 and e2e['tokens_per_s'] > 0
    assert e2e['tpot_p90_ms'] > 0
    assert res['checks']['gap_max']['value'] <= 1e-3


@pytest.mark.parametrize('levers', [
    {}, {'prefill_chunk': 32, 'decode_chunk': 2}])
def test_traced_serving_run_feeds_the_readers(levers):
    """With the engine's levers at their defaults, and with a prefill
    chunk of two blocks and two decode steps a dispatch: the counts the
    readers work from follow the engine, not its defaults."""
    ctx = tiny.ctx(tiny.config(True, 0), tiny.serve_mix(),
                   tiny.SERVE_LIMITS, 2**31 + 5, 1.5, trace=True)
    ctx['engine_overrides'] = levers
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['correct']
    ctx = res['reader_ctx']
    work = ctx['work']
    assert work['window_s'] > 0
    assert work['decode_positions']
    assert work['chunk'] == levers.get('prefill_chunk', 16)
    pad = sum(-p % work['chunk'] for p in work['prompts_finished'])
    assert work['prompt_tokens_prefilled'] == \
        work['prefill_chunks'] * work['chunk'] - pad
    assert work['decode_steps'] == sum(k for k, _ in ctx['dispatches'])
    assert work['decode_steps'] >= len(ctx['dispatches']) > 0
    # every token generated in the stretch came out of a step logged in it
    assert len(work['decode_positions']) <= \
        sum(k * slots for k, slots in ctx['dispatches']) + 8
    read = lambda n: common.load_module('metrics', n).read(ctx)
    assert 0 < read('batch_occupancy_pct') <= 100
    assert 0 < read('kv_blocks_peak_pct') <= 100
    assert read('queue_wait_p50_ms') >= 0
    assert read('step_mfu.serve') > 0
    # no TPU plane in a CPU trace: the device readers find nothing
    assert read('device_idle_pct.serve') is None
    assert read('decode_hbm_roofline') is None
    assert read('prefill_mfu') is None


def test_a_dispatch_of_several_steps_counts_as_many():
    """What `decode_chunk` will do: the steps are the engine's count, not
    the number of programs run."""
    read = common.load_module('metrics', 'batch_occupancy_pct').read
    assert read({'dispatches': [(2, 3), (1, 4)], 'num_slots': 4}) == \
        pytest.approx(100.0 * (2 * 3 + 4) / 3 / 4)
    assert read({'dispatches': [], 'num_slots': 4}) is None

    class OneSecondOfDecode:
        def ops_within_modules(self, _pattern):
            return 1.0

    cfg = tiny.config(False, 0)
    ctx = {'trace': OneSecondOfDecode(), 'config': cfg, 'chips': 1,
           'peaks': {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e9},
           'work': {'decode_steps': 1, 'decode_positions': [9]}}
    roofline = common.load_module('metrics', 'decode_hbm_roofline').read
    one = roofline(ctx)
    ctx['work'] = {'decode_steps': 4, 'decode_positions': [9]}
    four = roofline(ctx)
    import flops_bytes
    weights = flops_bytes.weight_bytes_per_step(cfg)
    assert four - one == pytest.approx(100.0 * 3 * weights / 1e9)


def test_control_in_lower_precision_is_not_correct():
    """The reference in float8, put in the program's place."""
    mix = dict(tiny.serve_mix(), check_requests=16)
    res = _run('closed_loop', tiny.config(False, 40), mix,
               tiny.SERVE_LIMITS, seconds=2.5, control='fp8')
    assert not res['correct']
    assert not (res['checks']['gap_max']['ok']
                and res['checks']['gap_mean']['ok'])


def test_programs_own_int8_path_is_not_correct():
    """The control as it is run on the chip: the engine with its own
    weight-only int8 switched on, held to the float32 limits."""
    mix = dict(tiny.serve_mix(), check_requests=16)
    ctx = tiny.ctx(tiny.config(False, 0), mix,
                   dict(tiny.SERVE_LIMITS, gap_max=5e-4, gap_mean=5e-6),
                   31, 3.0)
    ctx['engine_overrides'] = {'quantize': 'int8'}
    res = common.load_module('drivers', 'closed_loop').run(ctx)
    assert res['failed'] == 0
    assert not res['correct'], res['checks']


def test_fault_token_altered_where_it_is_produced(monkeypatch):
    """Every 5th sampled token is replaced before it is emitted."""
    import limit_readings
    from skypilot_tpu.models.inference import ContinuousBatchingEngine
    monkeypatch.setattr(
        ContinuousBatchingEngine, '_emit',
        limit_readings.altering_emit(ContinuousBatchingEngine._emit))
    res = _run('closed_loop', tiny.config(False, 40),
               tiny.serve_mix(), tiny.SERVE_LIMITS)
    assert not res['correct']
    assert not res['checks']['gap_max']['ok']


def test_training_driver_is_correct():
    res = _run('train', tiny.config(False, 40), tiny.train_mix(),
               tiny.TRAIN_LIMITS, seconds=1.0)
    assert res['correct'], res['checks']
    assert res['e2e']['train_tokens_per_s_chip'] > 0
    assert res['numbers']['leaves_left_out'] == 0


def test_training_control_is_not_correct():
    res = _run('train', tiny.config(False, 40), tiny.train_mix(),
               tiny.TRAIN_LIMITS, seconds=0.5, control='int8')
    assert not res['correct']


def test_training_controls_read_in_the_references_place():
    """What perf/limit_readings.py --also reads: the reference in lower
    precision, and with half the batch left out, against itself."""
    ctx = tiny.ctx(tiny.config(False, 40), tiny.train_mix(),
                   tiny.TRAIN_LIMITS, 9, 0.3)
    ctx['also'] = ['fp8', 'half']
    res = common.load_module('drivers', 'train').run(ctx)
    assert res['correct']
    for what in ('fp8', 'half'):
        assert not res['also'][what]['correct'], what
        n = res['also'][what]['numbers']
        assert max(n['loss_gap_step1'], n['grad_norm_gap']) > \
            10 * max(res['numbers']['loss_gap_step1'],
                     res['numbers']['grad_norm_gap'], 1e-6), (what, n)


def _broken_step(kind):
    """make_train_step with the timed path broken underneath."""
    from skypilot_tpu.train import trainer
    real = trainer.make_train_step

    def make(cfg, mesh, shardings, **kw):
        step = real(cfg, mesh, shardings, **kw)

        def unchanged(state, batch):
            import jax
            kept = jax.tree.map(lambda x: x.copy(), state)
            _, metrics = step(state, batch)
            return kept, metrics

        def half(state, batch):
            import jax.numpy as jnp
            rows = batch['inputs'].shape[0]
            keep = (jnp.arange(rows) < rows // 2).astype(jnp.float32)
            batch = dict(batch, mask=batch['mask'] * keep[:, None])
            return step(state, batch)

        return {'unchanged': unchanged, 'half': half}[kind]

    return make


@pytest.mark.parametrize('kind', ['unchanged', 'half'])
def test_training_faults_are_not_correct(monkeypatch, kind):
    """A step that returns its state unchanged; half of the batch left
    out, the mean taken over the rest."""
    from skypilot_tpu.train import trainer
    monkeypatch.setattr(trainer, 'make_train_step', _broken_step(kind))
    res = _run('train', tiny.config(False, 40), tiny.train_mix(),
               tiny.TRAIN_LIMITS, seconds=0.5)
    assert not res['correct'], res['checks']
