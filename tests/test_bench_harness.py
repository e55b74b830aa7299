"""bench.py contract: one process holds the chip, a measurement row
refuses to run without one, a row that cannot be constructed emits a
structured skip, and the --dryrun-* CPU rows keep their dispatch."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), '..', 'bench.py')


def _load_bench():
    spec = importlib.util.spec_from_file_location('bench_mod', _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestStructuredSkip:

    def test_unrunnable_serve_combo_emits_structured_skip(self):
        """A serve flag combination the engine cannot construct (block
        size not dividing the window) must produce ONE machine-
        parseable {"skipped": true, ...} line naming the combo, not a
        stack trace with nothing to parse."""
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        proc = subprocess.run(
            [sys.executable, _BENCH, '--quick', '--serve',
             '--paged-block-size', '7', '--int8-kv',
             '--async-depth', '3'],
            capture_output=True, text=True, timeout=300, env=env,
            check=False)
        assert proc.returncode == 3, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        assert result['skipped'] is True
        assert 'unsupported serve combination' in result['reason']
        assert 'divisible' in result['reason']
        assert result['combo'] == {'kv_quant': 'int8',
                                   'speculative': 0,
                                   'paged_block_size': 7,
                                   'async_depth': 3,
                                   'decode_kernel': 'xla'}


class TestNeedsTheChip:
    """No fallback that hides the device: off the TPU the measurement
    rows stop with the reason instead of measuring the CPU under the
    chip's metric names."""

    def _run(self, *flags):
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        return subprocess.run(
            [sys.executable, _BENCH, *flags], capture_output=True,
            text=True, timeout=300, env=env, check=False)

    def test_default_run_without_tpu_is_an_error(self):
        proc = self._run()
        assert proc.returncode != 0
        assert 'no TPU' in proc.stderr and "'cpu'" in proc.stderr
        assert proc.stdout.strip() == ''  # no result line to misread

    def test_tune_attn_without_tpu_is_an_error(self):
        """--tune-attn times the compiled kernel; the interpreter's
        times rank nothing, --quick or not."""
        proc = self._run('--tune-attn', '--quick')
        assert proc.returncode != 0
        assert 'no TPU' in proc.stderr
        assert proc.stdout.strip() == ''

    def test_quick_runs_in_one_process_without_mfu(self):
        """--quick on the CPU smokes the train path in THIS process (no
        probe child, no worker child) and reports no utilization: the
        CPU has no published peak."""
        proc = self._run('--quick')
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        assert result['metric'] == 'test-tiny train tokens/sec/chip'
        assert result['value'] > 0
        assert result['mfu'] is None and result['vs_baseline'] is None
        assert 'preflight' not in proc.stderr
        assert 'MFU=not measured' in proc.stderr


class TestFleetDryrunDispatch:

    @pytest.mark.parametrize('flag', [
        '--dryrun-serve-sharded', '--dryrun-serve-fleet',
        '--dryrun-serve-disagg', '--dryrun-serve-multitenant',
        '--dryrun-serve-kernel', '--dryrun-trace',
        '--dryrun-train-zero1', '--dryrun-train-elastic'])
    def test_dryrun_rows_run_in_the_cpu_child(self, monkeypatch, flag):
        """A --dryrun-* row is a CPU row: main() hands it to the
        fake-device child and never runs it in this process, which on
        a machine with a chip would take the chip for a row that does
        not use it."""
        bench = _load_bench()
        calls = {}

        def fake_dryrun(argv):
            calls['dry'] = argv
            return 0

        monkeypatch.setattr(bench, '_supervise_dryrun', fake_dryrun)
        monkeypatch.setattr(
            bench, '_worker',
            lambda args: (_ for _ in ()).throw(
                AssertionError('row ran in the parent process')))
        monkeypatch.setattr(sys, 'argv', ['bench.py', flag])
        assert bench.main() == 0
        assert calls['dry'] == [flag]

    def test_dryrun_serve_multitenant_skip_on_unconstructable_engine(
            self, monkeypatch, capsys):
        """An engine combination the constructor rejects emits the
        structured {"skipped": true} line with the combo and rc=3."""
        bench = _load_bench()
        from skypilot_tpu.models import inference as inference_lib

        def boom(*_a, **_kw):
            raise ValueError('max_adapters requires adapter_rank')

        monkeypatch.setattr(inference_lib, 'ContinuousBatchingEngine',
                            boom)
        rc = bench._dryrun_serve_multitenant(
            bench._parse_args(['--dryrun-serve-multitenant',
                               '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert 'adapter_rank' in row['reason']
        assert row['combo']['max_adapters'] == 3

    def test_dryrun_serve_disagg_skip_on_unconstructable_engine(
            self, monkeypatch, capsys):
        """An engine combination the constructor rejects is a
        deterministic verdict: the worker emits the structured
        {"skipped": true} line with the combo and rc=3."""
        bench = _load_bench()
        from skypilot_tpu.models import inference as inference_lib

        def boom(*_a, **_kw):
            raise ValueError('paged_block_size does not divide')

        monkeypatch.setattr(inference_lib, 'ContinuousBatchingEngine',
                            boom)
        rc = bench._dryrun_serve_disagg(
            bench._parse_args(['--dryrun-serve-disagg', '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert 'paged_block_size' in row['reason']
        assert row['combo'] == {'paged_block_size': 8,
                                'prefix_cache': 8}

    def test_dryrun_serve_kernel_skip_on_unconstructable_engine(
            self, monkeypatch, capsys):
        """An engine combination the constructor rejects (e.g. the
        pallas knob on a config the kernel gates out) is a
        deterministic verdict: the structured {"skipped": true} line
        with the combo and rc=3."""
        bench = _load_bench()
        from skypilot_tpu.models import inference as inference_lib

        def boom(*_a, **_kw):
            raise NotImplementedError(
                "decode_kernel='pallas' requires a paged KV pool")

        monkeypatch.setattr(inference_lib, 'ContinuousBatchingEngine',
                            boom)
        rc = bench._dryrun_serve_kernel(
            bench._parse_args(['--dryrun-serve-kernel', '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert 'serve-kernel' in row['reason']
        assert row['combo'] == {'decode_kernel': 'pallas_interpret',
                                'paged_block_size': 8}

    def test_dryrun_trace_skip_on_unconstructable_engine(
            self, monkeypatch, capsys):
        """An engine combination the constructor rejects emits the
        structured {"skipped": true} line with the combo and rc=3."""
        bench = _load_bench()
        from skypilot_tpu.models import inference as inference_lib

        def boom(*_a, **_kw):
            raise ValueError('paged_block_size does not divide')

        monkeypatch.setattr(inference_lib, 'ContinuousBatchingEngine',
                            boom)
        rc = bench._dryrun_trace(
            bench._parse_args(['--dryrun-trace', '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert 'trace-dryrun' in row['reason']
        assert row['combo'] == {'paged_block_size': 8,
                                'prefix_cache': 6}

    def test_dryrun_train_elastic_skip_on_too_few_devices(
            self, monkeypatch, capsys):
        """An incompatible device count is a deterministic verdict: the
        worker emits the structured {"skipped": true} line and rc=3
        (the dryrun supervisor forwards it verbatim)."""
        bench = _load_bench()
        monkeypatch.setitem(
            sys.modules, '__graft_entry__',
            type(sys)('__graft_entry__'))
        sys.modules['__graft_entry__']._force_cpu_devices = \
            lambda n: None

        class _FakeJax:
            @staticmethod
            def devices():
                return [object()] * 2  # fewer than the 8 the row needs

        monkeypatch.setitem(sys.modules, 'jax', _FakeJax())
        rc = bench._dryrun_train_elastic(
            bench._parse_args(['--dryrun-train-elastic', '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert row['combo'] == {'canonical_dp': 4, 'n_devices': 2}

    def test_dryrun_train_zero1_skip_on_too_few_devices(
            self, monkeypatch, capsys):
        """An incompatible device count is a deterministic verdict: the
        worker emits the structured {"skipped": true} line and rc=3
        (the dryrun supervisor forwards it verbatim)."""
        bench = _load_bench()
        monkeypatch.setitem(
            sys.modules, '__graft_entry__',
            type(sys)('__graft_entry__'))
        sys.modules['__graft_entry__']._force_cpu_devices = \
            lambda n: None

        class _FakeJax:
            @staticmethod
            def devices():
                return [object()] * 2  # fewer than the dp=8 the row needs

        monkeypatch.setitem(sys.modules, 'jax', _FakeJax())
        rc = bench._dryrun_train_zero1(
            bench._parse_args(['--dryrun-train-zero1', '--worker']))
        out = capsys.readouterr().out.strip().splitlines()[-1]
        row = json.loads(out)
        assert rc == 3
        assert row['skipped'] is True
        assert row['combo'] == {'dp': 8, 'n_devices': 2}
