"""Bring-up contract, checked without a chip: what must FAIL does fail,
quickly and with its cause, and the pieces chip_smoke.py leans on (the
compile-cache helper, the peak table, the engine's start-up failure
path, the compiled-HLO kernel-operand probe) do what it assumes.

The passing side of chip_smoke.py needs the TPU and is run through the
chip tool (README "On the chip"); `python chip_smoke.py --rehearse` is
its CPU rehearsal.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, 'chip_smoke.py')


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get('ok') is True:
                return True
        except (ValueError, AttributeError):
            continue
    return False


class TestChipSmokeRefusals:

    def test_no_tpu_exits_nonzero_naming_the_reason(self):
        """On a machine where jax finds no accelerator the smoke fails
        at its first child, in seconds, says which platform it saw,
        and prints no result line."""
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        t0 = time.monotonic()
        proc = subprocess.run([sys.executable, _SMOKE],
                              capture_output=True, text=True, timeout=120,
                              env=env, check=False)
        assert proc.returncode != 0
        assert "saw platform 'cpu', need 'tpu'" in proc.stdout
        assert 'phase kernels: FAILED' in proc.stdout
        # It stops there: every later child would fail the same way.
        assert 'phase train' not in proc.stdout
        assert not _has_result_line(proc.stdout)
        assert time.monotonic() - t0 < 60

    def test_alone_in_a_directory_exits_nonzero(self, tmp_path):
        """A directory that holds chip_smoke.py and nothing else of the
        repository: fails before starting anything."""
        shutil.copy(_SMOKE, tmp_path / 'chip_smoke.py')
        proc = subprocess.run(
            [sys.executable, str(tmp_path / 'chip_smoke.py')],
            capture_output=True, text=True, timeout=60, cwd=tmp_path,
            check=False)
        assert proc.returncode != 0
        assert 'no skypilot_tpu/ next to' in proc.stdout
        assert not _has_result_line(proc.stdout)

    def test_parent_never_imports_jax(self):
        """One process for each chip: the parent only starts children.
        Importing the script (what `--child` does not do) must leave
        jax unimported, and so must `import skypilot_tpu`."""
        code = ('import sys, importlib.util\n'
                f'spec = importlib.util.spec_from_file_location('
                f'"chip_smoke", {_SMOKE!r})\n'
                'mod = importlib.util.module_from_spec(spec)\n'
                'spec.loader.exec_module(mod)\n'
                'import skypilot_tpu\n'
                'assert "jax" not in sys.modules, "jax was imported"\n')
        proc = subprocess.run([sys.executable, '-c', code],
                              capture_output=True, text=True, timeout=60,
                              cwd=_REPO, check=False)
        assert proc.returncode == 0, proc.stderr[-2000:]


class TestCompileCacheHelper:
    """parallel/distributed.enable_compile_cache: the one place that
    sets the directory. Each case is its own process (jax's config is
    process-wide)."""

    _PROBE = (
        'import json, jax\n'
        'from skypilot_tpu.parallel import distributed\n'
        'before = jax.config.jax_persistent_cache_min_compile_time_secs\n'
        'got = distributed.enable_compile_cache()\n'
        'print(json.dumps({"returned": got,\n'
        '  "dir": jax.config.jax_compilation_cache_dir,\n'
        '  "min_secs_before": before,\n'
        '  "min_secs": '
        'jax.config.jax_persistent_cache_min_compile_time_secs}))\n')

    def _probe(self, cwd, **env_changes):
        env = dict(os.environ,
                   PYTHONPATH=_REPO + os.pathsep +
                   os.environ.get('PYTHONPATH', ''))
        for key in ('JAX_PLATFORMS', 'JAX_COMPILATION_CACHE_DIR'):
            env.pop(key, None)
        env.update(env_changes)
        proc = subprocess.run([sys.executable, '-c', self._PROBE],
                              capture_output=True, text=True, timeout=120,
                              cwd=cwd, env=env, check=False)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_variable_set_means_hands_off(self, tmp_path):
        """jax reads JAX_COMPILATION_CACHE_DIR itself; the helper then
        changes nothing — not the directory, not the thresholds."""
        out = self._probe(tmp_path,
                          JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'c'))
        assert out['returned'] is None
        assert out['dir'] == str(tmp_path / 'c')     # jax's own reading
        assert out['min_secs'] == out['min_secs_before']

    def test_same_in_checkout_path_in_two_processes(self, tmp_path):
        """No variable: one fixed directory inside the checkout,
        whatever the working directory and the process."""
        a = self._probe(tmp_path)
        b = self._probe(_REPO)
        assert a['returned'] == a['dir'] == b['returned'] == b['dir']
        assert a['dir'] == os.path.join(_REPO, '.jax_cache')
        assert a['min_secs'] == 0.0      # small serving programs too
        with open(os.path.join(_REPO, '.gitignore'),
                  encoding='utf-8') as f:
            assert '.jax_cache/' in f.read().split()

    def test_process_held_to_the_cpu_is_left_alone(self, tmp_path):
        out = self._probe(tmp_path, JAX_PLATFORMS='cpu')
        assert out['returned'] is None and out['dir'] is None


class TestEngineThatCannotStart:

    def test_tp2_cache_init_failure_fails_generate_at_once(self):
        """A tp engine whose cache cannot be built (ROADMAP D0's shape:
        the failure used to kill the engine thread outside its handler
        and every request waited out its own 300 s timeout) fails the
        queued request in well under 5 s with THAT exception, fails
        the next one the same way, and serves again once the cause is
        gone."""
        from skypilot_tpu.models.inference import ContinuousBatchingEngine
        from skypilot_tpu.parallel.mesh import decode_mesh
        engine = ContinuousBatchingEngine('test-tiny', mesh=decode_mesh(2))

        def refuse():
            raise TypeError('cannot place the cache on this mesh')

        engine._init_cache_for_mode = refuse  # pylint: disable=protected-access
        try:
            for _ in range(2):
                t0 = time.monotonic()
                with pytest.raises(TypeError, match='cannot place'):
                    engine.generate([1, 2, 3], max_new_tokens=4,
                                    timeout=60)
                assert time.monotonic() - t0 < 5.0
            del engine._init_cache_for_mode
            tokens, _ = engine.generate([1, 2, 3], max_new_tokens=4,
                                        timeout=120)
            assert len(tokens) == 4
        finally:
            engine.stop()


class TestKernelOperandProbe:

    def test_custom_call_operands_reads_nested_layouts(self):
        """hlo_probe.custom_call_operands: the shapes a Pallas TPU
        kernel was handed, from `operand_layout_constraints` (whose
        value nests braces). Recorded from the compiled fsdp=4 train
        step of mistral-7b: one device's flash call sees batch/4 * 32
        heads = 32 rows, not the gathered 128."""
        from skypilot_tpu.parallel import hlo_probe
        hlo = (
            '%fwd = (bf16[32,2048,128]{2,1,0:T(8,128)(2,1)}, '
            'f32[32,1,2048]{2,1,0:T(1,128)}) custom-call(%a, %b, %c), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[32,2048,128]{2,1,0}, '
            'bf16[32,2048,128]{2,1,0}, bf16[32,2048,128]{2,1,0}}, '
            'frontend_attributes={kernel_metadata={}}\n'
            '%ar = f32[8]{0} all-reduce(%x), replica_groups={}\n'
            '%other = f32[4]{0} custom-call(%y), '
            'custom_call_target="Sharding"\n')
        assert hlo_probe.custom_call_operands(hlo) == [
            ['bf16[32,2048,128]'] * 3]
        assert hlo_probe.custom_call_operands('') == []
