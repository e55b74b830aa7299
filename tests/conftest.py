"""Test harness.

- Simulates an 8-device TPU-shaped mesh on CPU via
  ``--xla_force_host_platform_device_count`` (the reference has no way to
  test multi-node without real clouds — SURVEY §4.5; we close that gap).
- Isolates all on-disk state (~/.skytpu) per test.
- Stubs the enabled-cloud list so optimizer dryruns never touch credentials
  (the reference's monkeypatch trick, tests/common.py:11).
"""
import os

# Must be set before jax backends initialize: tests are hermetic and run
# on the CPU even on a machine that holds a chip (`chip_smoke.py` is what
# runs on the chip). The variable is enough; nothing here reaches into
# jax's backend registry.
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'slow: full end-to-end loops on the fake cloud')
    config.addinivalue_line(
        'markers', 'chaos: fault-injection resilience tests '
        '(deterministic, tier-1 — NOT slow)')
    config.addinivalue_line(
        'markers', 'deadline(seconds): hard per-test wall-clock bound '
        'enforced with SIGALRM — a wedged e2e test FAILS with a '
        'TimeoutError (and its children get reaped) instead of hanging '
        'the suite until the outer kill loses every result')
    config.addinivalue_line(
        'markers', 'sharded: tensor-parallel serving tests (tier-1). '
        'Their jax work runs in a SUBPROCESS on 8 fake CPU devices '
        '(the sharded_subprocess fixture) so the main pytest process '
        'keeps its single-device jit caches; pair with '
        '@pytest.mark.deadline(N) from the PR-6 SIGALRM fixture')


@pytest.fixture(autouse=True)
def _test_deadline(request):
    """Per-test deadline for tests carrying @pytest.mark.deadline(N).

    The fake-cloud e2e loops (serve up/probe/down, benchmark runs)
    block in subprocess waits and HTTP polls; under full-suite load a
    wedged child used to stall the whole run. SIGALRM interrupts any
    blocking syscall on the main thread, turning the stall into an
    ordinary test failure — the _isolate_state teardown then reaps the
    test's orphaned processes."""
    import signal
    import threading
    marker = request.node.get_closest_marker('deadline')
    if marker is None or not hasattr(signal, 'SIGALRM') or \
            threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = float(marker.args[0])

    def _expired(signum, frame):  # pylint: disable=unused-argument
        raise TimeoutError(
            f'{request.node.nodeid} exceeded its {seconds:.0f}s '
            f'deadline (fake-cloud e2e wedge?); failing fast instead '
            f'of hanging the suite')

    old_handler = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(autouse=True)
def _isolate_state(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_STATE_DB', str(tmp_path / 'state.db'))
    monkeypatch.setenv('SKYTPU_USER_HASH', 'testhash')
    monkeypatch.setenv('SKYTPU_CONFIG', str(tmp_path / 'config.yaml'))
    monkeypatch.setenv('SKYTPU_HOME', str(tmp_path / 'skytpu_home'))
    monkeypatch.setenv('SKYTPU_FAKE_CLOUD_STATE',
                       str(tmp_path / 'fake_cloud.json'))
    # Reset the global-state singleton so each test gets its own db.
    import skypilot_tpu.global_user_state as gus
    gus._db = None  # pylint: disable=protected-access
    yield
    # A chaos test that failed mid-flight must not leave faults armed
    # for every later test (and must not leave threads wedged on them).
    from skypilot_tpu.utils import fault_injection
    fault_injection.disarm_all()
    _reap_test_processes(str(tmp_path))


def _reap_test_processes(marker: str) -> None:
    """Kill any process whose environment carries this test's isolated
    state dir. A serve/jobs e2e that fails mid-flight can leave its
    `serve down` teardown half-run (observed under full-suite load:
    orphaned replica `http.server`s squatting on ports, cascading
    'Address already in use' into every later serve test). The tmp_path
    is unique per test, so matching SKYTPU_HOME/... in /proc environs
    reaps exactly this test's children."""
    import signal
    if not os.path.isdir('/proc'):   # non-Linux dev host: nothing to reap
        return
    me = os.getpid()
    for pid_dir in os.listdir('/proc'):
        if not pid_dir.isdigit() or int(pid_dir) == me:
            continue
        try:
            with open(f'/proc/{pid_dir}/environ', 'rb') as f:
                environ = f.read().decode(errors='replace')
        except OSError:
            continue
        if marker in environ:
            try:
                os.kill(int(pid_dir), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass


@pytest.fixture(scope='session')
def sharded_subprocess():
    """Runner for @pytest.mark.sharded tests: execute a python script
    in a SUBPROCESS with the 8-fake-CPU-device XLA_FLAGS, so the
    sharded SPMD compiles never touch this process's single-device jit
    caches. Returns (CompletedProcess, last-JSON-line-or-None)."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(script_path, *argv, timeout=600):
        env = dict(os.environ)
        env['JAX_PLATFORMS'] = 'cpu'
        # APPEND (don't clobber) so ambient XLA settings — determinism
        # or memory flags a CI sets suite-wide — hold in the child too,
        # keeping its engines comparable to this process's baselines.
        flags = env.get('XLA_FLAGS', '')
        if '--xla_force_host_platform_device_count' not in flags:
            env['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=8'
            ).strip()
        env['PYTHONPATH'] = repo + os.pathsep + env.get('PYTHONPATH', '')
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, script_path),
             *[str(a) for a in argv]],
            capture_output=True, text=True, timeout=timeout, env=env,
            check=False)
        parsed = None
        for line in reversed(proc.stdout.splitlines()):
            try:
                candidate = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            # Only a dict counts as the driver's result row: a stray
            # trailing scalar ('0', 'null') must not shadow it.
            if isinstance(candidate, dict):
                parsed = candidate
                break
        return proc, parsed

    return run


@pytest.fixture
def enable_clouds():
    """Mark gcp+kubernetes as enabled without touching credentials."""
    from skypilot_tpu import global_user_state
    global_user_state.set_enabled_clouds(['gcp', 'kubernetes'])
    yield ['gcp', 'kubernetes']
