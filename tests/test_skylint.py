"""skylint (tier-1, CPU, no engine compiles): the AST-based analyzer
behind `skytpu lint` — docs/static-analysis.md has the catalog.

- fixture trees: each checker catches a seeded violation grep could
  not express (hot-path device_get through a call chain, a lock-free
  mutation of lock-guarded state, a wall delta, an aliased
  PartitionSpec, drifted catalogs) and stays quiet on the matching
  known-good twin;
- waivers: honored, expired-resurfaces, unmatched-resurfaces,
  malformed-file → LintError;
- the CLI contract: exit codes 0/1/2 and the stable skylint/1 --json
  row (one JSON object on one line);
- the tier-1 pin: the REAL tree holds zero unwaived findings in
  bounded time — the debt this analyzer surfaced is fixed or waived,
  and stays that way.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from skypilot_tpu import analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_tree(tmp_path, files):
    """Write a fixture package `pkg/` (plus optional `docs/`, `tests/`
    siblings for the drift checkers) and return its root."""
    root = tmp_path / 'pkg'
    for rel, content in files.items():
        path = (tmp_path / rel) if rel.split('/')[0] in (
            'docs', 'tests') else (root / rel)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content), encoding='utf-8')
    root.mkdir(exist_ok=True)
    return str(root)


def lint(root, select):
    return analysis.run_lint(root=root, select=[select])


# ---------------------------------------------------------------------
# hot-path-host-sync
# ---------------------------------------------------------------------


HOT_BAD = {
    'models/inference.py': '''
        import jax
        import jax.numpy as jnp
        import numpy as np

        from pkg.util import helper


        def _upload(value):
            return jnp.asarray(value)


        class ContinuousBatchingEngine:

            def _tick(self, gen):
                feed = _upload([1, 2])       # funnel: allowed
                out = self._dispatch(feed)
                self._emit(out)

            def _dispatch(self, feed):
                return helper(feed)

            def _emit(self, out):
                cols = np.asarray(out)        # BAD: raw landing
                total = jnp.sum(cols)
                return float(total)           # BAD: float(device)
    ''',
    'util.py': '''
        import jax


        def helper(feed):
            return jax.device_get(feed)       # BAD: two modules deep
    ''',
    'cold.py': '''
        import jax


        def offline_eval(x):
            # Not reachable from a hot root: never flagged.
            return jax.device_get(x)
    ''',
}


class TestHotPathHostSync:

    def test_catches_seeded_syncs_through_the_call_graph(self, tmp_path):
        result = lint(make_tree(tmp_path, HOT_BAD),
                      'hot-path-host-sync')
        msgs = [str(f) for f in result.unwaived]
        # device_get two modules away from _tick — the violation no
        # grep over inference.py could see.
        assert any('util.py' in m and 'device_get' in m
                   for m in msgs), msgs
        assert any('np.asarray' in m or 'numpy.asarray' in m
                   for m in msgs), msgs
        assert any('float() on a device value' in m for m in msgs), msgs
        # The cold path stays quiet even though it textually matches.
        assert not any('cold.py' in m for m in msgs), msgs

    def test_funnels_and_async_copy_are_allowed(self, tmp_path):
        good = {
            'models/inference.py': '''
                import jax.numpy as jnp
                import numpy as np


                def _upload(value):
                    return jnp.asarray(value)


                def _land(value):
                    return np.asarray(value)


                class ContinuousBatchingEngine:

                    def _tick(self, gen):
                        feed = _upload([1, 2])
                        out = self._step(feed)
                        out.copy_to_host_async()
                        cols = _land(out)
                        return int(cols[0])

                    def _step(self, feed):
                        return feed
            ''',
        }
        result = lint(make_tree(tmp_path, good), 'hot-path-host-sync')
        assert not result.unwaived, [str(f) for f in result.unwaived]

    def test_pallas_launch_is_device_dispatch_not_host_sync(
            self, tmp_path):
        """A `pl.pallas_call` on the hot path (the fused decode
        kernel) must NOT be flagged — the launch is as async as any
        jax op (ALLOWED_DEVICE_DISPATCH) — while its result stays
        device-tainted: float()ing it without _land is still a
        finding."""
        tree = {
            'models/inference.py': '''
                import jax.numpy as jnp
                import numpy as np
                from jax.experimental import pallas as pl


                def _upload(value):
                    return jnp.asarray(value)


                def _land(value):
                    return np.asarray(value)


                def _kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...] * 2


                class ContinuousBatchingEngine:

                    def _tick(self, gen):
                        feed = _upload([1, 2])
                        out = pl.pallas_call(
                            _kernel,
                            out_shape=feed)(feed)   # launch: allowed
                        return float(out)           # BAD: device value
            ''',
        }
        result = lint(make_tree(tmp_path, tree), 'hot-path-host-sync')
        msgs = [str(f) for f in result.unwaived]
        assert not any('pallas_call' in m for m in msgs), msgs
        assert any('float() on a device value' in m for m in msgs), msgs

    def test_relative_imports_are_followed(self, tmp_path):
        """`from . import sibling` inside a package __init__ resolves
        against the package itself (not its parent) — a device_get
        behind such an import must still be reached."""
        bad = {
            'serve/__init__.py': '''
                from . import helpers


                def make_train_step(cfg):
                    def step(s, b):
                        return helpers.pull(s)
                    return step
            ''',
            'serve/helpers.py': '''
                import jax


                def pull(x):
                    return jax.device_get(x)
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'hot-path-host-sync')
        assert any('device_get' in f.message and 'helpers.py' in f.path
                   for f in result.unwaived), [
                       str(f) for f in result.findings]

    def test_train_step_factory_is_a_root(self, tmp_path):
        bad = {
            'train/trainer.py': '''
                import jax


                def make_train_step(cfg):
                    def step(state, batch):
                        loss = state + batch
                        return state, float(jax.device_get(loss))
                    return step
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'hot-path-host-sync')
        assert any('device_get' in f.message for f in result.unwaived)


# ---------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------


LOCK_BAD = {
    'engine.py': '''
        import threading


        class Engine:

            def __init__(self):
                self._lock = threading.Lock()
                self._slots = []
                self._gen = 0

            def recover(self):
                with self._lock:
                    self._gen += 1
                    self._slots = []

            def sneak(self):
                self._slots = [None]          # BAD: no lock

            def locked_helper(self):
                self._gen += 1                # ok: only called locked

            def bump(self):
                with self._lock:
                    self.locked_helper()
    ''',
}


class TestLockDiscipline:

    def test_catches_lock_free_mutation(self, tmp_path):
        result = lint(make_tree(tmp_path, LOCK_BAD), 'lock-discipline')
        msgs = [f.message for f in result.unwaived]
        assert any('sneak' in m and '_slots' in m for m in msgs), msgs
        # The helper whose every call site holds the lock is NOT
        # flagged — the inference grep can't do.
        assert not any('locked_helper' in m for m in msgs), msgs

    def test_two_different_locks_is_inconsistent_guarding(self,
                                                          tmp_path):
        """An attr mutated under lock A in one method and lock B in
        another is the lost-update race itself — neither writer
        excludes the other — and must be flagged even though every
        site holds *a* lock."""
        bad = {
            'engine.py': '''
                import threading


                class Engine:

                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()
                        self.count = 0

                    def inc_a(self):
                        with self._a:
                            self.count += 1

                    def inc_b(self):
                        with self._b:
                            self.count += 1
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'lock-discipline')
        assert len(result.unwaived) == 1, [
            str(f) for f in result.findings]
        assert 'DIFFERENT locks' in result.unwaived[0].message

    def test_clean_class_quiet(self, tmp_path):
        good = {
            'engine.py': '''
                import threading


                class Engine:

                    def __init__(self):
                        self._lock = threading.Lock()
                        self._state = {}

                    def put(self, k, v):
                        with self._lock:
                            self._state[k] = v

                    def read(self):
                        return dict(self._state)
            ''',
        }
        result = lint(make_tree(tmp_path, good), 'lock-discipline')
        assert not result.unwaived, [str(f) for f in result.unwaived]


# ---------------------------------------------------------------------
# wall-clock-duration
# ---------------------------------------------------------------------


class TestWallClockDuration:

    def test_catches_wall_delta_and_alias(self, tmp_path):
        bad = {
            'timing.py': '''
                import time as time_lib


                def elapsed():
                    t0 = time_lib.time()
                    work()
                    return time_lib.time() - t0
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'wall-clock-duration')
        assert len(result.unwaived) == 1
        assert 'time.monotonic' in result.unwaived[0].message

    def test_taint_flows_through_deadline_alias(self, tmp_path):
        """`t0 = time.time(); deadline = t0 + 5; deadline -
        time.time()` — the wall taint follows the Add through the
        named intermediate (the replica_managers pattern this PR
        fixed)."""
        bad = {
            'timing.py': '''
                import time


                def remaining():
                    t0 = time.time()
                    deadline = t0 + 5.0
                    return deadline - time.time()
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'wall-clock-duration')
        assert len(result.unwaived) == 1, [
            str(f) for f in result.findings]

    def test_monotonic_and_epoch_compares_are_fine(self, tmp_path):
        good = {
            'timing.py': '''
                import os
                import time


                def ok(deadline):
                    t0 = time.monotonic()
                    work()
                    elapsed = time.monotonic() - t0
                    expired = time.time() > deadline
                    age = time.time() - os.path.getmtime('/etc/hosts')
                    return elapsed, expired, age
            ''',
        }
        result = lint(make_tree(tmp_path, good), 'wall-clock-duration')
        assert not result.unwaived, [str(f) for f in result.unwaived]


# ---------------------------------------------------------------------
# sharding-containment
# ---------------------------------------------------------------------


class TestShardingContainment:

    def test_catches_aliased_pspec_and_collective(self, tmp_path):
        bad = {
            'parallel/sharding.py': 'LOGICAL_AXIS_RULES = ()\n',
            'model.py': '''
                from jax.sharding import PartitionSpec

                P = PartitionSpec                    # alias rebinding

                SPEC = P(None, 'tp')                 # BAD
                REPL = PartitionSpec()               # fine: replication
            ''',
            'ops.py': '''
                from jax import lax


                def reduce(x):
                    # An apostrophe in a comment doesn't fool the AST:
                    # it's fine.
                    return lax.psum(x, axis_name='tp')   # BAD
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'sharding-containment')
        msgs = [f.message for f in result.unwaived]
        assert any('PartitionSpec' in m and 'model.py' in str(f)
                   for f, m in zip(result.unwaived, msgs)), msgs
        assert any('psum' in m for m in msgs), msgs
        assert len(result.unwaived) == 2, msgs

    def test_duplicate_rule_table_flagged(self, tmp_path):
        bad = {
            'parallel/sharding.py': 'LOGICAL_AXIS_RULES = ()\n',
            'train/rules.py': 'LOGICAL_AXIS_RULES = ()\n',
        }
        result = lint(make_tree(tmp_path, bad), 'sharding-containment')
        assert any('rules.py' in f.path and 'LOGICAL_AXIS_RULES'
                   in f.message for f in result.unwaived)

    def test_containment_dir_itself_is_free(self, tmp_path):
        good = {
            'parallel/sharding.py': '''
                from jax.sharding import PartitionSpec

                LOGICAL_AXIS_RULES = (('heads', 'tp'),)

                SPEC = PartitionSpec('tp')
            ''',
        }
        result = lint(make_tree(tmp_path, good), 'sharding-containment')
        assert not result.unwaived, [str(f) for f in result.unwaived]


# ---------------------------------------------------------------------
# drift checkers
# ---------------------------------------------------------------------


class TestDriftCheckers:

    def test_injection_drift_both_directions(self, tmp_path):
        bad = {
            'utils/fault_injection.py': '''
                KNOWN_POINTS = ('a.one', 'b.dead')


                def point(name):
                    pass
            ''',
            'worker.py': '''
                from pkg.utils import fault_injection


                def run():
                    fault_injection.point('a.one')
                    fault_injection.point('c.undeclared')
            ''',
            'docs/resilience.md': 'Points: `a.one`, `b.dead`.\n',
            'tests/test_x.py': "POINTS = ['a.one', 'b.dead']\n",
        }
        result = lint(make_tree(tmp_path, bad), 'injection-drift')
        msgs = [f.message for f in result.unwaived]
        assert any("'c.undeclared'" in m and 'undeclared' in m
                   for m in msgs), msgs
        assert any("'b.dead'" in m and 'no call site' in m
                   for m in msgs), msgs

    def test_non_literal_known_points_is_a_finding(self, tmp_path):
        """Refactoring KNOWN_POINTS into concatenated sub-tuples must
        not silently disable the whole checker — it surfaces as a
        finding instead."""
        bad = {
            'utils/fault_injection.py': '''
                _CORE = ('a.one',)
                KNOWN_POINTS = _CORE + ('b.two',)


                def point(name):
                    pass
            ''',
        }
        result = lint(make_tree(tmp_path, bad), 'injection-drift')
        assert len(result.unwaived) == 1
        assert 'not a pure literal' in result.unwaived[0].message

    def test_metrics_drift_both_directions(self, tmp_path):
        bad = {
            'obs.py': '''
                from pkg.metrics import counter

                C = counter('skytpu_undocumented_total', 'help')
            ''',
            'metrics.py': '''
                def counter(name, help_text):
                    return name
            ''',
            'docs/observability.md':
                '| `skytpu_phantom_total` | stale row |\n',
        }
        result = lint(make_tree(tmp_path, bad), 'metrics-drift')
        msgs = [f.message for f in result.unwaived]
        assert any('skytpu_undocumented_total' in m and 'missing from'
                   in m for m in msgs), msgs
        assert any('skytpu_phantom_total' in m and 'stale' in m
                   for m in msgs), msgs


# ---------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------


WAIVED_TREE = {
    'timing.py': '''
        import time


        def elapsed():
            t0 = time.time()
            return time.time() - t0
    ''',
}


class TestWaivers:

    def _tree_with_waiver(self, tmp_path, extra=''):
        files = dict(WAIVED_TREE)
        files['analysis/waivers.toml'] = f'''
            [[waiver]]
            checker = "wall-clock-duration"
            path = "pkg/timing.py"
            contains = "wall-clock duration"
            reason = "fixture: reviewed"
            {extra}
        '''
        return make_tree(tmp_path, files)

    def test_waiver_honored(self, tmp_path):
        root = self._tree_with_waiver(tmp_path)
        result = lint(root, 'wall-clock-duration')
        assert not result.unwaived
        assert len(result.waived) == 1
        assert result.waived[0].waiver_reason == 'fixture: reviewed'

    def test_expired_waiver_resurfaces(self, tmp_path):
        root = self._tree_with_waiver(tmp_path,
                                      'expires = "2001-01-01"')
        result = lint(root, 'wall-clock-duration')
        kinds = {f.checker for f in result.unwaived}
        # The finding is back AND the dead waiver is reported.
        assert 'wall-clock-duration' in kinds, result.findings
        assert 'waivers' in kinds, result.findings

    def test_unmatched_waiver_reported(self, tmp_path):
        files = {'clean.py': 'X = 1\n'}
        files['analysis/waivers.toml'] = '''
            [[waiver]]
            checker = "wall-clock-duration"
            path = "pkg/gone.py"
            reason = "the code this waived was deleted"
        '''
        result = lint(make_tree(tmp_path, files), 'wall-clock-duration')
        assert [f.checker for f in result.unwaived] == ['waivers']
        assert 'unmatched' in result.unwaived[0].message

    def test_malformed_waiver_is_internal_error(self, tmp_path):
        files = {'clean.py': 'X = 1\n'}
        files['analysis/waivers.toml'] = '''
            [[waiver]]
            checker = "wall-clock-duration"
        '''
        with pytest.raises(analysis.LintError, match='required'):
            lint(make_tree(tmp_path, files), 'wall-clock-duration')

    def test_unknown_select_is_internal_error(self):
        with pytest.raises(analysis.LintError, match='unknown checker'):
            analysis.run_lint(select=['nope'])


# ---------------------------------------------------------------------
# CLI contract: exit codes + stable --json schema
# ---------------------------------------------------------------------


def run_cli(args, cwd=None):
    return subprocess.run(
        [sys.executable, '-m', 'skypilot_tpu.cli', 'lint'] + args,
        capture_output=True, text=True, cwd=cwd or REPO_ROOT,
        timeout=180,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'})


class TestCliContract:

    def test_exit_0_and_json_schema_on_clean_tree(self, tmp_path):
        make_tree(tmp_path, {'clean.py': 'X = 1\n'})
        proc = run_cli(['--json', '--root', str(tmp_path / 'pkg')])
        assert proc.returncode == 0, proc.stderr
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row['schema'] == 'skylint/1'
        assert row['ok'] is True
        assert set(row['summary']) == {'total', 'unwaived', 'waived',
                                       'by_checker', 'duration_s'}
        assert row['findings'] == []
        assert set(row['selected']) == set(analysis.all_checker_ids())

    def test_exit_1_with_findings(self, tmp_path):
        root = make_tree(tmp_path, WAIVED_TREE)
        proc = run_cli(['--json', '--root', root,
                        '--select', 'wall-clock-duration'])
        assert proc.returncode == 1, (proc.stdout, proc.stderr)
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row['ok'] is False
        assert row['summary']['unwaived'] == 1
        f = row['findings'][0]
        assert set(f) == {'checker', 'path', 'line', 'message',
                          'waived', 'waiver_reason'}
        assert f['checker'] == 'wall-clock-duration'
        assert f['path'] == 'pkg/timing.py'

    def test_exit_2_on_internal_error(self, tmp_path):
        proc = run_cli(['--select', 'no-such-checker'])
        assert proc.returncode == 2, (proc.stdout, proc.stderr)
        proc = run_cli(['--json', '--select', 'no-such-checker'])
        assert proc.returncode == 2
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row['ok'] is False and 'error' in row


# ---------------------------------------------------------------------
# the tier-1 pin: the real tree is (and stays) clean
# ---------------------------------------------------------------------


class TestRealTreePin:

    def test_zero_unwaived_findings_over_skypilot_tpu(self):
        """THE pin: every checker over the real tree, zero unwaived
        findings — new host syncs on the tick path, lock-free
        mutations of guarded state, wall deltas, escaped axis
        literals, or catalog drift fail CI here. Debt goes through
        analysis/waivers.toml with a written reason, or gets fixed."""
        started = time.monotonic()
        result = analysis.run_lint()
        elapsed = time.monotonic() - started
        assert result.selected == analysis.all_checker_ids()
        assert len(result.selected) >= 5
        assert not result.unwaived, (
            'skylint found unwaived findings (fix them or waive with '
            'a written reason in analysis/waivers.toml):\n' +
            '\n'.join(str(f) for f in result.unwaived))
        # The acceptance bound is 30s for the CLI run; in-process we
        # leave headroom for a loaded CI box.
        assert elapsed < 30, f'skylint took {elapsed:.1f}s'

    def test_analyzer_is_lint_clean_under_itself(self):
        """analysis/ is part of the tree the pin covers; assert it
        explicitly so a waiver for analysis/ itself can't slip in."""
        result = analysis.run_lint()
        assert not any(f.path.startswith('skypilot_tpu/analysis/')
                       for f in result.findings), [
                           str(f) for f in result.findings
                           if f.path.startswith('skypilot_tpu/analysis/')]

    def test_engine_waivers_still_match(self):
        """The engine's gen-guarded single-writer waivers are load-
        bearing: they must be matching real findings (not rotting),
        and every waived finding carries a reason."""
        result = analysis.run_lint()
        assert result.waived, 'expected the engine lock waivers to fire'
        assert all(f.waiver_reason for f in result.waived)
