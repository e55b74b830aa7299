"""Preemption-native elastic training (ISSUE-11 tentpole).

Three layers:

- in-process unit tests for the substrate (no SPMD compiles): the
  surviving-extent ladder, the preemption notice (SIGTERM wiring, the
  `train.notice` lost-in-delivery fault), the elastic.json sidecar +
  extent revalidation, and the checkpoint deadline/torn-write/pruning
  edges (the PR-6 artifact test matrix applied to train/checkpoints.py);
- one subprocess run of tests/elastic_driver.py on 8 fake CPU devices
  (the sharded_subprocess fixture) covering the 3-notice preemption
  storm with fault injection armed: resume at the surviving dp extent,
  grow-back, zero steps lost beyond the in-flight one, and loss
  BIT-PARITY across the dp=4→2→4 resize vs an unpreempted run;
- the managed-jobs ELASTIC strategy tests live in
  tests/test_managed_jobs.py (jobs domain).
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from skypilot_tpu.train import elastic
from skypilot_tpu.utils import fault_injection


class TestSurvivingExtent:

    def test_full_capacity_keeps_target(self):
        assert elastic.surviving_extent(4, 8) == 4
        assert elastic.surviving_extent(4, 4) == 4

    def test_degraded_capacity_picks_largest_divisor(self):
        assert elastic.surviving_extent(4, 3) == 2
        assert elastic.surviving_extent(4, 2) == 2
        assert elastic.surviving_extent(4, 1) == 1
        assert elastic.surviving_extent(6, 5) == 3
        assert elastic.surviving_extent(8, 7) == 4

    def test_no_devices_raises(self):
        with pytest.raises(ValueError):
            elastic.surviving_extent(4, 0)
        with pytest.raises(ValueError):
            elastic.surviving_extent(0, 4)


class TestPreemptionNotice:

    def test_deliver_and_clear(self):
        n = elastic.PreemptionNotice()
        assert not n.pending()
        n.deliver()
        assert n.pending()
        n.clear()
        assert not n.pending()

    def test_sigterm_sets_the_flag(self):
        n = elastic.PreemptionNotice()
        prev = signal.getsignal(signal.SIGTERM)
        try:
            n.install_sigterm()
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5.0
            while not n.pending() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert n.pending()
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_lost_notice_fault(self):
        """`train.notice` armed: the notice never reaches the trainer
        (the kill lands with no final checkpoint — the storm driver
        exercises the end-to-end consequence)."""
        n = elastic.PreemptionNotice()
        fault_injection.arm('train.notice', 'fail:1')
        with pytest.raises(fault_injection.InjectedFault):
            n.deliver()
        assert not n.pending()
        n.deliver()  # fail:1 exhausted — the next notice lands
        assert n.pending()

    def test_sigterm_swallows_lost_notice(self):
        """A signal handler must not raise: an armed notice fault makes
        the SIGTERM delivery silently lost, not a crash."""
        n = elastic.PreemptionNotice()
        prev = signal.getsignal(signal.SIGTERM)
        try:
            n.install_sigterm()
            fault_injection.arm('train.notice', 'fail')
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.05)
            assert not n.pending()
        finally:
            fault_injection.disarm_all()
            signal.signal(signal.SIGTERM, prev)


class TestElasticMeta:

    def test_sidecar_roundtrip_is_atomic(self, tmp_path):
        meta = elastic.ElasticMeta(canonical_dp=4, dp=2,
                                   lineage=[{'step': 3}])
        meta.save(str(tmp_path))
        assert not os.path.exists(
            elastic.ElasticMeta.path(str(tmp_path)) + '.tmp')
        loaded = elastic.ElasticMeta.load(str(tmp_path))
        assert loaded == meta

    def test_missing_or_garbage_sidecar_loads_none(self, tmp_path):
        assert elastic.ElasticMeta.load(str(tmp_path)) is None
        with open(elastic.ElasticMeta.path(str(tmp_path)), 'w',
                  encoding='utf-8') as f:
            f.write('not-json')
        assert elastic.ElasticMeta.load(str(tmp_path)) is None

    def test_revalidate_first_launch_writes_sidecar(self, tmp_path):
        meta = elastic.revalidate_extent(str(tmp_path), 4, 4, 0)
        assert meta.canonical_dp == 4 and meta.dp == 4
        assert meta.lineage == []
        assert elastic.ElasticMeta.load(str(tmp_path)) == meta

    def test_revalidate_records_resizes_both_directions(self, tmp_path):
        elastic.revalidate_extent(str(tmp_path), 4, 4, 0)
        down = elastic.revalidate_extent(str(tmp_path), 4, 2, 3)
        assert down.dp == 2
        assert down.lineage[-1]['from_dp'] == 4
        assert down.lineage[-1]['to_dp'] == 2
        up = elastic.revalidate_extent(str(tmp_path), 4, 4, 7)
        assert up.dp == 4
        assert [(e['from_dp'], e['to_dp']) for e in up.lineage] == \
            [(4, 2), (2, 4)]

    def test_canonical_extent_is_fixed_for_the_run(self, tmp_path):
        """Resizing the CANONICAL extent mid-run would silently void
        the bit-parity contract — refuse, pointing at the sidecar."""
        elastic.revalidate_extent(str(tmp_path), 4, 4, 0)
        with pytest.raises(ValueError, match='canonical extent'):
            elastic.revalidate_extent(str(tmp_path), 8, 8, 5)


def _np_state(scale=1.0, n=4):
    return {'w': np.full((n,), scale, np.float32),
            'b': np.arange(n, dtype=np.float32) * scale}


class TestCheckpointEdges:
    """The PR-6 artifact rules applied to train/checkpoints.py: torn
    writes never publish, keep-newest-N pruning keeps fallbacks, and a
    corrupt newest falls back older. Plain-numpy states keep these
    in-process (no SPMD compiles)."""

    def _manager(self, tmp_path, **kw):
        from skypilot_tpu.train.checkpoints import CheckpointManager
        kw.setdefault('save_interval_steps', 1)
        return CheckpointManager(str(tmp_path / 'ck'), **kw)

    def test_save_fault_injection_point(self, tmp_path):
        manager = self._manager(tmp_path)
        try:
            fault_injection.arm('train.save', 'fail:1')
            with pytest.raises(fault_injection.InjectedFault):
                manager.save(1, _np_state())
            # fail:1 exhausted — the mount came back; training goes on.
            assert manager.save(1, _np_state())
            manager.wait()
            assert manager.latest_step() == 1
        finally:
            fault_injection.disarm_all()
            manager.close()

    def test_deadline_save_commits_within_generous_budget(self, tmp_path):
        manager = self._manager(tmp_path)
        try:
            assert manager.save_within_deadline(1, _np_state(), 60.0)
            assert manager.latest_step() == 1
        finally:
            manager.close()

    def test_deadline_save_gives_up_without_publishing(
            self, tmp_path, monkeypatch):
        """A commit slower than the notice budget returns False and
        publishes nothing newer — the previous checkpoint stays the
        resume point (deterministic via a stalled commit wait, not a
        slow disk)."""
        manager = self._manager(tmp_path)
        try:
            manager.save(1, _np_state())
            manager.wait()
            monkeypatch.setattr(manager._manager, 'wait_until_finished',
                                lambda: time.sleep(1.0))
            assert not manager.save_within_deadline(2, _np_state(2.0),
                                                    0.05)
            assert manager.latest_step() == 1
        finally:
            manager.close()

    def test_killed_mid_save_never_publishes_torn(self, tmp_path):
        """SIGKILL mid-save: write-to-temp + commit-marker means the
        torn attempt is invisible to latest_step() in a fresh process."""
        ck = str(tmp_path / 'ck')
        script = f'''
import os, signal, threading, numpy as np
os.environ['JAX_PLATFORMS'] = 'cpu'
from skypilot_tpu.train.checkpoints import CheckpointManager
m = CheckpointManager({ck!r}, save_interval_steps=1)
state = {{'w': np.random.rand(4 << 20).astype(np.float32)}}
m.save(7, state)
# Kill as soon as bytes start landing on disk — mid-save, pre-commit.
deadline = __import__('time').monotonic() + 30
while __import__('time').monotonic() < deadline:
    for root, _dirs, files in os.walk({ck!r}):
        if files:
            os.kill(os.getpid(), signal.SIGKILL)
os.kill(os.getpid(), signal.SIGKILL)
'''
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   PYTHONPATH=repo + os.pathsep +
                   os.environ.get('PYTHONPATH', ''))
        proc = subprocess.run([sys.executable, '-c', script], env=env,
                              capture_output=True, text=True, timeout=120,
                              check=False)
        assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
        manager = self._manager(tmp_path)
        try:
            assert manager.latest_step() is None
            state, step = manager.restore_latest_valid(_np_state())
            assert step == 0
            np.testing.assert_array_equal(state['w'], _np_state()['w'])
        finally:
            manager.close()

    def test_pruning_keeps_fallbacks_and_corrupt_newest_falls_back(
            self, tmp_path):
        """keep-newest-N leaves N committed steps on disk; corrupting
        the newest one falls back to the next older instead of erroring
        (and never 'falls back' past every valid step to a fresh 0)."""
        manager = self._manager(tmp_path, max_to_keep=2)
        try:
            for step in range(1, 5):
                manager.save(step, _np_state(float(step)))
            manager.wait()
            assert manager.all_steps() == [3, 4]

            # Corrupt the newest step's largest blob.
            newest_dir = os.path.join(manager.directory, '4')
            blobs = []
            for root, _dirs, files in os.walk(newest_dir):
                blobs += [os.path.join(root, f) for f in files]
            victim = max(blobs, key=os.path.getsize)
            with open(victim, 'r+b') as f:
                f.truncate(max(1, os.path.getsize(victim) // 2))

            restored, step = manager.restore_latest_valid(_np_state())
            assert step == 3
            np.testing.assert_array_equal(restored['w'],
                                          _np_state(3.0)['w'])
        finally:
            manager.close()

    def test_every_checkpoint_damaged_restarts_from_zero(self, tmp_path):
        manager = self._manager(tmp_path, max_to_keep=2)
        try:
            manager.save(1, _np_state())
            manager.wait()
            for root, _dirs, files in os.walk(manager.directory):
                for f in files:
                    p = os.path.join(root, f)
                    with open(p, 'r+b') as fh:
                        fh.truncate(0)
            template = _np_state(9.0)
            restored, step = manager.restore_latest_valid(template)
            assert step == 0
            assert restored is template
        finally:
            manager.close()


@pytest.mark.chaos
@pytest.mark.sharded
@pytest.mark.deadline(900)
class TestElasticStormDriver:
    """One subprocess run on 8 fake CPU devices; assertions read its
    JSON row (tests/elastic_driver.py documents the scenario)."""

    @pytest.fixture(scope='class')
    def row(self, sharded_subprocess):
        proc, row = sharded_subprocess('tests/elastic_driver.py',
                                       timeout=780)
        assert proc.returncode == 0, (proc.stdout[-2000:],
                                      proc.stderr[-2000:])
        assert row is not None, proc.stdout[-2000:]
        return row

    def test_driver_ok(self, row):
        assert row['ok'], row

    def test_resumes_at_surviving_extent_and_grows_back(self, row):
        assert row['dp_survive'] == 2
        assert [inc['dp'] for inc in row['incarnations']] == [4, 2, 2, 4]
        assert row['grew_back']
        assert [tuple(e) for e in row['lineage']] == [(4, 2), (2, 4)]

    def test_zero_steps_lost_beyond_in_flight(self, row):
        """Each incident's resume point equals the exact checkpoint
        frontier the previous incarnation reached — no completed step
        is ever re-trained, across clean notices, a mid-step kill, and
        a lost notice."""
        assert row['frontiers'] == row['expected_frontiers']
        assert row['killed_midstep'] and row['killed_after_lost_notice']
        assert row['notice_lost']

    def test_loss_bit_parity_across_the_storm(self, row):
        """The headline guarantee: with clipping ACTIVE, every captured
        step of the stormed run — final loss included — is bit-identical
        to the unpreempted dp=4 baseline over the same data order."""
        assert row['clip_active']
        assert row['parity_mismatches'] == []
        assert row['final_parity']

    def test_notice_checkpoints_commit_within_budget(self, row):
        assert all(inc['committed'] for inc in row['incarnations'])
        assert row['gauge_save_count'] >= 1

    def test_corrupt_newest_falls_back_older(self, row):
        assert row['corrupt_fell_back']
        assert row['pruning_kept_fallbacks']
        assert row['gauge_restore_fallbacks'] >= 1

    def test_preemption_and_resize_metrics(self, row):
        assert row['gauge_preemptions'] == 3.0
        assert row['gauge_resizes_down'] == 1.0
        assert row['gauge_resizes_up'] == 1.0
