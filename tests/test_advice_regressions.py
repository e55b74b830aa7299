"""Regression tests for the round-1/2 advisor findings:

1. (high) replica env race: per-replica tasks built via copy.copy shared
   one _envs dict with the base task — concurrent launch threads raced.
2. storage commands ran via shell=True with unquoted user paths.
3. terminate_cluster swallowed exhausted retries → double-provision risk.
4. initial replica status write was unlocked.
5. storage upload fallback suppressed the primary tool's stderr.
"""
import threading

import pytest

import skypilot_tpu as sky
from skypilot_tpu import exceptions
from skypilot_tpu.serve.replica_managers import SkyPilotReplicaManager
from skypilot_tpu.serve.service_spec import SkyServiceSpec


def _manager():
    task = sky.Task(run='echo hi')
    task.set_resources(
        sky.Resources(cloud='fake', accelerators='tpu-v5e-1', ports=[8124]))
    spec = SkyServiceSpec(readiness_path='/', min_replicas=2, max_replicas=2)
    return SkyPilotReplicaManager('svc', spec, task), task


class TestReplicaEnvIsolation:

    def test_replica_tasks_have_distinct_envs(self, _isolate_state):
        mgr, base = _manager()
        t1 = mgr._replica_task(1, {})
        t2 = mgr._replica_task(2, {})
        assert t1.envs['SKYTPU_REPLICA_ID'] == '1'
        assert t2.envs['SKYTPU_REPLICA_ID'] == '2'
        # Building replica 2's task must not rewrite replica 1's.
        assert t1.envs['SKYTPU_REPLICA_ID'] == '1'
        # The base task must stay unpolluted.
        assert 'SKYTPU_REPLICA_ID' not in base.envs
        assert t1.envs is not t2.envs

    def test_concurrent_replica_tasks(self, _isolate_state):
        """Many threads building replica tasks concurrently: each must see
        its own id (the original bug let a neighbor's update leak in)."""
        mgr, base = _manager()
        results = {}
        errors = []

        def build(rid):
            try:
                for _ in range(50):
                    t = mgr._replica_task(rid, {})
                    if t.envs['SKYTPU_REPLICA_ID'] != str(rid):
                        errors.append(
                            (rid, t.envs['SKYTPU_REPLICA_ID']))
                results[rid] = True
            except Exception as e:  # pylint: disable=broad-except
                errors.append((rid, repr(e)))

        threads = [threading.Thread(target=build, args=(i,))
                   for i in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert 'SKYTPU_REPLICA_ID' not in base.envs


class TestTaskCopy:

    def test_copy_rebinds_mutable_containers(self):
        base = sky.Task(run='echo', envs={'A': '1'})
        base.set_file_mounts({'/dst': '/src'})
        cp = base.copy()
        cp.update_envs({'B': '2'})
        cp.update_file_mounts({'/dst2': '/src2'})
        cp.set_resources(sky.Resources(cloud='fake'))
        assert 'B' not in base.envs
        assert '/dst2' not in base.file_mounts
        assert base.resources is not cp.resources


class TestTerminateClusterRaises:

    def test_exhausted_retries_raise(self, _isolate_state, monkeypatch):
        from skypilot_tpu.jobs import recovery_strategy
        from skypilot_tpu import global_user_state

        task = sky.Task(run='echo')
        task.set_resources(sky.Resources(cloud='fake'))
        strat = recovery_strategy.StrategyExecutor('cl', task)

        monkeypatch.setattr(global_user_state, 'get_cluster_from_name',
                            lambda name: {'name': name})
        import skypilot_tpu.core as core

        def boom(*a, **k):
            raise RuntimeError('cloud API down')

        monkeypatch.setattr(core, 'down', boom)
        monkeypatch.setattr(recovery_strategy.time, 'sleep', lambda s: None)
        with pytest.raises(exceptions.ClusterTeardownError):
            strat.terminate_cluster(max_retry=2)


class TestStorageCommandSafety:

    def test_upload_failure_surfaces_all_stderr(self):
        from skypilot_tpu.data.storage import GcsStore
        with pytest.raises(exceptions.StorageUploadError) as ei:
            GcsStore._run_first_ok(
                [['sh', '-c', 'echo primary-diag >&2; exit 3'],
                 ['sh', '-c', 'echo fallback-diag >&2; exit 4']],
                what='sync')
        msg = str(ei.value)
        assert 'primary-diag' in msg
        assert 'fallback-diag' in msg

    def test_run_first_ok_stops_at_success(self):
        from skypilot_tpu.data.storage import GcsStore
        # Second command would fail; first succeeds so no raise.
        GcsStore._run_first_ok(
            [['true'], ['sh', '-c', 'exit 1']], what='probe')

    def test_no_shell_interpolation_of_paths(self, tmp_path, monkeypatch):
        """Paths with shell metacharacters must be passed verbatim
        (argv, no shell) — the old f-string + shell=True broke on, and
        could be injected through, such paths."""
        from skypilot_tpu.data.storage import GcsStore
        # Hide any real gcloud/gsutil: the point is the argv contract,
        # not a live (and potentially hanging) network call.
        bindir = tmp_path / 'emptybin'
        bindir.mkdir()
        monkeypatch.setenv('PATH', str(bindir))
        evil = tmp_path / 'x; touch pwned'
        evil.mkdir()
        store = GcsStore('bkt-regress', str(evil))
        with pytest.raises(exceptions.StorageUploadError):
            # No gcloud/gsutil on PATH: FileNotFoundError per attempt →
            # aggregated StorageUploadError. The key assertion: no shell
            # ran, so no side-effect file appeared.
            store.upload()
        assert not (tmp_path / 'pwned').exists()
