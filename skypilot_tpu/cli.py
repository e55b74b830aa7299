"""The `skytpu` CLI.

Reference parity: sky/cli.py (5,256 LoC, 32 click commands — SURVEY §2.1).
Same command surface, TPU-native semantics: `launch, exec, status, queue,
logs, cancel, stop, start, down, autostop, cost-report, check, show-tpus,
storage ls/delete, jobs launch/queue/cancel/logs, serve up/status/down/
logs`. Entry: `python -m skypilot_tpu.cli` (or the `skytpu` script).
TPU-native additions include `metrics` (scrape/print a Prometheus
/metrics endpoint), `trace` (render request traces / flight-record
postmortems), and `lint` (docs/observability.md,
docs/static-analysis.md).

YAML-or-inline entrypoint parsing and resource override flags mirror
cli.py:690,463; interactive confirm mirrors :532.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional

import click

import skypilot_tpu as sky
from skypilot_tpu import exceptions


def _fail(message: str) -> None:
    click.secho(f'Error: {message}', fg='red', err=True)
    sys.exit(1)


def _parse_env_file(path: str) -> List[tuple]:
    """dotenv-format KEY=VALUE lines ('#' comments, optional `export `
    prefix, optional single/double quotes around the value)."""
    pairs = []
    try:
        with open(os.path.expanduser(path), encoding='utf-8') as f:
            lines = f.readlines()
    except OSError as e:
        _fail(f'--env-file {path}: {e}')
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line or line.startswith('#'):
            continue
        if line.startswith('export '):
            line = line[len('export '):].lstrip()
        if '=' not in line:
            _fail(f'--env-file {path}:{i}: expected KEY=VALUE, '
                  f'got {line!r}')
        key, value = line.split('=', 1)
        value = value.strip()
        if len(value) >= 2 and value[0] == value[-1] and value[0] in '\'"':
            value = value[1:-1]
        pairs.append((key.strip(), value))
    return pairs


def _make_task(entrypoint: tuple, name: Optional[str],
               workdir: Optional[str], cloud: Optional[str],
               region: Optional[str], zone: Optional[str],
               accelerators: Optional[str], num_slices: Optional[int],
               use_spot: Optional[bool], env: tuple,
               ports: tuple, env_file: Optional[str] = None) -> 'sky.Task':
    """YAML-file-or-inline-command entrypoint (reference:
    _make_task_or_dag_from_entrypoint_with_overrides, cli.py:690)."""
    entry = ' '.join(entrypoint)
    is_yaml = entry.endswith(('.yaml', '.yml')) and os.path.exists(
        os.path.expanduser(entry))
    # --env applied after --env-file: explicit flags win on conflict
    # (the reference's documented precedence, sky/cli.py:237).
    env_overrides: Dict[str, str] = {}
    if env_file:
        env_overrides.update(_parse_env_file(env_file))
    env_overrides.update(e.split('=', 1) if '=' in e else (e, '')
                         for e in env)
    if is_yaml:
        # Overrides MUST flow through from_yaml: ${VAR} substitution in
        # run/setup/file_mounts happens at parse time, and required-env
        # (`VAR:` with no value) validation runs there too — appending
        # envs afterwards would silently leave the YAML defaults baked
        # into the command text.
        task = sky.Task.from_yaml(entry, env_overrides=env_overrides)
    else:
        if not entry:
            _fail('ENTRYPOINT required: a task YAML or an inline command.')
        task = sky.Task(run=entry)
        task.update_envs(env_overrides)
    if name is not None:
        task.name = name
    if workdir is not None:
        task.workdir = workdir

    overrides: Dict[str, Any] = {}
    if cloud is not None:
        overrides['cloud'] = cloud
    if region is not None:
        overrides['region'] = region
    if zone is not None:
        overrides['zone'] = zone
    if accelerators is not None:
        overrides['accelerators'] = accelerators
    if num_slices is not None:
        overrides['num_slices'] = num_slices
    if use_spot is not None:
        overrides['use_spot'] = use_spot
    if ports:
        overrides['ports'] = list(ports)
    if overrides:
        if task.resources:
            task.set_resources(
                {r.copy(**overrides) for r in task.resources})
        else:
            task.set_resources({sky.Resources(**overrides)})
    elif not task.resources:
        task.set_resources({sky.Resources()})
    return task


def _confirm(prompt: str, yes: bool) -> None:
    if not yes and not click.confirm(prompt, default=True):
        sys.exit(0)


def _print_table(rows: List[List[str]], headers: List[str]) -> None:
    widths = [
        max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows else
        len(str(h)) for i, h in enumerate(headers)
    ]
    line = '  '.join(h.ljust(w) for h, w in zip(headers, widths))
    click.secho(line, bold=True)
    for row in rows:
        click.echo('  '.join(
            str(c).ljust(w) for c, w in zip(row, widths)))


_TASK_OPTIONS = [
    click.option('--name', '-n', default=None, help='Task/cluster name.'),
    click.option('--workdir', default=None,
                 help='Directory synced to every host.'),
    click.option('--cloud', default=None, help='gcp | kubernetes | fake.'),
    click.option('--region', default=None),
    click.option('--zone', default=None),
    click.option('--accelerators', '--gpus', '--tpus', 'accelerators',
                 default=None,
                 help='TPU slice, e.g. tpu-v5e-8 or tpu-v5p-64.'),
    click.option('--num-slices', type=int, default=None,
                 help='Multislice: number of slices (DCN-connected).'),
    click.option('--use-spot/--no-use-spot', default=None,
                 help='Preemptible capacity.'),
    click.option('--env', multiple=True, help='KEY=VALUE (repeatable).'),
    click.option('--env-file', default=None,
                 help='dotenv file of KEY=VALUE lines; --env wins on '
                      'conflict.'),
    click.option('--ports', multiple=True, help='Ports to open.'),
]


def _with_task_options(fn):
    for option in reversed(_TASK_OPTIONS):
        fn = option(fn)
    return fn


@click.group()
@click.version_option(sky.__version__, prog_name='skytpu')
def cli() -> None:
    """skytpu: launch, manage, and serve TPU workloads."""


# ---------------- core lifecycle ----------------


@cli.command()
@click.argument('entrypoint', nargs=-1)
@_with_task_options
@click.option('--cluster', '-c', default=None, help='Cluster to (re)use.')
@click.option('--dryrun', is_flag=True, default=False)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--idle-minutes-to-autostop', '-i', type=int, default=None)
@click.option('--down', is_flag=True, default=False,
              help='Tear down when the job finishes.')
@click.option('--retry-until-up', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def launch(entrypoint, name, workdir, cloud, region, zone, accelerators,
           num_slices, use_spot, env, env_file, ports, cluster, dryrun,
           detach_run, idle_minutes_to_autostop, down, retry_until_up,
           yes):
    """Provision a TPU slice (with failover) and run ENTRYPOINT on it."""
    task = _make_task(entrypoint, name, workdir, cloud, region, zone,
                      accelerators, num_slices, use_spot, env, ports,
                      env_file=env_file)
    cluster = cluster or task.name
    if not dryrun:
        _confirm(f'Launching on cluster {cluster!r}. Proceed?', yes)
    from skypilot_tpu.utils import rich_utils
    import contextlib
    # Spinner only for detached launches: an attached launch streams the
    # job's logs to stdout, and a live spinner redrawing the line would
    # garble them. The plan table prints BEFORE the spinner starts (the
    # optimizer result is cached on the task, so launch won't re-print).
    use_spinner = detach_run and not dryrun
    quiet_opt = False
    if use_spinner:
        try:
            dag = sky.Dag()
            dag.add(task)
            sky.optimize(dag)
            quiet_opt = True
        except (exceptions.ResourcesUnavailableError, ValueError) as e:
            _fail(str(e))
    status_ctx = (rich_utils.safe_status(
        f'Launching on cluster {cluster or "<new>"}...')
        if use_spinner else contextlib.nullcontext())
    try:
        with status_ctx:
            job_id, handle = sky.launch(
                task, cluster_name=cluster, dryrun=dryrun,
                detach_run=detach_run, down=down,
                idle_minutes_to_autostop=idle_minutes_to_autostop,
                retry_until_up=retry_until_up,
                quiet_optimizer=quiet_opt)
    except (exceptions.ResourcesUnavailableError, ValueError) as e:
        _fail(str(e))
    if dryrun:
        return
    click.echo(f'Job {job_id} on cluster {handle.cluster_name!r}.')


@cli.command('exec')
@click.argument('cluster')
@click.argument('entrypoint', nargs=-1)
@click.option('--env', multiple=True)
@click.option('--env-file', default=None)
@click.option('--detach-run', '-d', is_flag=True, default=False)
def exec_cmd(cluster, entrypoint, env, env_file, detach_run):
    """Fast path: run ENTRYPOINT on an existing cluster (no provision)."""
    task = _make_task(entrypoint, None, None, None, None, None, None, None,
                      None, env, (), env_file=env_file)
    try:
        job_id, _ = sky.exec(task, cluster_name=cluster,
                             detach_run=detach_run)
    except exceptions.ClusterNotUpError as e:
        _fail(str(e))
    click.echo(f'Job {job_id} submitted to {cluster!r}.')


@cli.command()
@click.option('--refresh', '-r', is_flag=True, default=False,
              help='Reconcile with cloud state first.')
def status(refresh):
    """Cluster table (reference: sky status, cli.py:1507)."""
    from skypilot_tpu.utils import rich_utils
    if refresh:
        with rich_utils.safe_status('Refreshing cluster statuses...'):
            records = sky.status(refresh=True)
    else:
        records = sky.status(refresh=False)
    if not records:
        click.echo('No clusters.')
        return
    rows = []
    for r in records:
        handle = r['handle']
        resources = (str(handle.launched_resources)
                     if handle is not None else '-')
        endpoints = '-'
        if handle is not None and \
                handle.launched_resources.ports and handle.head_ip:
            endpoints = ' '.join(
                f'{handle.head_ip}:{p}'
                for p in handle.launched_resources.ports)
        rows.append([
            r['name'], r['status'].value, resources, endpoints,
            r.get('autostop', -1) if r.get('autostop', -1) >= 0 else '-'
        ])
    _print_table(rows, ['NAME', 'STATUS', 'RESOURCES', 'ENDPOINTS',
                        'AUTOSTOP(min)'])


@cli.command()
@click.argument('cluster')
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def queue(cluster, skip_finished):
    """Job queue of a cluster."""
    try:
        jobs = sky.queue(cluster, skip_finished=skip_finished)
    except exceptions.ClusterNotUpError as e:
        _fail(str(e))
    import datetime

    def fmt_ts(ts):
        if not ts:
            return '-'
        return datetime.datetime.fromtimestamp(float(ts)).strftime(
            '%Y-%m-%d %H:%M:%S')

    rows = [[j['job_id'], j.get('job_name') or '-', j['status'],
             fmt_ts(j.get('submitted_at'))] for j in jobs]
    _print_table(rows, ['ID', 'NAME', 'STATUS', 'SUBMITTED'])


@cli.command()
@click.argument('cluster')
@click.argument('job_id', type=int, required=False)
@click.option('--no-follow', is_flag=True, default=False)
@click.option('--status', 'status_only', is_flag=True, default=False,
              help="Print the job's status and exit 0 iff SUCCEEDED "
                   '(the scripting idiom: `skytpu logs c 1 --status`).')
def logs(cluster, job_id, no_follow, status_only):
    """Stream a job's combined (rank-prefixed) log."""
    try:
        if status_only:
            statuses = sky.job_status(cluster, [job_id] if job_id else None)
            jid, st = sorted(statuses.items())[-1]
            if st is None:
                _fail(f'Job {jid} not found on {cluster!r}.')
            label = f'Job {jid}' if jid >= 0 else 'Latest job'
            click.echo(f'{label}: {st}')
            sys.exit(0 if st == 'SUCCEEDED' else 1)
        sys.exit(sky.tail_logs(cluster, job_id, follow=not no_follow))
    except (exceptions.ClusterNotUpError, exceptions.JobNotFoundError) as e:
        _fail(str(e))


@cli.command()
@click.argument('cluster')
@click.argument('job_ids', type=int, nargs=-1)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def cancel(cluster, job_ids, all_jobs, yes):
    """Cancel jobs on a cluster."""
    if not job_ids and not all_jobs:
        _fail('Specify JOB_IDS or --all.')
    _confirm(f'Cancel jobs on {cluster!r}?', yes)
    cancelled = sky.cancel(cluster, list(job_ids) or None,
                           all_jobs=all_jobs)
    click.echo(f'Cancelled: {cancelled or "none"}')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def stop(clusters, yes):
    """Stop clusters (single-host, on-demand only — TPU pods/spot must
    use `down`; reference: clouds/gcp.py:184-190)."""
    _confirm(f'Stop {", ".join(clusters)}?', yes)
    for cluster in clusters:
        try:
            sky.stop(cluster)
            click.echo(f'Stopped {cluster!r}.')
        except (exceptions.NotSupportedError,
                exceptions.ClusterNotUpError) as e:
            _fail(str(e))


@cli.command()
@click.argument('cluster')
@click.option('--retry-until-up', is_flag=True, default=False)
def start(cluster, retry_until_up):
    """Restart a stopped cluster."""
    try:
        sky.start(cluster, retry_until_up=retry_until_up)
    except exceptions.SkyTpuError as e:
        _fail(str(e))
    click.echo(f'Cluster {cluster!r} is UP.')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
@click.option('--purge', is_flag=True, default=False,
              help='Remove state even if the cloud call fails.')
def down(clusters, yes, purge):
    """Terminate clusters (TPU slices are deleted, not stopped)."""
    _confirm(f'Terminate {", ".join(clusters)}?', yes)
    for cluster in clusters:
        try:
            sky.down(cluster, purge=purge)
            click.echo(f'Terminated {cluster!r}.')
        except exceptions.SkyTpuError as e:
            _fail(str(e))


@cli.command()
@click.argument('cluster')
@click.option('--idle-minutes', '-i', type=int, default=None)
@click.option('--cancel', 'cancel_autostop', is_flag=True, default=False)
@click.option('--down', 'autodown', is_flag=True, default=False)
def autostop(cluster, idle_minutes, cancel_autostop, autodown):
    """Arm/disarm idleness autostop for a cluster."""
    if cancel_autostop:
        idle_minutes = -1
    elif idle_minutes is None:
        idle_minutes = 5
    try:
        sky.autostop(cluster, idle_minutes, down=autodown)
    except exceptions.SkyTpuError as e:
        _fail(str(e))
    state = 'disarmed' if idle_minutes < 0 else f'{idle_minutes} min'
    click.echo(f'Autostop for {cluster!r}: {state}.')


@cli.command('cost-report')
def cost_report():
    """Accumulated cost per cluster (reference: cli.py cost-report)."""
    rows = []
    for r in sky.cost_report():
        hours = r['duration'] / 3600
        rows.append([
            r['name'], r['status'].value if r['status'] else 'TERMINATED',
            str(r['launched_resources'] or '-'), f'{hours:.1f}h',
            f"${r['total_cost']:.2f}"
        ])
    _print_table(rows, ['NAME', 'STATUS', 'RESOURCES', 'DURATION', 'COST'])


@cli.command()
@click.option('--url', default=None,
              help='Scrape a /metrics endpoint (serve replica, load '
                   'balancer, or dashboard), e.g. '
                   'http://127.0.0.1:8080/metrics. Default: this '
                   'process\'s own registry.')
@click.option('--raw', is_flag=True, default=False,
              help='Print the raw Prometheus text instead of a table.')
@click.option('--grep', 'pattern', default=None,
              help='Only show metric families whose name contains this '
                   'substring.')
def metrics(url, raw, pattern):
    """Show metrics: scrape a /metrics endpoint, or dump this process.

    The serving metric catalog (engine TTFT/TPOT, shed counters,
    circuit-breaker state, retry ladder) lives in
    docs/observability.md.
    """
    from skypilot_tpu.observability import exposition
    from skypilot_tpu.observability import metrics as obs
    if url is not None:
        if '://' not in url:
            url = 'http://' + url
        if not url.rstrip('/').endswith('/metrics'):
            url = url.rstrip('/') + '/metrics'
        import urllib.error
        import urllib.request
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                text = resp.read().decode('utf-8', errors='replace')
        except (urllib.error.URLError, OSError) as e:
            _fail(f'scrape of {url} failed: {e}')
    else:
        obs.enable()  # dumping IS exporting; record from here on
        text = exposition.generate_latest()
    if raw:
        click.echo(text, nl=False)
        return
    try:
        families = exposition.parse_prometheus_text(text)
    except ValueError as e:
        _fail(f'invalid Prometheus exposition from {url or "registry"}: '
              f'{e}')
    rows = []
    for name in sorted(families):
        if pattern and pattern not in name:
            continue
        fam = families[name]
        for (sample, labels), value in sorted(fam['samples'].items()):
            labels_str = ', '.join(f'{n}={v}' for n, v in labels) or '-'
            rows.append([sample, labels_str, fam['kind'] or 'untyped',
                         f'{value:g}'])
    if not rows:
        click.echo('no metrics recorded' + (
            f' matching {pattern!r}' if pattern else '') + '.')
        return
    _print_table(rows, ['METRIC', 'LABELS', 'TYPE', 'VALUE'])


@cli.command()
@click.option('--url', default=None,
              help='Fetch /traces from a serve replica or load '
                   'balancer, e.g. http://127.0.0.1:8080. Default: '
                   'this process\'s own span ring.')
@click.option('--dump', 'dump_path', default=None,
              help='Render a flight-record JSON file (the postmortem '
                   'a wedge recovery / tick failure / preemption '
                   'notice leaves under $SKYTPU_FLIGHT_DIR).')
@click.option('--grep', 'pattern', default=None,
              help='Only show traces containing a span whose name or '
                   'attrs match this substring.')
def trace(url, dump_path, pattern):
    """Render request traces or a flight-record postmortem.

    Traces show where ONE request's milliseconds went across the
    disaggregated fleet (LB routing → prefill → KV stream → decode
    ingest → decode ticks); flight records show what the engine was
    doing in the seconds before a wedge recovery or preemption.
    Span catalog + propagation format: docs/observability.md
    "Tracing".
    """
    import json as json_lib

    from skypilot_tpu.observability import tracing as tracing_lib
    if dump_path is not None:
        try:
            with open(os.path.expanduser(dump_path),
                      encoding='utf-8') as f:
                record = json_lib.load(f)
        except (OSError, ValueError) as e:
            _fail(f'cannot read flight record {dump_path}: {e}')
        if record.get('schema') != tracing_lib.FLIGHT_SCHEMA:
            _fail(f'{dump_path} is not a flight record (schema '
                  f'{record.get("schema")!r}, expected '
                  f'{tracing_lib.FLIGHT_SCHEMA!r})')
        for line in tracing_lib.render_flight_record(record):
            click.echo(line)
        return
    exemplars = {}
    if url is not None:
        if '://' not in url:
            url = 'http://' + url
        if not url.rstrip('/').endswith('/traces'):
            url = url.rstrip('/') + '/traces'
        import urllib.error
        import urllib.request
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                data = json_lib.loads(
                    resp.read().decode('utf-8', errors='replace'))
        except (urllib.error.URLError, OSError, ValueError) as e:
            _fail(f'fetch of {url} failed: {e}')
        spans = data.get('spans', [])
        exemplars = data.get('exemplars', {})
        if not data.get('enabled', False) and not spans:
            click.echo('tracing is disabled on that process '
                       '(set SKYTPU_TRACING=1 or call '
                       'tracing.enable()).')
            return
    else:
        spans = tracing_lib.snapshot()
    lines = tracing_lib.render_trace_tree(spans, grep=pattern)
    if not lines:
        click.echo('no traces recorded' + (
            f' matching {pattern!r}' if pattern else '') + '.')
        return
    for line in lines:
        click.echo(line)
    if exemplars:
        click.echo('\nexemplars (worst sample per window → trace):')
        for name in sorted(exemplars):
            ex = exemplars[name]
            click.echo(f'  {name}: {ex["value"]:g} '
                       f'(trace {ex["trace_id"]}, '
                       f'{ex["age_s"]:.0f}s ago)')


@cli.command()
@click.option('--select', default=None,
              help='Comma-separated checker ids to run (default: all; '
                   'see docs/static-analysis.md for the catalog).')
@click.option('--json', 'as_json', is_flag=True, default=False,
              help='Emit one machine-readable JSON row (schema '
                   'skylint/1) instead of human output.')
@click.option('--root', default=None,
              help='Package root to lint (default: the installed '
                   'skypilot_tpu tree).')
def lint(select, as_json, root):
    """Run skylint: the AST-based correctness analyzer.

    Checks hot-path host-sync discipline, lock discipline, wall-clock
    durations, sharding/collective containment, and the injection-
    point / metrics-catalog drift invariants. Reviewed debt lives in
    analysis/waivers.toml. Exit codes: 0 clean, 1 unwaived findings,
    2 internal error.
    """
    import json as json_lib

    from skypilot_tpu import analysis
    try:
        selected = ([s.strip() for s in select.split(',') if s.strip()]
                    if select else None)
        result = analysis.run_lint(root=root, select=selected)
    except analysis.LintError as e:
        if as_json:
            click.echo(json_lib.dumps(
                {'schema': 'skylint/1', 'ok': False, 'error': str(e)}))
        else:
            click.secho(f'skylint error: {e}', fg='red', err=True)
        sys.exit(2)
    if as_json:
        # ONE JSON object on one line, so CI can json.loads the last
        # stdout line.
        click.echo(json_lib.dumps(result.to_dict()))
    else:
        for finding in result.findings:
            color = 'yellow' if finding.waived else 'red'
            click.secho(str(finding), fg=color)
        summary = result.to_dict()['summary']
        click.echo(
            f"skylint: {summary['unwaived']} finding(s), "
            f"{summary['waived']} waived, "
            f"{len(result.selected)} checker(s) over "
            f"{result.root} in {summary['duration_s']}s")
    sys.exit(0 if result.ok else 1)


@cli.command()
def check():
    """Probe cloud credentials; cache the enabled-cloud list."""
    # Not sky.check(): the skypilot_tpu.check SUBMODULE shadows the lazy
    # function attr once imported (optimizer imports it).
    from skypilot_tpu import check as check_lib
    enabled = check_lib.check()
    if not enabled:
        _fail('No cloud is enabled. Configure GCP credentials or a '
              'kubeconfig, then rerun `skytpu check`.')
    click.echo(f'Enabled clouds: {", ".join(enabled)}')


@cli.group()
def local():
    """Local sandbox for iterating without cloud chips (reference:
    `sky local up`, cli.py:5076 — there a kind k8s cluster; here the
    docker debug backend, or the in-process fake cloud with --fake)."""


@local.command('up')
@click.option('--fake', is_flag=True, default=False,
              help='Use the in-process fake cloud instead of docker '
              '(no daemon needed; slices are local processes).')
def local_up(fake):
    """Enable the local backend so `launch --cloud docker|fake` works."""
    from skypilot_tpu import global_user_state
    from skypilot_tpu.clouds import registry
    name = 'fake' if fake else 'docker'
    if fake:
        # `local up --fake` IS the explicit opt-in the fake cloud's
        # test-only guard asks for — persist it so later processes
        # (`skytpu check`, launches) keep honoring it until local down.
        from skypilot_tpu import sky_config
        sky_config.write_user_config_key(('fake_cloud_enabled',), True)
    cloud = registry.get(name)
    ok, reason = cloud.check_credentials()
    if not ok:
        _fail(f'{name} backend unavailable: {reason}')
    cached = global_user_state.get_enabled_clouds()
    if cached is None:
        # Never-checked install: probe the real clouds first so enabling
        # the local backend doesn't mask valid GCP/k8s credentials behind
        # a cache that now exists but was never populated.
        from skypilot_tpu import check as check_lib
        cached = check_lib.check(quiet=True)
    enabled = set(cached)
    enabled.add(name)
    global_user_state.set_enabled_clouds(sorted(enabled))
    click.echo(f'Local {name} backend enabled.\n'
               f'Try: skytpu launch --cloud {name} '
               f'examples/docker/docker_app.yaml')


@local.command('down')
@click.option('--yes', '-y', is_flag=True, default=False)
def local_down(yes):
    """Tear down local (docker/fake) clusters and disable the backends."""
    from skypilot_tpu import global_user_state
    locals_ = [
        r['name'] for r in global_user_state.get_clusters()
        if r['handle'] is not None and getattr(
            r['handle'].launched_resources, 'cloud_name', None
        ) in ('docker', 'fake')
    ]
    if locals_:
        _confirm(f'Tear down local clusters: {", ".join(locals_)}?', yes)
        for name in locals_:
            sky.down(name)
            click.echo(f'Terminated {name!r}.')
    enabled = set(global_user_state.get_enabled_clouds() or [])
    enabled -= {'docker', 'fake'}
    global_user_state.set_enabled_clouds(sorted(enabled))
    from skypilot_tpu import sky_config
    if sky_config.get_nested(('fake_cloud_enabled',), False):
        sky_config.write_user_config_key(('fake_cloud_enabled',), False)
    click.echo('Local backends disabled.')


@cli.command('show-tpus')
@click.option('--all', '-a', 'show_all', is_flag=True, default=False)
def show_tpus(show_all):
    """TPU catalog: generations, slice shapes, pricing (reference:
    show-gpus, cli.py:2332)."""
    from skypilot_tpu import catalog
    rows = []
    for name, offerings in sorted(catalog.list_accelerators().items()):
        best = min(offerings, key=lambda o: o.price or 1e9)
        if not show_all and best.hosts > 16:
            continue
        rows.append([
            name, best.chips, best.hosts, best.topology,
            f'${best.price:.2f}' if best.price else '-',
            f'${best.spot_price:.2f}' if best.spot_price else '-',
            len(offerings),
        ])
    _print_table(rows, [
        'ACCELERATOR', 'CHIPS', 'HOSTS', 'TOPOLOGY', '$/HR', 'SPOT$/HR',
        'ZONES'
    ])


# ---------------- storage ----------------


@cli.group()
def storage():
    """Bucket storage objects."""


@storage.command('ls')
def storage_ls():
    rows = [[s['name'], s['status'].value,
             s['handle']['source'] if s['handle'] else '-']
            for s in sky.storage_ls()]
    _print_table(rows, ['NAME', 'STATUS', 'SOURCE'])


@storage.command('delete')
@click.argument('names', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def storage_delete(names, yes):
    _confirm(f'Delete storage {", ".join(names)}?', yes)
    for name in names:
        try:
            sky.storage_delete(name)
            click.echo(f'Deleted {name!r}.')
        except exceptions.StorageError as e:
            _fail(str(e))


# ---------------- managed jobs ----------------


@cli.group()
def jobs():
    """Managed jobs: auto-recovering (spot-friendly) jobs."""


@jobs.command('launch')
@click.argument('entrypoint', nargs=-1)
@_with_task_options
@click.option('--remote', is_flag=True, default=False,
              help='Run the controller on a dedicated controller cluster '
                   'so recovery survives this machine (reference: '
                   'jobs-controller.yaml.j2).')
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_launch(entrypoint, name, workdir, cloud, region, zone,
                accelerators, num_slices, use_spot, env, env_file, ports,
                remote, yes):
    """Launch a managed job (provision + monitor + recover)."""
    task = _make_task(entrypoint, name, workdir, cloud, region, zone,
                      accelerators, num_slices, use_spot, env, ports,
                      env_file=env_file)
    _confirm(f'Launching managed job {task.name!r}. Proceed?', yes)
    job_id = sky.jobs.launch(task, name=task.name, remote=remote)
    click.echo(f'Managed job {job_id} submitted'
               + (' (remote controller)' if remote else '') +
               f'. `skytpu jobs logs {job_id}` to stream.')


@jobs.command('queue')
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def jobs_queue(skip_finished):
    records = sky.jobs.queue(skip_finished=skip_finished)
    rows = [[
        r['job_id'], r['task_id'], r['job_name'] or '-',
        r['status'].value, r['recovery_count'],
        r['cluster_name'] or '-'
    ] for r in records]
    _print_table(
        rows, ['ID', 'TASK', 'NAME', 'STATUS', 'RECOVERIES', 'CLUSTER'])


@jobs.command('cancel')
@click.argument('job_ids', type=int, nargs=-1)
@click.option('--name', '-n', default=None)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_cancel(job_ids, name, all_jobs, yes):
    _confirm('Cancel managed jobs?', yes)
    try:
        cancelled = sky.jobs.cancel(name=name,
                                    job_ids=list(job_ids) or None,
                                    all_jobs=all_jobs)
    except (ValueError, exceptions.JobNotFoundError) as e:
        _fail(str(e))
    click.echo(f'Cancel signal sent: {cancelled or "none"}')


@jobs.command('logs')
@click.argument('job_id', type=int, required=False)
@click.option('--name', '-n', default=None)
@click.option('--controller', is_flag=True, default=False)
@click.option('--no-follow', is_flag=True, default=False)
def jobs_logs(job_id, name, controller, no_follow):
    try:
        sys.exit(
            sky.jobs.tail_logs(name=name, job_id=job_id,
                               follow=not no_follow,
                               controller=controller))
    except (exceptions.JobNotFoundError, ValueError) as e:
        _fail(str(e))


# ---------------- serve ----------------


@jobs.command('dashboard')
@click.option('--port', type=int, default=46590)
@click.option('--host', default='127.0.0.1')
def jobs_dashboard(port, host):
    """Serve a live web dashboard of jobs, services, and clusters
    (reference: sky/jobs/dashboard/dashboard.py)."""
    from skypilot_tpu import dashboard
    sys.exit(dashboard.main(['--host', host, '--port', str(port)]))


@cli.command()
@click.argument('shell', type=click.Choice(['bash', 'zsh', 'fish']))
def completion(shell):
    """Emit the shell-completion script (reference: sky/cli.py:345).

    Install with:  eval "$(skytpu completion bash)"  in ~/.bashrc.
    """
    # Drive click's native completion machinery directly (spawning a
    # subprocess doesn't work: click derives the env-var name from the
    # invoked prog name, which is not 'skytpu' under `python -m`).
    from click.shell_completion import get_completion_class
    comp_cls = get_completion_class(shell)
    if comp_cls is None:
        _fail(f'No completion support for {shell!r}.')
    comp = comp_cls(cli, {}, 'skytpu', '_SKYTPU_COMPLETE')
    click.echo(comp.source())


@cli.group()
def serve():
    """Serve: autoscaled replica fleets behind a load balancer."""


@serve.command('up')
@click.argument('entrypoint', nargs=-1)
@click.option('--service-name', '-n', default=None)
@click.option('--env', multiple=True, help='KEY=VALUE (repeatable).')
@click.option('--env-file', default=None)
@click.option('--remote', is_flag=True, default=False,
              help='Run the service runner on a dedicated controller '
                   'cluster so the fleet survives this machine.')
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_up(entrypoint, service_name, env, env_file, remote, yes):
    """Bring up a service from a task YAML with a `service:` section."""
    task = _make_task(entrypoint, None, None, None, None, None, None, None,
                      None, env, (), env_file=env_file)
    if task.service is None:
        _fail('Task YAML needs a `service:` section for serve up.')
    _confirm(f'Starting service {service_name or task.name!r}. Proceed?',
             yes)
    try:
        result = sky.serve.up(task, service_name, remote=remote)
    except (ValueError, exceptions.ServeUserTerminatedError) as e:
        _fail(str(e))
    click.echo(f"Service {result['name']!r} starting; endpoint: "
               f"{result['endpoint']}")


@serve.command('status')
@click.argument('service_name', required=False)
@click.option('--endpoint', 'endpoint_only', is_flag=True, default=False,
              help='Print only the endpoint (scripting: '
                   '`curl http://$(skytpu serve status NAME '
                   '--endpoint)/...`).')
def serve_status(service_name, endpoint_only):
    records = sky.serve.status(service_name)
    if endpoint_only:
        if not records or not records[0]['endpoint']:
            _fail(f'No endpoint for {service_name or "<any>"!r}.')
        click.echo(records[0]['endpoint'])
        return
    if not records:
        click.echo('No services.')
        return
    def _prewarm_cell(info):
        pw = info.get('last_prewarm')
        if not pw:
            return '-'
        if pw.get('status') == 'ok':
            partial = '/partial' if pw.get('partial') else ''
            return f"ok({pw.get('imported', 0)} pfx{partial})"
        return pw.get('status', '-')

    def _adapters_cell(info):
        # Multi-tenant serving (docs/serving.md): resident/capacity of
        # the replica's device-side adapter pool; old rows (and
        # adapter-less replicas) show '-'.
        ad = info.get('adapters')
        if not ad:
            return '-'
        return f"{ad.get('resident', 0)}/{ad.get('capacity', 0)}"

    def _tier_mix_cell(info):
        # Per-SLO-tier load snapshot (i=interactive, s=standard,
        # b=batch); old rows tolerate (the PR-13 TIER-column pattern).
        tl = info.get('tier_load')
        if not tl:
            return '-'
        return (f"i{tl.get('interactive', 0)}"
                f"/s{tl.get('standard', 0)}"
                f"/b{tl.get('batch', 0)}")

    for r in records:
        click.secho(f"{r['name']}  [{r['status'].value}]  "
                    f"endpoint: {r['endpoint'] or '-'}", bold=True)
        # Preemption lifecycle is first-class here: a replica mid-drain
        # shows DRAINING (not a generic NOT_READY), replacements carry
        # their preemption lineage, and PREWARM shows whether the
        # replacement came up with the fleet's hot prefixes restored
        # (docs/resilience.md "Preemption lifecycle").
        # TIER: prefill/decode for disaggregated fleets (docs/
        # serving.md), monolithic otherwise; old rows without the
        # field show monolithic.
        rows = [[i['replica_id'], i['status'], i['url'] or '-',
                 i.get('tier') or 'monolithic',
                 'spot' if i['is_spot'] else 'on-demand', i['version'],
                 i.get('preemption_count', 0) or '-',
                 _prewarm_cell(i), _adapters_cell(i), _tier_mix_cell(i)]
                for i in r['replica_info']]
        _print_table(rows,
                     ['REPLICA', 'STATUS', 'URL', 'TIER', 'CAPACITY',
                      'VERSION', 'PREEMPTS', 'PREWARM', 'ADAPTERS',
                      'TIER-MIX'])


@serve.command('update')
@click.argument('service_name')
@click.argument('entrypoint', nargs=-1)
@click.option('--env', multiple=True, help='KEY=VALUE (repeatable).')
@click.option('--env-file', default=None)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_update(service_name, entrypoint, env, env_file, yes):
    """Roll a service to a new task/spec version (blue-green-ish: new
    replicas use the new spec; reference: sky serve update,
    sky/cli.py:4076)."""
    task = _make_task(entrypoint, None, None, None, None, None, None, None,
                      None, env, (), env_file=env_file)
    if task.service is None:
        _fail('Task YAML needs a `service:` section for serve update.')
    _confirm(f'Update service {service_name!r} to a new version?', yes)
    try:
        version = sky.serve.update(task, service_name)
    except (ValueError, exceptions.ServeUserTerminatedError) as e:
        _fail(str(e))
    click.echo(f'Service {service_name!r} updated to version {version}.')


@serve.command('down')
@click.argument('service_names', nargs=-1, required=True)
@click.option('--purge', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_down(service_names, purge, yes):
    _confirm(f'Tear down {", ".join(service_names)}?', yes)
    for service_name in service_names:
        try:
            sky.serve.down(service_name, purge=purge)
            click.echo(f'Service {service_name!r} torn down.')
        except exceptions.ServeUserTerminatedError as e:
            _fail(str(e))


@serve.command('logs')
@click.argument('service_name')
@click.option('--replica-id', type=int, default=None)
def serve_logs(service_name, replica_id):
    try:
        sys.exit(
            sky.serve.tail_logs(
                service_name,
                target='replica' if replica_id is not None else
                'controller',
                replica_id=replica_id))
    except exceptions.ServeUserTerminatedError as e:
        _fail(str(e))


# ---------------- benchmark ----------------


@cli.group()
def bench():
    """Benchmark a task across candidate TPU slice shapes."""


@bench.command('launch')
@click.argument('entrypoint', nargs=-1)
@click.option('--benchmark', '-b', required=True, help='Benchmark name.')
@click.option('--candidate', '-k', 'candidates', multiple=True,
              required=True,
              help='Candidate accelerator (repeatable), e.g. tpu-v5e-8.')
@click.option('--cloud', default=None)
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_launch(entrypoint, benchmark, candidates, cloud, yes):
    """Launch ENTRYPOINT on every candidate slice shape in parallel."""
    from skypilot_tpu.benchmark import benchmark_utils
    task = _make_task(entrypoint, None, None, cloud, None, None,
                      candidates[0], None, None, (), ())
    _confirm(
        f'Launching {len(candidates)} benchmark clusters '
        f'({", ".join(candidates)}). Proceed?', yes)
    try:
        clusters = benchmark_utils.launch_benchmark(benchmark, task,
                                                    list(candidates))
    except (exceptions.SkyTpuError, ValueError) as e:
        _fail(str(e))
    click.echo(f'Benchmark {benchmark!r}: launched {", ".join(clusters)}. '
               f'`skytpu bench show {benchmark}` to compare.')


@bench.command('show')
@click.argument('benchmark')
@click.option('--steps', type=int, default=None,
              help='Report time/cost to reach this step count.')
@click.option('--save', is_flag=True, default=False,
              help='Persist the report to disk (survives bench down).')
def bench_show(benchmark, steps, save):
    from skypilot_tpu.benchmark import benchmark_utils
    try:
        benchmark_utils.update_benchmark_results(benchmark)
    except exceptions.SkyTpuError as e:
        _fail(str(e))
    if save:
        path = benchmark_utils.save_report(benchmark, steps_target=steps)
        click.echo(f'Report saved to {path}.')
    rows = []
    for r in benchmark_utils.report(benchmark, steps_target=steps):
        rows.append([
            r['cluster'], r['accelerator'], r['status'].value,
            r['num_steps'] or '-',
            f"{r['seconds_per_step']:.3f}s" if r['seconds_per_step']
            else '-',
            f"${r['cost_per_step']:.6f}" if r.get('cost_per_step')
            else '-',
            f"{r['seconds_to_target']/3600:.2f}h"
            if r.get('seconds_to_target') else '-',
        ])
    _print_table(rows, [
        'CLUSTER', 'ACCELERATOR', 'STATUS', 'STEPS', 'SEC/STEP', '$/STEP',
        'TIME-TO-TARGET'
    ])


@bench.command('down')
@click.argument('benchmark')
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_down(benchmark, yes):
    from skypilot_tpu.benchmark import benchmark_utils
    _confirm(f'Tear down benchmark {benchmark!r} clusters?', yes)
    try:
        # Preserve the final numbers before the state rows disappear.
        benchmark_utils.save_report(benchmark)
        benchmark_utils.down_benchmark(benchmark)
    except exceptions.SkyTpuError as e:
        _fail(str(e))
    click.echo(f'Benchmark {benchmark!r} torn down; final report kept '
               'on disk.')


@bench.command('race')
@click.argument('benchmark')
@click.option('--steps', type=int, required=True,
              help='Target step count for the projection.')
@click.option('--keep-top', type=int, default=1,
              help='Candidates to keep running; losers terminate.')
@click.option('--by', type=click.Choice(['cost', 'time']),
              default='cost')
@click.option('--timeout', type=float, default=3600.0)
def bench_race(benchmark, steps, keep_top, by, timeout):
    """Wait for measured step times, then terminate the losers early
    (keeps the top candidates running to the target)."""
    from skypilot_tpu.benchmark import benchmark_utils
    try:
        rows = benchmark_utils.wait_and_terminate_losers(
            benchmark, steps_target=steps, keep_top=keep_top, by=by,
            timeout=timeout)
    except exceptions.SkyTpuError as e:
        _fail(str(e))
    for r in rows:
        click.echo(f"{r['cluster']}: {r['status'].value} "
                   f"sec/step={r['seconds_per_step']}")


def main() -> None:
    cli()  # pylint: disable=no-value-for-parameter


if __name__ == '__main__':
    main()
