"""Training entrypoint: `python -m skypilot_tpu.train.run --model ...`.

The first-party training recipe (the reference delegates to external
engines — torchrun/MaxText; here the trainer is in-tree): multi-host
bootstrap → mesh → sharded state (restored from the latest checkpoint if
one exists) → jitted step loop with callbacks + Orbax async saves.

Preemption-safe by construction: run under a managed job with the
checkpoint dir on a MOUNT-mode bucket and a relaunch resumes at the last
saved step.
"""
from __future__ import annotations

import argparse
import logging
import math
import os
import sys

logger = logging.getLogger(__name__)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--model', default='llama3-1b')
    parser.add_argument('--batch', type=int, default=8)
    parser.add_argument('--seq', type=int, default=1024)
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--learning-rate', type=float, default=3e-4)
    parser.add_argument('--data-dir', default=None,
                        help='directory of SKYTOK token shards (*.bin); '
                        'omit for synthetic batches')
    parser.add_argument('--sft-data', default=None,
                        help='JSONL of pre-tokenized {"prompt", '
                        '"completion"} examples; loss is masked to '
                        'completion tokens (SFT)')
    parser.add_argument('--data-seed', type=int, default=0)
    parser.add_argument('--val-dir', default=None,
                        help='SKYTOK shards for validation loss (e.g. '
                        'the tokenize_tool --val-fraction output dir)')
    parser.add_argument('--eval-every', type=int, default=200,
                        help='steps between validation passes')
    parser.add_argument('--eval-batches', type=int, default=16,
                        help='batches per validation pass')
    parser.add_argument('--checkpoint-dir', default=None)
    parser.add_argument('--checkpoint-every', type=int, default=100)
    parser.add_argument('--grad-accum', type=int, default=1,
                        help='accumulate grads over N sequential '
                             'microbatches per optimizer step: the '
                             'effective batch is --batch, activation '
                             'memory is --batch/N — global batches '
                             'beyond slice HBM')
    parser.add_argument('--zero1', action='store_true',
                        help='ZeRO-1 cross-replica weight-update '
                             'sharding (arxiv 2004.13336): the fp32 '
                             'Adam moments shard over the dp axis '
                             '(born sharded, ~1/dp per device), '
                             'gradients scatter into the shards and '
                             'updated params all-gather back — same '
                             'math, bit-identical losses, the '
                             'optimizer-state HBM of a dp-replicated '
                             'run divided by dp. Checkpoints stay '
                             'restorable across dp extents')
    parser.add_argument('--elastic', action='store_true',
                        help='preemption-native elastic training: on a '
                             'preemption notice (SIGTERM) the run '
                             'checkpoints within '
                             '$SKYTPU_TRAIN_PREEMPT_NOTICE_BUDGET and '
                             'exits 75 so the managed-jobs ELASTIC '
                             'strategy relaunches it at the surviving '
                             'dp extent; steps use the extent-'
                             'invariant elastic step, so the loss '
                             'curve is bit-identical across dp resizes '
                             '(docs/resilience.md "Elastic training '
                             'lifecycle"). Requires --dp and '
                             '--checkpoint-dir; the FIRST launch\'s '
                             '--dp fixes the canonical extent; '
                             'relaunches pass the surviving extent')
    parser.add_argument('--probe-hlo', action='store_true',
                        help='AOT-compile the train step once more and '
                             'publish its collective-op counts '
                             '(skytpu_train_step_collectives) — the '
                             'compile-time proxy for how gradients '
                             'land (reduce-scatter vs all-reduce) and '
                             'params return (all-gather). Costs one '
                             'extra compile before the loop')
    parser.add_argument('--lora-rank', type=int, default=0,
                        help='LoRA fine-tune: adapter rank (0 = full '
                             'fine-tune). Only lora_a/lora_b train; '
                             'merge for serving with models/convert '
                             'export (auto-merges) ')
    parser.add_argument('--lora-alpha', type=float, default=16.0)
    parser.add_argument('--lora-targets', default='q,v',
                        help='comma list from {q,k,v,o,gate,up,down}')
    parser.add_argument('--init-from-hf', default=None,
                        help='local HuggingFace checkpoint dir to '
                        'initialize params from (models/convert.py); an '
                        'existing Orbax checkpoint still wins (resume)')
    parser.add_argument('--export-hf', default=None,
                        help='after training, write a loadable HF '
                        'checkpoint dir (config + safetensors) here')
    parser.add_argument('--tp', type=int, default=None)
    parser.add_argument('--sp', type=int, default=None)
    parser.add_argument('--dp', type=int, default=None)
    parser.add_argument('--ep', type=int, default=None,
                        help='expert-parallel axis size (MoE models)')
    parser.add_argument('--pp', type=int, default=None,
                        help='pipeline-parallel stage count')
    parser.add_argument('--microbatches', type=int, default=None,
                        help='microbatches for the pipelined schedule '
                        '(requires --pp > 1; defaults to 4x stages)')
    parser.add_argument('--pipeline-repeats', type=int, default=1,
                        help='circular pipeline laps (v>1 cuts the '
                        'bubble to (S-1)/(vM+S-1); layers must tile '
                        'pp*v and microbatches >= pp)')
    parser.add_argument('--log-every', type=int, default=10)
    parser.add_argument('--profile-dir', default=None,
                        help='capture an XLA/jax.profiler trace of steps '
                        '2-4 into this directory (view with xprof/'
                        'tensorboard)')
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format='%(asctime)s %(levelname)s: %(message)s')

    from skypilot_tpu import callbacks
    from skypilot_tpu.models import get_config
    from skypilot_tpu.parallel import (build_mesh, distributed,
                                       infer_mesh_config)
    from skypilot_tpu.train import (TrainConfig, create_sharded_state,
                                    make_train_step, synthetic_batch)

    # 1. Multi-host wiring (no-op on one host).
    topology = distributed.initialize()
    import jax
    logger.info('process %d/%d, %d local / %d global devices',
                topology.host_rank, topology.num_hosts,
                jax.local_device_count(), jax.device_count())

    # Fail fast, not after hours of training: export is single-host.
    if args.export_hf and topology.num_hosts > 1:
        raise SystemExit(
            '--export-hf is single-host only; on multi-host runs, use '
            '`python -m skypilot_tpu.models.export_tool` against the '
            'Orbax checkpoint afterwards')

    # 2. Mesh over every chip in the job — except under --elastic,
    # which must run a PURE-dp mesh: infer_mesh_config sends spare
    # devices to fsdp, and an fsdp>1 axis would pull the elastic step's
    # canonical-group batch axis onto ('dp','fsdp') shards, breaking
    # the device-major group alignment its bit-parity contract depends
    # on (make_elastic_train_step docstring).
    elastic_ctx = None
    if args.elastic:
        from skypilot_tpu.parallel.mesh import MeshConfig
        from skypilot_tpu.train import elastic as elastic_lib
        if not args.checkpoint_dir:
            raise SystemExit('--elastic requires --checkpoint-dir: the '
                             'notice handler has nowhere to commit the '
                             'final checkpoint without one')
        if args.dp is None:
            raise SystemExit('--elastic requires an explicit --dp (the '
                             'live extent; the first launch fixes the '
                             'canonical extent)')
        if (args.pp or 1) > 1 or args.microbatches \
                or args.grad_accum > 1 or args.lora_rank \
                or (args.tp or 1) > 1 or (args.sp or 1) > 1 \
                or (args.ep or 1) > 1:
            raise SystemExit('--elastic composes with dp/ZeRO-1 only '
                             'for now: drop --pp/--microbatches/'
                             '--grad-accum/--lora-rank/--tp/--sp/--ep')
        if args.dp > jax.device_count():
            raise SystemExit(f'--elastic --dp {args.dp} exceeds the '
                             f'{jax.device_count()} local devices')
        mesh_cfg = MeshConfig(dp=args.dp)
        mesh = build_mesh(mesh_cfg, list(jax.devices())[:args.dp])
        meta = elastic_lib.ElasticMeta.load(args.checkpoint_dir)
        canonical_dp = meta.canonical_dp if meta else mesh_cfg.dp
        if canonical_dp % mesh_cfg.dp:
            raise SystemExit(
                f'--elastic: live dp={mesh_cfg.dp} must divide the '
                f'run\'s canonical extent {canonical_dp} (from '
                f'{elastic_lib.ElasticMeta.path(args.checkpoint_dir)})')
        notice = elastic_lib.PreemptionNotice()
        notice.install_sigterm()
        elastic_ctx = (elastic_lib, canonical_dp, notice)
    else:
        mesh_cfg = infer_mesh_config(jax.device_count(), tp=args.tp,
                                     sp=args.sp, dp=args.dp, ep=args.ep,
                                     pp=args.pp)
        mesh = build_mesh(mesh_cfg)
    logger.info('mesh: %s', mesh_cfg)
    if args.zero1 and mesh_cfg.dp <= 1:
        # Silent-no-op guard: the default mesh sends every spare device
        # to fsdp, so without an explicit dp axis there is nothing to
        # shard the optimizer state over — the moments would stay fully
        # replicated while the flag suggests otherwise.
        raise SystemExit(
            f'--zero1 shards the optimizer state over the dp axis, but '
            f'the mesh is {mesh_cfg} (dp=1): pass --dp N (e.g. --dp '
            f'{jax.device_count()} for pure data parallelism) or drop '
            f'--zero1. Note fsdp already shards weights AND moments '
            f'ZeRO-3 style; --zero1 is the dp-axis lever.')

    # 3. Sharded state, restored if a checkpoint exists.
    cfg_overrides = {}
    if args.lora_rank:
        cfg_overrides.update(lora_rank=args.lora_rank,
                             lora_alpha=args.lora_alpha,
                             lora_targets=args.lora_targets)
    cfg = get_config(args.model, param_dtype='bfloat16', **cfg_overrides)
    train_config = TrainConfig(learning_rate=args.learning_rate,
                               total_steps=args.steps)
    state, shardings = create_sharded_state(cfg, mesh,
                                            jax.random.PRNGKey(0),
                                            train_config,
                                            zero_sharding=args.zero1)
    distributed.log_device_memory('after state init')
    from skypilot_tpu.train import metrics as metrics_lib
    opt_total, opt_per_dev = metrics_lib.publish_opt_state_bytes(state)
    if args.zero1:
        logger.info(
            'zero1: optimizer state %.1f MB global, %.1f MB/device '
            '(%.3fx)', opt_total / 2**20, opt_per_dev / 2**20,
            opt_per_dev / max(1, opt_total))
    manager = None
    start_step = 0
    if args.checkpoint_dir:
        from skypilot_tpu.train.checkpoints import CheckpointManager
        manager = CheckpointManager(
            args.checkpoint_dir,
            save_interval_steps=args.checkpoint_every)
        if cfg.lora_rank:
            # Sidecar so export/serving can't silently merge with the
            # wrong alpha/targets (models/export_tool reads this).
            import json
            lora_meta = os.path.join(
                os.path.expanduser(args.checkpoint_dir), 'lora.json')
            meta = {'lora_rank': cfg.lora_rank,
                    'lora_alpha': cfg.lora_alpha,
                    'lora_targets': cfg.lora_targets}
            if os.path.exists(lora_meta):
                # The sidecar is the source of truth for the run that
                # created this checkpoint dir; resuming with different
                # adapter flags must not silently rewrite it. EVERY
                # process that can see the file checks BEFORE the
                # restore below (a cross-process collective): if only
                # rank 0 exited here, the other ranks would hang at the
                # restore barrier instead of erroring.
                with open(lora_meta, 'r', encoding='utf-8') as f:
                    existing = json.load(f)
                if existing != meta:
                    raise SystemExit(
                        f'LoRA flags do not match the existing sidecar '
                        f'{lora_meta}: checkpoint was written with '
                        f'{existing}, current flags are {meta}. Resume '
                        f'with the original flags or use a fresh '
                        f'--checkpoint-dir.')
            elif jax.process_index() == 0:
                os.makedirs(os.path.dirname(lora_meta), exist_ok=True)
                with open(lora_meta, 'w', encoding='utf-8') as f:
                    json.dump(meta, f)
        if elastic_ctx is not None:
            # Corrupt-newest falls back older + resize bookkeeping
            # (lineage sidecar, skytpu_train_elastic_resizes_total).
            state, start_step = manager.restore_latest_valid(state)
            elastic_lib, canonical_dp, _ = elastic_ctx
            elastic_lib.revalidate_extent(args.checkpoint_dir,
                                          canonical_dp, mesh_cfg.dp,
                                          start_step)
        else:
            state, start_step = manager.maybe_restore(state)
    if args.init_from_hf and start_step == 0:
        # Fine-tune from a local HF checkpoint: convert on host, place
        # each leaf straight onto its mesh sharding. Skipped entirely on
        # preemption resume (start_step > 0) — the Orbax restore already
        # holds the fine-tuned params, and re-converting a multi-GB HF
        # checkpoint only to discard it is dead work.
        from skypilot_tpu.models.convert import load_hf_checkpoint
        hf_params = load_hf_checkpoint(args.init_from_hf, cfg)
        if cfg.lora_rank:
            # HF supplies the frozen base; the fresh init keeps the
            # adapters (lora_a/lora_b) the HF checkpoint can't have.
            # overlay_place device_puts only the HF leaves — the placed
            # adapter arrays stay put (multi-host safe: no device_get).
            from skypilot_tpu.models.lora import overlay_place
            placed = overlay_place(state.params, hf_params,
                                   shardings.params)
        else:
            placed = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                hf_params, shardings.params)
        state = state.replace(params=placed)
        logger.info('initialized params from HF checkpoint %s',
                    args.init_from_hf)

    # 4. The step loop.
    microbatches = args.microbatches
    if microbatches and mesh_cfg.pp <= 1:
        raise SystemExit('--microbatches requires a pp>1 mesh '
                         '(pass --pp); with pp=1 the sequential step '
                         'would silently ignore it')
    if args.pipeline_repeats < 1:
        raise SystemExit('--pipeline-repeats must be >= 1')
    if args.pipeline_repeats > 1 and mesh_cfg.pp <= 1:
        raise SystemExit('--pipeline-repeats requires a pp>1 mesh '
                         '(pass --pp); with pp=1 the sequential step '
                         'would silently ignore it')
    if args.grad_accum < 1:
        raise SystemExit('--grad-accum must be >= 1')
    if args.grad_accum > 1 and args.batch % args.grad_accum:
        raise SystemExit(f'--batch {args.batch} must be divisible by '
                         f'--grad-accum {args.grad_accum}')
    # Everything downstream of accumulation sees ONE slice of the
    # batch: pipeline microbatching and the dp/fsdp batch sharding
    # both divide batch/grad_accum, not the full batch.
    per_step_batch = args.batch // args.grad_accum
    batch_extent = mesh_cfg.dp * mesh_cfg.fsdp
    if per_step_batch % batch_extent:
        raise SystemExit(
            f'per-accumulation batch {per_step_batch} '
            f'(--batch {args.batch} / --grad-accum {args.grad_accum}) '
            f'must be divisible by dp*fsdp = {batch_extent}')
    if microbatches and per_step_batch % microbatches:
        raise SystemExit(f'per-accumulation batch {per_step_batch} must '
                         f'be divisible by --microbatches {microbatches}')
    if mesh_cfg.pp > 1 and microbatches is None:
        # Target 4 per stage ((S-1)/(M+S-1) bubble ≈ 1/5), clamped to
        # the largest divisor of the batch ≥ pp — fail fast here, not
        # after state init, if even pp microbatches can't divide it.
        want = 4 * mesh_cfg.pp
        microbatches = next(
            (m for m in range(min(want, per_step_batch),
                              mesh_cfg.pp - 1, -1)
             if per_step_batch % m == 0), None)
        if microbatches is None:
            raise SystemExit(
                f'per-accumulation batch {per_step_batch} has no '
                f'divisor >= pp={mesh_cfg.pp} to use as a microbatch '
                f'count; raise --batch or pass --microbatches '
                f'explicitly')
        logger.info('pipeline: pp=%d, defaulting to %d microbatches',
                    mesh_cfg.pp, microbatches)
    if elastic_ctx is not None:
        from skypilot_tpu.train import make_elastic_train_step
        step_fn = make_elastic_train_step(cfg, mesh, shardings,
                                          elastic_ctx[1])
    else:
        step_fn = make_train_step(cfg, mesh, shardings,
                                  microbatches=microbatches,
                                  pipeline_repeats=args.pipeline_repeats,
                                  grad_accum=args.grad_accum)
    callbacks.init(total_steps=args.steps)
    dataset = None
    if args.data_dir and args.sft_data:
        raise SystemExit('--data-dir and --sft-data are mutually '
                         'exclusive')
    if args.sft_data:
        from skypilot_tpu.train.data import SftJsonlDataset
        dataset = SftJsonlDataset(args.sft_data, args.batch, args.seq,
                                  host_rank=topology.host_rank,
                                  num_hosts=topology.num_hosts,
                                  seed=args.data_seed,
                                  start_batch=start_step)
        logger.info('sft data: %d examples/host',
                    dataset.num_examples)
        batch_for = lambda step: dataset.next_batch()  # noqa: E731
    elif args.data_dir:
        from skypilot_tpu.train.data import TokenDataset
        dataset = TokenDataset(args.data_dir, args.batch, args.seq,
                               host_rank=topology.host_rank,
                               num_hosts=topology.num_hosts,
                               seed=args.data_seed,
                               start_batch=start_step)
        logger.info('data: %d windows/host (%s loader)',
                    dataset.num_windows,
                    'native' if dataset.native else 'numpy')
        batch_for = lambda step: dataset.next_batch()  # noqa: E731
    else:
        batches = [
            synthetic_batch(jax.random.PRNGKey(i), args.batch, args.seq,
                            cfg.unpadded_vocab_size or cfg.vocab_size)
            for i in range(8)
        ]
        batch_for = lambda step: batches[step % len(batches)]  # noqa: E731
    # Validation: forward-only loss on a FIXED set of held-out batches
    # (materialized once — successive evals must score the same data or
    # the val curve jitters from sampling, not model change).
    eval_fn = None
    eval_batches = []
    if args.val_dir:
        from skypilot_tpu.train import make_eval_step
        from skypilot_tpu.train.data import TokenDataset
        eval_fn = make_eval_step(cfg, mesh, shardings,
                                 pipeline_repeats=args.pipeline_repeats)
        val_dataset = TokenDataset(args.val_dir, args.batch, args.seq,
                                   host_rank=topology.host_rank,
                                   num_hosts=topology.num_hosts,
                                   seed=args.data_seed + 1)
        eval_batches = [val_dataset.next_batch()
                        for _ in range(args.eval_batches)]
        val_dataset.close()

    def run_eval(state, step):
        # Device-side accumulation: one host sync for the whole pass,
        # not one per batch.
        total = None
        for batch in eval_batches:
            loss_i = eval_fn(state, batch)
            total = loss_i if total is None else total + loss_i
        val_loss = float(total) / max(len(eval_batches), 1)
        logger.info('step %d val_loss=%.4f val_ppl=%.2f', step, val_loss,
                    math.exp(min(val_loss, 30.0)))
        return val_loss

    import contextlib

    from skypilot_tpu.parallel import sharding as sharding_lib

    def step_ctx():
        """What every trace of the step runs under: the ambient mesh
        (the flash kernel's shard_map reads it). The elastic step's
        bit-parity contract requires running WITHOUT a mesh context
        (make_elastic_train_step docstring); placements are carried
        entirely by the jit shardings either way."""
        if elastic_ctx is not None:
            return contextlib.nullcontext()
        return sharding_lib.use_mesh(mesh)

    if args.probe_hlo:
        from skypilot_tpu.train.trainer import compiled_step_collectives
        # Datasets advance on every next_batch: probe with the first
        # batch, then hand that same batch back to the loop so no
        # training data is skipped.
        probed_batch = batch_for(start_step)
        with step_ctx():
            probe = compiled_step_collectives(
                step_fn, state, probed_batch, dp=mesh_cfg.dp)
        inner_batch_for = batch_for
        replay = {'batch': probed_batch}

        def batch_for(step):  # noqa: F811
            held = replay.pop('batch', None)
            return held if held is not None else inner_batch_for(step)
        metrics_lib.publish_step_collectives(probe)
        logger.info(
            'compiled step collectives: all_reduce=%d all_gather=%d '
            'reduce_scatter=%d (+%d unfused partition-scatter)',
            probe['all_reduce'], probe['all_gather'],
            probe['reduce_scatter'], probe['partition_scatter'])
        # Under a mesh each Pallas kernel must have been handed its
        # per-device shard, not the gathered batch.
        logger.info('compiled step kernel operands: %s',
                    probe['kernel_operands'])

    loss = float('nan')
    # Profile a small steady-state slice: step 2 (past compile+warmup)
    # through step 4 — falling back to the first steps when the run is
    # too short, so an explicit --profile-dir always yields a trace.
    profile_start = start_step + 2
    if profile_start >= args.steps:
        profile_start = start_step
    profile_stop = min(profile_start + 3, args.steps)
    if args.profile_dir and profile_start >= args.steps:
        logger.warning('--profile-dir given but no steps remain to '
                       'profile (start_step=%d, steps=%d)', start_step,
                       args.steps)
    profiling = False
    import time
    # Wall time between logged steps, over the steps in between. The
    # float(loss) below waits for the step, so with --log-every 1 this
    # is the step time; the first one includes the compile.
    last_log = (time.perf_counter(), start_step - 1)
    with step_ctx():
        for step in range(start_step, args.steps):
            if elastic_ctx is not None and elastic_ctx[2].pending():
                from skypilot_tpu.train import elastic as elastic_lib
                elastic_lib.record_preemption()
                # Only what REMAINS of the budget: the kill clock
                # started at notice delivery, possibly mid-step.
                committed = manager.save_within_deadline(
                    step, state, elastic_ctx[2].remaining_budget(
                        elastic_lib.notice_budget_seconds()))
                logger.warning(
                    'preempted at step %d: checkpoint %s, exiting 75 '
                    'for an elastic relaunch', step,
                    'committed' if committed else
                    'did NOT commit within the notice budget — the '
                    'previous checkpoint is the resume point')
                if dataset is not None:
                    dataset.close()
                if committed:
                    manager.close()
                # else: close() would block on the same stuck save the
                # deadline logic just abandoned (wait_until_finished has
                # no timeout) — the kill is imminent, leave the daemon
                # waiter behind and EXIT inside the notice window.
                raise SystemExit(75)
            if args.profile_dir and step == profile_start:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            with callbacks.step():
                state, metrics = step_fn(state, batch_for(step))
            if profiling and step + 1 >= profile_stop:
                jax.block_until_ready(metrics['loss'])
                jax.profiler.stop_trace()
                profiling = False
                logger.info('profile trace written to %s',
                            args.profile_dir)
            if manager is not None:
                manager.save(step + 1, state)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics['loss'])
                now = time.perf_counter()
                logger.info('step %d/%d loss=%.4f grad_norm=%.3f '
                            'step_time=%.3fs', step, args.steps, loss,
                            float(metrics['grad_norm']),
                            (now - last_log[0]) / (step - last_log[1]))
                last_log = (now, step)
            if eval_fn is not None and (
                    (step + 1) % args.eval_every == 0 or
                    step == args.steps - 1):
                run_eval(state, step + 1)
    if profiling:  # --steps ended inside the profile window
        jax.profiler.stop_trace()
        logger.info('profile trace written to %s', args.profile_dir)
    if dataset is not None:
        dataset.close()
    if manager is not None:
        if manager.latest_step() != args.steps:
            manager.save(args.steps, state, force=True)
        manager.close()
    if args.export_hf:
        from skypilot_tpu.models.convert import export_hf_checkpoint
        # to_hf casts to float32 itself — device_get only here, or a
        # multi-GB bf16 tree would make two full fp32 host copies.
        host_params = jax.tree.map(jax.device_get, state.params)
        export_hf_checkpoint(host_params, cfg, args.export_hf)
    logger.info('done: %d steps, final loss %.4f', args.steps, loss)
    return 0


if __name__ == '__main__':
    sys.exit(main())
