"""Sharded training: state init and the jitted train step.

The whole training story is three jax transforms (the scaling-book recipe):
annotate shardings (parallel/sharding.py), jit the step over a Mesh, let XLA
insert the collectives (gradient psum over dp/fsdp, weight all-gathers for
fsdp, per-layer all-reduce for tp) on ICI/DCN. No NCCL, no torchrun, no
process groups — the reference's per-rank wiring (SURVEY §2.9) disappears
into the compiler.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.transformer import Transformer
from skypilot_tpu.parallel import sharding as sharding_lib


class TrainState(train_state.TrainState):
    """flax TrainState; extension point for EMA/schedule-free variants."""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95


def make_optimizer(tc: TrainConfig,
                   lora_only: bool = False) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        decay_steps=max(tc.total_steps, tc.warmup_steps + 1),
        end_value=tc.learning_rate * 0.1)
    base = optax.chain(
        optax.clip_by_global_norm(tc.grad_clip_norm),
        optax.adamw(schedule, b1=tc.b1, b2=tc.b2,
                    weight_decay=tc.weight_decay),
    )
    if not lora_only:
        return base

    # LoRA: only the adapters (lora_a/lora_b leaves) update; every base
    # weight is frozen with zero updates. The adamw moments then exist
    # only for the (tiny) adapter leaves — the HBM point of LoRA.
    def label_fn(params):
        # Match ANY path element (not just the last): at init time the
        # leaves sit inside flax LogicallyPartitioned boxes, so the path
        # continues past the 'lora_a'/'lora_b' dict key — labels must
        # come out identical for the boxed (init) and unboxed (update)
        # trees or the masked inner states misalign.
        return jax.tree_util.tree_map_with_path(
            lambda path, _: 'train'
            if any(getattr(k, 'key', None) in ('lora_a', 'lora_b')
                   for k in path)
            else 'freeze', params)

    return optax.multi_transform(
        {'train': base, 'freeze': optax.set_to_zero()}, label_fn)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token loss; logits in fp32 for a stable softmax."""
    logits = logits.astype(jnp.float32)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(losses)


def batch_sharding(mesh: Mesh) -> Dict[str, NamedSharding]:
    spec = sharding_lib.spec_for('batch', 'seq')
    s = NamedSharding(mesh, spec)
    return {'inputs': s, 'targets': s, 'mask': s}


def create_sharded_state(
    cfg: ModelConfig,
    mesh: Mesh,
    rng: jax.Array,
    train_config: Optional[TrainConfig] = None,
    zero_sharding: bool = False,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState with every array born sharded on `mesh`.

    Params (and therefore Adam moments, which mirror the param tree and
    inherit its logical metadata) are placed per the logical axis rules —
    nothing ever materializes replicated on one host.
    Returns (state, state_shardings).

    `zero_sharding` turns on ZeRO-1-style cross-replica weight-update
    sharding (arxiv 2004.13336): the optimizer-state shardings are
    additionally split over the `dp` mesh axis
    (parallel/sharding.zero_update_shardings), so the fp32 Adam moments
    are BORN at 1/dp per device — the jit init below materializes them
    straight into their shards, never whole on one device. The returned
    state_shardings carry the augmentation; pass them to
    make_train_step/make_eval_step and to checkpoint restores unchanged
    and the whole pipeline (step in/out shardings, Orbax per-shard
    save/restore) follows. The step MATH is untouched — the sharding of
    the update is carried entirely by these annotations (the paper's
    "automatic" thesis), which is what keeps sharded and unsharded
    training bit-identical (pinned by tests/zero1_driver.py).
    """
    tc = train_config or TrainConfig()
    model = Transformer(cfg)
    # The init forward pass exists only to create the params, and its
    # one-row batch cannot be split over the mesh's batch axes the way
    # the flash kernel's shard_map asks: attention there is plain XLA.
    init_model = Transformer(dataclasses.replace(cfg,
                                                 attention_impl='xla'))
    tx = make_optimizer(tc, lora_only=cfg.lora_rank > 0)
    dummy = jnp.ones((1, min(cfg.max_seq_len, 128)), jnp.int32)

    def init_fn(rng_):
        variables = init_model.init(rng_, dummy)
        return TrainState.create(apply_fn=model.apply,
                                 params=variables['params'], tx=tx)

    abstract_state = jax.eval_shape(init_fn, rng)
    # The logical→physical translation lives in parallel/sharding.py
    # (tree_shardings) and is shared with the inference engines — no
    # train-local copy of the rule application.
    state_shardings = sharding_lib.tree_shardings(mesh, abstract_state)
    if zero_sharding:
        state_shardings = state_shardings.replace(
            opt_state=sharding_lib.zero_update_shardings(
                mesh, nn.unbox(abstract_state).opt_state,
                nn.unbox(state_shardings).opt_state))
    with mesh:
        state = jax.jit(init_fn, out_shardings=state_shardings)(rng)
    state = nn.unbox(state)
    return state, state_shardings


def make_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    state_shardings: Any,
    microbatches: Optional[int] = None,
    pipeline_repeats: int = 1,
    grad_accum: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]],
              Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted train step: loss → grad → clip → adamw update.

    Donates the state so params/moments update in place (HBM win).

    `grad_accum` A>1 splits the batch's leading dim into A sequential
    microbatches inside the jitted step (lax.scan): grads accumulate in
    fp32 and ONE optimizer update applies — activation memory stays one
    microbatch's while the effective batch is the full one. Exactly
    equal to the single-shot step for unmasked LM batches; with SFT
    masks the per-microbatch means are weighted equally (the standard
    accumulation semantics) rather than by token count. Composes with
    the pipeline schedule (accumulation wraps the pipelined forward).

    `microbatches` (with a pp>1 mesh) switches the forward to the
    microbatched SPMD pipeline schedule (parallel/pipeline.py): embed →
    pipelined layer stack (vmap over stages + collective-permute
    shifts) → head, over the SAME param tree as the sequential path —
    checkpoints stay interchangeable across pp settings.

    `pipeline_repeats` v>1 selects the circular/interleaved schedule
    (bubble (S-1)/(vM+S-1)). NOTE: circular executes the stacked layers
    in `pipeline.circular_execution_order` — fine from scratch; to
    continue a sequentially-trained checkpoint, reorder its stack with
    `pipeline.reorder_stack_for_circular` first.

    ZeRO-1 weight-update sharding needs NO flag here: it is carried
    entirely by `state_shardings` (create_sharded_state(zero_sharding=
    True) augments the optimizer-state entries with the dp axis). The
    step body is IDENTICAL either way — the gradients are pinned to the
    PARAMS' shardings (a no-op placement-wise: that is where a gradient
    already lands), which fixes the clip/global-norm reduction order to
    whole-leaf reductions in both modes, and the dp-sharded moments then
    make XLA scatter the update (reduce-scatter on backends whose
    pipeline fuses it; all-reduce + partition-slice on the CPU proxy)
    and all-gather the updated params back per the out-shardings. One
    code path, bit-identical losses, sharded memory — the accumulate-
    then-update math cannot fork because there is nothing to fork.
    With grad_accum the fp32 gradient carry stays at the params'
    placement through the scan, so the update scatter and the param
    all-gather are issued ONCE per accumulation step, not per
    microbatch.
    """
    model = Transformer(cfg)
    num_stages = mesh.shape.get('pp', 1) if hasattr(mesh, 'shape') else 1
    pipelined = bool(microbatches) and num_stages > 1
    if pipelined and not cfg.scan_layers:
        raise ValueError('pipeline parallelism requires scan_layers=True '
                         '(stacked layer params)')
    if pipelined and cfg.num_layers % (num_stages * pipeline_repeats):
        raise ValueError(
            f'{cfg.num_layers} layers not divisible by pp={num_stages}'
            + (f' x repeats={pipeline_repeats}'
               if pipeline_repeats > 1 else ''))

    def loss_fn(params, batch):
        if pipelined:
            from skypilot_tpu.models.transformer import (
                DecoderLayer, checkpoint_policy_for)
            from skypilot_tpu.parallel import pipeline
            x, positions = model.apply({'params': params},
                                       batch['inputs'], mode='embed')
            layer_module = DecoderLayer(cfg)

            def layer_apply(p_layer, h, pos):
                return layer_module.apply({'params': p_layer}, h, pos)

            x = pipeline.pipeline_apply(
                layer_apply, params['layers']['layer'], x, positions,
                num_stages=num_stages, num_microbatches=microbatches,
                num_repeats=pipeline_repeats, remat=cfg.remat,
                checkpoint_policy=checkpoint_policy_for(cfg))
            logits = model.apply({'params': params}, x, mode='head')
        else:
            logits = model.apply({'params': params}, batch['inputs'])
        return cross_entropy_loss(logits, batch['targets'],
                                  batch.get('mask'))

    unboxed_shardings = nn.unbox(state_shardings)

    def step(state: TrainState, batch):
        if grad_accum <= 1:
            batch = {
                k: sharding_lib.constrain(v, 'batch', 'seq')
                for k, v in batch.items()
            }
            loss, grads = jax.value_and_grad(loss_fn)(state.params,
                                                      batch)
            if cfg.lora_rank > 0:
                # Zero the frozen-base grads (the optimizer discards
                # them via set_to_zero anyway) so the reported
                # grad_norm matches the accumulation path below —
                # otherwise toggling --grad-accum would discontinuously
                # change the metric under LoRA.
                grads = jax.tree_util.tree_map_with_path(
                    lambda path, g: g if any(
                        getattr(k, 'key', None) in ('lora_a', 'lora_b')
                        for k in path) else jnp.zeros_like(g),
                    grads)
        else:
            # Gradient accumulation: lax.scan over A microbatches —
            # activation memory is ONE microbatch's, so the effective
            # global batch scales past slice HBM. Accumulate in fp32
            # (bf16 running sums lose low bits across many micro
            # steps), then average and cast back so the optimizer sees
            # the dtype the single-shot path produces.
            rows = batch['inputs'].shape[0]
            extent = 1
            if hasattr(mesh, 'shape'):
                extent = (mesh.shape.get('dp', 1) *
                          mesh.shape.get('fsdp', 1))
            if rows % grad_accum:
                raise ValueError(f'batch {rows} not divisible by '
                                 f'grad_accum={grad_accum}')
            if (rows // grad_accum) % extent:
                # GSPMD would PAD the uneven microbatch over the batch
                # axes (involuntary rematerialization, silent dp loss)
                # rather than erroring — refuse with a usable message.
                raise ValueError(
                    f'per-accumulation batch {rows // grad_accum} '
                    f'(batch {rows} / grad_accum {grad_accum}) must be '
                    f'divisible by dp*fsdp = {extent}')
            micro = {
                k: v.reshape((grad_accum, v.shape[0] // grad_accum)
                             + v.shape[1:])
                for k, v in batch.items()
            }

            # With LoRA the base weights are frozen (set_to_zero in the
            # optimizer), so a full param-shaped fp32 carry would burn
            # HBM on gradients that are discarded — the accumulator
            # holds real buffers only for adapter leaves and scalar
            # placeholders for frozen ones (same path test as the
            # optimizer's label_fn).
            def _is_trained(path):
                return cfg.lora_rank == 0 or any(
                    getattr(k, 'key', None) in ('lora_a', 'lora_b')
                    for k in path)

            def acc(carry, mb):
                mb = {k: sharding_lib.constrain(v, 'batch', 'seq')
                      for k, v in mb.items()}
                loss_i, grads_i = jax.value_and_grad(loss_fn)(
                    state.params, mb)
                acc_loss, acc_grads = carry
                acc_grads = jax.tree_util.tree_map_with_path(
                    lambda path, a, g: (a + g.astype(jnp.float32)
                                        if _is_trained(path) else a),
                    acc_grads, grads_i)
                return (acc_loss + loss_i, acc_grads), None

            zero = jax.tree_util.tree_map_with_path(
                lambda path, p: jnp.zeros(
                    p.shape if _is_trained(path) else (), jnp.float32),
                state.params)
            (loss, grads), _ = jax.lax.scan(acc, (jnp.float32(0.0), zero),
                                            micro)
            loss = loss / grad_accum
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g, p: ((g / grad_accum).astype(p.dtype)
                                    if _is_trained(path)
                                    else jnp.zeros(p.shape, p.dtype)),
                grads, state.params)
        # Pin the gradients to the PARAMS' placement (dp-replicated
        # under pure data parallelism, fsdp/tp-sharded where the params
        # are). Placement-wise a no-op — this is where a gradient lands
        # anyway — but it anchors the clip/global-norm reductions to
        # whole-leaf order in BOTH the plain and the ZeRO-1 trainer:
        # without it, dp-sharded moments pull the gradients (and the
        # norm's sum-of-squares) into per-shard order and the clip
        # scale drifts in the low bits vs the unsharded run. The
        # update's dp scatter then happens AFTER the norm, where it is
        # order-free (elementwise).
        grads = jax.lax.with_sharding_constraint(
            grads, unboxed_shardings.params)
        new_state = state.apply_gradients(grads=grads)
        metrics = {
            'loss': loss,
            'grad_norm': optax.global_norm(grads),
            'step': new_state.step,
        }
        return new_state, metrics

    replicated = sharding_lib.replicated(mesh)
    return jax.jit(
        step,
        in_shardings=(unboxed_shardings, batch_sharding(mesh)),
        out_shardings=(unboxed_shardings,
                       {'loss': replicated, 'grad_norm': replicated,
                        'step': replicated}),
        donate_argnums=(0,),
    )


def make_elastic_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    state_shardings: Any,
    canonical_dp: int,
) -> Callable[[TrainState, Dict[str, jax.Array]],
              Tuple[TrainState, Dict[str, jax.Array]]]:
    """The dp-extent-invariant train step for elastic (preemption-native)
    training: loss AND gradients are bit-identical whether the mesh runs
    dp = canonical_dp or any divisor of it — the property that lets a
    spot run reshard dp=4→2 mid-storm and grow back to 4 with the final
    loss bit-equal to a never-preempted run over the same data order
    (pinned by tests/elastic_driver.py).

    Why the plain step can't promise this: XLA sums gradient partials in
    whatever association the current extent induces (a dp=4 all-reduce
    of four partials vs a dp=2 local-sum-then-all-reduce of two), so a
    resize perturbs the low bits and the runs diverge step by step.
    This step removes every extent-dependent reduction:

    1. CANONICAL GROUPS — the global batch is split into `canonical_dp`
       fixed groups (device-major, so a device's contiguous batch shard
       holds its own groups). A lax.scan runs canonical_dp/dp rounds;
       each round vmaps one group per device, so the per-group forward/
       backward always runs at the same local shapes no matter the live
       extent — the compiled per-group kernels cannot differ.
    2. FIXED COMBINE — per-group loss/mask SUMS and gradients gather
       replicated (pure data movement), then combine through an explicit
       left-to-right chain of elementwise adds. A jnp.sum over the group
       axis would let the SPMD partitioner rewrite it as local-partial-
       reduce + collective — reassociating by extent, exactly the drift
       being removed. Elementwise adds cannot be reassociated.
    3. NO MESH CONTEXT — callers must NOT wrap calls in `with mesh:`;
       every placement is carried by explicit NamedShardings. Under the
       mesh context the partitioner makes extent-dependent sharding
       choices inside the vmapped backward (observed: low-bit drift in
       every dense-kernel gradient at dp=2 vs dp=4).

    The price: per-group gradients materialize stacked ([canonical_dp] ×
    the gradient tree, replicated for the combine), and the loss is
    computed as sum-of-group-sums / sum-of-group-masks — mathematically
    the same mean, numerically NOT bit-comparable to make_train_step.
    Bit-parity is promised among elastic runs sharing a canonical extent
    and data order, not across step implementations
    (docs/resilience.md "Elastic training lifecycle").

    ZeRO-1 rides along unchanged: dp-sharded Adam moments make XLA
    scatter the (replicated, extent-invariant) update and all-gather
    params back — elementwise, so the resharding never perturbs values.
    """
    if canonical_dp < 1:
        raise ValueError(f'canonical_dp must be >= 1, got {canonical_dp}')
    dp = mesh.shape.get('dp', 1) if hasattr(mesh, 'shape') else 1
    if canonical_dp % dp:
        raise ValueError(
            f'elastic step: live dp={dp} must divide the canonical '
            f'extent {canonical_dp} — resize to a divisor (e.g. '
            f'{canonical_dp}→{canonical_dp // 2}) so the canonical '
            f'groups tile the surviving devices')
    model = Transformer(cfg)
    unboxed_shardings = nn.unbox(state_shardings)
    replicated = sharding_lib.replicated(mesh)
    rounds = canonical_dp // dp

    def loss_sums(params, group):
        logits = model.apply({'params': params}, group['inputs'])
        logits = logits.astype(jnp.float32)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, group['targets'])
        mask = group.get('mask')
        mask = (jnp.ones_like(losses) if mask is None
                else mask.astype(jnp.float32))
        return jnp.sum(losses * mask), jnp.sum(mask)

    grad_fn = jax.value_and_grad(loss_sums, has_aux=True)

    def fixed_sum(x):
        # Explicit left-to-right chain over the canonical-group axis:
        # elementwise adds, which the partitioner cannot reassociate.
        return functools.reduce(lambda a, b: a + b,
                                [x[i] for i in range(canonical_dp)])

    def step(state: TrainState, batch):
        rows = batch['inputs'].shape[0]
        if rows % canonical_dp:
            raise ValueError(f'batch {rows} not divisible by '
                             f'canonical_dp={canonical_dp}')
        groups = {
            k: sharding_lib.constrain(
                v.reshape((dp, rounds, rows // canonical_dp)
                          + v.shape[1:]),
                'batch', None, None, 'seq')
            for k, v in batch.items()
        }

        def round_fn(_, r):
            g = {k: jax.lax.dynamic_index_in_dim(v, r, axis=1,
                                                 keepdims=False)
                 for k, v in groups.items()}
            g = {k: sharding_lib.constrain(v, 'batch', None, 'seq')
                 for k, v in g.items()}
            (lsum, msum), grads = jax.vmap(grad_fn, in_axes=(None, 0))(
                state.params, g)
            return None, (lsum, msum, grads)

        _, (lsums, msums, grads) = jax.lax.scan(
            round_fn, None, jnp.arange(rounds))

        def canonical(x):
            # [rounds, dp, ...] -> replicated [canonical_dp, ...] in
            # group order (group g = device*rounds + round, matching the
            # device-major batch reshape above). Pure data movement.
            x = jax.lax.with_sharding_constraint(x, replicated)
            return jnp.swapaxes(x, 0, 1).reshape((canonical_dp,)
                                                 + x.shape[2:])

        lsums, msums = canonical(lsums), canonical(msums)
        grads = jax.tree.map(canonical, grads)
        total_mask = fixed_sum(msums)
        loss = fixed_sum(lsums) / total_mask
        grads = jax.tree.map(
            lambda g, p: (fixed_sum(g.astype(jnp.float32)) /
                          total_mask).astype(p.dtype),
            grads, state.params)
        # Same anchor as make_train_step: pin the combined gradients to
        # the PARAMS' placement so the clip/global-norm reductions stay
        # whole-leaf in both the plain and the ZeRO-1 trainer.
        grads = jax.lax.with_sharding_constraint(
            grads, unboxed_shardings.params)
        new_state = state.apply_gradients(grads=grads)
        metrics = {
            'loss': loss,
            'grad_norm': optax.global_norm(grads),
            'step': new_state.step,
        }
        return new_state, metrics

    return jax.jit(
        step,
        in_shardings=(unboxed_shardings, batch_sharding(mesh)),
        out_shardings=(unboxed_shardings,
                       {'loss': replicated, 'grad_norm': replicated,
                        'step': replicated}),
        donate_argnums=(0,),
    )


def compiled_step_collectives(step_fn, state, batch,
                              dp: Optional[int] = None
                              ) -> Dict[str, Any]:
    """Collective-op stats of the COMPILED train step — the training
    counterpart of the engines' decode_hlo_stats. Counts and shapes,
    not times.

    Lowers and compiles `step_fn` AOT (an honest second compile:
    `.lower().compile()` does NOT reuse the jit dispatch cache — spend
    it in bench/dryrun rows or behind train.run's --probe-hlo, off the
    step loop) and parses the optimized HLO with parallel/hlo_probe.
    Adds `partition_scatter` — the CPU backend's unfused spelling of
    reduce-scatter (all-reduce + partition-id slice; see
    hlo_probe.partition_scatter_count) — and `reduce_scatter_effective`
    = native + unfused, the number the ZeRO-1 pins read on any backend;
    and `kernel_operands`, the operand shapes each Pallas TPU kernel in
    the step was handed (hlo_probe.custom_call_operands: empty off the
    TPU, the per-device shard under a mesh).
    """
    from skypilot_tpu.parallel import hlo_probe
    text = step_fn.lower(state, batch).compile().as_text()
    stats = hlo_probe.collective_stats(text)
    stats['kernel_operands'] = hlo_probe.custom_call_operands(text)
    stats['partition_scatter'] = hlo_probe.partition_scatter_count(
        text, shards=dp)
    stats['reduce_scatter_effective'] = (stats['reduce_scatter'] +
                                         stats['partition_scatter'])
    return stats


def make_eval_step(
    cfg: ModelConfig,
    mesh: Mesh,
    state_shardings: Any,
    pipeline_repeats: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], jax.Array]:
    """Jitted forward-only loss (no grads, no state mutation) for the
    validation loop. Always the sequential execution path — eval
    batches are small and pipelining buys nothing without a backward —
    but a CIRCULAR-trained stack (pipeline_repeats > 1) is stored in
    stage-major permuted order, so its layers are gathered back into
    execution order first (a weights gather per eval pass; the trained
    function, not a layer-scrambled one)."""
    model = Transformer(cfg)
    num_stages = mesh.shape.get('pp', 1)
    order = None
    if pipeline_repeats > 1 and num_stages > 1:
        from skypilot_tpu.parallel import pipeline
        order = jnp.asarray(pipeline.circular_execution_order(
            cfg.num_layers, num_stages, pipeline_repeats))

    def step(state: TrainState, batch):
        batch = {
            k: sharding_lib.constrain(v, 'batch', 'seq')
            for k, v in batch.items()
        }
        params = state.params
        if order is not None:
            layers = jax.tree.map(lambda a: a[order],
                                  params['layers']['layer'])
            params = {**params, 'layers': {'layer': layers}}
        logits = model.apply({'params': params}, batch['inputs'])
        return cross_entropy_loss(logits, batch['targets'],
                                  batch.get('mask'))

    unboxed_shardings = nn.unbox(state_shardings)
    return jax.jit(
        step,
        in_shardings=(unboxed_shardings, batch_sharding(mesh)),
        out_shardings=sharding_lib.replicated(mesh),
    )


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int,
                    vocab_size: int) -> Dict[str, jax.Array]:
    """Deterministic synthetic LM batch (bench + hermetic tests)."""
    tokens = jax.random.randint(rng, (batch_size, seq_len + 1), 0,
                                vocab_size, dtype=jnp.int32)
    return {
        'inputs': tokens[:, :-1],
        'targets': tokens[:, 1:],
        'mask': jnp.ones((batch_size, seq_len), jnp.int32),
    }
