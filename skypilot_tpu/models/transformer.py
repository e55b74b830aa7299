"""Llama-3-style decoder-only transformer, written mesh-first.

Every weight and activation carries *logical* axis names (parallel/
sharding.py maps them to the physical mesh), so the same model code runs
1-chip, v5e-256 (dp×fsdp×tp), or multislice v5p (dp over DCN) without
modification — the TPU-native replacement for the reference's approach of
shelling out to torchrun/vLLM (SURVEY §2.9: reference has no in-tree model
stack; ours is the MaxText-equivalent).

Compute notes (MXU-first):
- bf16 activations/weights at matmul inputs, fp32 accumulation
  (preferred_element_type) and fp32 softmax/norm statistics.
- layers are stacked and scanned (lax.scan) ⇒ one layer compiles once;
  the stacked dim carries logical axis 'layers' which pipeline parallelism
  shards over `pp`.
- per-layer remat (jax.checkpoint) trades FLOPs for HBM.
"""
from __future__ import annotations

import typing
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.ssm import Mamba2Mixer, scaled
from skypilot_tpu.ops.flash_attention import flash_attention
from skypilot_tpu.ops.fused_lora import fused_multi_lora
from skypilot_tpu.ops.paged_attention import paged_decode_attention
from skypilot_tpu.parallel import sharding

Dtype = Any


def _dtype(cfg: ModelConfig) -> Dtype:
    return jnp.dtype(cfg.dtype)


def _param_dtype(cfg: ModelConfig) -> Dtype:
    return jnp.dtype(cfg.param_dtype)


def checkpoint_policy_for(cfg: ModelConfig):
    """The remat_policy → jax.checkpoint policy mapping, shared by the
    sequential scan path (below) and the pipeline executor
    (train/trainer.py) so the two execution strategies remat alike."""
    if cfg.remat_policy == 'dots':
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


class QuantDenseGeneral(nn.Module):
    """Weight-only int8 dense: `kernel_q` (int8) + per-output-channel
    `kernel_scale` (fp32), produced from a float checkpoint by
    models/quantize.py. Decode reads half the weight bytes from HBM; the
    int8→compute-dtype convert fuses into the matmul. Same submodule
    name/shape contract as the nn.DenseGeneral it replaces, so only the
    kernel params differ."""
    cfg: ModelConfig
    features: Any                 # int or tuple
    kernel_axes: Tuple[str, ...]
    axis: Any = -1                # int or tuple: contracted input dims
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        features = (self.features if isinstance(self.features, tuple)
                    else (self.features,))
        axis = (self.axis if isinstance(self.axis, tuple)
                else (self.axis,))
        axis = tuple(a % x.ndim for a in axis)
        in_shape = tuple(x.shape[a] for a in axis)
        kshape = in_shape + features
        kernel_q = self.param(
            'kernel_q',
            nn.with_logical_partitioning(
                lambda key, shape, dtype: jnp.zeros(shape, dtype),
                self.kernel_axes),
            kshape, jnp.int8)
        scale = self.param(
            'kernel_scale',
            nn.with_logical_partitioning(
                nn.initializers.ones, self.kernel_axes[len(in_shape):]),
            features, jnp.float32)
        y = jax.lax.dot_general(
            x, kernel_q.astype(_dtype(cfg)),
            ((axis, tuple(range(len(in_shape)))), ((), ())),
            preferred_element_type=jnp.float32)
        y = y * scale
        y = y.astype(_dtype(cfg))
        if self.use_bias:
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(
                    nn.initializers.zeros,
                    self.kernel_axes[len(in_shape):]),
                features, _param_dtype(cfg))
            y = y + bias.astype(_dtype(cfg))
        return y


class LoRADenseGeneral(nn.Module):
    """DenseGeneral + low-rank adapter: y = W·x + (alpha/r)·B(A(x)).

    Base params keep nn.DenseGeneral's exact names/shapes in THIS
    module's scope ('kernel'/'bias'), so checkpoints and from_hf line
    up unchanged; the adapter adds 'lora_a' (N(0, 1/r) init) and
    'lora_b' (zeros init — forward equals the base layer at step 0).
    A's input dims shard like the kernel's; the rank dim (tiny) is
    replicated. Train with trainer.py's masked optimizer; fold into
    the kernel with models/lora.merge_lora for serving/export.
    """
    cfg: ModelConfig
    features: Any                 # int or tuple
    kernel_axes: Tuple[str, ...]
    axis: Any = -1                # int or tuple: contracted input dims
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        features = (self.features if isinstance(self.features, tuple)
                    else (self.features,))
        axis = (self.axis if isinstance(self.axis, tuple)
                else (self.axis,))
        axis = tuple(a % x.ndim for a in axis)
        in_shape = tuple(x.shape[a] for a in axis)
        contract = ((axis, tuple(range(len(in_shape)))), ((), ()))
        kernel = self.param(
            'kernel',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         self.kernel_axes),
            in_shape + features, _param_dtype(cfg))
        y = jax.lax.dot_general(x, kernel.astype(_dtype(cfg)), contract)
        r = cfg.lora_rank
        lora_a = self.param(
            'lora_a',
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=r ** -0.5),  # A ~ N(0, 1/r)
                self.kernel_axes[:len(in_shape)] + ('lora_rank',)),
            in_shape + (r,), _param_dtype(cfg))
        lora_b = self.param(
            'lora_b',
            nn.with_logical_partitioning(
                nn.initializers.zeros,
                ('lora_rank',) + self.kernel_axes[len(in_shape):]),
            (r,) + features, _param_dtype(cfg))
        z = jax.lax.dot_general(x, lora_a.astype(_dtype(cfg)), contract)
        z = jax.lax.dot_general(
            z, lora_b.astype(_dtype(cfg)),
            (((z.ndim - 1,), (0,)), ((), ())))
        y = y + z * (cfg.lora_alpha / r)
        if self.use_bias:
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(
                    nn.initializers.zeros,
                    self.kernel_axes[len(in_shape):]),
                features, _param_dtype(cfg))
            y = y + bias.astype(_dtype(cfg))
        return y


class MultiLoRADenseGeneral(nn.Module):
    """Multi-tenant serving twin of LoRADenseGeneral: one base matmul
    plus a PER-ROW low-rank delta gathered from a resident adapter
    stack — y[b] = W·x[b] + (alpha/r)·B[id_b](A[id_b](x[b])).

    Base params keep nn.DenseGeneral's exact names/shapes in this
    module's scope ('kernel'/'bias'), so plain (lora-free) checkpoints
    line up unchanged. The adapter stacks live in the separate
    'adapters' variable collection — NOT 'params' — as
    (serve_adapters+1, *in, r) 'lora_a' and (serve_adapters+1, r, *out)
    'lora_b' leaves (a leading scanned-layers axis stacks on top under
    nn.scan). Slot 0 is the all-zero identity: a base-model request
    contributes an exactly-zero delta and rides the same compiled
    kernel as every adapter request — that is what lets one decode
    dispatch batch requests for DIFFERENT adapters (the engine feeds a
    per-slot adapter-index vector; models/inference.py owns slot
    residency/LRU/refcounts via serve/tenancy.AdapterPool).

    Numerics contract (pinned by tests/test_multitenant.py): the base
    matmul and the two low-rank matmuls use EXACTLY LoRADenseGeneral's
    op order — the gather only adds a batch dimension to the same
    contractions — so each row's greedy output is bit-identical to a
    dedicated single-adapter (or base) engine.
    """
    cfg: ModelConfig
    features: Any                 # int or tuple
    kernel_axes: Tuple[str, ...]
    axis: Any = -1                # int or tuple: contracted input dims
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jax.Array,
                 adapter_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        features = (self.features if isinstance(self.features, tuple)
                    else (self.features,))
        axis = (self.axis if isinstance(self.axis, tuple)
                else (self.axis,))
        axis = tuple(a % x.ndim for a in axis)
        in_shape = tuple(x.shape[a] for a in axis)
        n_in = len(in_shape)
        contract = ((axis, tuple(range(n_in))), ((), ()))
        kernel = self.param(
            'kernel',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         self.kernel_axes),
            in_shape + features, _param_dtype(cfg))
        y = jax.lax.dot_general(x, kernel.astype(_dtype(cfg)), contract)
        r = cfg.lora_rank
        slots = cfg.serve_adapters + 1
        # Replicated on any mesh: adapters are tiny (rank·dims per
        # slot) next to the weights; the per-row gather then needs no
        # collectives.
        lora_a = self.variable(
            'adapters', 'lora_a',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, (None,) * (n_in + 2))(
                    (slots,) + in_shape + (r,), _param_dtype(cfg)))
        lora_b = self.variable(
            'adapters', 'lora_b',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, (None,) * (len(features) + 2))(
                    (slots, r) + features, _param_dtype(cfg)))

        def unboxed(var):
            box = var.value
            return box.unbox() if hasattr(box, 'unbox') else box

        a_arr = unboxed(lora_a)
        b_arr = unboxed(lora_b)
        if adapter_ids is None:
            # init / adapter-less callers: every row is the identity.
            adapter_ids = jnp.zeros((x.shape[0],), jnp.int32)
        if cfg.decode_kernel in ('pallas', 'pallas_interpret'):
            # Fused gather+dot (ops/fused_lora): the per-row A/B tiles
            # stream straight from the resident stack through a
            # scalar-prefetched index map — no materialized
            # a_sel/b_sel intermediates through HBM. Contracted input
            # dims and feature dims flatten to one axis each (the dots
            # are identical under the reshape); x's batch-leading
            # layout is guaranteed because axis 0 is never contracted
            # (projections contract trailing dims only).
            slots_n = a_arr.shape[0]
            in_elems = 1
            for d in in_shape:
                in_elems *= d
            out_elems = 1
            for d in features:
                out_elems *= d
            keep_shape = tuple(x.shape[i] for i in range(x.ndim)
                               if i not in axis)
            x_flat = x.reshape(keep_shape[0], -1, in_elems)
            z = fused_multi_lora(
                x_flat.astype(_dtype(cfg)),
                a_arr.reshape(slots_n, in_elems, r).astype(_dtype(cfg)),
                b_arr.reshape(slots_n, r, out_elems).astype(_dtype(cfg)),
                adapter_ids,
                interpret=cfg.decode_kernel == 'pallas_interpret')
            z = z.reshape(keep_shape + features)
        else:
            a_sel = jnp.take(a_arr, adapter_ids, axis=0)  # (B, *in, r)
            b_sel = jnp.take(b_arr, adapter_ids, axis=0)  # (B, r, *out)
            z = jax.lax.dot_general(
                x, a_sel.astype(_dtype(cfg)),
                ((axis, tuple(range(1, n_in + 1))), ((0,), (0,))))
            z = jax.lax.dot_general(
                z, b_sel.astype(_dtype(cfg)),
                (((z.ndim - 1,), (1,)), ((0,), (0,))))
        y = y + z * (cfg.lora_alpha / r)
        if self.use_bias:
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(
                    nn.initializers.zeros,
                    self.kernel_axes[len(in_shape):]),
                features, _param_dtype(cfg))
            y = y + bias.astype(_dtype(cfg))
        return y


def _apply_proj(module: nn.Module, x: jax.Array,
                adapter_ids: Optional[jax.Array]) -> jax.Array:
    """Call a dense_general-produced projection, routing the per-row
    adapter indices only into the multi-LoRA variant (the other dense
    flavors take just x)."""
    if isinstance(module, MultiLoRADenseGeneral):
        return module(x, adapter_ids)
    return module(x)


def lora_target_names(cfg: ModelConfig) -> Tuple[str, ...]:
    """'q,v' → ('q_proj', 'v_proj'); validates the token set."""
    valid = ('q', 'k', 'v', 'o', 'gate', 'up', 'down')
    names = []
    for tok in cfg.lora_targets.split(','):
        tok = tok.strip()
        if not tok:
            continue
        if tok not in valid:
            raise ValueError(f'lora_targets token {tok!r} not in {valid}')
        names.append(f'{tok}_proj')
    if cfg.lora_rank > 0 and not names:
        raise ValueError('lora_rank > 0 but lora_targets is empty')
    return tuple(names)


def dense_general(cfg: ModelConfig, features, kernel_axes, name: str,
                  axis=-1, use_bias: bool = False):
    """nn.DenseGeneral, or its int8-serving twin when
    cfg.weight_quant == 'int8', or the LoRA-adapted variant when
    cfg.lora_rank > 0 targets this projection — same module name and
    base-param paths in every case, so checkpoints/from_hf line up and
    quantize_params stays a leaf rewrite."""
    if cfg.serve_adapters > 0 and name in lora_target_names(cfg):
        # Multi-tenant serving: base params stay nn.DenseGeneral's, the
        # resident adapter stacks live in the 'adapters' collection.
        if cfg.weight_quant == 'int8':
            raise NotImplementedError(
                'multi-LoRA serving composes with int8 KV, not int8 '
                'WEIGHTS: the adapter delta applies to the float base '
                'projection (serve unquantized, or merge+quantize a '
                'single adapter)')
        return MultiLoRADenseGeneral(cfg, features=features,
                                     kernel_axes=tuple(kernel_axes),
                                     axis=axis, use_bias=use_bias,
                                     name=name)
    if cfg.lora_rank > 0 and name in lora_target_names(cfg):
        if cfg.weight_quant == 'int8':
            raise NotImplementedError(
                'LoRA trains against float base weights; serve the '
                'merged checkpoint with int8 instead '
                '(models/lora.merge_lora then quantize)')
        return LoRADenseGeneral(cfg, features=features,
                                kernel_axes=tuple(kernel_axes),
                                axis=axis, use_bias=use_bias, name=name)
    if cfg.weight_quant == 'int8':
        return QuantDenseGeneral(cfg, features=features,
                                 kernel_axes=tuple(kernel_axes),
                                 axis=axis, use_bias=use_bias, name=name)
    return nn.DenseGeneral(
        features=features, axis=axis, use_bias=use_bias,
        dtype=_dtype(cfg), param_dtype=_param_dtype(cfg),
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), tuple(kernel_axes)),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros,
            tuple(kernel_axes)[1:] if isinstance(axis, int)
            else (tuple(kernel_axes)[-1],)),
        name=name)


class RMSNorm(nn.Module):
    """Pre-norm in the family's dialect: 'rms' (Llama), 'rms_plus1'
    (Gemma — the stored weight is a delta from 1), 'layernorm' (GPT-2 —
    mean-centred with a bias). Statistics in fp32 regardless of compute
    dtype."""
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        init = (nn.initializers.zeros if cfg.norm_style == 'rms_plus1'
                else nn.initializers.ones)
        scale = self.param(
            'scale',
            nn.with_logical_partitioning(init, ('embed',)),
            (x.shape[-1],), _param_dtype(cfg))
        x32 = x.astype(jnp.float32)
        if cfg.norm_style == 'layernorm':
            x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
        w = scale.astype(jnp.float32)
        if cfg.norm_style == 'rms_plus1':
            w = 1.0 + w
        out = normed * w
        if cfg.norm_style == 'layernorm' and cfg.norm_bias:
            bias = self.param(
                'bias',
                nn.with_logical_partitioning(nn.initializers.zeros,
                                             ('embed',)),
                (x.shape[-1],), _param_dtype(cfg))
            out = out + bias.astype(jnp.float32)
        return out.astype(_dtype(cfg))


def _llama3_scale_freqs(freqs: jax.Array, scaling) -> jax.Array:
    """Llama-3.1 long-context rope correction (HF rope_type 'llama3'):
    frequencies whose wavelength exceeds the ORIGINAL training window
    divide by `factor`; short wavelengths pass through; the band between
    interpolates smoothly. scaling = (factor, low_freq_factor,
    high_freq_factor, original_max_position_embeddings)."""
    factor, low_f, high_f, old_len = scaling
    wavelen = 2.0 * jnp.pi / freqs
    low_wl = old_len / low_f
    high_wl = old_len / high_f
    smooth = (old_len / wavelen - low_f) / (high_f - low_f)
    interpolated = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(wavelen > low_wl, freqs / factor,
                     jnp.where(wavelen < high_wl, freqs, interpolated))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_dim: int = 0, scaling=None) -> jax.Array:
    """Rotary position embedding. x: (B, S, H, D); positions: (B, S).
    rotary_dim > 0 (Phi/NeoX partial rotary): only the first rotary_dim
    dims rotate, the rest pass through unchanged. scaling: llama3
    long-context frequency correction (see _llama3_scale_freqs)."""
    if rotary_dim and rotary_dim < x.shape[-1]:
        rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate(
            [apply_rope(rot, positions, theta, scaling=scaling), rest],
            axis=-1)
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is not None:
        freqs = _llama3_scale_freqs(freqs, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]                       # (B,S,1,half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _int8_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-token-per-kv-head absmax int8 quantization for KV-cache
    writes. ONE definition shared by the contiguous and paged decode
    paths: their bit-identity contract (tests/test_composition_matrix)
    holds only while both layouts quantize with the exact same op
    order, so any numerics change lands in both by construction.
    x: (B, cur, KVH, D) → (int8 payload, fp32 scales (B, cur, KVH))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q8 = jnp.round(x.astype(jnp.float32) / scale[..., None]).astype(
        jnp.int8)
    return q8, scale


def _attend_window(cfg: ModelConfig, q: jax.Array, k_win: jax.Array,
                   v_win: jax.Array, k_scale: Optional[jax.Array],
                   v_scale: Optional[jax.Array],
                   positions: jax.Array) -> jax.Array:
    """Score/softmax/weighted-sum over one gathered-or-contiguous KV
    window — the single XLA definition of the decode attention math,
    and in particular of the int8 DEQUANT op order (`_int8_quantize`'s
    consumer side). The contiguous path, the XLA paged path, and the
    fused Pallas kernel's reference twin all run THIS function, so the
    bit-identity contract between layouts (and the kernel's
    tolerance/greedy contract against them) cannot drift — the PR-5
    quantize-hoist lesson applied to dequant.

    int8 op order (mirrored exactly by ops/paged_attention's kernels):
    K/V convert int8 → compute dtype at the matmul read; the per-token
    K scale applies to the fp32-accumulated scores AFTER the matmul
    (it factors out of the contracted head_dim); the per-token V scale
    folds into the probabilities (it cannot factor out of the summed
    sequence dim), which then cast to the compute dtype before the V
    matmul.

    q: (B, T, H, D); k_win/v_win: (B, S, KV, D) (int8 when scales are
    given); k_scale/v_scale: (B, S, KV) fp32 or None (together);
    positions: (B, T). Returns (B, T, H, D).
    """
    batch, cur_len = q.shape[:2]
    seq_len, kv_heads = k_win.shape[1], k_win.shape[2]
    kv_quant = k_scale is not None
    # Grouped-query attention directly against the unrepeated KV
    # window: repeating kv→num_heads over the whole window would 4x
    # (n_rep x) the HBM traffic of the op that dominates decode cost.
    n_rep = cfg.num_heads // kv_heads
    q_grouped = q.reshape(batch, cur_len, kv_heads, n_rep, cfg.head_dim)
    # int8: the matmul reads int8 (the astype fuses into the HBM
    # read); the per-token scale factors out of the contracted
    # head_dim and is applied to the scores afterwards.
    key_in = (k_win.astype(q.dtype) if kv_quant else k_win)
    scores = jnp.einsum('bqkrd,bskd->bkrqs', q_grouped, key_in,
                        preferred_element_type=jnp.float32)
    if kv_quant:
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None,
                                                     None, :]
    scores = scores * (cfg.head_dim**-0.5)
    if cfg.attn_logit_softcap:
        cap = cfg.attn_logit_softcap
        scores = cap * jnp.tanh(scores / cap)
    q_pos = positions[:, :, None]                          # (b, q, 1)
    k_pos = jnp.arange(seq_len)[None, None, :]             # (1, 1, s)
    mask = k_pos <= cfg.last_key_seen(q_pos)     # (block-)causal+fill
    if layer_window(cfg) is not None:
        mask &= q_pos - k_pos < layer_window(cfg)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if kv_quant:
        # V's per-token scale cannot factor out of the summed s dim;
        # fold it into the probabilities instead (elementwise, tiny
        # next to the cache-streaming matmul it enables). Masked
        # positions carry exactly-zero probs, so stale scale rows in
        # scratch/freed blocks contribute exactly 0.
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None,
                                                   None, :]
        probs = probs.astype(_dtype(cfg))
        out = jnp.einsum('bkrqs,bskd->bqkrd', probs,
                         v_win.astype(_dtype(cfg)))
    else:
        probs = probs.astype(v_win.dtype)
        out = jnp.einsum('bkrqs,bskd->bqkrd', probs, v_win)
    return out.reshape(batch, cur_len, cfg.num_heads, cfg.head_dim)


class Attention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None,
                 adapter_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        dense = lambda feats, axes, name: dense_general(
            cfg, feats, axes, name, use_bias=cfg.qkv_bias)
        # µP multipliers (Falcon-H1): on the input, the keys and the
        # output. 1.0 traces nothing.
        x = scaled(x, cfg.attn_in_multiplier)
        q = _apply_proj(dense((cfg.num_heads, cfg.head_dim),
                              ('embed', 'heads', 'qkv_dim'), 'q_proj'),
                        x, adapter_ids)
        k = _apply_proj(dense((cfg.num_kv_heads, cfg.head_dim),
                              ('embed', 'kv_heads', 'qkv_dim'),
                              'k_proj'), x, adapter_ids)
        v = _apply_proj(dense((cfg.num_kv_heads, cfg.head_dim),
                              ('embed', 'kv_heads', 'qkv_dim'),
                              'v_proj'), x, adapter_ids)
        k = scaled(k, cfg.key_multiplier)
        if cfg.qkv_clip:
            # DBRX clip_qkv: clamp projections to ±clip (training
            # stability; must match at inference for logit parity).
            q = jnp.clip(q, -cfg.qkv_clip, cfg.qkv_clip)
            k = jnp.clip(k, -cfg.qkv_clip, cfg.qkv_clip)
            v = jnp.clip(v, -cfg.qkv_clip, cfg.qkv_clip)
        q = sharding.constrain(q, 'batch', 'seq', 'act_heads', None)
        k = sharding.constrain(k, 'batch', 'seq', 'act_heads', None)
        v = sharding.constrain(v, 'batch', 'seq', 'act_heads', None)
        if cfg.pos_embedding == 'rope':
            rot = 0
            if cfg.rotary_pct != 1.0:
                if not 0.0 < cfg.rotary_pct < 1.0:
                    raise ValueError(
                        f'rotary_pct must be in (0, 1], got '
                        f'{cfg.rotary_pct}')
                # Even (rope pairs dims) and nonzero: int() truncation
                # to 0 would silently mean FULL rotary (the sentinel).
                rot = max(2, int(cfg.head_dim * cfg.rotary_pct) // 2 * 2)
            q = apply_rope(q, positions, cfg.rope_theta, rotary_dim=rot,
                           scaling=cfg.rope_scaling)
            k = apply_rope(k, positions, cfg.rope_theta, rotary_dim=rot,
                           scaling=cfg.rope_scaling)
        if cfg.decode:
            out = self._decode_attention(q, k, v, positions, block_tables)
        else:
            block_kw = {}
            if cfg.attn_block_q:
                block_kw['block_q'] = cfg.attn_block_q
            if cfg.attn_block_k:
                block_kw['block_k'] = cfg.attn_block_k
            out = flash_attention(q, k, v, causal=True,
                                  impl=cfg.attention_impl,
                                  logit_softcap=cfg.attn_logit_softcap,
                                  window=cfg.sliding_window, **block_kw)
        out = _apply_proj(
            dense_general(cfg, cfg.d_model,
                          ('heads', 'qkv_dim', 'embed'), 'o_proj',
                          axis=(-2, -1), use_bias=cfg.o_bias),
            out, adapter_ids)
        out = scaled(out, cfg.attn_out_multiplier)
        return sharding.constrain(out, 'batch', 'seq', 'act_embed')

    def _decode_attention(self, q: jax.Array, k: jax.Array,
                          v: jax.Array,
                          positions: jax.Array,
                          block_tables: Optional[jax.Array] = None
                          ) -> jax.Array:
        """KV-cached attention for prefill + autoregressive decode.

        The cache (`'cache'` variable collection) holds K/V over a static
        max_seq_len window (kv heads sharded on tp, batch on dp/fsdp —
        under the serving mesh these logical annotations are load-
        bearing: the continuous-batching engine places the cache with
        parallel/sharding.tree_shardings and XLA partitions every
        decode dispatch from the layouts alone).
        One call appends the current chunk — the whole prompt at prefill,
        one token per decode step — at the caller-provided `positions`
        and attends q to everything at-or-before each query's position.
        Positions are PER ROW: each batch row (slot) may sit at a
        different depth, which is what makes continuous batching possible
        (a slot mid-decode coexists with freshly prefilled ones). Static
        shapes keep a single compiled step; the causal mask hides
        unfilled/stale cache slots. (The reference delegates this
        machinery to vLLM's paged attention — SURVEY §2.9; here it is the
        in-tree engine behind serve replicas.)

        INVARIANT (caller-enforced — see InferenceEngine.generate's
        length assert): per-row positions stay < max_seq_len for every
        row whose OUTPUT is consumed, and each chunk is written
        contiguously from positions[:, 0]. Positions are traced, so
        this cannot be checked here; past the window,
        dynamic_update_slice clamps and silently overwrites old
        entries. The continuous-batching engine's device-resident feed
        leans on that clamp: inert rows (empty/prefilling slots) ride
        decode dispatches with in-graph-advancing positions, their
        writes land in their own row (contiguous — overwritten whole by
        the next _insert) or the scratch block (paged), and their
        outputs are never read (models/inference.py, async pipeline).

        How a layer reaches its part of a leaf: under the cache-carrying
        layer loop (`cache_carry.current_layer()` is this layer's index)
        every leaf is the stacked (L, B, S, ...) one that the loop
        carries, the chunk is written at (layer, row, start) by one
        scatter on it and the window is read at `layer`, where it lies;
        no layer's slice is taken out and none is written back. With no
        index (init, the unrolled model) the leaf is this layer's own
        and the same scatter runs under a leading axis of one.
        """
        # Late import: only a decoding model loads the module.
        from skypilot_tpu.models import cache_carry
        cfg = self.cfg
        layer = cache_carry.current_layer()
        batch, cur_len, _, _ = q.shape
        if cur_len > cfg.max_seq_len:
            raise ValueError(
                f'prompt chunk {cur_len} exceeds max_seq_len '
                f'{cfg.max_seq_len}')
        if cfg.paged_block_size:
            return self._paged_decode_attention(q, k, v, positions,
                                                block_tables, layer)
        kv_heads = k.shape[2]
        kv_quant = cfg.kv_cache_quant == 'int8'
        cache_dtype = jnp.int8 if kv_quant else k.dtype
        cache_shape = (batch, cfg.max_seq_len, kv_heads, cfg.head_dim)
        cached_key = self.variable(
            'cache', 'cached_key',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, ('batch', None, 'kv_heads', None))(
                    cache_shape, cache_dtype))
        cached_value = self.variable(
            'cache', 'cached_value',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, ('batch', None, 'kv_heads', None))(
                    cache_shape, cache_dtype))
        if kv_quant:
            # Per-token-per-kv-head absmax scales: the 4/head_dim byte
            # overhead that lets the (B, S, H, D) payload live as int8.
            scale_shape = (batch, cfg.max_seq_len, kv_heads)
            key_scale = self.variable(
                'cache', 'cached_key_scale',
                lambda: nn.with_logical_partitioning(
                    jnp.ones, ('batch', None, 'kv_heads'))(
                        scale_shape, jnp.float32))
            value_scale = self.variable(
                'cache', 'cached_value_scale',
                lambda: nn.with_logical_partitioning(
                    jnp.ones, ('batch', None, 'kv_heads'))(
                        scale_shape, jnp.float32))

        def unbox(var):
            box = var.value
            return (box.unbox() if hasattr(box, 'unbox') else box), box

        def rebox(var, box, arr):
            if hasattr(box, 'replace_boxed'):
                var.value = box.replace_boxed(arr)
            else:
                var.value = arr

        key_arr, key_box = unbox(cached_key)
        value_arr, value_box = unbox(cached_value)
        start_pos = positions[:, 0]
        # Per-row contiguous write at positions[:, 0] (one scatter; rows
        # at different depths write independently), on the carried leaf
        # itself at (layer, row, start) under the layer loop.
        write = cache_carry.write_window
        if kv_quant:
            k, k_s = _int8_quantize(k)
            v, v_s = _int8_quantize(v)
            ks_arr, ks_box = unbox(key_scale)
            vs_arr, vs_box = unbox(value_scale)
            ks_arr = write(ks_arr, k_s, start_pos, layer)
            vs_arr = write(vs_arr, v_s, start_pos, layer)
            rebox(key_scale, ks_box, ks_arr)
            rebox(value_scale, vs_box, vs_arr)
        key_arr = write(key_arr, k, start_pos, layer)
        value_arr = write(value_arr, v, start_pos, layer)
        rebox(cached_key, key_box, key_arr)
        rebox(cached_value, value_box, value_arr)

        # This layer's window, read where it lies.
        mine = lambda arr: cache_carry.read_layer(arr, layer)
        # Score/softmax/weighted-sum over the full contiguous window:
        # ONE shared op-order definition with the paged path
        # (_attend_window), so the layouts' bit-identity contract holds
        # by construction.
        return _attend_window(cfg, q, mine(key_arr), mine(value_arr),
                              mine(ks_arr) if kv_quant else None,
                              mine(vs_arr) if kv_quant else None,
                              positions)

    def _paged_decode_attention(self, q: jax.Array, k: jax.Array,
                                v: jax.Array, positions: jax.Array,
                                block_tables: Optional[jax.Array],
                                layer: Optional[jax.Array] = None
                                ) -> jax.Array:
        """Paged variant of _decode_attention: K/V live in a SHARED pool
        of `cfg.paged_num_blocks` blocks of `cfg.paged_block_size`
        tokens; `block_tables` (batch, max_seq_len//block_size + 1)
        maps each row's logical block index to a physical block id.

        Writes scatter the current chunk to
        table[row, pos // bs] * bs + pos % bs; reads gather each row's
        full logical window back to (B, S, KV, D) and run EXACTLY the
        contiguous score/softmax math, so greedy outputs are
        bit-identical to the contiguous layout (pinned by
        tests/test_paged_cache.py). Unwritten logical blocks map to the
        scratch block (id 0, also the table's extra last column, which
        absorbs pad-token writes past max_seq_len via index clipping);
        whatever garbage they hold is causally masked to -1e30 before
        softmax, so it contributes exactly 0.

        int8 KV (cfg.kv_cache_quant == 'int8') composes: the pool
        stores int8 K/V plus per-token-per-kv-head scale ROWS laid out
        per block — (nblocks, bs, kv_heads, 1), the trailing singleton
        keeping the block axis at ndim-4 for EVERY pool leaf so the
        engine's copy-on-write clone copies scale rows alongside data
        with the same slice. Quantize-on-write / dequantize-on-gather
        use the exact op order of the contiguous int8 path, so greedy
        outputs stay bit-identical to contiguous int8 (pinned by
        tests/test_composition_matrix.py) and the HBM win multiplies:
        ~4x tokens held per pool byte for bf16 on top of paged's
        tokens-held (not slots x max_seq_len) scaling.

        The capacity win: pool HBM scales with tokens actually held
        (shared prefix blocks are stored ONCE and referenced by many
        rows' tables), not slots × max_seq_len. Engine-side allocation,
        refcounts, and copy-on-write live in models/kv_cache.py.

        How a layer reaches its part of a leaf: under the cache-carrying
        layer loop (`layer` given, models/cache_carry.py) each leaf is
        the stacked (L, nblocks, bs, ...) one that the loop CARRIES, and
        this layer is rows [layer * nblocks * bs, (layer + 1) * nblocks
        * bs) of its flat view: the chunk is scattered and the window
        gathered there, on the carried buffer itself, which XLA then
        updates in place. Taking `leaf[layer]` first and scattering into
        that is what a scanned cache does, and costs a slice out, a
        write back and a whole-leaf copy after the loop. With no `layer`
        (init, the unrolled model) the leaf is this layer's own and its
        first block is block 0. The Pallas kernel takes no slice
        either: it is handed every layer's blocks as one pool and tables
        offset to this layer's (run by no cell; not measured).
        """
        cfg = self.cfg
        if block_tables is None:
            raise ValueError('paged KV cache requires block_tables')
        batch, cur_len, kv_heads, _ = k.shape
        bs = cfg.paged_block_size
        nblocks = cfg.paged_num_blocks
        bps = cfg.max_seq_len // bs          # logical blocks per row
        kv_quant = cfg.kv_cache_quant == 'int8'
        cache_dtype = jnp.int8 if kv_quant else k.dtype
        cache_shape = (nblocks, bs, kv_heads, cfg.head_dim)
        # No batch axis: the pool is shared across rows (that is the
        # point), so it shards on kv_heads (tp) only. Under a tp
        # serving mesh (models/inference.py places the pool via
        # parallel/sharding.tree_shardings) every device holds its
        # kv-head slice of EVERY block; the scatter/gather indices
        # below are computed from replicated block tables, so they are
        # identical on all devices and the paged path partitions
        # without collectives — the per-layer all-reduce happens in
        # o_proj/down_proj, exactly as on the contiguous path.
        cached_key = self.variable(
            'cache', 'cached_key',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, (None, None, 'kv_heads', None))(
                    cache_shape, cache_dtype))
        cached_value = self.variable(
            'cache', 'cached_value',
            lambda: nn.with_logical_partitioning(
                jnp.zeros, (None, None, 'kv_heads', None))(
                    cache_shape, cache_dtype))
        if kv_quant:
            # Scale rows live per block next to the data they scale.
            scale_shape = (nblocks, bs, kv_heads, 1)
            key_scale = self.variable(
                'cache', 'cached_key_scale',
                lambda: nn.with_logical_partitioning(
                    jnp.ones, (None, None, 'kv_heads', None))(
                        scale_shape, jnp.float32))
            value_scale = self.variable(
                'cache', 'cached_value_scale',
                lambda: nn.with_logical_partitioning(
                    jnp.ones, (None, None, 'kv_heads', None))(
                        scale_shape, jnp.float32))

        def unbox(var):
            box = var.value
            return (box.unbox() if hasattr(box, 'unbox') else box), box

        def rebox(var, box, arr):
            if hasattr(box, 'replace_boxed'):
                var.value = box.replace_boxed(arr)
            else:
                var.value = arr

        key_arr, key_box = unbox(cached_key)
        value_arr, value_box = unbox(cached_value)
        # This layer's first block in the leaf's flat view: the carried
        # leaf holds `nblocks` blocks a layer, the layer's own starts
        # at 0.
        first = 0 if layer is None else layer * nblocks
        # ---- write the current chunk through the table ----
        # Pad tokens past max_seq_len clip into the table's extra last
        # column, which the engine pins to the scratch block.
        log_block = jnp.clip(positions // bs, 0, block_tables.shape[1] - 1)
        phys = jnp.take_along_axis(block_tables, log_block, axis=1)
        flat_idx = ((first + phys) * bs + positions % bs).reshape(-1)
        kf = key_arr.reshape(-1, kv_heads, cfg.head_dim)
        vf = value_arr.reshape(-1, kv_heads, cfg.head_dim)
        if kv_quant:
            k, k_s = _int8_quantize(k)
            v, v_s = _int8_quantize(v)
            ks_arr, ks_box = unbox(key_scale)
            vs_arr, vs_box = unbox(value_scale)
            ksf = ks_arr.reshape(-1, kv_heads, 1).at[flat_idx].set(
                k_s.reshape(-1, kv_heads, 1))
            vsf = vs_arr.reshape(-1, kv_heads, 1).at[flat_idx].set(
                v_s.reshape(-1, kv_heads, 1))
            rebox(key_scale, ks_box, ksf.reshape(ks_arr.shape))
            rebox(value_scale, vs_box, vsf.reshape(vs_arr.shape))
        kf = kf.at[flat_idx].set(k.reshape(-1, kv_heads, cfg.head_dim))
        vf = vf.at[flat_idx].set(v.reshape(-1, kv_heads, cfg.head_dim))
        rebox(cached_key, key_box, kf.reshape(key_arr.shape))
        rebox(cached_value, value_box, vf.reshape(value_arr.shape))
        if cfg.decode_kernel in ('pallas', 'pallas_interpret'):
            # Fused kernel: the block-table walk happens IN KERNEL
            # (scalar-prefetched indices drive the K/V tile fetches),
            # dequant+score+streaming-softmax+weighted-sum run in one
            # VMEM pass per live block — no gathered (B, S, KV, D)
            # intermediate through HBM. Streaming softmax reorders the
            # reduction, so this path pins tolerance + greedy-token
            # equivalence against the XLA twin below, not bit identity
            # (tests/test_paged_attention.py, test_composition_matrix).
            # Unsupported combos (softcap; non-paged) were refused at
            # engine construction, never here mid-trace. Its pool is
            # every layer's blocks, its tables offset to this layer's.
            blocks = lambda flat: flat.reshape((-1, bs) + flat.shape[1:])
            return paged_decode_attention(
                q, blocks(kf), blocks(vf),
                first + block_tables[:, :bps], positions,
                k_scale=blocks(ksf) if kv_quant else None,
                v_scale=blocks(vsf) if kv_quant else None,
                window=cfg.sliding_window,
                logit_softcap=cfg.attn_logit_softcap,
                interpret=cfg.decode_kernel == 'pallas_interpret')
        # ---- gather each row's logical window and attend (XLA) ----
        gidx = ((first + block_tables[:, :bps, None]) * bs +
                jnp.arange(bs)[None, None, :]).reshape(batch, bps * bs)
        k_full = kf[gidx]                              # (B, S, KV, D)
        v_full = vf[gidx]
        # Score/softmax/weighted-sum over the gathered window: ONE
        # shared op-order definition with the contiguous path
        # (_attend_window) — exactly the contiguous (int8) math, so
        # the layouts stay bit-identical by construction.
        return _attend_window(cfg, q, k_full, v_full,
                              ksf[gidx][..., 0] if kv_quant else None,
                              vsf[gidx][..., 0] if kv_quant else None,
                              positions)


class SwiGLU(nn.Module):
    """Feed-forward in the family's dialect: GLU (gate·act × up → down;
    silu = Llama SwiGLU, gelu = Gemma GeGLU) or 'plain' (up → act → down;
    GPT-2), with optional biases (GPT-2)."""
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 adapter_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        act = nn.silu if cfg.mlp_activation == 'silu' else (
            lambda y: nn.gelu(y, approximate=True))
        dense = lambda feats, axes, name: dense_general(
            cfg, feats, axes, name, use_bias=cfg.mlp_bias)
        up = _apply_proj(dense(cfg.d_mlp, ('embed', 'mlp'), 'up_proj'),
                         x, adapter_ids)
        if cfg.mlp_style == 'glu':
            gate = _apply_proj(
                dense(cfg.d_mlp, ('embed', 'mlp'), 'gate_proj'),
                x, adapter_ids)
            h = act(scaled(gate, cfg.mlp_multipliers[0])) * up
        else:
            h = act(up)
        h = sharding.constrain(h, 'batch', 'seq', 'mlp')
        out = _apply_proj(dense(cfg.d_model, ('mlp', 'embed'),
                                'down_proj'), h, adapter_ids)
        out = scaled(out, cfg.mlp_multipliers[1])
        return sharding.constrain(out, 'batch', 'seq', 'act_embed')


class DecoderLayer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 positions: jax.Array,
                 block_tables: Optional[jax.Array] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 state_rows: Optional[Tuple] = None) -> jax.Array:
        cfg = self.cfg
        h = RMSNorm(cfg, name='attn_norm')(x)
        if cfg.ssm_heads:
            if cfg.is_moe or cfg.parallel_block:
                raise NotImplementedError(
                    'the parallel state-space mixer is modeled with a '
                    'sequential dense MLP only (Falcon-H1)')
            # Falcon-H1: the mixer and attention read ONE pre-norm and
            # add into the residual together; its state (`state_rows`
            # says whose, and how many positions are real) sits beside
            # K and V in the cache (models/ssm.py).
            x = (x + Mamba2Mixer(cfg, name='mixer')(h, positions,
                                                    state_rows)
                 + Attention(cfg, name='attn')(h, positions,
                                               block_tables, adapter_ids))
            h = RMSNorm(cfg, name='mlp_norm')(x)
            return x + SwiGLU(cfg, name='mlp')(h, adapter_ids)
        if cfg.parallel_block:
            if cfg.is_moe:
                raise NotImplementedError(
                    'parallel_block + MoE is not modeled (no family '
                    'uses it); use the sequential block for MoE')
            # Falcon: ONE shared pre-norm; attention and MLP read the
            # same normed input and their outputs sum into the residual
            # in a single step — the two matmul chains are independent,
            # so XLA overlaps them freely.
            return (x + Attention(cfg, name='attn')(h, positions,
                                                    block_tables,
                                                    adapter_ids)
                    + SwiGLU(cfg, name='mlp')(h, adapter_ids))
        x = x + Attention(cfg, name='attn')(h, positions, block_tables,
                                            adapter_ids)
        h = RMSNorm(cfg, name='mlp_norm')(x)
        if cfg.is_moe:
            from skypilot_tpu.models.moe import MoEBlock
            x = x + MoEBlock(cfg, name='moe')(h)
        else:
            x = x + SwiGLU(cfg, name='mlp')(h, adapter_ids)
        return x


class _ScannedLayer(nn.Module):
    """Adapter giving DecoderLayer the (carry, _) -> (carry, out) signature
    nn.scan expects."""
    cfg: ModelConfig

    @nn.compact
    def __call__(self, carry, _):
        x, positions, block_tables, adapter_ids, state_rows = carry
        x = DecoderLayer(self.cfg, name='layer')(x, positions,
                                                 block_tables,
                                                 adapter_ids, state_rows)
        return (x, positions, block_tables, adapter_ids, state_rows), None


class Transformer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 mode: str = 'full',
                 block_tables: Optional[jax.Array] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 head_rows: Optional[jax.Array] = None,
                 state_rows: Optional[Tuple] = None) -> jax.Array:
        """mode: 'full' (tokens → logits, the normal path), or the two
        halves the pipeline executor (parallel/pipeline.py) sandwiches
        around its microbatched layer schedule — 'embed' (tokens →
        (hidden, positions), stops before the layer stack) and 'head'
        (`tokens` IS the hidden state [B,T,D]; final norm + unembed).
        All modes share one param tree; init uses 'full'.

        head_rows (B,) int32: unembed only row head_rows[b] of each
        sequence, giving (B, 1, V) logits — a prefill chunk needs one
        row's logits, not T x V of them.

        state_rows = (slots, valid), each (B,) int32 or None, for models
        with recurrent state (models/ssm.py): the slot whose state each
        batch row reads and writes, and how many of its T positions are
        real (the rest are right pads, or the row is inert).

        The layers run as one loop over stacked weights. Training scans
        them and nothing else. A decoding model (`cfg.decode`) whose
        cache exists takes `cache_carry.carry_layers` instead: the same
        layer applied to x, with the cache leaves (stacked (L, ...) like
        the weights) CARRIED by the loop and each layer reading and
        writing its own part of a leaf in place, at its index. Were the
        cache scanned like the weights, as it is while init creates it,
        every leaf would be a scanned input and output: a slice out, a
        write back into a new buffer and a whole-leaf copy after the
        loop, three passes over the cache a program."""
        cfg = self.cfg
        # Tied models reuse this table as the unembed projection: init at
        # d^-1/2 so step-0 logits land at O(1) (and the Gemma sqrt(d)
        # input scaling restores O(1) activations). Untied keeps the
        # historical stddev=1 (checkpoint/loss-curve compatibility).
        embed_std = cfg.d_model**-0.5 if cfg.tie_embeddings else 1.0
        embed = nn.Embed(
            num_embeddings=cfg.vocab_size, features=cfg.d_model,
            dtype=_dtype(cfg), param_dtype=_param_dtype(cfg),
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=embed_std),
                ('vocab', 'embed')),
            name='embed')
        if mode == 'head':
            return self._head(embed, tokens)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
                tokens.shape)
        x = scaled(embed(tokens), cfg.embed_multiplier)
        if cfg.scale_embed_by_dim:
            x = x * jnp.asarray(cfg.d_model**0.5, dtype=x.dtype)
        if cfg.pos_embedding == 'learned':
            x = x + nn.Embed(
                num_embeddings=cfg.max_seq_len, features=cfg.d_model,
                dtype=_dtype(cfg), param_dtype=_param_dtype(cfg),
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02),
                    (None, 'embed')),
                name='pos_embed')(positions)
        x = sharding.constrain(x, 'batch', 'seq', 'act_embed')
        if mode == 'embed':
            return x, positions

        if cfg.has_layer_pattern or (cfg.decode and cfg.scan_layers
                and self.has_variable('cache', 'layers')):
            # A decoding model whose cache exists: the loop CARRIES the
            # stacked (L, ...) cache leaves and every layer writes its
            # part in place, by its index (models/cache_carry.py says
            # why); a layer pattern always comes here. A branch of its
            # own, so that the loop below stays the trainer's, line for
            # line; late import, so that a trainer never loads it.
            from skypilot_tpu.models.cache_carry import carry_layers
            x = carry_layers(cfg, x, positions, block_tables, adapter_ids,
                             state_rows, self.has_variable('cache', 'layers'))
            if head_rows is not None:
                x = jnp.take_along_axis(x, head_rows[:, None, None],
                                        axis=1)
            return x if mode == 'hidden' else self._head(embed, x)

        if cfg.scan_layers:
            layer_cls = _ScannedLayer
            if cfg.remat:
                layer_cls = nn.remat(layer_cls, prevent_cse=False,
                                     policy=checkpoint_policy_for(cfg))
            variable_axes = {'params': 0, 'cache': 0}
            if cfg.serve_adapters > 0:
                # Per-layer adapter stacks scan exactly like params.
                variable_axes['adapters'] = 0
            scanned = nn.scan(
                layer_cls,
                variable_axes=variable_axes,
                split_rngs={'params': True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: 'layers'},
            )(cfg, name='layers')
            (x, _, _, _, _), _ = scanned(
                (x, positions, block_tables, adapter_ids, state_rows),
                None)
        else:
            # Remat is an execution knob: the param tree keys must not
            # depend on it (checkpoint compatibility).
            layer_ctor = (nn.remat(DecoderLayer, prevent_cse=False)
                          if cfg.remat else DecoderLayer)
            for i in range(cfg.num_layers):
                x = layer_ctor(cfg, name=f'layer_{i}')(x, positions,
                                                       block_tables,
                                                       adapter_ids,
                                                       state_rows)

        if head_rows is not None:
            x = jnp.take_along_axis(x, head_rows[:, None, None], axis=1)
        return self._head(embed, x)

    def _head(self, embed: nn.Embed, x: jax.Array) -> jax.Array:
        """Final norm + unembed (+ softcap + pad-row mask). Plain helper
        inside the compact scope — `embed` is the single shared instance
        (tied unembed)."""
        cfg = self.cfg
        x = RMSNorm(cfg, name='final_norm')(x)
        if cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = dense_general(cfg, cfg.vocab_size,
                                   ('embed', 'vocab'), 'lm_head',
                                   use_bias=cfg.lm_head_bias)(x)
        logits = scaled(logits, cfg.lm_head_multiplier)
        if cfg.final_logit_softcap:
            cap = cfg.final_logit_softcap
            logits = (cap * jnp.tanh(
                logits.astype(jnp.float32) / cap)).astype(logits.dtype)
        if 0 < cfg.unpadded_vocab_size < cfg.vocab_size:
            # Tiling-padded vocab rows score ~0 (zero embeddings) —
            # mask them so sampling can never emit an invalid id.
            valid = jnp.arange(cfg.vocab_size) < cfg.unpadded_vocab_size
            logits = jnp.where(valid[None, None, :], logits,
                               jnp.asarray(-1e30, logits.dtype))
        return sharding.constrain(logits, 'batch', 'seq', 'vocab')


def layer_window(cfg: ModelConfig) -> Optional[Any]:
    """Keys a query of the layer being traced looks back over: under a
    layer pattern's loop the layer's own window, a traced scalar that
    the loop carries (models/cache_carry.py); else the model's one
    `sliding_window`. None: no window, no mask."""
    if cfg.has_layer_pattern:
        from skypilot_tpu.models.cache_carry import current_kind
        kind = current_kind()
        if kind is not None:
            return kind[0]
    return cfg.sliding_window or None
