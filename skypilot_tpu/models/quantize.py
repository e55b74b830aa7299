"""Weight-only int8 quantization for serving.

Converts a float Transformer param tree into the tree the
`weight_quant='int8'` model expects: each dense kernel becomes
`kernel_q` (int8) + `kernel_scale` (fp32, one scale per output channel,
absmax/127). Embeddings, norms and biases stay float — they are a
rounding error of the weight bytes; the dense kernels are where decode's
HBM traffic lives. (The reference reaches the same optimization by
delegating serving to vLLM/TGI quantized engines — SURVEY §2.9; here it
is in-tree, one flag on the serve replica.)

MoE expert kernels are left float for now (dispatch einsum layout);
`quantize_params` raises on MoE configs rather than silently serving a
half-quantized model.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from skypilot_tpu.models.configs import ModelConfig

# Dense submodules that carry a quantizable 'kernel', mapped to
# (input_ndim, feature_ndim): a kernel is (*stack, *inputs, *features) —
# scan-stacked layers prepend a layers dim, which the per-channel scale
# must KEEP (per-layer scales), so reduction happens only over the
# input dims, addressed from the right.
_QUANT_MODULES = {
    'q_proj': (1, 2), 'k_proj': (1, 2), 'v_proj': (1, 2),
    'o_proj': (2, 1),                        # (heads, head_dim) → embed
    'gate_proj': (1, 1), 'up_proj': (1, 1), 'down_proj': (1, 1),
    'lm_head': (1, 1),
    # The state-space mixer's two projections (models/ssm.py). Its
    # decay, step bias, skip, convolution and norm stay float: a few
    # thousand numbers a layer, and the recurrence is sensitive to them.
    'in_proj': (1, 1), 'out_proj': (1, 1),
}


def quantize_kernel(w: jax.Array, input_ndim: int, feature_ndim: int):
    """absmax per-output-channel: returns (int8 kernel, fp32 scale with
    the kernel's shape minus its input dims). Input dims sit immediately
    before the trailing `feature_ndim` dims; anything further left (the
    scan layer stack) is preserved in the scale."""
    w32 = w.astype(jnp.float32)
    lo = w.ndim - feature_ndim - input_ndim
    in_axes = tuple(range(lo, w.ndim - feature_ndim))
    absmax = jnp.max(jnp.abs(w32), axis=in_axes)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    # Broadcast the scale back over the reduced input dims for division.
    scale_b = jnp.expand_dims(scale, tuple(range(lo, lo + input_ndim)))
    q = jnp.clip(jnp.round(w32 / scale_b), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_params(params: Any, cfg: ModelConfig) -> Any:
    """Float param tree → int8-serving param tree (pure function, runs
    once at engine load)."""
    if cfg.is_moe:
        raise NotImplementedError(
            'int8 serving is dense-model only for now (MoE expert '
            'kernels keep the dispatch einsum float)')
    if not isinstance(params, dict):
        raise TypeError(f'params must be a plain dict tree (unfreeze '
                        f'FrozenDicts first), got {type(params)}')

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, sub in tree.items():
            feat = _QUANT_MODULES.get(name)
            if (feat is not None and isinstance(sub, dict)
                    and 'kernel' in sub):
                q, scale = _quantize_kernel_jit(sub['kernel'], *feat)
                new_sub = {k: v for k, v in sub.items() if k != 'kernel'}
                new_sub['kernel_q'] = q
                new_sub['kernel_scale'] = scale
                out[name] = new_sub
            else:
                out[name] = walk(sub)
        return out

    # One jitted dispatch a kernel (a program a distinct shape, a dozen
    # in all), not one for the whole tree: that one holds the float
    # tree, the int8 tree and a copy of every leaf that stays float at
    # once, which a 10.5 GB tree on a 16 GB chip cannot (the embedding
    # alone is 2.7 GB passed through). Leaves that stay float are
    # returned as they are. Op by op would compile and launch every op
    # of every leaf on its own.
    return walk(params)


_quantize_kernel_jit = jax.jit(quantize_kernel, static_argnums=(1, 2))
