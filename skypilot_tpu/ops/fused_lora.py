"""Pallas fused multi-LoRA delta: per-row adapter gather + A/B dots.

models/transformer.MultiLoRADenseGeneral computes each row's low-rank
delta by materializing per-row adapter selections —
`a_sel = jnp.take(a_stack, adapter_ids, 0)` writes (B, in, r) (and the
(B, r, out) B twin) through HBM every projection call before two
batched dot_generals read them back. This kernel is the PR-18 second
leg: the grid is one cell per batch row, the adapter ids ride in SMEM
as a scalar-prefetched operand, and the BlockSpec index maps address
the A/B STACKS directly through `ids[b]` — the row's adapter tiles
stream straight from the resident stack into VMEM and both dots run in
one pass. No gathered a_sel/b_sel intermediate ever exists.

Dtype discipline matches the XLA twin: both dots take operands in the
input compute dtype, accumulate in float32 (the MXU's way; Mosaic wants
it said) and round each product to the compute dtype, which is what
XLA's default-precision dots do. fp32 engines see bit-level agreement
under the interpreter; on the v5e the compiled kernel matched the twin
at bf16, d=2048, r=16 (chip_smoke.py, PR 21).

The fusion removes B·(in·r + r·out) HBM round-trip bytes per adapted
projection per step. Whether that shows next to the base W·x matmul
that runs either way is not measured (ROADMAP S5, R8); until it is,
the kernel rides the SAME decode_kernel knob rather than its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lora_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """Grid cell (b,): z = (x[b] @ A[ids[b]]) @ B[ids[b]].
    x (1, T, IN); a (1, IN, R); b (1, R, OUT); o (1, T, OUT)."""
    x = x_ref[0]
    # float32 accumulator, rounded to the compute dtype: see "Dtype
    # discipline" above.
    z = jax.lax.dot_general(
        x, a_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[0] = jax.lax.dot_general(
        z, b_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def fused_multi_lora(x: jax.Array,
                     a_stack: jax.Array,
                     b_stack: jax.Array,
                     adapter_ids: jax.Array,
                     *,
                     interpret: bool = False) -> jax.Array:
    """Per-row fused low-rank delta (UNSCALED — the caller applies
    alpha/r, keeping the scale in one place with the XLA twin).

    Args:
      x: (B, T, IN) input activations (contracted dims pre-flattened
        to one IN axis by the caller; same for OUT).
      a_stack: (slots, IN, R) resident adapter A stack.
      b_stack: (slots, R, OUT) resident adapter B stack.
      adapter_ids: (B,) int32 per-row slot indices (0 = identity).
      interpret: Pallas interpreter (CPU tier-1 pinning).

    Returns (B, T, OUT) in x.dtype.
    """
    batch, seq, d_in = x.shape
    _, _, rank = a_stack.shape
    d_out = b_stack.shape[-1]
    out = pl.pallas_call(
        _lora_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch,),
            in_specs=[
                pl.BlockSpec((1, seq, d_in), lambda b, ids: (b, 0, 0)),
                pl.BlockSpec((1, d_in, rank),
                             lambda b, ids: (ids[b], 0, 0)),
                pl.BlockSpec((1, rank, d_out),
                             lambda b, ids: (ids[b], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, seq, d_out),
                                   lambda b, ids: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((batch, seq, d_out), x.dtype),
        interpret=interpret,
    )(adapter_ids.astype(jnp.int32), x, a_stack, b_stack)
    return out
