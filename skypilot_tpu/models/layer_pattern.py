"""A stack that is not one layer type: the layer of a model whose
blocks differ by position (`cfg.has_layer_pattern`).

Training's `DecoderLayer` is one kind of block repeated. Here a model
may open with dense layers and go on with expert layers
(`cfg.num_dense_layers`), and every layer has an attention KIND of its
own, `cfg.layer_kinds[l] = (window, rope)`: the keys a query looks back
over (0 = all) and whether rotary position is applied. The kinds are
not compiled in: the layer loop scans them beside the stacked weights
(`cache_carry.carry_layers`), a layer reads its own through
`cache_carry.current_kind()`, and so there is still ONE program over
each group of layers, whatever the pattern. A layer's MIXER kind
(`cfg.mixer_kinds`: attention, Kimi delta attention, latent attention)
and its expert clamps are compiled in: the loop runs a group a run of
one such kind (`cache_carry.layer_groups`). K/V leaves have one shape
for both kinds (a windowed layer keeps its blocks for the whole
context, in the one shared table), so the carried cache stays one
stacked leaf a group.

The block (sandwich norms when `cfg.post_norms`):

    x = x + post_attn_norm(attn(attn_norm(x)))
    x = x + post_mlp_norm(mlp(mlp_norm(x)))        mlp: SwiGLU | MoEBlock

and its attention: RMSNorm over head_dim on every q and k head
(`cfg.qk_norm`), rotary on the layers whose kind says so, the kind's
window in the mask (`transformer.layer_window`), a sigmoid gate on the
heads' output before `o_proj` (`cfg.attn_gate`). The cache, paged or
contiguous, is `Attention`'s own, inherited: this file adds no cache
code.

Serving only: the full-sequence path (init, and `Transformer.apply` of a
whole sequence in the tests) materialises the scores, and nothing here
is rematerialised for a backward pass.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from skypilot_tpu.models import cache_carry
from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.moe import MoEBlock
from skypilot_tpu.models.transformer import (Attention, RMSNorm, SwiGLU,
                                             _apply_proj, _attend_window,
                                             apply_rope, dense_general)
from skypilot_tpu.parallel import sharding


class PatternAttention(Attention):
    """`Attention` with a kind a layer and the switches no uniform model
    has. The projections and the decode cache are the parent's."""

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None,
                 adapter_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        if cfg.pos_embedding != 'rope' or cfg.rotary_pct != 1.0:
            raise NotImplementedError(
                'a layer pattern is modeled with whole-head rotary only')
        dense = lambda feats, axes, name: dense_general(
            cfg, feats, axes, name, use_bias=cfg.qkv_bias)
        heads = (cfg.num_heads, cfg.head_dim)
        kv = (cfg.num_kv_heads, cfg.head_dim)
        q = _apply_proj(dense(heads, ('embed', 'heads', 'qkv_dim'),
                              'q_proj'), x, adapter_ids)
        k = _apply_proj(dense(kv, ('embed', 'kv_heads', 'qkv_dim'),
                              'k_proj'), x, adapter_ids)
        v = _apply_proj(dense(kv, ('embed', 'kv_heads', 'qkv_dim'),
                              'v_proj'), x, adapter_ids)
        if cfg.qk_norm:
            q = RMSNorm(cfg, name='q_norm')(q)
            k = RMSNorm(cfg, name='k_norm')(k)
        kind = cache_carry.current_kind()
        if kind is None or cfg.rope_scaling is not None:
            raise NotImplementedError(
                'PatternAttention runs inside cache_carry.carry_layers, '
                'with plain rotary')
        # Rotary at angle zero is the identity to the last bit, so a
        # layer without it multiplies its positions by 0: one program
        # for both kinds.
        rot = positions * kind[1].astype(positions.dtype)
        q = apply_rope(q, rot, cfg.rope_theta)
        k = apply_rope(k, rot, cfg.rope_theta)
        if cfg.decode:
            out = self._decode_attention(q, k, v, positions, block_tables)
        else:
            # a whole sequence from position 0: the keys ARE the window
            out = _attend_window(cfg, q, k, v, None, None, positions)
        if cfg.attn_gate:
            gate = _apply_proj(dense(heads, ('embed', 'heads', 'qkv_dim'),
                                     'gate_proj'), x, adapter_ids)
            out = out * nn.sigmoid(gate)
        out = _apply_proj(
            dense_general(cfg, cfg.d_model, ('heads', 'qkv_dim', 'embed'),
                          'o_proj', axis=(-2, -1), use_bias=cfg.o_bias),
            out, adapter_ids)
        return sharding.constrain(out, 'batch', 'seq', 'act_embed')


class PatternLayer(nn.Module):
    """One block. `dense` says which MLP it carries: the dense SwiGLU
    (a leading layer, or any layer of a model without experts) or the
    expert block; `mixer` what mixes positions: attention of the layer's
    kind, Kimi delta attention (models/kda.py; its state beside the
    cache's K/V, by slot) or latent attention (models/mla.py; a latent
    pool in K/V's place); `limits` the expert block's (routed, shared)
    SwiGLU clamps."""
    cfg: ModelConfig
    dense: bool
    mixer: str = 'attention'
    limits: Tuple[float, float] = (0.0, 0.0)

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None,
                 adapter_ids: Optional[jax.Array] = None,
                 state_rows: Optional[Tuple] = None,
                 stacks: Optional[Tuple] = None) -> jax.Array:
        cfg = self.cfg
        if cfg.ssm_heads or cfg.parallel_block:
            raise NotImplementedError(
                'a layer pattern is modeled with the sequential '
                'attention-then-MLP block only')
        post = (lambda name, y: RMSNorm(cfg, name=name)(y)) \
            if cfg.post_norms else (lambda name, y: y)
        h = RMSNorm(cfg, name='attn_norm')(x)
        if self.mixer == 'kda':
            from skypilot_tpu.models.kda import KimiDeltaMixer
            y = KimiDeltaMixer(cfg, name='mixer')(h, positions, state_rows)
        elif self.mixer == 'mla':
            from skypilot_tpu.models.mla import LatentAttention
            y = LatentAttention(cfg, name='attn')(h, positions,
                                                  block_tables)
        else:
            y = PatternAttention(cfg, name='attn')(
                h, positions, block_tables, adapter_ids)
        x = x + post('post_attn_norm', y)
        h = RMSNorm(cfg, name='mlp_norm')(x)
        if self.dense:
            y = SwiGLU(cfg, name='mlp')(h, adapter_ids)
        else:
            y = MoEBlock(cfg, self.limits, name='moe')(
                h, real_positions(h, state_rows), stacks)
        return x + post('post_mlp_norm', y)


def real_positions(x: jax.Array, state_rows: Optional[Tuple]
                   ) -> Optional[jax.Array]:
    """(B, T) bool: which positions of x are real tokens, from
    `state_rows = (slots, valid)` as `Transformer.__call__` takes it
    (valid[b] of row b's T positions are real: a chunk's right pads and
    an inert decode row are not). None where the caller says nothing:
    every position is real."""
    if state_rows is None or state_rows[1] is None:
        return None
    return jnp.arange(x.shape[1])[None, :] < state_rows[1][:, None]
