"""Latent attention (MLA, DeepSeek-V2's form without a q down-projection):
the attention of a layer whose cache holds one latent a position.

With H = num_heads, n = mla_nope_dim, r = mla_rope_dim, R = mla_kv_rank
and v = mla_v_dim:

    q        = q_norm(q_proj x)                       (H, n + r), a head's RMSNorm (cfg.qk_norm)
    [c, kr]  = kv_down x;  c = kv_norm(c)             (R,) and (r,), kr one for every head
    q_r, kr  = rope(q[n:]), rope(kr)                  theta rope_theta, the pair (i, i + r/2)
    [kn, vh] = c @ kv_up                              (H, n) and (H, v)
    a        = softmax((q[:n] . kn + q_r . kr) / sqrt(n + r), causal) vh
    out      = o_proj(a * sigmoid(gate_proj x))      one gate a head

Cached is `[c | kr]`, R + r values a position (576 at Ling-3.0-flash's
sizes), and nothing is cached a head. Every path attends ABSORBED: a
head's query is taken into the latent space, `q_lat = kv_up_k q[:n]`
(R values), so that its score against a position is `[q_lat | q_r] .
[c | kr]` and its output is `(sum p c) @ kv_up_v`: one shared "head" of
R + r values is both key and value (its first R values the value), read
once for all H query heads. Prefill chunks, whole sequences and decode
steps take the same form; the expanded one (`kn`, `vh` a position) is
the reference's (perf/references/ling_hybrid.py) and the tests'.

The pool: (blocks, block, W) a layer, W = R + r rounded up to the lane
(640 for 576): a TPU lays a 576-wide row out over five 128-lane tiles
anyway, so the zeros in the last 64 cost no memory, and the decode
kernel (ops/paged_attention, one kv head of W with a query padded to W)
sees aligned rows. Positions are written through the block table as
K/V are (`transformer.Attention._paged_decode_attention`); a layer of
the carried stack reaches its part of the leaf by its index. A program
of few query rows a slot under the decode kernels reads a slot's live
blocks through ops/paged_attention, the same pool handed as K and as V;
a prefill chunk gathers the window and attends in XLA. Served from the
paged pool only (`cfg.paged_block_size`); the contiguous cache has no
latent layout.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.ops import paged_attention

F32 = jnp.float32
LANE = 128
LATENT = 'cached_latent'


def cache_width(cfg: ModelConfig) -> int:
    """Values a position of the pool holds: the latent and the rotary
    key, rounded up to a whole lane."""
    return -(-(cfg.mla_kv_rank + cfg.mla_rope_dim) // LANE) * LANE


def attend_latent(cfg: ModelConfig, q: jax.Array, window: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """The absorbed attention over a window of cached latents, in XLA.
    q: (B, T, H, W) absorbed queries; window: (B, S, W) the cached
    rows of positions 0 .. S - 1; positions: (B, T). Returns (B, T, H,
    R): each head's weighted sum of latents."""
    scale = (cfg.mla_nope_dim + cfg.mla_rope_dim) ** -0.5
    scores = jnp.einsum('bthw,bsw->bhts', q, window,
                        preferred_element_type=F32) * scale
    k_pos = jnp.arange(window.shape[1])[None, None, :]
    mask = k_pos <= cfg.last_key_seen(positions[:, :, None])
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(window.dtype)
    return jnp.einsum('bhts,bsr->bthr', probs,
                      window[..., :cfg.mla_kv_rank],
                      preferred_element_type=F32)


class LatentAttention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 block_tables: Optional[jax.Array] = None) -> jax.Array:
        # Late import: transformer.py is this module's importer's.
        from skypilot_tpu.models.transformer import (RMSNorm, apply_rope,
                                                     dense_general)
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h, nope, rope = cfg.num_heads, cfg.mla_nope_dim, cfg.mla_rope_dim
        rank, vdim = cfg.mla_kv_rank, cfg.mla_v_dim
        width = cache_width(cfg)
        q = dense_general(cfg, (h, nope + rope),
                          ('embed', 'heads', 'qkv_dim'), 'q_proj')(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg, name='q_norm')(q)
        down = dense_general(cfg, rank + rope, ('embed', None),
                             'kv_down')(x)
        c = RMSNorm(cfg, name='kv_norm')(down[..., :rank])
        k_rope = apply_rope(down[..., None, rank:], positions,
                            cfg.rope_theta)[:, :, 0]
        q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        kv_up = self.param('kv_up', nn.with_logical_partitioning(
            nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (None, 'heads', 'qkv_dim')), (rank, h, nope + vdim),
            jnp.dtype(cfg.param_dtype)).astype(dtype)
        q_lat = jnp.einsum('bthn,rhn->bthr', q[..., :nope],
                           kv_up[..., :nope],
                           preferred_element_type=F32).astype(dtype)
        pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                                + [(0, width - rank - rope)])
        q_abs = pad(jnp.concatenate([q_lat, q_rope], axis=-1))
        rows = pad(jnp.concatenate([c, k_rope], axis=-1))   # (B, T, W)
        if cfg.decode:
            lat = self._paged(q_abs, rows, positions, block_tables)
        else:
            # a whole sequence from position 0: the rows ARE the window
            lat = attend_latent(cfg, q_abs, rows, positions)
        out = jnp.einsum('bthr,rhv->bthv', lat.astype(dtype),
                         kv_up[..., nope:],
                         preferred_element_type=F32)
        gate = dense_general(cfg, h, ('embed', 'heads'), 'gate_proj')(x)
        out = out * jax.nn.sigmoid(gate.astype(F32))[..., None]
        return dense_general(cfg, cfg.d_model,
                             ('heads', 'qkv_dim', 'embed'), 'o_proj',
                             axis=(-2, -1))(out.astype(dtype))

    def _paged(self, q_abs, rows, positions, block_tables):
        """Write this chunk's rows into the pool through the table and
        attend over each slot's window. Returns (B, T, H, R)."""
        # Late import: only a decoding model loads the module.
        from skypilot_tpu.models import cache_carry
        from skypilot_tpu.models.ssm import _rebox, _unbox
        cfg = self.cfg
        if not cfg.paged_block_size or block_tables is None:
            raise NotImplementedError(
                'a latent cache is served from the paged pool only '
                '(paged_block_size > 0, with block tables)')
        bs, nblocks = cfg.paged_block_size, cfg.paged_num_blocks
        bps = cfg.max_seq_len // bs
        width = rows.shape[-1]
        pool_var = self.variable(
            'cache', LATENT, lambda: nn.with_logical_partitioning(
                jnp.zeros, (None, None, None))(
                    (nblocks, bs, width), rows.dtype))
        pool, box = _unbox(pool_var)
        layer = cache_carry.current_layer()
        first = 0 if layer is None else layer * nblocks
        log_block = jnp.clip(positions // bs, 0, block_tables.shape[1] - 1)
        phys = jnp.take_along_axis(block_tables, log_block, axis=1)
        flat_idx = ((first + phys) * bs + positions % bs).reshape(-1)
        flat = pool.reshape(-1, width).at[flat_idx].set(
            rows.reshape(-1, width))
        _rebox(pool_var, box, flat.reshape(pool.shape))
        batch, cur_len = positions.shape
        if (cfg.decode_kernel != 'xla'
                and paged_attention.takes_rows(cur_len)):
            # one kv head of W, both key and value: the latents' first
            # R values of the output are the weighted sum
            blocks = flat.reshape(-1, bs, 1, width)
            out = paged_attention.paged_decode_attention(
                q_abs, blocks, blocks, block_tables[:, :bps], positions,
                last_keys=cfg.last_key_seen(positions), first_block=first,
                sm_scale=(cfg.mla_nope_dim + cfg.mla_rope_dim) ** -0.5,
                interpret=cfg.decode_kernel == 'pallas_interpret')
            return out[..., :cfg.mla_kv_rank]
        gidx = ((first + block_tables[:, :bps, None]) * bs
                + jnp.arange(bs)[None, None, :]).reshape(batch, bps * bs)
        return attend_latent(cfg, q_abs, flat[gidx], positions)
