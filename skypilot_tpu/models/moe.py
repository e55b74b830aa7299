"""Mixture-of-Experts block with expert parallelism.

Experts are a stacked weight dim carrying logical axis 'expert' → mesh axis
`ep`. Three formulations, selected by ``cfg.moe_impl``. **Whose is
which:** the TRAINER uses `dispatch` (the default) or `dense`; the
ENGINE serves `dropless`. Capacity dispatch drops the tokens that
overflow an expert's buffer, and which tokens those are depends on who
else is in the batch: a served request's output would change with its
neighbours, and neither the engine's tests nor a comparison with a
reference could hold. So nothing that serves takes `dispatch`.

- **dispatch** (default; training): GShard/Switch-style capacity-based
  token dispatch. Each token's top-k experts get it via a one-hot
  dispatch einsum into per-expert capacity buffers (E, C, D); only the
  chosen experts compute — k/E of the dense formulation's expert FLOPs.
  Under `ep` sharding GSPMD turns the token-sharded → expert-sharded
  buffer movement into the EP collective (an all-to-all when tokens and
  experts ride the same mesh axis; otherwise an all-reduce of the
  capacity buffers with identical volume) — the TPU-native EP data
  path, MaxText's dense-dispatch formulation. (jucor/skypilot has no
  in-tree MoE; its Mixtral/dbrx recipes delegate EP to vLLM/megablocks,
  SURVEY §2.9.) Tokens over an expert's capacity are dropped (standard
  GShard semantics; capacity_factor 1.25 gives headroom).
- **dense** (training's exact reference, tiny configs): every expert
  computes every token and a top-k one-hot combine zeroes the rest.
  Exact (no drops), E/k× more expert FLOPs.
- **dropless** (serving): the layer is told which experts it holds
  (`cfg.experts_held`, `cfg.first_expert`: one chip's share of an
  expert-parallel layer, or all of them). The router keeps its whole
  width; selection, the weights' normalisation and the scale are over
  the token's whole top k, whoever holds them. The (token, choice)
  pairs held here are sorted by expert and go through three grouped
  matrix products over the expert stacks (`ops/grouped_matmul.py`: on
  a TPU a Pallas kernel of the repo's own that streams each touched
  expert's matrix from HBM once, elsewhere `jax.lax.ragged_dot`; an
  expert no token chose is not read; PERF.md section 6, PR 34); what
  the absent experts would have added is left out, and no code stands
  in for their chips or their exchange. A layer loop that holds every
  layer's experts in one stack hands them over whole (`ExpertStacks`)
  beside the layer's index, which the products add where they address
  the weights: a slice of the layer's experts would be copied first. A
  token whose choices all lie elsewhere gets the shared expert's output
  alone. Shapes are static whatever the routing: every pair has a row,
  the pairs held elsewhere sorted behind the last group, where no
  product reaches them. Pads of a chunk and inert slots of a decode
  step (`valid`) route nowhere and count nowhere. Scoring is Mixtral's
  softmax over the chosen k, or a sigmoid over all experts with a
  selection bias (`cfg.router_score`, `cfg.router_bias`), chosen within
  a token's best groups where the experts are grouped (`cfg.n_group`,
  `limit_groups`; without groups the selection is untouched); an expert's
  width may differ from `d_mlp` (`cfg.d_expert`), and one shared expert
  may run on every token (`cfg.d_shared_expert`).

  The dropless layer sows what it routed into the 'moe_stats'
  collection (a no-op unless the caller makes it mutable, as the engine
  does): `ROUTE_COUNTS` a call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

import dataclasses
from typing import Optional, Tuple

from skypilot_tpu.models.configs import ModelConfig
from skypilot_tpu.models.transformer import SwiGLU, swiglu_clamp
from skypilot_tpu.ops.grouped_matmul import grouped_matmul
from skypilot_tpu.parallel import sharding

# What a dropless layer counts a call, in this order: (token, choice)
# pairs routed (k a real token), pairs whose expert is held here,
# distinct held experts that got a token, the largest held expert's
# tokens, real tokens whose kept groups hold an expert held here (every
# real token without groups).
ROUTE_COUNTS = ('pairs_routed', 'pairs_held', 'experts_touched',
                'max_expert_load', 'tokens_group_held')


def expert_stacks(module: nn.Module, cfg: ModelConfig, layers: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(w_gate, w_up, w_down) of the experts held here, declared in
    `module`'s scope for `layers` layers at once: (layers, held, in,
    out)."""
    d, m, held = cfg.d_model, cfg.expert_width, cfg.held_experts
    stack = lambda name, shape, axes: module.param(
        name, nn.with_logical_partitioning(
            nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                         batch_axis=(0, 1)),
            ('layers', 'expert') + axes),
        (layers, held) + shape, jnp.dtype(cfg.param_dtype))
    return (stack('w_gate', (d, m), ('embed', 'mlp')),
            stack('w_up', (d, m), ('embed', 'mlp')),
            stack('w_down', (m, d), ('mlp', 'embed')))


class ExpertStacks(nn.Module):
    """Every expert layer's held experts as three whole leaves, declared
    OUTSIDE the layer loop (`cache_carry.carry_layers`), which hands
    them to each layer unsliced beside the layer's index."""
    cfg: ModelConfig
    layers: int

    @nn.compact
    def __call__(self):
        return expert_stacks(self, self.cfg, self.layers)


def limit_groups(cfg: ModelConfig, select: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Group-limited selection (`cfg.n_group` > 1): a group's score is
    the sum of its two best `select` values, and the experts outside a
    token's `cfg.topk_group` best groups are put out of reach (-inf).
    Returns (select, (n, n_group) bool: the groups each token keeps)."""
    n, e = select.shape
    grouped = select.reshape(n, cfg.n_group, e // cfg.n_group)
    best2 = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)     # (n, groups)
    _, kept = jax.lax.top_k(best2, cfg.topk_group)
    keep = jnp.zeros((n, cfg.n_group), bool).at[
        jnp.arange(n)[:, None], kept].set(True)
    select = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, e)
    return select, keep


class MoEBlock(nn.Module):
    cfg: ModelConfig
    # (routed, shared) SwiGLU clamps of this layer (0: none)
    limits: Tuple[float, float] = (0.0, 0.0)

    @nn.compact
    def __call__(self, x: jax.Array,
                 valid: Optional[jax.Array] = None,
                 stacks: Optional[Tuple] = None) -> jax.Array:
        """x: (B, S, D). The dropless layer alone reads the rest.
        valid: (B, S) bool or None, the positions that are real tokens.
        stacks: None (the layer declares its own expert weights), or
        `(ExpertStacks' three leaves, this layer's index in them)` from
        a layer loop that holds every layer's experts in one stack."""
        cfg = self.cfg
        if cfg.moe_impl == 'dropless':
            return self._dropless(x, valid, stacks)
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        e, d, m = cfg.num_experts, cfg.d_model, cfg.d_mlp

        router_w = self.param(
            'router',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('embed', 'expert')),
            (d, e), pdtype)
        w_gate = self.param(
            'w_gate',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('expert', 'embed', 'mlp')),
            (e, d, m), pdtype)
        w_up = self.param(
            'w_up',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('expert', 'embed', 'mlp')),
            (e, d, m), pdtype)
        w_down = self.param(
            'w_down',
            nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                         ('expert', 'mlp', 'embed')),
            (e, m, d), pdtype)

        # Routing: top-k softmax over experts, renormalized (Mixtral rule).
        logits = jnp.einsum('bsd,de->bse', x.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        topk_vals, topk_idx = jax.lax.top_k(logits, cfg.experts_per_token)
        topk_probs = jax.nn.softmax(topk_vals, axis=-1)       # (B,S,k)

        if cfg.moe_impl == 'dense':
            return self._dense(x, topk_idx, topk_probs,
                               (w_gate, w_up, w_down), dtype)
        if cfg.moe_impl != 'dispatch':
            # A typo must not silently switch semantics (dispatch drops
            # over-capacity tokens; dense is exact).
            raise ValueError(
                f'Unknown moe_impl {cfg.moe_impl!r}; expected '
                f"'dispatch', 'dense' or 'dropless'.")
        return self._dispatch(x, topk_idx, topk_probs,
                              (w_gate, w_up, w_down), dtype)

    # ---------------- dense reference ----------------

    def _dense(self, x, topk_idx, topk_probs, weights, dtype):
        cfg = self.cfg
        e = cfg.num_experts
        w_gate, w_up, w_down = weights
        # Combine weights as a dense (B,S,E) map (one-hot sum over k).
        combine = jnp.sum(
            jax.nn.one_hot(topk_idx, e, dtype=jnp.float32) *
            topk_probs[..., None], axis=-2)                    # (B,S,E)
        combine = sharding.constrain(combine, 'batch', 'seq', None)

        xb = x.astype(dtype)
        # Dense dispatch: each expert runs all tokens; EP partitions `e`.
        gate = jnp.einsum('bsd,edm->ebsm', xb, w_gate.astype(dtype))
        up = jnp.einsum('bsd,edm->ebsm', xb, w_up.astype(dtype))
        h = nn.silu(gate) * up                                 # (E,B,S,M)
        out = jnp.einsum('ebsm,emd->ebsd', h, w_down.astype(dtype))
        out = jnp.einsum('ebsd,bse->bsd', out.astype(jnp.float32),
                         combine)
        out = out.astype(dtype)
        return sharding.constrain(out, 'batch', 'seq', 'act_embed')

    # ---------------- capacity-based dispatch ----------------

    @staticmethod
    def _group_size(g: int) -> int:
        """Largest divisor of g that is ≤1024 and a power of two when
        possible. Grouping bounds the one-hot dispatch/combine tensors to
        num_groups × gs × E × C = G·gs·k·cf elements — LINEAR in total
        tokens (ungrouped, C ≈ G·k/E makes them quadratic in G and OOMs
        at exactly the batch·seq scales MoE targets; GShard/MaxText group
        the same way)."""
        gs = 1
        while gs * 2 <= min(g, 1024) and g % (gs * 2) == 0:
            gs *= 2
        if gs == 1 and g <= 4096:
            return g  # odd small token counts: one group
        return gs

    def _dispatch(self, x, topk_idx, topk_probs, weights, dtype):
        cfg = self.cfg
        e, k = cfg.num_experts, cfg.experts_per_token
        w_gate, w_up, w_down = weights
        b, s, d = x.shape
        g = b * s  # tokens
        gs = self._group_size(g)
        n = g // gs  # groups
        # Per-expert capacity PER GROUP (static: shapes must not depend
        # on routing).
        capacity = int(-(-gs * k // e) * cfg.moe_capacity_factor)
        capacity = max(1, min(capacity, gs))

        flat_idx = topk_idx.reshape(n, gs, k)                  # (N,g,k)
        flat_probs = topk_probs.reshape(n, gs, k).astype(jnp.float32)
        xf = x.reshape(n, gs, d).astype(dtype)

        # Position of each (token, choice) within its expert's per-group
        # buffer: running count of prior assignments to the same expert,
        # priority by (choice rank, token order) — GShard's ordering.
        choice_onehot = jax.nn.one_hot(flat_idx, e,
                                       dtype=jnp.int32)       # (N,g,k,E)
        # Flatten choices k-major so 1st choices beat 2nd choices.
        seq_onehot = choice_onehot.transpose(0, 2, 1, 3).reshape(
            n, k * gs, e)
        positions = jnp.cumsum(seq_onehot, axis=1) - seq_onehot
        positions = jnp.sum(positions * seq_onehot, axis=-1)   # (N,k*g)
        positions = positions.reshape(n, k, gs).transpose(0, 2, 1)
        keep = positions < capacity                             # (N,g,k)

        # dispatch[n,g,e,c] = 1 iff token (n,g) fills slot c of expert e.
        pos_onehot = jax.nn.one_hot(positions, capacity,
                                    dtype=jnp.float32)         # (N,g,k,C)
        dispatch = jnp.einsum(
            'ngke,ngkc->ngec',
            choice_onehot.astype(jnp.float32) *
            keep[..., None].astype(jnp.float32),
            pos_onehot)                                         # (N,g,E,C)
        combine = jnp.einsum(
            'ngke,ngkc,ngk->ngec',
            choice_onehot.astype(jnp.float32),
            pos_onehot,
            flat_probs * keep.astype(jnp.float32))              # (N,g,E,C)

        # Token-sharded → expert-sharded: this reshape IS the EP
        # collective under `ep` (GSPMD inserts it from the constraints).
        expert_in = jnp.einsum('ngd,ngec->encd', xf,
                               dispatch.astype(dtype))          # (E,N,C,D)
        expert_in = sharding.constrain(expert_in, 'expert', None, None,
                                       None)
        gate = jnp.einsum('encd,edm->encm', expert_in,
                          w_gate.astype(dtype))
        up = jnp.einsum('encd,edm->encm', expert_in, w_up.astype(dtype))
        h = nn.silu(gate) * up                                  # (E,N,C,M)
        h = sharding.constrain(h, 'expert', None, None, 'mlp')
        expert_out = jnp.einsum('encm,emd->encd', h,
                                w_down.astype(dtype))           # (E,N,C,D)
        expert_out = sharding.constrain(expert_out, 'expert', None, None,
                                        None)
        # Expert-sharded → token-sharded (the return collective), with
        # the router probabilities applied in fp32.
        out = jnp.einsum('encd,ngec->ngd',
                         expert_out.astype(jnp.float32), combine)
        out = out.reshape(b, s, d).astype(dtype)
        return sharding.constrain(out, 'batch', 'seq', 'act_embed')

    # ---------------- dropless, for serving ----------------

    def _dropless(self, x, valid, stacks):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        e, k, d = cfg.num_experts, cfg.experts_per_token, cfg.d_model
        held, first = cfg.held_experts, cfg.first_expert
        if not 0 <= first <= e - held:
            raise ValueError(
                f'experts [{first}, {first + held}) are not among the '
                f'{e} the router scores')
        if cfg.mlp_style != 'glu':
            raise NotImplementedError(
                'the dropless expert is a gated MLP (gate, up, down)')
        router_w = self.param(
            'router', nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('embed', None)),
            (d, e), jnp.dtype(cfg.param_dtype))
        if stacks is None:
            # a layer on its own: its experts are a stack of one layer
            w_gate, w_up, w_down = expert_stacks(self, cfg, 1)
            layer = 0
        else:
            (w_gate, w_up, w_down), layer = stacks
        # The grouped products are handed EVERY layer's experts whole,
        # beside this layer's index in them: a slice of the layer's own
        # would be a copy of it, 1.8 GB a layer at Trinity's widths,
        # before a kernel could read it.

        b, s, _ = x.shape
        n = b * s
        xf = x.reshape(n, d)
        # ---- routing, in float32, over every expert the model has ----
        logits = jnp.dot(xf.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if cfg.router_score == 'sigmoid':
            scores = jax.nn.sigmoid(logits)
        elif cfg.router_score == 'softmax':
            scores = logits        # Mixtral: softmax over the chosen k
        else:
            raise ValueError(
                f'Unknown router_score {cfg.router_score!r}; expected '
                f"'softmax' or 'sigmoid'.")
        select = scores
        if cfg.router_bias:
            # for SELECTION only: the weights are the scores themselves
            select = scores + self.param(
                'expert_bias', nn.with_logical_partitioning(
                    nn.initializers.zeros, (None,)),
                (e,), jnp.float32)
        real = (jnp.ones((n,), bool) if valid is None
                else valid.reshape(n))
        if cfg.n_group > 1:
            select, keep = limit_groups(cfg, select)
            size = e // cfg.n_group
            groups = jnp.arange(cfg.n_group)
            mine = ((groups + 1) * size > first) & (
                groups * size < first + held)
            group_held = real & jnp.any(keep & mine, axis=-1)
        else:
            group_held = real
        _, chosen = jax.lax.top_k(select, k)                  # (n, k)
        weight = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.router_score == 'softmax':
            weight = jax.nn.softmax(weight, axis=-1)
        elif cfg.route_norm:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        weight = weight * cfg.route_scale

        # ---- the pairs held here, sorted by expert ----
        local = chosen - first
        here = (local >= 0) & (local < held) & real[:, None]  # (n, k)
        # a pair held elsewhere (or a pad's) sorts behind the last group
        group = jnp.where(here, local, held).reshape(n * k)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        rows = xf[order // k].astype(dtype)                  # (n k, d)
        gate, up = swiglu_clamp(grouped_matmul(rows, w_gate, sizes, layer),
                                grouped_matmul(rows, w_up, sizes, layer),
                                self.limits[0])
        hidden = (nn.silu(gate) * up).astype(dtype)
        out = grouped_matmul(hidden, w_down, sizes, layer,
                             preferred_element_type=jnp.float32)
        # ---- back to token order, weighted; rows past the last group
        # hold nothing that was computed, so they are selected out, not
        # multiplied by zero ----
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        out = out[back].reshape(n, k, d)
        routed = jnp.sum(
            jnp.where(here[..., None], out * weight[..., None], 0.0),
            axis=1)
        y = routed.astype(dtype).reshape(b, s, d)
        if cfg.d_shared_expert:
            y = y + SwiGLU(dataclasses.replace(
                cfg, d_mlp=cfg.d_shared_expert), self.limits[1],
                name='shared')(x)
        if not self.is_initializing():
            self.sow('moe_stats', 'counts', jnp.stack([
                k * jnp.sum(real, dtype=jnp.int32),
                jnp.sum(here, dtype=jnp.int32),
                jnp.sum(sizes > 0, dtype=jnp.int32),
                jnp.max(sizes),
                jnp.sum(group_held, dtype=jnp.int32)]),
                init_fn=lambda: jnp.zeros((len(ROUTE_COUNTS),), jnp.int32),
                reduce_fn=lambda a, c: a + c)
        return sharding.constrain(y, 'batch', 'seq', 'act_embed')
