"""Model configuration registry.

The recipe tree (llm/) references these by name, the way the reference's
recipes name HF checkpoints (reference: llm/llama-3_1-finetuning,
llm/mixtral, llm/gemma, llm/qwen, llm/gpt-2 per Appendix A of SURVEY.md).
The base architecture is Llama-3-style decoder-only (RMSNorm, RoPE, GQA,
SwiGLU); the family knobs below compose to express the other families the
reference's recipe tree serves — Gemma ((1+w)-RMSNorm, GeGLU, embedding
scaling, tied unembed, 256-wide heads), Gemma-2 (attention/final logit
softcaps), Qwen2 (QKV bias), GPT-2 (LayerNorm, learned positions, plain
GELU MLP, biases everywhere) — and MoE (Mixtral-style) is switched by
``num_experts``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    d_mlp: int
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    # Llama-3.1-style rope frequency scaling for long context, as
    # (factor, low_freq_factor, high_freq_factor,
    #  original_max_position_embeddings) — None ⇒ plain rope. Low
    # frequencies (long wavelengths vs the original training window)
    # divide by `factor`, high frequencies pass through, the band
    # between interpolates smoothly (HF `rope_type: llama3`).
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    norm_eps: float = 1e-5
    # --- Architecture-family knobs (compose; Llama-3 is all-defaults) ---
    # Gemma fixes head_dim=256 independent of d_model/num_heads.
    head_dim_override: Optional[int] = None
    # GLU gate activation ('silu' = SwiGLU/Llama, 'gelu' = GeGLU/Gemma).
    mlp_activation: str = 'silu'
    # 'glu' = gate/up/down (3 matmuls); 'plain' = up/down (GPT-2).
    mlp_style: str = 'glu'
    # 'rms' (Llama), 'rms_plus1' (Gemma: out = normed·(1+w)),
    # 'layernorm' (GPT-2: mean-centred, scale+bias).
    norm_style: str = 'rms'
    # 'rope' | 'learned' (GPT-2 absolute position table).
    pos_embedding: str = 'rope'
    # LayerNorm bias (norm_style='layernorm' only): GPT-2/Falcon carry
    # scale+bias; DBRX is bias-free (scale-only mean-centred norm).
    norm_bias: bool = True
    # Partial rotary (Phi/NeoX style): rope rotates only the first
    # rotary_pct·head_dim dims; the remainder passes through unrotated.
    rotary_pct: float = 1.0
    # Phi puts a bias on the (untied) unembed projection.
    lm_head_bias: bool = False
    # Clamp Q/K/V activations to ±qkv_clip after projection (DBRX's
    # clip_qkv=8 training-stability trick; 0 ⇒ off).
    qkv_clip: float = 0.0
    qkv_bias: bool = False            # Qwen2 (and GPT-2)
    o_bias: bool = False              # GPT-2
    mlp_bias: bool = False            # GPT-2
    tie_embeddings: bool = False      # Gemma, GPT-2: unembed = embedᵀ
    scale_embed_by_dim: bool = False  # Gemma: x ·= sqrt(d_model)
    # Gemma-2 logit softcaps (0 ⇒ off). Softcapped attention runs on the
    # XLA path (tanh fuses into the fwd matmul); the pallas kernel rejects
    # it explicitly rather than silently dropping the cap.
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # Falcon-style parallel block: ONE shared pre-norm feeds attention
    # and MLP, whose outputs add into the residual together
    # (x + attn(ln(x)) + mlp(ln(x))) — vs the sequential default. Pairs
    # with MQA (num_kv_heads=1) and 'layernorm' in the Falcon family.
    parallel_block: bool = False
    # --- Parallel state-space mixer (Falcon-H1; models/ssm.py) ---
    # ssm_heads > 0 ⇒ every block runs a Mamba-2 (SSD) mixer BESIDE
    # attention on one shared pre-norm; both outputs add into the
    # residual together, then a sequential MLP follows. The mixer is
    # ssm_heads x ssm_head_dim channels wide (d_ssm), each head a
    # (ssm_head_dim, ssm_state) recurrent state with one scalar decay;
    # ssm_groups sets how many B/C pairs the heads share; a causal
    # depthwise convolution of ssm_conv taps runs over x, B and C.
    # Prefill walks a chunk in blocks of ssm_chunk positions (the
    # chunked SSD form), decode by the one-step recurrence.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_conv_bias: bool = True
    ssm_proj_bias: bool = False
    # Gated grouped RMSNorm on the mixer's output (mamba_rms_norm), and
    # whether the norm comes before the gate (mamba_norm_before_gate).
    ssm_gated_norm: bool = True
    ssm_norm_before_gate: bool = False
    # The recurrent state's type in the 'cache' collection (the
    # convolution's carried inputs are held in the compute dtype).
    ssm_state_dtype: str = 'float32'
    # Rows of the per-slot recurrent-state leaves. 0 ⇒ the call's batch
    # (contiguous caches); a paged engine sets it to num_slots, because
    # its prefill runs at batch 1 over the one shared cache tree.
    state_slots: int = 0
    # µP forward multipliers (Falcon-H1). All 1.0 ⇒ no multiply is
    # traced, so every other family's program is unchanged.
    embed_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    # one a segment of the mixer's input projection: z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    # (inside the gate's activation, on the MLP's output)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # Mistral-style uniform sliding window, in keys (0 ⇒ full causal).
    # The pallas kernels skip blocks outside the window, so long-sequence
    # attention compute drops from O(S²) to O(S·window).
    sliding_window: int = 0
    # Weight-only quantization for SERVING ('none'|'int8'). Decode is
    # HBM-bandwidth-bound on reading weights; int8 kernels + per-output-
    # channel fp32 scales halve that traffic (models/quantize.py converts
    # a float checkpoint; training always runs float).
    weight_quant: str = 'none'
    # LoRA fine-tuning (0 ⇒ off; reference recipe this serves:
    # llm/llama-3_1-finetuning/lora.yaml — there torchtune LoRA on GPUs).
    # When lora_rank > 0 each targeted projection keeps its frozen base
    # kernel and adds y += (alpha/r)·B(A(x)) with A ~ N(0, 1/r), B = 0 —
    # identical forward at init. `lora_targets` is a comma list from
    # {q,k,v,o,gate,up,down} (module names <t>_proj). Train with
    # trainer.py's masked optimizer (only lora_a/lora_b update); merge
    # for serving/export with models/lora.merge_lora.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: str = 'q,v'
    # Multi-tenant serving (docs/serving.md "Multi-tenant serving"):
    # >0 ⇒ each LoRA-targeted projection becomes
    # transformer.MultiLoRADenseGeneral — base kernel params unchanged
    # (plain checkpoints line up), plus a device-resident STACK of
    # serve_adapters loadable adapters in the separate 'adapters'
    # variable collection ((serve_adapters+1, ...) leaves; slot 0 is
    # the all-zero identity so base-model requests ride the same
    # kernel). A per-row adapter-index vector drives a segmented
    # gather inside the projection, so one decode dispatch serves
    # many tenants' adapters at once. Uses lora_rank/lora_alpha/
    # lora_targets for the adapter geometry (uniform across residents).
    serve_adapters: int = 0
    # When vocab_size is padded for MXU tiling (e.g. GPT-2 50257→50304),
    # the REAL vocabulary size: logits beyond it are masked to -inf so
    # temperature sampling can never emit an invalid token id (padded
    # embedding rows are zeros, which would otherwise score ~0 — often
    # above real tokens). 0 ⇒ no padding.
    unpadded_vocab_size: int = 0
    # MoE (0 ⇒ dense SwiGLU MLP).
    num_experts: int = 0
    experts_per_token: int = 2
    # Three formulations (models/moe.py says which is whose):
    # 'dispatch' = capacity-based token dispatch (GShard-style: only the
    # chosen k experts compute each token; the dispatch einsum reshapes
    # tokens expert-major, which under `ep` sharding lowers to an
    # all-to-all over ICI). 'dense' = every expert computes every token
    # with a one-hot combine (exact, simple, E/k× more FLOPs — kept as
    # the reference implementation and for tiny configs). 'dropless' =
    # the (token, choice) pairs sorted by expert and grouped matrix
    # products over the expert stacks: no capacity, nothing dropped, a
    # token's output does not depend on its batch: what the engine
    # serves.
    moe_impl: str = 'dispatch'
    # Per-expert buffer = ceil(tokens·k/E) · capacity_factor; tokens over
    # capacity are dropped (their combine weight contributes nothing —
    # standard GShard/Switch semantics).
    moe_capacity_factor: float = 1.25
    # --- The dropless expert layer (moe_impl='dropless'; serving) ---
    # experts_held > 0 ⇒ this program holds experts [first_expert,
    # first_expert + experts_held) of the num_experts the router scores:
    # one chip's share of an expert-parallel layer. The router keeps
    # its whole width, and selection, normalisation and scale are over
    # the token's whole top k, whoever holds them; the layer adds what
    # ITS experts give and leaves the rest out (models/moe.py). 0 ⇒
    # all of them.
    experts_held: int = 0
    first_expert: int = 0
    # An expert's hidden width where it is not d_mlp (0 ⇒ d_mlp), and
    # the width of one shared expert that every token passes through
    # beside its routed ones (0 ⇒ none).
    d_expert: int = 0
    d_shared_expert: int = 0
    # 'softmax' over the chosen k (Mixtral), or 'sigmoid' over all
    # experts, in float32; router_bias adds a per-expert bias to the
    # scores for SELECTION only (the weights are the chosen scores);
    # route_norm divides the chosen scores by their sum, route_scale
    # multiplies them.
    router_score: str = 'softmax'
    router_bias: bool = False
    route_norm: bool = True
    route_scale: float = 1.0
    # --- A stack that is not one layer type (models/layer_pattern.py) ---
    # The first num_dense_layers layers of an expert model carry a dense
    # MLP of width d_mlp; they are a stacked group of their own
    # ('dense_layers') before the expert group ('layers').
    num_dense_layers: int = 0
    # One (window, rope) pair a layer: the keys a query looks back over
    # (0 ⇒ all) and whether rotary position is applied. () ⇒ every
    # layer is of the one kind that sliding_window and pos_embedding
    # give. The layer loop carries the pairs beside the stacked weights,
    # so there is still one program (models/cache_carry.py).
    layer_kinds: Tuple[Tuple[int, bool], ...] = ()
    # RMSNorm over head_dim on every q and k head, before rotary.
    qk_norm: bool = False
    # o_proj(attn_out * sigmoid(gate_proj(x))), gate_proj as wide as q.
    attn_gate: bool = False
    # Sandwich norms: a second RMSNorm on each branch's OUTPUT before it
    # is added to the residual.
    post_norms: bool = False
    # --- Mixers of more than one kind (a layer pattern) ---
    # One a layer: 'attention' (the kinds above), 'kda' (Kimi delta
    # attention, models/kda.py: num_heads heads of head_dim, a float32
    # (head_dim, head_dim) state a head and slot, rewritten every step)
    # or 'mla' (latent attention, models/mla.py: one cached latent of
    # mla_kv_rank + mla_rope_dim values a position, no kv-head axis).
    # () ⇒ every layer is attention. The layer loop runs a group a run
    # of one (dense or expert, mixer) kind (cache_carry.layer_groups),
    # each with only its own cache leaves.
    mixer_kinds: Tuple[str, ...] = ()
    # KDA: the causal depthwise convolution's taps over q, k and v, and
    # the lower bound of the per-channel log decay (g = bound *
    # sigmoid(exp(A_log) * (f_proj(x) + dt_bias)), in (bound, 0); the
    # chunked form needs 16 x |bound| inside float32's exp: kda.CHUNK).
    kda_conv: int = 4
    kda_gate_bound: float = -5.0
    # MLA (DeepSeek-V2 form without a q down-projection): num_heads
    # query heads of mla_nope_dim + mla_rope_dim, a latent of
    # mla_kv_rank (RMSNormed) and one rotary key of mla_rope_dim shared
    # by the heads; keys' nope part and values (mla_v_dim) are
    # up-projected from the latent; a sigmoid gate a head on the output.
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    # Group-limited routing: the experts form n_group groups of equal
    # size; a group scores the sum of its two best biased scores, and a
    # token chooses its experts_per_token within its topk_group best
    # groups. 1 ⇒ no groups: every expert is a candidate.
    n_group: int = 1
    topk_group: int = 1
    # Per-layer clamps of the expert SwiGLUs (0 ⇒ none): gate clamped
    # from above, up to [-limit, limit], before silu(gate) * up; one a
    # layer for the routed experts and one for the shared expert. () ⇒
    # none anywhere.
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    # --- Generation by diffusion over blocks (docs/serving.md) ---
    # block_length B > 0 ⇒ attention is causal between blocks of B
    # positions (aligned to position 0) and runs both ways inside one
    # (`last_key_seen`), and the engine generates a block at a time:
    # B mask tokens are denoised over passes that each unmask the most
    # confident positions (denoising_steps passes a whole block; B /
    # steps a pass, the remainder to the first passes), then one more
    # pass over the clean block writes its K/V. 0 ⇒ causal and
    # autoregressive: every other model's programs are what they were.
    block_length: int = 0
    denoising_steps: int = 0
    mask_token_id: int = -1
    # Execution knobs.
    scan_layers: bool = True          # lax.scan over stacked layers
    remat: bool = True                # checkpoint each layer
    # 'full' = recompute everything (max memory headroom); 'dots' = save
    # matmul outputs (fewer recomputed FLOPs; the difference is not
    # measured on this code).
    remat_policy: str = 'dots'
    attention_impl: str = 'auto'      # 'auto'|'pallas'|'xla'|'ring'
    # Pallas flash-attention tile sizes (0 ⇒ the kernel's default).
    # Exposed for per-chip tuning; no sweep on the chip has chosen them
    # yet (ROADMAP S2).
    attn_block_q: int = 0
    attn_block_k: int = 0
    dtype: str = 'bfloat16'           # activation/compute dtype
    param_dtype: str = 'float32'
    # Autoregressive decode mode: Attention reads/writes a KV cache (the
    # 'cache' variable collection) instead of full-sequence attention.
    # Same parameter tree as training — flip with dataclasses.replace.
    decode: bool = False
    # '' | 'int8': store the decode KV cache as int8 with per-token-
    # per-kv-head absmax scales. Decode cost is dominated by streaming
    # the cache from HBM every tick — int8 halves that traffic; the
    # matmuls read int8 directly (XLA fuses the convert) and the scales
    # are applied outside the contracted dim (JetStream-style).
    kv_cache_quant: str = ''
    # Paged KV cache (decode only; vLLM-style). >0 ⇒ Attention stores
    # K/V in a shared pool of `paged_num_blocks` fixed-size blocks of
    # `paged_block_size` tokens instead of one (batch, max_seq_len)
    # window per row; callers pass per-row block tables (logical block →
    # physical block id) and attention gathers through them. Block 0 is
    # the engine's scratch block (pad/inactive-row writes land there).
    # HBM then scales with TOKENS HELD, not slots × max_seq_len — see
    # docs/performance.md. 0 ⇒ the contiguous reference layout.
    # Composes with kv_cache_quant='int8' (the pool stores int8 K/V
    # plus per-token scale rows laid out per block — the HBM wins
    # multiply) and with multi-token chunks at arbitrary per-row
    # positions (chunked prefill AND speculative verification read the
    # logical window through the same block-table gather).
    paged_block_size: int = 0
    paged_num_blocks: int = 0
    # Paged-decode attention implementation, set by the engine
    # (models/inference.py _resolve_decode_kernel). 'xla' (this
    # field's default): scatter writes + a gathered-window read feeding
    # the shared attention math (transformer._attend_window).
    # 'pallas_attention' (what an engine's default resolves to on a TPU
    # with the paged pool, tp=1 and no softcap): the ops/paged_attention
    # kernel in the programs with few query rows a slot (decode step,
    # block pass, verify span) — each slot reads the blocks it holds,
    # several a tile — and, for a model with a scan state, the
    # ops/ssd_state_step kernel in the all-slots decode step (models/
    # ssm.takes_state_kernel). 'pallas': the same, asked for by name, and
    # multi-LoRA engines also route the adapter gather+dot through
    # ops/fused_lora. 'pallas_interpret': 'pallas' under the Pallas
    # interpreter (CPU tier-1 pinning).
    decode_kernel: str = 'xla'

    def __post_init__(self):
        # A JSON override hands lists: keep the config hashable.
        kinds = tuple((int(w), bool(r)) for w, r in self.layer_kinds)
        object.__setattr__(self, 'layer_kinds', kinds)
        for name in ('mixer_kinds', 'expert_swiglu_limits',
                     'shared_swiglu_limits'):
            value = tuple(getattr(self, name))
            object.__setattr__(self, name, value)
            if value and len(value) != self.num_layers:
                raise ValueError(
                    f'{self.name}: {name} names {len(value)} layers, '
                    f'num_layers is {self.num_layers}')
        if 'kda' in self.mixer_kinds and not -5.0 <= self.kda_gate_bound < 0:
            raise ValueError(
                f'{self.name}: a KDA gate bound of {self.kda_gate_bound} '
                f'takes a block of 16 positions past float32\'s exp')
        unknown = set(self.mixer_kinds) - {'attention', 'kda', 'mla'}
        if unknown:
            raise ValueError(f'{self.name}: unknown mixer kinds '
                             f'{sorted(unknown)}')
        if self.n_group > 1 and (
                self.num_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group):
            raise ValueError(
                f'{self.name}: {self.num_experts} experts in '
                f'{self.n_group} groups, {self.topk_group} kept')
        if self.block_length and not (
                0 < self.denoising_steps <= self.block_length
                and self.mask_token_id >= 0):
            raise ValueError(
                f'{self.name}: block_length {self.block_length} needs '
                f'1 to {self.block_length} denoising_steps (got '
                f'{self.denoising_steps}) and a mask_token_id (got '
                f'{self.mask_token_id})')
        if kinds and len(kinds) != self.num_layers:
            raise ValueError(
                f'{self.name}: layer_kinds names {len(kinds)} layers, '
                f'num_layers is {self.num_layers}')

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.num_heads

    @property
    def has_layer_pattern(self) -> bool:
        """True when the stack is not one layer type, or its layers
        carry what only models/layer_pattern.py's layer has (q/k norms,
        the output gate, post-norms): such a model takes the grouped
        layer loop, training's plain scan never sees it."""
        return bool(self.layer_kinds or self.num_dense_layers
                    or self.qk_norm or self.attn_gate or self.post_norms
                    or self.block_length or self.mixer_kinds)

    def mixer_kind(self, layer: int) -> str:
        """'attention', 'kda' or 'mla': the mixer of layer `layer`."""
        return self.mixer_kinds[layer] if self.mixer_kinds else 'attention'

    def swiglu_limits(self, layer: int) -> Tuple[float, float]:
        """(routed, shared) SwiGLU clamps of layer `layer`; 0 is none."""
        pick = lambda limits: float(limits[layer]) if limits else 0.0
        return (pick(self.expert_swiglu_limits),
                pick(self.shared_swiglu_limits))

    def last_key_seen(self, q_pos):
        """The last key position a query at `q_pos` attends to (an int
        or an array of positions; plain arithmetic, whatever holds
        them): itself under the causal mask, the end of its block
        under the block-causal one. The one definition every XLA
        attention site masks by (`transformer._attend_window`)."""
        if not self.block_length:
            return q_pos
        return (q_pos // self.block_length + 1) * self.block_length - 1

    def unmask_schedule(self) -> Tuple[int, ...]:
        """Positions a denoising pass unmasks, by pass number: B / steps
        each, the remainder to the first passes (SDAR's
        `get_num_transfer_tokens`)."""
        base, extra = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (i < extra)
                     for i in range(self.denoising_steps))

    @property
    def held_experts(self) -> int:
        """Experts whose weights this program holds."""
        return self.experts_held or self.num_experts

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_mlp

    @property
    def has_recurrent_state(self) -> bool:
        """True when a sequence's state is more than its K and V: the
        mixer's scan state and convolution inputs, of fixed size, owned
        by a slot and rewritten every step."""
        return self.ssm_heads > 0 or 'kda' in self.mixer_kinds

    @property
    def has_latent_cache(self) -> bool:
        """True when some layer caches a latent a position (MLA), with
        no kv-head axis, in place of K and V."""
        return 'mla' in self.mixer_kinds

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """Channels the depthwise convolution runs over: x, B and C."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_width(self) -> int:
        """Outputs of the mixer's input projection: z | x | B | C | dt."""
        return self.d_ssm + self.ssm_conv_channels + self.ssm_heads

    def assert_tp_compatible(self, tp: int) -> None:
        """Raise ValueError when a tensor-parallel degree cannot shard
        this architecture evenly. Every dimension the `tp` rules in
        parallel/sharding.py touch must divide: attention heads and KV
        heads (QKV/O projections and the KV cache's kv-head axis), the
        MLP hidden dim, and the (un)embedding vocab. GSPMD would pad an
        uneven dim silently — wasted HBM and a broken per-device
        footprint guarantee — so serving refuses it up front."""
        if tp <= 1:
            return
        if self.has_latent_cache:
            raise NotImplementedError(
                f'{self.name}: tp={tp} with a latent cache: the pool '
                f'has no kv-head axis to split (each chip would hold '
                f'every latent, or the heads\' absorbed products would '
                f'have to split over the mesh); serve it at tp=1')
        if self.has_recurrent_state:
            raise NotImplementedError(
                f'{self.name}: tp={tp} with a recurrent-state mixer: the '
                f'scan state, the convolution and the grouped norm are '
                f'not sharded over `tp` yet (heads and groups would '
                f'have to split together); serve it at tp=1')
        if self.block_length:
            raise NotImplementedError(
                f'{self.name}: tp={tp} with generation by diffusion over '
                f'blocks: the block step\'s feed and its (slots, B) '
                f'logits carry no tp rule yet, and the q/k norms of its '
                f'layer none either; serve it at tp=1')
        if self.moe_impl == 'dropless' and self.is_moe:
            raise NotImplementedError(
                f'{self.name}: tp={tp} with a dropless expert layer: '
                f'serving has no expert axis yet (the held experts '
                f'would have to split over the mesh and the sorted '
                f'pairs with them); serve it at tp=1, one share a chip '
                f'(experts_held, first_expert)')
        if self.has_layer_pattern:
            raise NotImplementedError(
                f'{self.name}: tp={tp} with a layer pattern: the q/k '
                f'norms and the output gate of models/layer_pattern.py '
                f'carry no tp rule yet; serve it at tp=1')
        dims = {'num_heads': self.num_heads,
                'num_kv_heads': self.num_kv_heads,
                'd_mlp': self.d_mlp,
                'vocab_size': self.vocab_size}
        bad = {k: v for k, v in dims.items() if v % tp}
        if bad:
            raise ValueError(
                f'{self.name}: tp={tp} does not divide '
                + ', '.join(f'{k}={v}' for k, v in sorted(bad.items()))
                + ' (pick tp dividing all of num_heads/num_kv_heads/'
                  'd_mlp/vocab_size)')

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def num_params(self) -> int:
        """Parameter count (tied unembed counted once; biases included)."""
        embed = self.vocab_size * self.d_model * \
            (1 if self.tie_embeddings else 2)
        if self.lm_head_bias:
            embed += self.vocab_size
        if self.pos_embedding == 'learned':
            embed += self.max_seq_len * self.d_model
        attn = (self.d_model * self.num_heads * self.head_dim +        # q
                2 * self.d_model * self.num_kv_heads * self.head_dim +  # k,v
                self.num_heads * self.head_dim * self.d_model)          # o
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * \
                self.head_dim
        if self.o_bias:
            attn += self.d_model
        if self.attn_gate:
            attn += self.d_model * self.num_heads * self.head_dim
        if self.qk_norm:
            attn += 2 * self.head_dim
        mlp_mats = 3 if self.mlp_style == 'glu' else 2
        dense_mlp = mlp_mats * self.d_model * self.d_mlp
        if self.is_moe:
            # every expert of the published model, held here or not,
            # and the one shared expert
            mlp = mlp_mats * self.d_model * (
                self.num_experts * self.expert_width
                + self.d_shared_expert)
            router = self.d_model * self.num_experts
            if self.router_bias:
                router += self.num_experts
        else:
            mlp = dense_mlp
            router = 0
        if self.mlp_bias:
            mlp += (mlp_mats - 1) * self.d_mlp + self.d_model
        norm_params = (2 if self.norm_style == 'layernorm' else 1) * \
            self.d_model
        # Parallel-block layers (Falcon) share ONE pre-norm for attn+mlp;
        # sandwich norms double them.
        norms = (1 if self.parallel_block else 2) * norm_params * \
            (2 if self.post_norms else 1)
        mixer = 0
        if self.ssm_heads:
            mixer = (self.d_model * self.ssm_proj_width        # in_proj
                     + self.ssm_conv * self.ssm_conv_channels  # conv
                     + 3 * self.ssm_heads             # A_log, D, dt_bias
                     + self.d_ssm * self.d_model)              # out_proj
            if self.ssm_conv_bias:
                mixer += self.ssm_conv_channels
            if self.ssm_gated_norm:
                mixer += self.d_ssm
            if self.ssm_proj_bias:
                mixer += self.ssm_proj_width + self.d_model
        per_layer = mlp + router + norms + mixer
        # leading dense layers of an expert model carry the dense MLP
        dense = self.num_dense_layers if self.is_moe else 0
        mixers = sum(attn if kind == 'attention'
                     else self._other_mixer_params(kind)
                     for kind in map(self.mixer_kind,
                                     range(self.num_layers)))
        return (embed + self.num_layers * per_layer + mixers
                + norm_params - dense * (mlp + router - dense_mlp))

    def _other_mixer_params(self, kind: str) -> int:
        """Parameters of one KDA or MLA mixer (models/kda.py,
        models/mla.py)."""
        d, h, hd = self.d_model, self.num_heads, self.head_dim
        if kind == 'kda':
            # q, k, v, f, g and o; beta; the convolution; A_log a head,
            # dt_bias a channel; the heads' norm
            return (6 * d * h * hd + d * h + self.kda_conv * 3 * h * hd
                    + h + h * hd + hd)
        qk = self.mla_nope_dim + self.mla_rope_dim
        rank = self.mla_kv_rank
        # q (and its norm), the latent's down-projection and norm, keys'
        # and values' up-projection, the gate a head, o
        return (d * h * qk + (qk if self.qk_norm else 0)
                + d * (rank + self.mla_rope_dim) + rank
                + rank * h * (self.mla_nope_dim + self.mla_v_dim)
                + d * h + h * self.mla_v_dim * d)

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Training FLOPs/token (fwd+bwd ≈ 6 × params-matmul + attention
        term; the standard 6N + 12·L·d·s accounting used for MFU)."""
        seq_len = seq_len or self.max_seq_len
        if self.is_moe:
            # Only active experts do work.
            active_mlp = 3 * self.d_model * (
                self.experts_per_token * self.expert_width
                + self.d_shared_expert)
            attn = (self.d_model * self.num_heads * self.head_dim +
                    2 * self.d_model * self.num_kv_heads * self.head_dim +
                    self.num_heads * self.head_dim * self.d_model)
            active_per_layer = attn + active_mlp
            matmul_params = (self.vocab_size * self.d_model * 2 +
                             self.num_layers * active_per_layer)
        else:
            matmul_params = self.num_params()
            if self.tie_embeddings:
                # The unembed matmul still burns FLOPs even though its
                # weights are counted once in num_params.
                matmul_params += self.vocab_size * self.d_model
        # causal attention: 12 * L * d * s * 0.5
        attn_flops = 6 * self.num_layers * self.d_model * seq_len
        return 6.0 * matmul_params + attn_flops


_REGISTRY = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


# Hermetic-test size: runs on the 8-device CPU mesh in <1s.
TEST_TINY = _register(ModelConfig(
    name='test-tiny', vocab_size=512, d_model=64, num_layers=2,
    num_heads=4, num_kv_heads=2, d_mlp=256, max_seq_len=128,
    attention_impl='xla', remat=False))

TEST_TINY_MOE = _register(ModelConfig(
    name='test-tiny-moe', vocab_size=512, d_model=64, num_layers=2,
    num_heads=4, num_kv_heads=2, d_mlp=256, max_seq_len=128,
    num_experts=4, experts_per_token=2, attention_impl='xla', remat=False))

# Flagship architecture at a size that trains on ONE v5e chip (16 GB HBM):
# ~0.94B params ⇒ ~11 GB for fp32 params + Adam moments. chip_smoke.py
# trains it; the 8B/70B configs below are the multi-chip targets.
LLAMA3_1B = _register(ModelConfig(
    name='llama3-1b', vocab_size=32768, d_model=2048, num_layers=16,
    num_heads=16, num_kv_heads=8, d_mlp=6144, max_seq_len=2048))

LLAMA3_8B = _register(ModelConfig(
    name='llama3-8b', vocab_size=128256, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=8, d_mlp=14336, max_seq_len=8192))

LLAMA3_70B = _register(ModelConfig(
    name='llama3-70b', vocab_size=128256, d_model=8192, num_layers=80,
    num_heads=64, num_kv_heads=8, d_mlp=28672, max_seq_len=8192))

# --- Llama-3.1: same weights shape as Llama-3, 128k context via llama3
# rope scaling (factor 8 over the 8192-token original window). The
# flagship long-context serving/finetune target
# (llm/llama-3_1-finetuning); pairs with `attention_impl: ring` for sequence
# parallelism past one chip's HBM.
LLAMA31_8B = _register(ModelConfig(
    name='llama31-8b', vocab_size=128256, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=8, d_mlp=14336, max_seq_len=131072,
    rope_scaling=(8.0, 1.0, 4.0, 8192)))

LLAMA31_70B = _register(ModelConfig(
    name='llama31-70b', vocab_size=128256, d_model=8192, num_layers=80,
    num_heads=64, num_kv_heads=8, d_mlp=28672, max_seq_len=131072,
    rope_scaling=(8.0, 1.0, 4.0, 8192)))

# --- Llama-2 family (reference recipes: llm/llama-2, llm/vicuna-llama-2,
# llm/codellama). Plain pre-Llama-3 shape: MHA for 7B/13B (num_kv_heads
# == num_heads), GQA only at 70B, rope 10k, 4k context, vocab 32000
# (already a multiple of 128 — no MXU pad needed).
LLAMA2_7B = _register(ModelConfig(
    name='llama2-7b', vocab_size=32000, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=32, d_mlp=11008, max_seq_len=4096,
    rope_theta=10000.0))

LLAMA2_13B = _register(ModelConfig(
    name='llama2-13b', vocab_size=32000, d_model=5120, num_layers=40,
    num_heads=40, num_kv_heads=40, d_mlp=13824, max_seq_len=4096,
    rope_theta=10000.0))

LLAMA2_70B = _register(ModelConfig(
    name='llama2-70b', vocab_size=32000, d_model=8192, num_layers=80,
    num_heads=64, num_kv_heads=8, d_mlp=28672, max_seq_len=4096,
    rope_theta=10000.0))

# CodeLlama-7B: Llama-2-7B shape retrained for code — 16 tokens added
# for infilling/EOT (vocab 32016, MXU-padded to 32128 with the pad rows
# masked), rope theta raised to 1e6 for the 16k context window.
CODELLAMA_7B = _register(ModelConfig(
    name='codellama-7b', vocab_size=32128, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=32, d_mlp=11008, max_seq_len=16384,
    rope_theta=1e6, unpadded_vocab_size=32016))

MIXTRAL_8X7B = _register(ModelConfig(
    name='mixtral-8x7b', vocab_size=32000, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=8, d_mlp=14336, max_seq_len=8192,
    rope_theta=1e6, num_experts=8, experts_per_token=2))

# --- Gemma family (reference recipe: llm/gemma). (1+w)-RMSNorm, GeGLU,
# sqrt(d)-scaled embeddings, tied unembed, 256-wide heads, rope 10k.
# vocab_size is MXU-padded 256000 → 256128; unpadded_vocab_size both (a)
# masks the 128 pad rows out of the logits (they score ~0 via the tied
# attend — above real logits — so sampling could emit invalid ids) and
# (b) makes HF export emit the real 256000-row tokenizer size. Note (a)
# deliberately changes the softmax normalizer vs a config without the
# guard: the pad rows were never real tokens.
GEMMA_2B = _register(ModelConfig(
    name='gemma-2b', vocab_size=256128, d_model=2048, num_layers=18,
    num_heads=8, num_kv_heads=1, d_mlp=16384, max_seq_len=8192,
    rope_theta=10000.0, norm_eps=1e-6, head_dim_override=256,
    mlp_activation='gelu', norm_style='rms_plus1', tie_embeddings=True,
    scale_embed_by_dim=True, unpadded_vocab_size=256000))

GEMMA_7B = _register(ModelConfig(
    name='gemma-7b', vocab_size=256128, d_model=3072, num_layers=28,
    num_heads=16, num_kv_heads=16, d_mlp=24576, max_seq_len=8192,
    rope_theta=10000.0, norm_eps=1e-6, head_dim_override=256,
    mlp_activation='gelu', norm_style='rms_plus1', tie_embeddings=True,
    scale_embed_by_dim=True, unpadded_vocab_size=256000))

# Gemma-2 adds attention/final logit softcaps (tanh-capped on the XLA
# attention path). Approximations vs the released architecture: the
# interleaved sliding-window layers are not modeled (full causal
# attention everywhere — a strict superset window) and the per-block
# POST-norms are omitted (pre-norm only), so released Gemma-2 weights
# are not load-compatible; gemma-1 weights are (tests/test_convert.py).
GEMMA2_9B = _register(ModelConfig(
    name='gemma2-9b', vocab_size=256128, d_model=3584, num_layers=42,
    num_heads=16, num_kv_heads=8, d_mlp=14336, max_seq_len=8192,
    rope_theta=10000.0, norm_eps=1e-6, head_dim_override=256,
    mlp_activation='gelu', norm_style='rms_plus1', tie_embeddings=True,
    scale_embed_by_dim=True, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, attention_impl='xla',
    unpadded_vocab_size=256000))

# --- Mistral (reference recipes: llm/vicuna-llama-2 era serving stacks):
# Llama shape + uniform 4096-key sliding window on every layer — the
# config the sliding-window kernel path exists for.
MISTRAL_7B = _register(ModelConfig(
    name='mistral-7b', vocab_size=32000, d_model=4096, num_layers=32,
    num_heads=32, num_kv_heads=8, d_mlp=14336, max_seq_len=8192,
    rope_theta=10000.0, sliding_window=4096))

# --- Qwen2 family (reference recipe: llm/qwen): Llama shape + QKV bias.
QWEN2_7B = _register(ModelConfig(
    name='qwen2-7b', vocab_size=152064, d_model=3584, num_layers=28,
    num_heads=28, num_kv_heads=4, d_mlp=18944, max_seq_len=8192,
    rope_theta=1e6, norm_eps=1e-6, qkv_bias=True))

QWEN2_72B = _register(ModelConfig(
    name='qwen2-72b', vocab_size=152064, d_model=8192, num_layers=80,
    num_heads=64, num_kv_heads=8, d_mlp=29568, max_seq_len=8192,
    rope_theta=1e6, norm_eps=1e-6, qkv_bias=True))

# --- GPT-2 (reference recipe: llm/gpt-2, llm.c pretrain): LayerNorm,
# learned positions, plain GELU MLP, biases, tied unembed. Vocab padded
# 50257 → 50304 (×128) so the unembed matmul tiles the MXU cleanly, the
# same padding llm.c applies.
# --- DBRX (reference recipe: llm/dbrx). 132B fine-grained MoE: 16
# experts top-4 (vs Mixtral's 8 top-2), GQA, bias-free LayerNorm,
# clip_qkv=8, untied 100352-vocab embeddings (÷128 exact), rope 5e5.
DBRX = _register(ModelConfig(
    name='dbrx', vocab_size=100352, d_model=6144, num_layers=40,
    num_heads=48, num_kv_heads=8, d_mlp=10752, max_seq_len=32768,
    rope_theta=500000.0, norm_style='layernorm', norm_bias=False,
    qkv_clip=8.0, num_experts=16, experts_per_token=4))

# --- Phi (Microsoft). Parallel block like Falcon but biased
# everywhere (qkv/o/mlp/lm_head + LayerNorm biases), MHA, partial
# rotary (40% of head_dim), plain GELU MLP, untied embeddings.
PHI_2 = _register(ModelConfig(
    name='phi-2', vocab_size=51200, d_model=2560, num_layers=32,
    num_heads=32, num_kv_heads=32, d_mlp=10240, max_seq_len=2048,
    rope_theta=10000.0, norm_style='layernorm', mlp_style='plain',
    mlp_activation='gelu', parallel_block=True, qkv_bias=True,
    o_bias=True, mlp_bias=True, lm_head_bias=True, rotary_pct=0.4))

# --- Falcon family (reference recipe: llm/falcon). Parallel block
# (shared LayerNorm feeds attn AND mlp, both add into the residual),
# multi-query attention (1 KV head — the original MQA paper's serving
# win: the KV cache is num_heads× smaller), plain GELU MLP, tied
# embeddings, rope 10k. falcon-7b is the multi_query=True pre-GQA
# architecture (new_decoder_architecture=False in HF terms).
FALCON_7B = _register(ModelConfig(
    name='falcon-7b', vocab_size=65024, d_model=4544, num_layers=32,
    num_heads=71, num_kv_heads=1, d_mlp=18176, max_seq_len=2048,
    rope_theta=10000.0, norm_style='layernorm', mlp_style='plain',
    mlp_activation='gelu', tie_embeddings=True, parallel_block=True))

# --- Falcon-H1 (TII, 2025): in every block a Mamba-2 mixer runs in
# PARALLEL with grouped-query attention on one shared pre-norm, their
# outputs add into the residual together, and a sequential SwiGLU
# follows; µP multipliers are folded into the forward pass. A
# sequence's state is then two things: K and V, growing with the
# position, and the mixer's fixed-size scan and convolution state
# (models/ssm.py; docs/serving.md "Models with recurrent state"). The
# published config of Falcon-H1-34B-Instruct, key for key.
FALCON_H1_34B = _register(ModelConfig(
    name='falcon-h1-34b', vocab_size=261120, d_model=5120, num_layers=72,
    num_heads=20, num_kv_heads=4, head_dim_override=128, d_mlp=21504,
    max_seq_len=262144, rope_theta=1e11, norm_eps=1e-5,
    ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
    ssm_conv=4, ssm_chunk=128, ssm_conv_bias=True, ssm_proj_bias=False,
    ssm_gated_norm=True, ssm_norm_before_gate=False,
    embed_multiplier=5.656854249492381, attn_in_multiplier=1.0,
    key_multiplier=0.011048543456039804, attn_out_multiplier=0.0375,
    ssm_in_multiplier=0.25,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    lm_head_multiplier=0.0078125))

# --- Trinity-Large-Preview (Arcee, 2026; model_type afmoe), the
# published config: 60 layers, the first 6 with a dense SwiGLU of 12288,
# the rest with 256 routed experts of 3072 (sigmoid scores, a selection
# bias, top 4 normalised and scaled by 2.448) and one shared expert;
# three sliding layers (4096 keys, rotary) then one full layer (no
# rotary), repeated; q/k RMSNorm, a sigmoid output gate, sandwich norms,
# the embedding times sqrt(d). One chip holds a share of it
# (experts_held, first_expert, a slice of the vocabulary, a cut in
# depth: perf/configs/trinity-large-l5-ep8.json).
TRINITY_LARGE_PREVIEW = _register(ModelConfig(
    name='trinity-large-preview', vocab_size=200192, d_model=3072,
    num_layers=60, num_heads=48, num_kv_heads=8, head_dim_override=128,
    d_mlp=12288, max_seq_len=262144, rope_theta=10000.0, norm_eps=1e-5,
    scale_embed_by_dim=True, num_experts=256, experts_per_token=4,
    moe_impl='dropless', d_expert=3072, d_shared_expert=3072,
    router_score='sigmoid', router_bias=True, route_norm=True,
    route_scale=2.448, num_dense_layers=6,
    layer_kinds=(((4096, True),) * 3 + ((0, False),)) * 15,
    qk_norm=True, attn_gate=True, post_norms=True,
    attention_impl='xla', remat=False))

# --- SDAR-30B-A3B-Chat (JetLM, 2025; model_type sdar_moe), the
# published config: the Qwen3-MoE decoder it was continued from (48
# layers, 128 routed experts of 768, 8 a token, softmax weights
# normalised over the chosen, none shared, no dense layer, q/k RMSNorm,
# theta 1e6, untied) under a block-causal mask, generating by diffusion
# over blocks. Block length, steps and the mask token's id are not in
# the published config (SDAR's generate.py: perf/configs/
# sdar-30b-a3b-l6.json lists them under `assumed`).
SDAR_30B_A3B_CHAT = _register(ModelConfig(
    name='sdar-30b-a3b-chat', vocab_size=151936, d_model=2048,
    num_layers=48, num_heads=32, num_kv_heads=4, head_dim_override=128,
    d_mlp=6144, max_seq_len=32768, rope_theta=1000000.0, norm_eps=1e-6,
    num_experts=128, experts_per_token=8, moe_impl='dropless',
    d_expert=768, router_score='softmax', route_norm=True,
    qk_norm=True, block_length=4, denoising_steps=4,
    mask_token_id=151669, attention_impl='xla', remat=False))

# --- Ling-3.0-flash (inclusionAI, 2026; model_type bailing_hybrid), the
# language model of the published Ling-3.0-flash-VL config: 42 layers in
# groups of six, five of Kimi delta attention (a per-channel gated
# delta rule over a float32 state a head) and the sixth of latent
# attention (MLA: a latent of 512 and a rotary key of 64 a position);
# the first two layers with a dense SwiGLU of 6144, the rest with 512
# routed experts of 768 (sigmoid scores, a selection bias, 8 chosen
# within the best 4 of 8 groups, normalised and scaled by 2.5) and one
# shared expert of 768; SwiGLU clamps on the last layers. One chip
# holds a share of it (perf/configs/ling-3.0-flash-l7-ep8.json).
LING_3_FLASH = _register(ModelConfig(
    name='ling-3.0-flash', vocab_size=157184, d_model=2560,
    num_layers=42, num_heads=32, num_kv_heads=32, head_dim_override=128,
    d_mlp=6144, max_seq_len=131072, rope_theta=6e6, norm_eps=1e-6,
    num_experts=512, experts_per_token=8, moe_impl='dropless',
    d_expert=768, d_shared_expert=768, router_score='sigmoid',
    router_bias=True, route_norm=True, route_scale=2.5,
    num_dense_layers=2, n_group=8, topk_group=4,
    mixer_kinds=(('kda',) * 5 + ('mla',)) * 7,
    kda_conv=4, kda_gate_bound=-5.0, mla_kv_rank=512, mla_nope_dim=128,
    mla_rope_dim=64, mla_v_dim=128, qk_norm=True,
    expert_swiglu_limits=(0.0,) * 35 + (4.0,) * 7,
    shared_swiglu_limits=(0.0,) * 34 + (5.0,) * 6 + (7.0,) * 2,
    attention_impl='xla', remat=False))

GPT2_124M = _register(ModelConfig(
    name='gpt2-124m', vocab_size=50304, d_model=768, num_layers=12,
    num_heads=12, num_kv_heads=12, d_mlp=3072, max_seq_len=1024,
    mlp_activation='gelu', mlp_style='plain', norm_style='layernorm',
    pos_embedding='learned', qkv_bias=True, o_bias=True, mlp_bias=True,
    tie_embeddings=True, unpadded_vocab_size=50257))

GPT2_1_5B = _register(ModelConfig(
    name='gpt2-1.5b', vocab_size=50304, d_model=1600, num_layers=48,
    num_heads=25, num_kv_heads=25, d_mlp=6400, max_seq_len=1024,
    mlp_activation='gelu', mlp_style='plain', norm_style='layernorm',
    pos_embedding='learned', qkv_bias=True, o_bias=True, mlp_bias=True,
    tie_embeddings=True, unpadded_vocab_size=50257))


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _REGISTRY:
        raise ValueError(f'Unknown model {name!r}. '
                         f'Known: {sorted(_REGISTRY)}')
    cfg = _REGISTRY[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def list_configs():
    return sorted(_REGISTRY)
