"""HuggingFace checkpoint → skypilot_tpu param tree.

A user switching from the reference arrives with HF checkpoints (the
reference's recipes pull them for vLLM/torchtune — SURVEY §2.9); this
module maps the `transformers` state_dicts of the supported families
onto the mesh-first Transformer's param tree:

    Llama / Mistral / Qwen2  (LlamaForCausalLM-shaped keys, QKV bias ok)
    Gemma / Gemma-2          (same keys; (1+w)-norm deltas map directly)
    GPT-2                    (Conv1D [in,out] weights, combined c_attn)
    Mixtral                  (block_sparse_moe expert stacks)
    Falcon-H1                (mamba.* mixer beside self_attn.*; the
                              depthwise conv1d [ch, 1, taps] → [taps, ch])

Conventions verified against the HF implementations:
- torch Linear stores [out, in] → our kernels are the transpose.
- GPT-2 Conv1D already stores [in, out] → no transpose.
- Rotary embeddings: both sides use the non-interleaved (GPT-NeoX)
  half-split convention with inv_freq = theta^(-2i/d), so Q/K map with
  no permutation (pinned by the cross-framework logit-parity tests,
  tests/test_convert.py).
- Tied unembeds (Gemma, GPT-2) load the embedding once.
- Vocab padding (e.g. GPT-2 50257 → 50304 for MXU tiling) zero-fills
  the extra rows.

Everything is numpy on the host; shard/device placement happens when
the caller feeds the tree into a jitted step with shardings.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Mapping

import numpy as np

from skypilot_tpu.models.configs import ModelConfig

logger = logging.getLogger(__name__)


def _np(t) -> np.ndarray:
    if hasattr(t, 'detach'):
        t = t.detach().cpu()
        if str(t.dtype) == 'torch.bfloat16':
            # numpy has no bf16: widen first (users commonly hold HF
            # weights as bf16 via torch_dtype='auto').
            t = t.float()
        t = t.numpy()
    return np.asarray(t)


class _TrackedDict(dict):
    """Records key reads so from_hf can prove it consumed every weight
    (an architecturally incompatible checkpoint must fail loudly, not
    silently drop tensors)."""

    def __init__(self, d):
        super().__init__(d)
        self.used = set()

    def __getitem__(self, k):
        self.used.add(k)
        return super().__getitem__(k)


# Non-weight buffers HF state_dicts carry that have no place in the
# param tree: rotary caches and GPT-2's causal-mask buffers.
_IGNORABLE = ('rotary_emb.inv_freq', '.attn.bias', '.attn.masked_bias')


def _pad_vocab(w: np.ndarray, vocab: int) -> np.ndarray:
    """Zero-pad embedding/unembed rows up to cfg.vocab_size."""
    if w.shape[0] == vocab:
        return w
    if w.shape[0] > vocab:
        raise ValueError(f'checkpoint vocab {w.shape[0]} exceeds config '
                         f'vocab {vocab}')
    pad = np.zeros((vocab - w.shape[0], w.shape[1]), w.dtype)
    return np.concatenate([w, pad], axis=0)


def from_hf(state_dict: Mapping[str, Any],
            cfg: ModelConfig) -> Dict[str, Any]:
    """HF state_dict → param tree matching Transformer(cfg) with
    scan_layers=True (per-layer tensors stacked on a leading axis)."""
    if not cfg.scan_layers:
        raise NotImplementedError('from_hf targets the scanned layout; '
                                  'use scan_layers=True')
    sd = _TrackedDict({k: _np(v) for k, v in state_dict.items()})
    gpt2 = cfg.pos_embedding == 'learned' and cfg.mlp_style == 'plain'
    if cfg.ssm_heads:
        params, layer = _falcon_h1_top(sd, cfg), _falcon_h1_layer
    elif cfg.parallel_block and cfg.qkv_bias:
        params, layer = _phi_top(sd, cfg), _phi_layer
    elif cfg.parallel_block:
        params, layer = _falcon_top(sd, cfg), _falcon_layer
    elif cfg.is_moe and cfg.norm_style == 'layernorm':
        params, layer = _dbrx_top(sd, cfg), _dbrx_layer
    elif gpt2:
        params, layer = _gpt2_top(sd, cfg), _gpt2_layer
    else:
        params, layer = _llama_top(sd, cfg), _llama_layer
    per_layer = [layer(sd, cfg, i) for i in range(cfg.num_layers)]
    import jax
    params['layers'] = {
        'layer': jax.tree_util.tree_map(
            lambda *xs: np.stack(xs, axis=0), *per_layer)
    }
    if cfg.tie_embeddings:
        sd.used.add('lm_head.weight')  # tied alias of the embedding
    leftover = sorted(
        k for k in sd if k not in sd.used
        and not any(k.endswith(s) or s in k for s in _IGNORABLE))
    if leftover:
        raise ValueError(
            f'checkpoint has {len(leftover)} weight tensor(s) this '
            f'architecture does not consume (incompatible checkpoint? '
            f'e.g. Gemma-2 post-norms are not modeled): '
            f'{leftover[:6]}{"..." if len(leftover) > 6 else ""}')
    return params


def load_hf_model(hf_model, cfg: ModelConfig) -> Dict[str, Any]:
    """Convenience: convert a live transformers model."""
    return from_hf(hf_model.state_dict(), cfg)


def load_hf_checkpoint(path: str, cfg: ModelConfig) -> Dict[str, Any]:
    """Load a LOCAL HF checkpoint dir and convert it, casting to
    cfg.param_dtype. The one entry point serve/server.py and
    train/run.py share — cfg must already carry any max_seq_len
    override, since conversion validates/slices position tables
    against it."""
    import jax.numpy as jnp
    import transformers
    hf = transformers.AutoModelForCausalLM.from_pretrained(path)
    params = load_hf_model(hf, cfg)
    del hf
    # jnp.dtype resolves extension dtypes (bfloat16) numpy alone lacks.
    dtype = jnp.dtype(cfg.param_dtype)
    return {k: _cast_tree(v, dtype) for k, v in params.items()}


def _cast_tree(tree, dtype):
    if isinstance(tree, dict):
        return {k: _cast_tree(v, dtype) for k, v in tree.items()}
    return np.asarray(tree, dtype)


def to_hf(params: Mapping[str, Any],
          cfg: ModelConfig) -> Dict[str, np.ndarray]:
    """Param tree → HF state_dict (numpy, float32) — the inverse of
    from_hf, so a model fine-tuned here loads into `transformers` (and
    therefore into anything that serves HF checkpoints). Round-trip and
    HF-side logit parity are pinned in tests/test_convert.py.

    GPT-2's packed-Conv1D layout is reconstructed; tied models emit the
    embedding under both the embed and lm_head keys the way HF ties
    them. MXU vocab-padding rows (cfg.unpadded_vocab_size <
    cfg.vocab_size, e.g. Gemma 256000→256128, GPT-2 50257→50304) ARE
    stripped so the export matches the real tokenizer — hf_config_for
    emits the unpadded size to match; from_hf re-pads on the way back.
    """
    from skypilot_tpu.models import lora as lora_lib
    if cfg.lora_rank > 0:
        # Exporting raw LoRA params would emit the UNTUNED base — fold
        # the adapters in first so the export carries the fine-tune.
        params = lora_lib.merge_lora(params, cfg)
    elif lora_lib.has_lora(params):
        raise ValueError(
            'param tree contains lora_a/lora_b but cfg.lora_rank == 0: '
            'pass the LoRA config (or merge_lora first) — a silent '
            'export here would drop the fine-tune')
    p = {k: _cast_tree(v, np.float32) for k, v in params.items()}
    if 0 < cfg.unpadded_vocab_size < cfg.vocab_size:
        n = cfg.unpadded_vocab_size
        p['embed'] = {'embedding': p['embed']['embedding'][:n]}
        if not cfg.tie_embeddings and 'lm_head' in p:
            head = {'kernel': p['lm_head']['kernel'][:, :n]}
            if 'bias' in p['lm_head']:   # Phi-style biased unembed
                head['bias'] = p['lm_head']['bias'][:n]
            p['lm_head'] = head
    layers = p['layers']['layer']
    gpt2 = cfg.pos_embedding == 'learned' and cfg.mlp_style == 'plain'
    sd: Dict[str, np.ndarray] = {}
    if cfg.ssm_heads:
        return _falcon_h1_to_hf(p, cfg)
    if cfg.is_moe and cfg.norm_style == 'layernorm':
        d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim)
        e, ffn = cfg.num_experts, cfg.d_mlp
        sd['transformer.wte.weight'] = p['embed']['embedding']
        sd['transformer.norm_f.weight'] = p['final_norm']['scale']
        sd['lm_head.weight'] = p['lm_head']['kernel'].T
        for i in range(cfg.num_layers):
            li = jax_tree_index(layers, i)
            pre = f'transformer.blocks.{i}.'
            attn = li['attn']
            fused = np.concatenate([
                attn['q_proj']['kernel'].reshape(d, nh * hd),
                attn['k_proj']['kernel'].reshape(d, nkv * hd),
                attn['v_proj']['kernel'].reshape(d, nkv * hd)], axis=1)
            sd[pre + 'norm_attn_norm.attn.Wqkv.weight'] = fused.T
            sd[pre + 'norm_attn_norm.attn.out_proj.weight'] = \
                attn['o_proj']['kernel'].reshape(nh * hd, d).T
            sd[pre + 'norm_attn_norm.norm_1.weight'] = \
                li['attn_norm']['scale']
            sd[pre + 'norm_attn_norm.norm_2.weight'] = \
                li['mlp_norm']['scale']
            moe = li['moe']
            sd[pre + 'ffn.router.layer.weight'] = moe['router'].T
            sd[pre + 'ffn.experts.mlp.w1'] = \
                moe['w_gate'].transpose(0, 2, 1).reshape(e * ffn, d)
            sd[pre + 'ffn.experts.mlp.v1'] = \
                moe['w_up'].transpose(0, 2, 1).reshape(e * ffn, d)
            sd[pre + 'ffn.experts.mlp.w2'] = \
                moe['w_down'].reshape(e * ffn, d)
        return sd
    if cfg.parallel_block and cfg.qkv_bias:
        # Phi: biased everything, untied, partial rotary.
        if cfg.mlp_style != 'plain' or cfg.tie_embeddings:
            raise NotImplementedError(
                'biased parallel_block export maps the Phi layout only '
                '(plain MLP, untied lm_head) — a GLU/tied config would '
                'silently drop weights the Phi HF architecture has no '
                'keys for')
        d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim)
        sd['model.embed_tokens.weight'] = p['embed']['embedding']
        sd['model.final_layernorm.weight'] = p['final_norm']['scale']
        sd['model.final_layernorm.bias'] = p['final_norm']['bias']
        sd['lm_head.weight'] = p['lm_head']['kernel'].T
        sd['lm_head.bias'] = p['lm_head']['bias']
        for i in range(cfg.num_layers):
            li = jax_tree_index(layers, i)
            pre = f'model.layers.{i}.'
            attn = li['attn']
            for name, heads in (('q_proj', nh), ('k_proj', nkv),
                                ('v_proj', nkv)):
                sd[pre + f'self_attn.{name}.weight'] = \
                    attn[name]['kernel'].reshape(d, heads * hd).T
                sd[pre + f'self_attn.{name}.bias'] = \
                    attn[name]['bias'].reshape(-1)
            sd[pre + 'self_attn.dense.weight'] = \
                attn['o_proj']['kernel'].reshape(nh * hd, d).T
            sd[pre + 'self_attn.dense.bias'] = attn['o_proj']['bias']
            sd[pre + 'input_layernorm.weight'] = li['attn_norm']['scale']
            sd[pre + 'input_layernorm.bias'] = li['attn_norm']['bias']
            sd[pre + 'mlp.fc1.weight'] = li['mlp']['up_proj']['kernel'].T
            sd[pre + 'mlp.fc1.bias'] = li['mlp']['up_proj']['bias']
            sd[pre + 'mlp.fc2.weight'] = \
                li['mlp']['down_proj']['kernel'].T
            sd[pre + 'mlp.fc2.bias'] = li['mlp']['down_proj']['bias']
        return sd
    if cfg.parallel_block:
        if (cfg.num_kv_heads != 1 or cfg.mlp_style != 'plain'
                or cfg.qkv_bias or cfg.o_bias or cfg.mlp_bias):
            raise NotImplementedError(
                'parallel_block export maps the falcon-7b layout only '
                '(MQA, plain bias-free MLP) — a composed config would '
                'silently drop weights (gate_proj/biases) the Falcon '
                'HF architecture has no keys for')
        d, nh, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
        sd['transformer.word_embeddings.weight'] = p['embed']['embedding']
        sd['transformer.ln_f.weight'] = p['final_norm']['scale']
        sd['transformer.ln_f.bias'] = p['final_norm']['bias']
        sd['lm_head.weight'] = (p['embed']['embedding']
                                if cfg.tie_embeddings
                                else p['lm_head']['kernel'].T)
        for i in range(cfg.num_layers):
            li = jax_tree_index(layers, i)
            pre = f'transformer.h.{i}.'
            attn = li['attn']
            fused = np.concatenate([
                attn['q_proj']['kernel'].reshape(d, nh * hd),
                attn['k_proj']['kernel'].reshape(d, hd),
                attn['v_proj']['kernel'].reshape(d, hd)], axis=1)
            sd[pre + 'self_attention.query_key_value.weight'] = fused.T
            sd[pre + 'self_attention.dense.weight'] = \
                attn['o_proj']['kernel'].reshape(nh * hd, d).T
            sd[pre + 'input_layernorm.weight'] = li['attn_norm']['scale']
            sd[pre + 'input_layernorm.bias'] = li['attn_norm']['bias']
            sd[pre + 'mlp.dense_h_to_4h.weight'] = \
                li['mlp']['up_proj']['kernel'].T
            sd[pre + 'mlp.dense_4h_to_h.weight'] = \
                li['mlp']['down_proj']['kernel'].T
        return sd
    if gpt2:
        sd['transformer.wte.weight'] = p['embed']['embedding']
        sd['transformer.wpe.weight'] = p['pos_embed']['embedding']
        sd['transformer.ln_f.weight'] = p['final_norm']['scale']
        sd['transformer.ln_f.bias'] = p['final_norm']['bias']
        sd['lm_head.weight'] = p['embed']['embedding']
        for i in range(cfg.num_layers):
            li = jax_tree_index(layers, i)
            pre = f'transformer.h.{i}.'
            d = cfg.d_model
            attn = li['attn']
            wq = attn['q_proj']['kernel'].reshape(d, -1)
            wk = attn['k_proj']['kernel'].reshape(d, -1)
            wv = attn['v_proj']['kernel'].reshape(d, -1)
            sd[pre + 'attn.c_attn.weight'] = np.concatenate(
                [wq, wk, wv], axis=1)
            sd[pre + 'attn.c_attn.bias'] = np.concatenate([
                attn['q_proj']['bias'].reshape(-1),
                attn['k_proj']['bias'].reshape(-1),
                attn['v_proj']['bias'].reshape(-1)])
            sd[pre + 'attn.c_proj.weight'] = \
                attn['o_proj']['kernel'].reshape(-1, d)
            sd[pre + 'attn.c_proj.bias'] = attn['o_proj']['bias']
            sd[pre + 'ln_1.weight'] = li['attn_norm']['scale']
            sd[pre + 'ln_1.bias'] = li['attn_norm']['bias']
            sd[pre + 'ln_2.weight'] = li['mlp_norm']['scale']
            sd[pre + 'ln_2.bias'] = li['mlp_norm']['bias']
            sd[pre + 'mlp.c_fc.weight'] = li['mlp']['up_proj']['kernel']
            sd[pre + 'mlp.c_fc.bias'] = li['mlp']['up_proj']['bias']
            sd[pre + 'mlp.c_proj.weight'] = \
                li['mlp']['down_proj']['kernel']
            sd[pre + 'mlp.c_proj.bias'] = li['mlp']['down_proj']['bias']
        return sd

    sd['model.embed_tokens.weight'] = p['embed']['embedding']
    sd['model.norm.weight'] = p['final_norm']['scale']
    sd['lm_head.weight'] = (p['embed']['embedding']
                            if cfg.tie_embeddings
                            else p['lm_head']['kernel'].T)
    d = cfg.d_model
    for i in range(cfg.num_layers):
        li = jax_tree_index(layers, i)
        pre = f'model.layers.{i}.'
        attn = li['attn']
        sd[pre + 'input_layernorm.weight'] = li['attn_norm']['scale']
        sd[pre + 'post_attention_layernorm.weight'] = \
            li['mlp_norm']['scale']
        for name in ('q_proj', 'k_proj', 'v_proj'):
            sd[pre + f'self_attn.{name}.weight'] = \
                attn[name]['kernel'].reshape(d, -1).T
            if cfg.qkv_bias:
                sd[pre + f'self_attn.{name}.bias'] = \
                    attn[name]['bias'].reshape(-1)
        sd[pre + 'self_attn.o_proj.weight'] = \
            attn['o_proj']['kernel'].reshape(-1, d).T
        if cfg.is_moe:
            moe = li['moe']
            sd[pre + 'block_sparse_moe.gate.weight'] = moe['router'].T
            for j in range(cfg.num_experts):
                sd[pre + f'block_sparse_moe.experts.{j}.w1.weight'] = \
                    moe['w_gate'][j].T
                sd[pre + f'block_sparse_moe.experts.{j}.w3.weight'] = \
                    moe['w_up'][j].T
                sd[pre + f'block_sparse_moe.experts.{j}.w2.weight'] = \
                    moe['w_down'][j].T
        else:
            sd[pre + 'mlp.gate_proj.weight'] = \
                li['mlp']['gate_proj']['kernel'].T
            sd[pre + 'mlp.up_proj.weight'] = \
                li['mlp']['up_proj']['kernel'].T
            sd[pre + 'mlp.down_proj.weight'] = \
                li['mlp']['down_proj']['kernel'].T
    return sd


def jax_tree_index(tree, i: int):
    """Slice layer i out of a scan-stacked layer tree."""
    if isinstance(tree, dict):
        return {k: jax_tree_index(v, i) for k, v in tree.items()}
    return np.asarray(tree)[i]


def hf_config_for(cfg: ModelConfig):
    """Build the matching transformers config (family chosen from the
    same flags the forward pass branches on). Emits the UNPADDED vocab
    size when the config pads for MXU tiling (Gemma 256000, GPT-2
    50257), matching what to_hf exports and the real tokenizer."""
    import transformers
    if cfg.ssm_heads:
        raise NotImplementedError(
            'no transformers config is built for the Falcon-H1 family '
            '(to_hf gives the state_dict under the checkpoint\'s own '
            'names; write config.json from the published one)')
    hf_vocab = (cfg.unpadded_vocab_size
                if 0 < cfg.unpadded_vocab_size < cfg.vocab_size
                else cfg.vocab_size)
    if cfg.attn_logit_softcap or cfg.final_logit_softcap:
        raise NotImplementedError(
            'softcapped (Gemma-2-style) configs have no faithful HF '
            'export: this architecture omits Gemma-2 post-norms, so '
            'neither GemmaConfig nor Gemma2Config reproduces it')
    if cfg.parallel_block and cfg.qkv_bias:
        if cfg.mlp_style != 'plain' or cfg.mlp_activation != 'gelu':
            raise NotImplementedError(
                'biased parallel_block config emission maps the Phi '
                'layout only (plain GELU MLP)')
        return transformers.PhiConfig(
            vocab_size=hf_vocab, hidden_size=cfg.d_model,
            intermediate_size=cfg.d_mlp,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.num_kv_heads,
            max_position_embeddings=cfg.max_seq_len,
            rope_theta=cfg.rope_theta,
            partial_rotary_factor=cfg.rotary_pct,
            layer_norm_eps=cfg.norm_eps,
            tie_word_embeddings=cfg.tie_embeddings)
    if cfg.parallel_block:
        if cfg.num_kv_heads != 1:
            raise NotImplementedError(
                'parallel_block HF export supports the multi_query '
                'layout only (num_kv_heads=1, falcon-7b)')
        return transformers.FalconConfig(
            vocab_size=hf_vocab, hidden_size=cfg.d_model,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            ffn_hidden_size=cfg.d_mlp,
            max_position_embeddings=cfg.max_seq_len,
            rope_theta=cfg.rope_theta,
            layer_norm_epsilon=cfg.norm_eps,
            multi_query=True, parallel_attn=True, bias=False,
            alibi=False, new_decoder_architecture=False,
            tie_word_embeddings=cfg.tie_embeddings)
    if cfg.pos_embedding == 'learned' and cfg.mlp_style == 'plain':
        return transformers.GPT2Config(
            vocab_size=hf_vocab, n_embd=cfg.d_model,
            n_layer=cfg.num_layers, n_head=cfg.num_heads,
            n_inner=cfg.d_mlp, n_positions=cfg.max_seq_len,
            layer_norm_epsilon=cfg.norm_eps)
    common = dict(
        vocab_size=hf_vocab, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_mlp, num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        max_position_embeddings=cfg.max_seq_len,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        tie_word_embeddings=cfg.tie_embeddings)
    if cfg.rope_scaling is not None:
        factor, low_f, high_f, old_len = cfg.rope_scaling
        # HF `rope_type: llama3` — the Llama-3.1 long-context scaling.
        common['rope_scaling'] = {
            'rope_type': 'llama3',
            'factor': factor,
            'low_freq_factor': low_f,
            'high_freq_factor': high_f,
            'original_max_position_embeddings': int(old_len),
        }
    if cfg.is_moe and cfg.norm_style == 'layernorm':
        return transformers.DbrxConfig(
            d_model=cfg.d_model, n_heads=cfg.num_heads,
            n_layers=cfg.num_layers, max_seq_len=cfg.max_seq_len,
            vocab_size=hf_vocab,
            attn_config={'kv_n_heads': cfg.num_kv_heads,
                         'rope_theta': cfg.rope_theta,
                         'clip_qkv': cfg.qkv_clip or None},
            ffn_config={'ffn_hidden_size': cfg.d_mlp,
                        'moe_num_experts': cfg.num_experts,
                        'moe_top_k': cfg.experts_per_token},
            tie_word_embeddings=cfg.tie_embeddings)
    if cfg.is_moe:
        return transformers.MixtralConfig(
            num_local_experts=cfg.num_experts,
            num_experts_per_tok=cfg.experts_per_token, **common)
    if cfg.norm_style == 'rms_plus1':
        return transformers.GemmaConfig(head_dim=cfg.head_dim, **common)
    if cfg.sliding_window:
        return transformers.MistralConfig(
            sliding_window=cfg.sliding_window, **common)
    if cfg.qkv_bias:
        return transformers.Qwen2Config(**common)
    return transformers.LlamaConfig(**common)


def export_hf_checkpoint(params: Mapping[str, Any], cfg: ModelConfig,
                         out_dir: str) -> str:
    """Write a loadable HF checkpoint dir (config + safetensors) from a
    param tree — the "fine-tune on TPU, serve anywhere" exit ramp."""
    import torch
    import transformers
    sd = {k: torch.tensor(np.ascontiguousarray(v))
          for k, v in to_hf(params, cfg).items()}
    model = transformers.AutoModelForCausalLM.from_config(
        hf_config_for(cfg))
    missing, unexpected = model.load_state_dict(sd, strict=False)
    if unexpected:
        raise ValueError(f'export produced unexpected keys: {unexpected}')
    real_missing = [k for k in missing if 'inv_freq' not in k]
    if real_missing:
        raise ValueError(f'export left weights uninitialized: '
                         f'{real_missing}')
    model.save_pretrained(out_dir)
    logger.info('exported HF checkpoint to %s', out_dir)
    return out_dir


# ---------------- Llama-family (Llama/Mistral/Qwen2/Gemma/Mixtral) ----


def _llama_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    embed = _pad_vocab(sd['model.embed_tokens.weight'], cfg.vocab_size)
    params: Dict[str, Any] = {
        'embed': {'embedding': embed},
        'final_norm': {'scale': sd['model.norm.weight']},
    }
    if not cfg.tie_embeddings:
        params['lm_head'] = {
            'kernel': _pad_vocab(sd['lm_head.weight'], cfg.vocab_size).T}
    return params


def _llama_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    p = f'model.layers.{i}.'
    d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)

    def proj(name, heads):
        w = sd[p + f'self_attn.{name}.weight']      # (heads*hd, d)
        out = {'kernel': w.T.reshape(d, heads, hd)}
        if cfg.qkv_bias:
            out['bias'] = sd[p + f'self_attn.{name}.bias'].reshape(
                heads, hd)
        return out

    attn = {
        'q_proj': proj('q_proj', nh),
        'k_proj': proj('k_proj', nkv),
        'v_proj': proj('v_proj', nkv),
        'o_proj': {
            'kernel':
                sd[p + 'self_attn.o_proj.weight'].T.reshape(nh, hd, d)},
    }
    layer = {
        'attn_norm': {'scale': sd[p + 'input_layernorm.weight']},
        'attn': attn,
        'mlp_norm': {'scale': sd[p + 'post_attention_layernorm.weight']},
    }
    if cfg.is_moe:
        e = cfg.num_experts
        moe = p + 'block_sparse_moe.'
        layer['moe'] = {
            'router': sd[moe + 'gate.weight'].T,            # (d, e)
            'w_gate': np.stack([
                sd[moe + f'experts.{j}.w1.weight'].T for j in range(e)]),
            'w_up': np.stack([
                sd[moe + f'experts.{j}.w3.weight'].T for j in range(e)]),
            'w_down': np.stack([
                sd[moe + f'experts.{j}.w2.weight'].T for j in range(e)]),
        }
    else:
        layer['mlp'] = {
            'gate_proj': {'kernel': sd[p + 'mlp.gate_proj.weight'].T},
            'up_proj': {'kernel': sd[p + 'mlp.up_proj.weight'].T},
            'down_proj': {'kernel': sd[p + 'mlp.down_proj.weight'].T},
        }
    return layer


# ---------------- Falcon-H1 (mixer beside attention) -----------------
#
# A `falcon_h1` checkpoint's keys, a layer (model.layers.{i}.): the
# shared pre-norm `input_layernorm`, the mixer under `mamba.` (in_proj,
# conv1d, A_log, D, dt_bias, norm, out_proj), attention under
# `self_attn.`, the MLP under `feed_forward.` on `pre_ff_layernorm`;
# whole: model.embed_tokens, model.final_layernorm, lm_head. The
# multipliers are the config's, not the checkpoint's. (program leaf,
# checkpoint key, transposed?) for the mixer and the MLP:
_FALCON_H1_MIXER = (
    (('in_proj', 'kernel'), 'mamba.in_proj.weight', True),
    (('out_proj', 'kernel'), 'mamba.out_proj.weight', True),
    (('A_log',), 'mamba.A_log', False),
    (('D',), 'mamba.D', False),
    (('dt_bias',), 'mamba.dt_bias', False),
)
_FALCON_H1_MLP = (('gate_proj', 'feed_forward.gate_proj.weight'),
                  ('up_proj', 'feed_forward.up_proj.weight'),
                  ('down_proj', 'feed_forward.down_proj.weight'))


def _falcon_h1_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    params: Dict[str, Any] = {
        'embed': {'embedding': _pad_vocab(sd['model.embed_tokens.weight'],
                                          cfg.vocab_size)},
        'final_norm': {'scale': sd['model.final_layernorm.weight']},
    }
    if not cfg.tie_embeddings:
        params['lm_head'] = {
            'kernel': _pad_vocab(sd['lm_head.weight'], cfg.vocab_size).T}
    return params


def _falcon_h1_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    p = f'model.layers.{i}.'
    d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
    mixer: Dict[str, Any] = {}
    for path, key, transposed in _FALCON_H1_MIXER:
        w = sd[p + key].T if transposed else sd[p + key]
        if len(path) == 2:
            mixer.setdefault(path[0], {})[path[1]] = w
        else:
            mixer[path[0]] = w
    # torch Conv1d depthwise weight: (channels, 1, taps)
    mixer['conv_kernel'] = sd[p + 'mamba.conv1d.weight'][:, 0, :].T
    if cfg.ssm_conv_bias:
        mixer['conv_bias'] = sd[p + 'mamba.conv1d.bias']
    if cfg.ssm_gated_norm:
        mixer['norm_scale'] = sd[p + 'mamba.norm.weight']
    if cfg.ssm_proj_bias:
        mixer['in_proj']['bias'] = sd[p + 'mamba.in_proj.bias']
        mixer['out_proj']['bias'] = sd[p + 'mamba.out_proj.bias']
    qkv = lambda name, heads: {
        'kernel': sd[p + f'self_attn.{name}.weight'].T.reshape(
            d, heads, hd)}
    return {
        'attn_norm': {'scale': sd[p + 'input_layernorm.weight']},
        'mixer': mixer,
        'attn': {
            'q_proj': qkv('q_proj', nh), 'k_proj': qkv('k_proj', nkv),
            'v_proj': qkv('v_proj', nkv),
            'o_proj': {'kernel': sd[p + 'self_attn.o_proj.weight']
                       .T.reshape(nh, hd, d)}},
        'mlp_norm': {'scale': sd[p + 'pre_ff_layernorm.weight']},
        'mlp': {name: {'kernel': sd[p + key].T}
                for name, key in _FALCON_H1_MLP},
    }


def _falcon_h1_to_hf(p, cfg: ModelConfig) -> Dict[str, np.ndarray]:
    """The inverse of _falcon_h1_top/_falcon_h1_layer (`p`: the float32
    tree)."""
    d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
    sd = {'model.embed_tokens.weight': p['embed']['embedding'],
          'model.final_layernorm.weight': p['final_norm']['scale']}
    if not cfg.tie_embeddings:
        sd['lm_head.weight'] = p['lm_head']['kernel'].T
    for i in range(cfg.num_layers):
        li = jax_tree_index(p['layers']['layer'], i)
        pre = f'model.layers.{i}.'
        mixer, attn = li['mixer'], li['attn']
        sd[pre + 'input_layernorm.weight'] = li['attn_norm']['scale']
        sd[pre + 'pre_ff_layernorm.weight'] = li['mlp_norm']['scale']
        for path, key, transposed in _FALCON_H1_MIXER:
            w = mixer[path[0]]
            w = w[path[1]] if len(path) == 2 else w
            sd[pre + key] = w.T if transposed else w
        sd[pre + 'mamba.conv1d.weight'] = mixer['conv_kernel'].T[:, None, :]
        if cfg.ssm_conv_bias:
            sd[pre + 'mamba.conv1d.bias'] = mixer['conv_bias']
        if cfg.ssm_gated_norm:
            sd[pre + 'mamba.norm.weight'] = mixer['norm_scale']
        if cfg.ssm_proj_bias:
            sd[pre + 'mamba.in_proj.bias'] = mixer['in_proj']['bias']
            sd[pre + 'mamba.out_proj.bias'] = mixer['out_proj']['bias']
        for name, heads in (('q_proj', nh), ('k_proj', nkv),
                            ('v_proj', nkv)):
            sd[pre + f'self_attn.{name}.weight'] = \
                attn[name]['kernel'].reshape(d, heads * hd).T
        sd[pre + 'self_attn.o_proj.weight'] = \
            attn['o_proj']['kernel'].reshape(nh * hd, d).T
        for name, key in _FALCON_H1_MLP:
            sd[pre + key] = li['mlp'][name]['kernel'].T
    return sd


# ---------------- DBRX (fine-grained MoE + GQA + clip_qkv) -----------


def _dbrx_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    return {
        'embed': {'embedding': _pad_vocab(sd['transformer.wte.weight'],
                                          cfg.vocab_size)},
        'final_norm': {'scale': sd['transformer.norm_f.weight']},
        'lm_head': {'kernel': _pad_vocab(sd['lm_head.weight'],
                                         cfg.vocab_size).T},
    }


def _dbrx_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    p = f'transformer.blocks.{i}.'
    d, nh, nkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
    e, ffn = cfg.num_experts, cfg.d_mlp
    # Fused Wqkv rows = [q·(nh·hd), k·(nkv·hd), v·(nkv·hd)].
    w = sd[p + 'norm_attn_norm.attn.Wqkv.weight'].T       # (d, out)
    q, k, v = np.split(w, [nh * hd, (nh + nkv) * hd], axis=1)
    # Experts ship as one (E·ffn, d) block per matrix; per-expert
    # chunks are (ffn, d) applied as x·w1ᵀ (gate/up) and h·w2 (down).
    w1 = sd[p + 'ffn.experts.mlp.w1'].reshape(e, ffn, d)
    v1 = sd[p + 'ffn.experts.mlp.v1'].reshape(e, ffn, d)
    w2 = sd[p + 'ffn.experts.mlp.w2'].reshape(e, ffn, d)
    return {
        'attn_norm': {'scale': sd[p + 'norm_attn_norm.norm_1.weight']},
        'mlp_norm': {'scale': sd[p + 'norm_attn_norm.norm_2.weight']},
        'attn': {
            'q_proj': {'kernel': q.reshape(d, nh, hd)},
            'k_proj': {'kernel': k.reshape(d, nkv, hd)},
            'v_proj': {'kernel': v.reshape(d, nkv, hd)},
            'o_proj': {'kernel':
                       sd[p + 'norm_attn_norm.attn.out_proj.weight']
                       .T.reshape(nh, hd, d)},
        },
        'moe': {
            'router': sd[p + 'ffn.router.layer.weight'].T,   # (d, E)
            'w_gate': w1.transpose(0, 2, 1),                 # (E, d, ffn)
            'w_up': v1.transpose(0, 2, 1),
            'w_down': w2,                                    # (E, ffn, d)
        },
    }


# ---------------- Phi (biased parallel block + partial rotary) -------


def _phi_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    return {
        'embed': {'embedding': _pad_vocab(sd['model.embed_tokens.weight'],
                                          cfg.vocab_size)},
        'final_norm': {'scale': sd['model.final_layernorm.weight'],
                       'bias': sd['model.final_layernorm.bias']},
        'lm_head': {
            'kernel': _pad_vocab(sd['lm_head.weight'], cfg.vocab_size).T,
            'bias': _pad_vocab(sd['lm_head.bias'][:, None],
                               cfg.vocab_size)[:, 0],
        },
    }


def _phi_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    p = f'model.layers.{i}.'
    d, nh, hd = cfg.d_model, cfg.num_heads, cfg.head_dim

    def proj(name, heads):
        return {
            'kernel': sd[p + f'self_attn.{name}.weight'].T.reshape(
                d, heads, hd),
            'bias': sd[p + f'self_attn.{name}.bias'].reshape(heads, hd),
        }

    return {
        'attn_norm': {'scale': sd[p + 'input_layernorm.weight'],
                      'bias': sd[p + 'input_layernorm.bias']},
        'attn': {
            'q_proj': proj('q_proj', nh),
            'k_proj': proj('k_proj', cfg.num_kv_heads),
            'v_proj': proj('v_proj', cfg.num_kv_heads),
            'o_proj': {
                'kernel': sd[p + 'self_attn.dense.weight'].T.reshape(
                    nh, hd, d),
                'bias': sd[p + 'self_attn.dense.bias'],
            },
        },
        'mlp': {
            'up_proj': {'kernel': sd[p + 'mlp.fc1.weight'].T,
                        'bias': sd[p + 'mlp.fc1.bias']},
            'down_proj': {'kernel': sd[p + 'mlp.fc2.weight'].T,
                          'bias': sd[p + 'mlp.fc2.bias']},
        },
    }


# ---------------- Falcon (parallel block + MQA) ----------------------


def _falcon_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    return {
        'embed': {'embedding': _pad_vocab(
            sd['transformer.word_embeddings.weight'], cfg.vocab_size)},
        'final_norm': {'scale': sd['transformer.ln_f.weight'],
                       'bias': sd['transformer.ln_f.bias']},
    }


def _falcon_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    if cfg.num_kv_heads != 1:
        raise NotImplementedError(
            'Falcon conversion supports the multi_query layout '
            '(num_kv_heads=1, falcon-7b); the 40B '
            'new_decoder_architecture interleaves KV per head group')
    p = f'transformer.h.{i}.'
    d, nh, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    # Fused QKV, multi_query layout: rows = [q·(nh·hd), k·hd, v·hd].
    w = sd[p + 'self_attention.query_key_value.weight'].T  # (d, out)
    q, k, v = np.split(w, [nh * hd, nh * hd + hd], axis=1)
    return {
        'attn_norm': {'scale': sd[p + 'input_layernorm.weight'],
                      'bias': sd[p + 'input_layernorm.bias']},
        'attn': {
            'q_proj': {'kernel': q.reshape(d, nh, hd)},
            'k_proj': {'kernel': k.reshape(d, 1, hd)},
            'v_proj': {'kernel': v.reshape(d, 1, hd)},
            'o_proj': {'kernel':
                       sd[p + 'self_attention.dense.weight'].T.reshape(
                           nh, hd, d)},
        },
        'mlp': {
            'up_proj': {'kernel': sd[p + 'mlp.dense_h_to_4h.weight'].T},
            'down_proj': {'kernel': sd[p + 'mlp.dense_4h_to_h.weight'].T},
        },
    }


# ---------------- GPT-2 ----------------------------------------------


def _gpt2_top(sd, cfg: ModelConfig) -> Dict[str, Any]:
    wpe = sd['transformer.wpe.weight']
    if wpe.shape[0] < cfg.max_seq_len:
        raise ValueError(f'checkpoint supports {wpe.shape[0]} positions '
                         f'< max_seq_len {cfg.max_seq_len}')
    return {
        'embed': {'embedding': _pad_vocab(sd['transformer.wte.weight'],
                                          cfg.vocab_size)},
        'pos_embed': {'embedding': wpe[:cfg.max_seq_len]},
        'final_norm': {'scale': sd['transformer.ln_f.weight'],
                       'bias': sd['transformer.ln_f.bias']},
    }


def _gpt2_layer(sd, cfg: ModelConfig, i: int) -> Dict[str, Any]:
    p = f'transformer.h.{i}.'
    d, nh, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    # Conv1D stores [in, out]; c_attn packs q,k,v along out.
    w = sd[p + 'attn.c_attn.weight']                 # (d, 3d)
    b = sd[p + 'attn.c_attn.bias']                   # (3d,)
    wq, wk, wv = np.split(w, 3, axis=1)
    bq, bk, bv = np.split(b, 3)
    attn = {
        'q_proj': {'kernel': wq.reshape(d, nh, hd),
                   'bias': bq.reshape(nh, hd)},
        'k_proj': {'kernel': wk.reshape(d, nh, hd),
                   'bias': bk.reshape(nh, hd)},
        'v_proj': {'kernel': wv.reshape(d, nh, hd),
                   'bias': bv.reshape(nh, hd)},
        'o_proj': {'kernel': sd[p + 'attn.c_proj.weight'].reshape(
            nh, hd, d),
                   'bias': sd[p + 'attn.c_proj.bias']},
    }
    return {
        'attn_norm': {'scale': sd[p + 'ln_1.weight'],
                      'bias': sd[p + 'ln_1.bias']},
        'attn': attn,
        'mlp_norm': {'scale': sd[p + 'ln_2.weight'],
                     'bias': sd[p + 'ln_2.bias']},
        'mlp': {
            'up_proj': {'kernel': sd[p + 'mlp.c_fc.weight'],
                        'bias': sd[p + 'mlp.c_fc.bias']},
            'down_proj': {'kernel': sd[p + 'mlp.c_proj.weight'],
                          'bias': sd[p + 'mlp.c_proj.bias']},
        },
    }
