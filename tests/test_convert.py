"""HF-checkpoint conversion: cross-framework logit parity.

For each supported family, build a TINY randomly-initialized
`transformers` model locally (no downloads), convert its state_dict with
models/convert.py, and require our Transformer to reproduce the HF
implementation's logits on the same tokens. This pins every convention
at once: weight transposes, head layouts, rotary split, norm deltas,
tied unembeds, GQA repeat, biases, MoE routing.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip('torch')
transformers = pytest.importorskip('transformers')

from skypilot_tpu.models import ModelConfig, Transformer  # noqa: E402
from skypilot_tpu.models.convert import from_hf, load_hf_model  # noqa: E402

ATOL = 3e-4


def _logit_parity(hf_model, cfg, seq=12, vocab_limit=None):
    hf_model.eval()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size if vocab_limit is None
                          else vocab_limit, size=(1, seq))
    with torch.no_grad():
        want = hf_model(torch.tensor(tokens)).logits.numpy()
    params = load_hf_model(hf_model, cfg)
    got = np.asarray(
        Transformer(cfg).apply({'params': params},
                               jnp.asarray(tokens, jnp.int32)),
        np.float32)
    if vocab_limit is not None:
        got = got[..., :vocab_limit]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=ATOL)


def _base_cfg(**kw):
    defaults = dict(name='convert-test', vocab_size=256, d_model=64,
                    num_layers=2, num_heads=4, num_kv_heads=2, d_mlp=128,
                    max_seq_len=64, rope_theta=10000.0, norm_eps=1e-6,
                    attention_impl='xla', remat=False, dtype='float32',
                    param_dtype='float32')
    defaults.update(kw)
    return ModelConfig(**defaults)


class TestLlamaFamily:

    def test_llama_logits_match(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        _logit_parity(model, _base_cfg())

    def test_llama2_mha_logits_match(self):
        """Llama-2 shape: MHA (num_kv_heads == num_heads), rope 10k —
        the pre-GQA repeat-kv degenerate case must still be exact."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        _logit_parity(model, _base_cfg(num_kv_heads=4))

    def test_llama31_rope_scaling_logits_match(self):
        """Llama-3.1 shape: llama3 long-context rope scaling (factor 8
        over a short original window so EVERY frequency band — scaled,
        pass-through, interpolated — is exercised at seq 12). Parity
        against transformers' rope_type='llama3' implementation."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            rope_scaling={'rope_type': 'llama3', 'factor': 8.0,
                          'low_freq_factor': 1.0,
                          'high_freq_factor': 4.0,
                          'original_max_position_embeddings': 8},
            attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        _logit_parity(model,
                      _base_cfg(rope_scaling=(8.0, 1.0, 4.0, 8)))

    def test_llama31_scaling_changes_logits(self):
        """The scaling must actually DO something: same weights with and
        without rope_scaling disagree beyond tolerance."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 256, size=(1, 12)),
                             jnp.int32)
        plain_cfg = _base_cfg()
        scaled_cfg = _base_cfg(rope_scaling=(8.0, 1.0, 4.0, 8))
        params = load_hf_model(model, plain_cfg)
        plain = np.asarray(Transformer(plain_cfg).apply(
            {'params': params}, tokens))
        scaled = np.asarray(Transformer(scaled_cfg).apply(
            {'params': params}, tokens))
        assert np.abs(plain - scaled).max() > 1e-3

    def test_codellama_padded_vocab_logits_match(self):
        """CodeLlama shape: HF vocab 260 (≅32016: not MXU-aligned) into
        a padded-vocab config; pad rows must be masked, real rows exact."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=260, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            rope_theta=1e6, rms_norm_eps=1e-6,
            attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg = _base_cfg(vocab_size=384, unpadded_vocab_size=260,
                        num_kv_heads=4, rope_theta=1e6)
        _logit_parity(model, cfg, vocab_limit=260)
        params = load_hf_model(model, cfg)
        logits = np.asarray(Transformer(cfg).apply(
            {'params': params}, jnp.asarray([[1, 2, 3]], jnp.int32)))
        assert (logits[..., 260:] < -1e29).all()

    def test_mistral_sliding_window_logits_match(self):
        hf_cfg = transformers.MistralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6, sliding_window=8,
            attn_implementation='eager')
        model = transformers.MistralForCausalLM(hf_cfg)
        # seq 16 > window 8: the window mask must actually matter.
        _logit_parity(model, _base_cfg(sliding_window=8), seq=16)

    def test_qwen2_bias_logits_match(self):
        hf_cfg = transformers.Qwen2Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            attn_implementation='eager')
        model = transformers.Qwen2ForCausalLM(hf_cfg)
        _logit_parity(model, _base_cfg(qkv_bias=True))

    def test_gemma_logits_match(self):
        hf_cfg = transformers.GemmaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=1, head_dim=16,
            max_position_embeddings=64, rope_theta=10000.0,
            rms_norm_eps=1e-6, attn_implementation='eager')
        model = transformers.GemmaForCausalLM(hf_cfg)
        cfg = _base_cfg(num_kv_heads=1, head_dim_override=16,
                        mlp_activation='gelu', norm_style='rms_plus1',
                        tie_embeddings=True, scale_embed_by_dim=True)
        _logit_parity(model, cfg)

    def test_mixtral_logits_match(self):
        hf_cfg = transformers.MixtralConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6, num_local_experts=4,
            num_experts_per_tok=2, attn_implementation='eager')
        model = transformers.MixtralForCausalLM(hf_cfg)
        # moe_impl='dense' is the exact (no-capacity-drop) path — the
        # right one for a bitwise-ish comparison.
        cfg = _base_cfg(num_experts=4, experts_per_token=2,
                        moe_impl='dense')
        _logit_parity(model, cfg)


class TestDbrx:

    def _hf(self, clip=8.0):
        hf_cfg = transformers.DbrxConfig(
            d_model=64, n_heads=4, n_layers=2, max_seq_len=64,
            vocab_size=256,
            attn_config={'kv_n_heads': 2, 'rope_theta': 10000.0,
                         'clip_qkv': clip},
            ffn_config={'ffn_hidden_size': 128, 'moe_num_experts': 4,
                        'moe_top_k': 2},
            attn_implementation='eager')
        return transformers.DbrxForCausalLM(hf_cfg)

    def _cfg(self):
        return _base_cfg(num_experts=4, experts_per_token=2,
                         moe_impl='dense', norm_style='layernorm',
                         norm_bias=False, qkv_clip=8.0, norm_eps=1e-5)

    def test_dbrx_logits_match(self):
        """DBRX: fine-grained MoE (fused expert blocks), GQA, bias-free
        LayerNorm, clip_qkv — all four dialect knobs at once."""
        _logit_parity(self._hf(), self._cfg())

    def test_clip_qkv_matters(self):
        """The ±clip clamp must actually change outputs (guards against
        the knob silently not wiring through)."""
        import dataclasses as _dc
        model = self._hf(clip=0.05)   # aggressive clip: visible effect
        cfg = _dc.replace(self._cfg(), qkv_clip=0.05)
        _logit_parity(model, cfg)
        params = load_hf_model(model, cfg)
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, size=(1, 8))
        clipped = Transformer(cfg).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32))
        unclipped = Transformer(_dc.replace(cfg, qkv_clip=0.0)).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32))
        assert not np.allclose(np.asarray(clipped),
                               np.asarray(unclipped), atol=1e-3)

    def test_dbrx_round_trip(self):
        model = self._hf()
        cfg = self._cfg()
        params = load_hf_model(model, cfg)
        from skypilot_tpu.models.convert import to_hf
        sd = to_hf(params, cfg)
        want = {k: v.numpy() for k, v in model.state_dict().items()
                if 'inv_freq' not in k}
        assert set(sd) == set(want), set(sd) ^ set(want)
        for k in want:
            np.testing.assert_allclose(sd[k], want[k], atol=1e-6,
                                       err_msg=k)


class TestPhi:

    def _hf(self, rotary=0.5):
        hf_cfg = transformers.PhiConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            rope_theta=10000.0, partial_rotary_factor=rotary,
            layer_norm_eps=1e-5, attn_implementation='eager')
        return transformers.PhiForCausalLM(hf_cfg)

    def _cfg(self, rotary=0.5):
        return _base_cfg(num_kv_heads=4, mlp_style='plain',
                         mlp_activation='gelu', norm_style='layernorm',
                         parallel_block=True, qkv_bias=True, o_bias=True,
                         mlp_bias=True, lm_head_bias=True,
                         rotary_pct=rotary, norm_eps=1e-5)

    def test_phi_logits_match(self):
        """Phi-2 architecture: biased parallel block, partial rotary
        (40%-style), plain GELU, untied + biased lm_head."""
        _logit_parity(self._hf(), self._cfg())

    def test_partial_rotary_matters(self):
        """rotary_pct must actually gate the rotation: the same weights
        under full rotary produce different logits."""
        import dataclasses as _dc
        model = self._hf(rotary=0.5)
        cfg = self._cfg(rotary=0.5)
        params = load_hf_model(model, cfg)
        rng = np.random.default_rng(1)
        tokens = rng.integers(0, cfg.vocab_size, size=(1, 12))
        partial = Transformer(cfg).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32))
        full = Transformer(_dc.replace(cfg, rotary_pct=1.0)).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32))
        assert not np.allclose(np.asarray(partial), np.asarray(full),
                               atol=1e-3)

    def test_phi_round_trip(self):
        model = self._hf()
        cfg = self._cfg()
        params = load_hf_model(model, cfg)
        from skypilot_tpu.models.convert import to_hf
        sd = to_hf(params, cfg)
        want = {k: v.numpy() for k, v in model.state_dict().items()
                if 'inv_freq' not in k}
        assert set(sd) == set(want), set(sd) ^ set(want)
        for k in want:
            np.testing.assert_allclose(sd[k], want[k], atol=1e-6,
                                       err_msg=k)


class TestFalcon:

    def test_falcon_parallel_block_mqa_logits_match(self):
        """Falcon-7b architecture: parallel block (shared LayerNorm,
        attn+mlp both add into the residual), MQA (1 KV head), fused
        QKV split, plain GELU MLP, tied embeddings."""
        hf_cfg = transformers.FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, ffn_hidden_size=128,
            max_position_embeddings=64, rope_theta=10000.0,
            layer_norm_epsilon=1e-6, multi_query=True,
            parallel_attn=True, bias=False, alibi=False,
            new_decoder_architecture=False, tie_word_embeddings=True,
            attn_implementation='eager')
        model = transformers.FalconForCausalLM(hf_cfg)
        cfg = _base_cfg(num_kv_heads=1, mlp_style='plain',
                        mlp_activation='gelu', norm_style='layernorm',
                        tie_embeddings=True, parallel_block=True)
        _logit_parity(model, cfg)

    def test_falcon_round_trip(self):
        hf_cfg = transformers.FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, ffn_hidden_size=128,
            max_position_embeddings=64, layer_norm_epsilon=1e-6,
            multi_query=True, parallel_attn=True, bias=False,
            alibi=False, new_decoder_architecture=False,
            tie_word_embeddings=True, attn_implementation='eager')
        model = transformers.FalconForCausalLM(hf_cfg)
        cfg = _base_cfg(num_kv_heads=1, mlp_style='plain',
                        mlp_activation='gelu', norm_style='layernorm',
                        tie_embeddings=True, parallel_block=True)
        params = load_hf_model(model, cfg)
        from skypilot_tpu.models.convert import to_hf
        sd = to_hf(params, cfg)
        want = {k: v.numpy() for k, v in model.state_dict().items()
                if 'inv_freq' not in k}
        assert set(sd) == set(want), set(sd) ^ set(want)
        for k in want:
            np.testing.assert_allclose(sd[k], want[k], atol=1e-6,
                                       err_msg=k)


class TestGPT2:

    def test_gpt2_logits_match(self):
        hf_cfg = transformers.GPT2Config(
            vocab_size=96, n_embd=48, n_layer=2, n_head=4,
            n_positions=64, attn_implementation='eager')
        model = transformers.GPT2LMHeadModel(hf_cfg)
        cfg = _base_cfg(vocab_size=96, d_model=48, num_heads=4,
                        num_kv_heads=4, d_mlp=192, mlp_activation='gelu',
                        mlp_style='plain', norm_style='layernorm',
                        pos_embedding='learned', qkv_bias=True,
                        o_bias=True, mlp_bias=True, tie_embeddings=True,
                        norm_eps=1e-5)
        _logit_parity(model, cfg)

    def test_padded_vocab_logits_masked(self):
        """unpadded_vocab_size masks padding-id logits to -inf so
        sampling can never emit an invalid id."""
        hf_cfg = transformers.GPT2Config(
            vocab_size=96, n_embd=48, n_layer=2, n_head=4,
            n_positions=64, attn_implementation='eager')
        model = transformers.GPT2LMHeadModel(hf_cfg)
        cfg = _base_cfg(vocab_size=128, unpadded_vocab_size=96,
                        d_model=48, num_heads=4, num_kv_heads=4,
                        d_mlp=192, mlp_activation='gelu',
                        mlp_style='plain', norm_style='layernorm',
                        pos_embedding='learned', qkv_bias=True,
                        o_bias=True, mlp_bias=True, tie_embeddings=True,
                        norm_eps=1e-5)
        params = load_hf_model(model, cfg)
        logits = np.asarray(Transformer(cfg).apply(
            {'params': params}, jnp.asarray([[1, 2, 3]], jnp.int32)))
        assert (logits[..., 96:] < -1e29).all()
        assert np.isfinite(logits[..., :96]).all()

    def test_gpt2_vocab_padding(self):
        """Converting into a padded-vocab config (50257-style → ×128)
        zero-fills the extra rows; real-token logits are unchanged."""
        hf_cfg = transformers.GPT2Config(
            vocab_size=96, n_embd=48, n_layer=2, n_head=4,
            n_positions=64, attn_implementation='eager')
        model = transformers.GPT2LMHeadModel(hf_cfg)
        cfg = _base_cfg(vocab_size=128, d_model=48, num_heads=4,
                        num_kv_heads=4, d_mlp=192, mlp_activation='gelu',
                        mlp_style='plain', norm_style='layernorm',
                        pos_embedding='learned', qkv_bias=True,
                        o_bias=True, mlp_bias=True, tie_embeddings=True,
                        norm_eps=1e-5)
        _logit_parity(model, cfg, vocab_limit=96)


class TestConversionErrors:

    def test_vocab_shrink_rejected(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2)
        model = transformers.LlamaForCausalLM(hf_cfg)
        with pytest.raises(ValueError, match='vocab'):
            load_hf_model(model, _base_cfg(vocab_size=128))

    def test_gpt2_position_table_too_small_rejected(self):
        hf_cfg = transformers.GPT2Config(
            vocab_size=96, n_embd=48, n_layer=2, n_head=4,
            n_positions=32, attn_implementation='eager')
        model = transformers.GPT2LMHeadModel(hf_cfg)
        cfg = _base_cfg(vocab_size=96, d_model=48, num_heads=4,
                        num_kv_heads=4, d_mlp=192, mlp_style='plain',
                        norm_style='layernorm', pos_embedding='learned',
                        qkv_bias=True, o_bias=True, mlp_bias=True,
                        tie_embeddings=True, max_seq_len=64)
        with pytest.raises(ValueError, match='positions'):
            load_hf_model(model, cfg)

    def test_load_hf_checkpoint_casts_param_dtype(self, tmp_path):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2)
        transformers.LlamaForCausalLM(hf_cfg).save_pretrained(
            str(tmp_path / 'hf'))
        from skypilot_tpu.models.convert import load_hf_checkpoint
        params = load_hf_checkpoint(
            str(tmp_path / 'hf'), _base_cfg(param_dtype='bfloat16'))
        assert str(params['embed']['embedding'].dtype) == 'bfloat16'

    def test_unconsumed_weights_rejected(self):
        """An architecturally incompatible checkpoint (extra weight
        tensors, e.g. Gemma-2 post-norms) must fail loudly instead of
        silently dropping weights."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2)
        model = transformers.LlamaForCausalLM(hf_cfg)
        sd = dict(model.state_dict())
        sd['model.layers.0.post_feedforward_layernorm.weight'] = \
            torch.ones(64)
        with pytest.raises(ValueError, match='does not consume'):
            from_hf(sd, _base_cfg())

    def test_dropped_bias_rejected(self):
        """Qwen2 checkpoint into a no-bias config: the biases would be
        silently dropped — must raise."""
        hf_cfg = transformers.Qwen2Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2)
        model = transformers.Qwen2ForCausalLM(hf_cfg)
        with pytest.raises(ValueError, match='does not consume'):
            load_hf_model(model, _base_cfg(qkv_bias=False))

    def test_bf16_checkpoint_converts(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2)
        model = transformers.LlamaForCausalLM(hf_cfg).to(torch.bfloat16)
        params = load_hf_model(model, _base_cfg())
        assert params['embed']['embedding'].dtype == np.float32

    def test_softcap_config_export_rejected(self):
        from skypilot_tpu.models.convert import hf_config_for
        with pytest.raises(NotImplementedError, match='softcap'):
            hf_config_for(_base_cfg(attn_logit_softcap=30.0))

    def test_unscanned_layout_rejected(self):
        with pytest.raises(NotImplementedError, match='scan'):
            from_hf({}, dataclasses.replace(_base_cfg(),
                                            scan_layers=False))


class TestTrainerInitFromHf:

    def test_train_run_init_from_hf(self, tmp_path):
        """Fine-tune path end to end: save a tiny HF llama locally,
        `train.run --init-from-hf` converts + shards it and trains."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            rope_theta=500000.0, rms_norm_eps=1e-5,
            attn_implementation='eager')
        transformers.LlamaForCausalLM(hf_cfg).save_pretrained(
            str(tmp_path / 'hf'))
        from skypilot_tpu.train import run as train_run
        rc = train_run.main([
            '--model', 'test-tiny', '--batch', '8', '--seq', '64',
            '--steps', '2', '--init-from-hf', str(tmp_path / 'hf'),
            '--log-every', '1'])
        assert rc == 0


class TestToHf:
    """Reverse conversion: a model trained here must load back into
    transformers bit-for-bit."""

    def _hf_llama(self):
        return transformers.LlamaForCausalLM(transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            attn_implementation='eager'))

    def test_round_trip_llama(self):
        from skypilot_tpu.models.convert import to_hf
        model = self._hf_llama()
        cfg = _base_cfg()
        params = load_hf_model(model, cfg)
        back = from_hf(to_hf(params, cfg), cfg)

        def assert_same(a, b, path=''):
            if isinstance(a, dict):
                assert a.keys() == b.keys(), path
                for k in a:
                    assert_same(a[k], b[k], f'{path}/{k}')
            else:
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), err_msg=path)

        assert_same(params, back)

    def test_exported_weights_load_into_transformers(self):
        """Strongest check: load_state_dict into a fresh HF model and
        compare ITS logits against ours."""
        from skypilot_tpu.models.convert import to_hf
        src = self._hf_llama()
        cfg = _base_cfg()
        params = load_hf_model(src, cfg)
        sd = {k: torch.tensor(v) for k, v in to_hf(params, cfg).items()}
        dst = self._hf_llama()
        missing, unexpected = dst.load_state_dict(sd, strict=False)
        assert not unexpected
        # rotary inv_freq buffers may be reported missing; no weights.
        assert all('inv_freq' in k for k in missing)
        dst.eval()
        rng = np.random.default_rng(3)
        tokens = rng.integers(0, cfg.vocab_size, size=(1, 10))
        with torch.no_grad():
            want = dst(torch.tensor(tokens)).logits.numpy()
        got = np.asarray(Transformer(cfg).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32)))
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=ATOL)

    def test_round_trip_gpt2(self):
        from skypilot_tpu.models.convert import to_hf
        model = transformers.GPT2LMHeadModel(transformers.GPT2Config(
            vocab_size=96, n_embd=48, n_layer=2, n_head=4,
            n_positions=64, attn_implementation='eager'))
        cfg = _base_cfg(vocab_size=96, d_model=48, num_heads=4,
                        num_kv_heads=4, d_mlp=192, mlp_activation='gelu',
                        mlp_style='plain', norm_style='layernorm',
                        pos_embedding='learned', qkv_bias=True,
                        o_bias=True, mlp_bias=True, tie_embeddings=True,
                        norm_eps=1e-5)
        params = load_hf_model(model, cfg)
        back = from_hf(to_hf(params, cfg), cfg)
        leaf_a = params['layers']['layer']['attn']['q_proj']['kernel']
        leaf_b = back['layers']['layer']['attn']['q_proj']['kernel']
        np.testing.assert_array_equal(np.asarray(leaf_a),
                                      np.asarray(leaf_b))


class TestExportHfCheckpoint:

    def test_train_then_export_reloads_in_transformers(self, tmp_path):
        """Full exit ramp: train 2 steps → --export-hf → transformers
        loads the result and produces logits matching ours."""
        from skypilot_tpu.train import run as train_run
        out = str(tmp_path / 'export')
        rc = train_run.main([
            '--model', 'test-tiny', '--batch', '8', '--seq', '32',
            '--steps', '2', '--export-hf', out, '--log-every', '1'])
        assert rc == 0
        hf = transformers.AutoModelForCausalLM.from_pretrained(out)
        hf.eval()
        from skypilot_tpu.models import get_config
        cfg = get_config('test-tiny', dtype='float32',
                         param_dtype='float32')
        params = load_hf_model(hf, cfg)
        rng = np.random.default_rng(5)
        tokens = rng.integers(0, cfg.vocab_size, size=(1, 8))
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()
        got = np.asarray(Transformer(cfg).apply(
            {'params': params}, jnp.asarray(tokens, jnp.int32)),
            np.float32)
        # The exported weights were trained in bf16: the comparison is
        # HF-vs-us on the SAME (exported) float32 weights, so it is
        # tight.
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=ATOL)


class TestExportTool:

    def test_checkpoint_to_hf_roundtrip(self, tmp_path):
        """Multi-host story: train with --checkpoint-dir, export the
        checkpoint via the standalone tool, reload in transformers."""
        from skypilot_tpu.train import run as train_run
        ckpt = str(tmp_path / 'ckpt')
        rc = train_run.main([
            '--model', 'test-tiny', '--batch', '8', '--seq', '32',
            '--steps', '2', '--checkpoint-dir', ckpt,
            '--checkpoint-every', '1', '--log-every', '1'])
        assert rc == 0
        from skypilot_tpu.models import export_tool
        out = str(tmp_path / 'hf')
        rc = export_tool.main(['--model', 'test-tiny',
                               '--checkpoint-dir', ckpt, '--out', out])
        assert rc == 0
        hf = transformers.AutoModelForCausalLM.from_pretrained(out)
        assert hf.config.vocab_size == 512

    def test_restore_on_different_device_count(self, tmp_path):
        """The serving story restore_params_only promises: a checkpoint
        saved on an 8-device mesh must restore on a 1-device process.
        Regression: orbax fell back to save-time shardings (unbuildable
        at a different device count) unless explicit ArrayRestoreArgs
        carry the restoring mesh's shardings."""
        import os as os_lib
        import subprocess
        import sys as _sys
        from skypilot_tpu.train import run as train_run
        ckpt = str(tmp_path / 'ckpt')
        rc = train_run.main([
            '--model', 'test-tiny', '--batch', '8', '--seq', '32',
            '--steps', '2', '--lora-rank', '4', '--checkpoint-dir',
            ckpt, '--checkpoint-every', '1', '--log-every', '1'])
        assert rc == 0
        env = dict(os_lib.environ, JAX_PLATFORMS='cpu')
        env['XLA_FLAGS'] = ''  # 1 device — unlike this 8-device process
        out = str(tmp_path / 'hf')
        proc = subprocess.run(
            [_sys.executable, '-m', 'skypilot_tpu.models.export_tool',
             '--model', 'test-tiny', '--lora-rank', '4',
             '--checkpoint-dir', ckpt, '--out', out],
            capture_output=True, text=True, timeout=420, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        hf = transformers.AutoModelForCausalLM.from_pretrained(out)
        assert not any('lora' in k for k in hf.state_dict())

    def test_missing_checkpoint_fails(self, tmp_path):
        from skypilot_tpu.models import export_tool
        with pytest.raises(FileNotFoundError):
            export_tool.main(['--model', 'test-tiny', '--checkpoint-dir',
                              str(tmp_path / 'nope'), '--out',
                              str(tmp_path / 'o')])


class TestQuantizeAfterConvert:

    def test_converted_params_quantize_and_run(self):
        """The serving path end to end: HF checkpoint → convert →
        int8 quantize → decode-mode forward."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, attn_implementation='eager')
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg = _base_cfg()
        params = load_hf_model(model, cfg)
        from skypilot_tpu.models.inference import InferenceEngine
        eng = InferenceEngine(cfg, params=params, batch_size=1,
                              quantize='int8')
        out, _ = eng.generate(jnp.asarray([[5, 7, 11]], jnp.int32),
                              max_new_tokens=4)
        assert out.shape == (1, 4)
