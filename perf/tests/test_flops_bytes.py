"""flops_bytes on shapes worked by hand."""
import flops_bytes as fb

MISTRAL = {'hidden_size': 4096, 'intermediate_size': 14336,
           'num_hidden_layers': 16, 'num_attention_heads': 32,
           'num_key_value_heads': 8, 'head_dim': 128, 'vocab_size': 32000,
           'sliding_window': 4096}
QWEN = {'hidden_size': 3584, 'intermediate_size': 18944,
        'num_hidden_layers': 14, 'num_attention_heads': 28,
        'num_key_value_heads': 4, 'head_dim': 128, 'vocab_size': 152064,
        'sliding_window': 131072, 'use_sliding_window': False,
        'attention_bias': True}


def test_layer_params_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x14336
    assert fb.layer_matmul_params(MISTRAL) == (
        4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    assert fb.layer_matmul_params(MISTRAL) == 218_103_808
    assert fb.unembed_params(QWEN) == 3584 * 152064
    # the issue's 233.0 M a layer
    assert round(fb.layer_matmul_params(QWEN) / 1e6, 1) == 233.0


def test_window_is_read_from_the_config():
    assert fb.window(MISTRAL) == 4096
    assert fb.window(QWEN) == 0


def test_window_caps_attention_at_4096_keys():
    assert fb.keys_seen(10, 4096) == 11
    assert fb.keys_seen(4095, 4096) == 4096
    assert fb.keys_seen(9000, 4096) == 4096
    assert fb.keys_seen(9000, 0) == 9001
    # sum over positions 0..5 with a window of 4: 1+2+3+4+4+4
    assert fb.keys_seen_sum(0, 6, 4) == 18
    assert fb.keys_seen_sum(2, 6, 4) == 15
    assert fb.keys_seen_sum(5, 8, 4) == 12
    assert fb.keys_seen_sum(0, 6, 0) == 21
    brute = sum(fb.keys_seen(p, 4096) for p in range(3000, 6000))
    assert fb.keys_seen_sum(3000, 6000, 4096) == brute


def test_decode_flops_and_bytes_by_hand():
    mm = 16 * 218_103_808
    want = 2 * mm + 4 * 16 * 32 * 128 * 101 + 2 * 4096 * 32000
    assert fb.decode_flops(MISTRAL, 100) == want
    # K and V of 8 kv heads x 128 in bf16 over 16 layers: 64 KiB a token
    assert fb.kv_bytes_per_token(MISTRAL) == 65536
    assert fb.decode_kv_bytes(MISTRAL, 99) == 100 * 65536
    assert fb.weight_bytes_per_step(MISTRAL) == 2 * (
        16 * (218_103_808 + 2 * 4096) + 4096 * 32000 + 4096)


def test_prefill_needs_one_row_of_logits():
    a = fb.prefill_flops(MISTRAL, 0, 16, last=False)
    b = fb.prefill_flops(MISTRAL, 0, 16, last=True)
    assert b - a == 2 * 4096 * 32000
    assert a == 2 * 16 * 218_103_808 * 16 + 4 * 16 * 32 * 128 * 136


def test_a_lora_step_is_not_6n():
    rows, seq = 1, 4096
    tokens = rows * seq
    n = 16 * 218_103_808 + 4096 * 32000
    got = fb.lora_train_flops(MISTRAL, rows, seq, 16)
    attn = fb.attention_flops(MISTRAL, fb.keys_seen_sum(0, seq, 4096))
    assert got < 6 * n * tokens            # no base-weight gradients
    assert got > 4 * (n - 4096 * 6144) * tokens
    lora = 16 * 16 * (4096 + 4096 + 4096 + 1024)
    assert fb.lora_params(MISTRAL, 16) == lora
    want = (2 * n * tokens + attn
            + 2 * (n - 4096 * 6144) * tokens + 2 * attn
            + 6 * lora * tokens)
    assert got == want


def test_flash_work_and_roofline_bound():
    w = fb.flash_attention_work(MISTRAL, 1, 4096)
    pairs = 4096 * 4097 // 2
    assert w['flops'] == 12 * 16 * 32 * 128 * pairs
    peaks = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    t, bound = fb.roofline_seconds(w['flops'], w['bytes'], peaks)
    assert bound == 'compute' and t == w['flops'] / 197e12
    t, bound = fb.roofline_seconds(1e9, 1e9, peaks)
    assert bound == 'memory'
