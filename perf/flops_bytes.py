"""Required work from shapes: the operations and bytes the mathematics
asks for, whatever implements it. Recomputed operations, padding and
gradients nobody needs are not counted.

`cfg` is a configuration file's dict (the published config's keys).
A multiply-add counts as two operations.
"""
from __future__ import annotations


def window(cfg: dict) -> int:
    """Keys a query may look back over; 0 where the configuration has no
    window or says it is not used."""
    if not cfg.get('use_sliding_window', True):
        return 0
    return cfg.get('sliding_window') or 0


def dims(cfg: dict) -> dict:
    d = cfg['hidden_size']
    h = cfg['num_attention_heads']
    kv = cfg['num_key_value_heads']
    hd = cfg.get('head_dim') or d // h
    return {'d': d, 'h': h, 'kv': kv, 'hd': hd,
            'f': cfg['intermediate_size'], 'v': cfg['vocab_size'],
            'layers': cfg['num_hidden_layers'],
            'window': window(cfg)}


def qkv_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * (s['h'] + 2 * s['kv']) * s['hd']


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that sit in a matrix multiplication."""
    s = dims(cfg)
    return (qkv_params(cfg) + s['h'] * s['hd'] * s['d']
            + 3 * s['d'] * s['f'])


def unembed_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['v']


def keys_seen(position: int, window: int) -> int:
    """Keys a query at `position` (from 0) attends to."""
    n = position + 1
    return min(n, window) if window else n


def keys_seen_sum(start: int, stop: int, window: int) -> int:
    """Sum of keys_seen over positions start .. stop - 1."""
    def tri(n):  # sum of keys_seen(p) for p < n, no window
        return n * (n + 1) // 2
    if not window or stop <= window:
        return tri(stop) - tri(start)
    if start >= window:
        return (stop - start) * window
    return tri(window) - tri(start) + (stop - window) * window


def attention_flops(cfg: dict, key_count: int) -> int:
    """Scores and weighted sum for queries that see `key_count` keys in
    all (summed over the queries): 2 matmuls over every layer and head."""
    s = dims(cfg)
    return 4 * s['layers'] * s['h'] * s['hd'] * key_count


def forward_flops(cfg: dict, tokens: int, key_count: int,
                  logits_for: int) -> int:
    """Forward pass over `tokens` tokens whose queries see `key_count`
    keys in all; the unembedding is needed for `logits_for` of them."""
    s = dims(cfg)
    return (2 * s['layers'] * layer_matmul_params(cfg) * tokens
            + attention_flops(cfg, key_count)
            + 2 * unembed_params(cfg) * logits_for)


def prefill_flops(cfg: dict, start: int, stop: int,
                  last: bool) -> int:
    """Prompt positions start .. stop - 1 of one request; `last` says the
    prompt ends here, so one row of logits is needed."""
    w = dims(cfg)['window']
    return forward_flops(cfg, stop - start, keys_seen_sum(start, stop, w),
                         1 if last else 0)


def prefilled_flops(cfg: dict, work: dict) -> int:
    """Operations of the prompt tokens prefilled in a traced stretch.
    Which positions its chunks covered is not recorded: the prompts that
    finished in it are taken as prefilled from their start, shortest
    first, until the count of tokens is used up; exact but for the
    prompts that straddle the stretch's ends."""
    flops, left = 0, work['prompt_tokens_prefilled']
    for p in sorted(work['prompts_finished']):
        n = min(p, left)
        if n <= 0:
            break
        flops += prefill_flops(cfg, 0, n, n == p)
        left -= n
    if left > 0:
        flops += prefill_flops(cfg, 0, left, False)
    return flops


def decode_flops(cfg: dict, position: int) -> int:
    """One generated token fed back at `position`."""
    w = dims(cfg)['window']
    return forward_flops(cfg, 1, keys_seen(position, w), 1)


def weight_bytes_per_step(cfg: dict, bytes_per_weight: int = 2) -> int:
    """What one decode step cannot avoid reading of the weights: every
    layer, the final norm and the unembedding, once. (The embedding is
    read one row a token.)"""
    s = dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * s['d']
    if cfg.get('attention_bias'):
        per_layer += (s['h'] + 2 * s['kv']) * s['hd']
    return bytes_per_weight * (s['layers'] * per_layer
                               + unembed_params(cfg) + s['d'])


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = dims(cfg)
    return 2 * s['layers'] * s['kv'] * s['hd'] * bytes_per_value


def decode_kv_bytes(cfg: dict, position: int,
                    bytes_per_value: int = 2) -> int:
    """K and V one decode step must read for a slot at `position`."""
    return keys_seen(position, dims(cfg)['window']) * \
        kv_bytes_per_token(cfg, bytes_per_value)


def lora_params(cfg: dict, rank: int, targets=('q', 'v')) -> int:
    s = dims(cfg)
    out = {'q': s['h'] * s['hd'], 'k': s['kv'] * s['hd'],
           'v': s['kv'] * s['hd'], 'o': s['d']}
    inp = {'q': s['d'], 'k': s['d'], 'v': s['d'], 'o': s['h'] * s['hd']}
    return s['layers'] * sum(rank * (inp[t] + out[t]) for t in targets)


def lora_train_flops(cfg: dict, rows: int, seq: int, rank: int,
                     targets=('q', 'v')) -> int:
    """One LoRA step on rows x seq tokens: the forward pass; the backward
    pass through the activations (one matmul per weight matrix, not two:
    the frozen weights need no gradient), through attention (twice the
    forward's), and the adapters' own three matmuls each way. The first
    layer's input needs no gradient, so its Q/K/V input-gradients are
    left out. Nothing recomputed is counted."""
    s = dims(cfg)
    tokens = rows * seq
    keys = rows * keys_seen_sum(0, seq, s['window'])
    mm = s['layers'] * layer_matmul_params(cfg) + unembed_params(cfg)
    fwd = 2 * mm * tokens + attention_flops(cfg, keys)
    bwd = (2 * (mm - qkv_params(cfg)) * tokens
           + 2 * attention_flops(cfg, keys))
    adapters = 6 * lora_params(cfg, rank, targets) * tokens
    return fwd + bwd + adapters


def flash_attention_work(cfg: dict, rows: int, seq: int,
                         bytes_per_value: int = 2) -> dict:
    """Forward and backward attention of one training step, over all
    layers: operations (forward 2 matmuls, backward 4, on the causal,
    windowed pairs only) and the bytes that must cross HBM (read Q, K, V
    forward; read Q, K, V, O, dO and write dQ, dK, dV backward; write O)."""
    s = dims(cfg)
    pairs = rows * keys_seen_sum(0, seq, s['window'])
    flops = 12 * s['layers'] * s['h'] * s['hd'] * pairs
    q_elems = rows * seq * s['h'] * s['hd']
    kv_elems = rows * seq * s['kv'] * s['hd']
    fwd_bytes = (q_elems + 2 * kv_elems) + q_elems
    bwd_bytes = (3 * q_elems + 2 * kv_elems) + (q_elems + 2 * kv_elems)
    return {'flops': flops,
            'bytes': s['layers'] * bytes_per_value * (fwd_bytes + bwd_bytes)}


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound: 'compute' |
    'memory')."""
    t_c = flops / peaks['bf16_flops_per_s']
    t_m = bytes_ / peaks['hbm_bytes_per_s']
    return (t_c, 'compute') if t_c >= t_m else (t_m, 'memory')
