"""Tiny stand-ins for a configuration, the mixes and a run's context, for
rehearsing the drivers on the CPU from the tests. Float32 throughout, so
the program and the reference agree to rounding."""


def config(bias: bool, window: int) -> dict:
    return {
        'name': 'tiny', 'family': 'llama_shaped', 'hidden_size': 64,
        'intermediate_size': 128, 'num_hidden_layers': 2,
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'head_dim': 16, 'vocab_size': 512, 'rms_norm_eps': 1e-5,
        'rope_theta': 10000.0, 'sliding_window': window,
        'attention_bias': bias,
        'program': {'registry_name': 'test-tiny', 'overrides': {
            'num_layers': 2, 'param_dtype': 'float32', 'dtype': 'float32',
            'd_model': 64, 'num_heads': 4, 'num_kv_heads': 2,
            'd_mlp': 128, 'vocab_size': 512, 'qkv_bias': bias,
            'norm_eps': 1e-5, 'rope_theta': 10000.0,
            'sliding_window': window, 'attention_impl': 'xla'}}}


def serve_mix() -> dict:
    return {
        'driver': 'closed_loop',
        'engine': {'num_slots': 4, 'max_seq_len': 128,
                   'paged_block_size': 16},
        'prompt_tokens': {'median': 24, 'sigma': 0.5, 'min': 8, 'max': 64},
        'output_tokens': {'median': 8, 'sigma': 0.5, 'min': 4, 'max': 16},
        'warmup_s': 0.5, 'clients': 6, 'pool_size': 32,
        'trace_after_s': 0.3, 'trace_s': 0.5, 'check_requests': 4}


def train_mix() -> dict:
    return {
        'driver': 'train', 'batch': 2, 'seq': 64,
        'lora': {'rank': 4, 'alpha': 4.0, 'targets': 'q,v'},
        'optimizer': {'learning_rate': 3e-4, 'warmup_steps': 0,
                      'total_steps': 10000, 'weight_decay': 0.1,
                      'grad_clip_norm': 1.0, 'b1': 0.9, 'b2': 0.95},
        'batch_pool': 4, 'trace_after_s': 0.2, 'trace_steps': 2}


SERVE_LIMITS = {'gap_max': 1e-3, 'gap_mean': 1e-4, 'min_tokens': 10}
TRAIN_LIMITS = {'loss_gap_step1': 1e-5, 'loss_gap_step2': 1e-5,
                'loss_gap_step3': 1e-5, 'grad_norm_gap': 1e-3,
                'change_norm_gap': 1e-3}


def ctx(cfg: dict, mix: dict, limits: dict, seed: int, seconds: float,
        trace: bool = False) -> dict:
    """What run.py hands a driver, less its look for a chip."""
    return {'cell': {'name': f'tiny.{mix["driver"]}', 'chips': 1},
            'config': cfg, 'mix': mix, 'limits': limits, 'seed': seed,
            'seconds': seconds, 'trace': trace,
            'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 1},
            'peaks': {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}}
