"""The Falcon-H1 decoder as the harness needs to know it (its plain
reference is `references/falcon_h1.py`): which of the program's
parameter paths the reference takes and under which names, how each
leaf is drawn, the sizes, switches and multipliers that the
configuration file and the program's `ModelConfig` have to agree on, and
the work its mathematics requires (`flops_bytes.py` says what each count
is).

`cfg` is a configuration file's dict (the published config's keys);
`prog` the program's `ModelConfig`, read by attribute and not imported.

A serving family only: no adapters (`LORA` is empty) and no training
counts, so a training mix over it is refused by name.
"""
from __future__ import annotations

import math

import numpy as np

from flops_bytes import keys_seen, keys_seen_sum

# ---- names: program's parameter path -> the reference's name ---------

LAYER = {
    'layers/layer/attn_norm/scale': 'attn_norm',
    'layers/layer/mixer/in_proj/kernel': 'w_in',
    'layers/layer/mixer/in_proj/bias': 'b_in',
    'layers/layer/mixer/conv_kernel': 'conv_w',
    'layers/layer/mixer/conv_bias': 'conv_b',
    'layers/layer/mixer/A_log': 'a_log',
    'layers/layer/mixer/D': 'd_skip',
    'layers/layer/mixer/dt_bias': 'dt_bias',
    'layers/layer/mixer/norm_scale': 'ssm_norm',
    'layers/layer/mixer/out_proj/kernel': 'w_out',
    'layers/layer/mixer/out_proj/bias': 'b_out',
    'layers/layer/attn/q_proj/kernel': 'wq',
    'layers/layer/attn/k_proj/kernel': 'wk',
    'layers/layer/attn/v_proj/kernel': 'wv',
    'layers/layer/attn/o_proj/kernel': 'wo',
    'layers/layer/mlp_norm/scale': 'mlp_norm',
    'layers/layer/mlp/gate_proj/kernel': 'w_gate',
    'layers/layer/mlp/up_proj/kernel': 'w_up',
    'layers/layer/mlp/down_proj/kernel': 'w_down',
}
WHOLE = {
    'embed/embedding': 'embed',
    'final_norm/scale': 'final_norm',
    'lm_head/kernel': 'lm_head',
}
LORA = {}
# names a tree may lack: the projections' biases (`mamba_proj_bias`
# false), the convolution's (`mamba_conv_bias` false), the gated norm
# (`mamba_rms_norm` false)
OPTIONAL = frozenset({'b_in', 'b_out', 'conv_b', 'ssm_norm'})


# ---- sizes ---------------------------------------------------------------

def dims(cfg: dict) -> dict:
    heads, p = cfg['mamba_n_heads'], cfg['mamba_d_head']
    d_ssm = cfg.get('mamba_d_ssm') or cfg['mamba_expand'] * \
        cfg['hidden_size']
    g, n = cfg['mamba_n_groups'], cfg['mamba_d_state']
    return {'d': cfg['hidden_size'], 'h': cfg['num_attention_heads'],
            'kv': cfg['num_key_value_heads'], 'hd': cfg['head_dim'],
            'f': cfg['intermediate_size'], 'v': cfg['vocab_size'],
            'layers': cfg['num_hidden_layers'],
            'ssm_heads': heads, 'ssm_head_dim': p, 'd_ssm': d_ssm,
            'ssm_state': n, 'ssm_groups': g, 'ssm_conv': cfg['mamba_d_conv'],
            'ssm_chunk': cfg['mamba_chunk_size'],
            'conv_channels': d_ssm + 2 * g * n,
            'proj_width': 2 * d_ssm + 2 * g * n + heads}


MULTIPLIERS = {
    # the file's key -> the program's attribute
    'embedding_multiplier': 'embed_multiplier',
    'attention_in_multiplier': 'attn_in_multiplier',
    'key_multiplier': 'key_multiplier',
    'attention_out_multiplier': 'attn_out_multiplier',
    'ssm_in_multiplier': 'ssm_in_multiplier',
    'ssm_out_multiplier': 'ssm_out_multiplier',
    'lm_head_multiplier': 'lm_head_multiplier',
}


def file_sizes(cfg: dict) -> dict:
    """What the configuration file says, key for key with
    `program_sizes`: every size, switch and multiplier the reference's
    equations read, and the two state types."""
    note_multipliers(cfg)
    s = dims(cfg)
    out = {k: s[k] for k in ('d', 'h', 'kv', 'hd', 'f', 'v', 'layers',
                             'ssm_heads', 'ssm_head_dim', 'd_ssm',
                             'ssm_state', 'ssm_groups', 'ssm_conv',
                             'ssm_chunk')}
    out.update(norm_eps=float(cfg['rms_norm_eps']),
               rope_theta=float(cfg['rope_theta']),
               qkv_bias=bool(cfg.get('attention_bias')),
               mlp_bias=bool(cfg.get('mlp_bias')),
               tied=bool(cfg.get('tie_word_embeddings')),
               activation=cfg['hidden_act'],
               ssm_conv_bias=bool(cfg['mamba_conv_bias']),
               ssm_proj_bias=bool(cfg['mamba_proj_bias']),
               ssm_gated_norm=bool(cfg['mamba_rms_norm']),
               ssm_norm_before_gate=bool(cfg['mamba_norm_before_gate']))
    for key in MULTIPLIERS:
        out[key] = float(cfg[key])
    for i, m in enumerate(cfg['ssm_multipliers']):
        out[f'ssm_multipliers_{i}'] = float(m)
    for i, m in enumerate(cfg['mlp_multipliers']):
        out[f'mlp_multipliers_{i}'] = float(m)
    dtypes = cfg.get('dtype') or {}
    out['ssm_state_dtype'] = dtypes.get('ssm_state', 'float32')
    out['conv_state_dtype'] = dtypes.get('conv_state',
                                         dtypes.get('compute', 'float32'))
    return out


def program_sizes(prog) -> dict:
    out = {'d': prog.d_model, 'h': prog.num_heads,
           'kv': prog.num_kv_heads, 'hd': prog.head_dim,
           'f': prog.d_mlp, 'v': prog.vocab_size,
           'layers': prog.num_layers,
           'ssm_heads': prog.ssm_heads, 'ssm_head_dim': prog.ssm_head_dim,
           'd_ssm': prog.d_ssm, 'ssm_state': prog.ssm_state,
           'ssm_groups': prog.ssm_groups, 'ssm_conv': prog.ssm_conv,
           'ssm_chunk': prog.ssm_chunk,
           'norm_eps': float(prog.norm_eps),
           'rope_theta': float(prog.rope_theta),
           'qkv_bias': bool(prog.qkv_bias),
           'mlp_bias': bool(prog.mlp_bias),
           'tied': bool(prog.tie_embeddings),
           'activation': prog.mlp_activation,
           'ssm_conv_bias': bool(prog.ssm_conv_bias),
           'ssm_proj_bias': bool(prog.ssm_proj_bias),
           'ssm_gated_norm': bool(prog.ssm_gated_norm),
           'ssm_norm_before_gate': bool(prog.ssm_norm_before_gate)}
    for key, attr in MULTIPLIERS.items():
        out[key] = float(getattr(prog, attr))
    for i, m in enumerate(prog.ssm_multipliers):
        out[f'ssm_multipliers_{i}'] = float(m)
    for i, m in enumerate(prog.mlp_multipliers):
        out[f'mlp_multipliers_{i}'] = float(m)
    out['ssm_state_dtype'] = prog.ssm_state_dtype
    # the convolution's carried inputs are held in the compute type
    out['conv_state_dtype'] = prog.dtype
    return out


def reference_config(cfg: dict) -> dict:
    """The dict the reference is handed: the file's own."""
    note_multipliers(cfg)
    return cfg


# ---- weight rules ------------------------------------------------------
#
# A rule is the (mean, std) of a normal draw. µP stores a weight at the
# scale that its multiplier undoes, so a kernel that multipliers follow
# is drawn with std 1 / (product of the multipliers on its path *
# sqrt(fan_in)), and the embedding with std 1 / embedding_multiplier:
# every branch then adds to the residual at the order of the residual
# itself, the keys tell positions apart and the logits span units, so
# that a fault in any one branch moves `correct`. (Drawn N(0, 1/fan_in)
# like the other family's, the MLP would add 1% of the residual.)
#
# `leaf_rule(path, unit_shape)` is not handed the configuration, so the
# multipliers are noted when the harness hands it over: in `file_sizes`
# (`serve_common.program_config`, before any weight is made) and in
# `reference_config` (before the reference's weights are). One process
# runs one configuration; until one is noted the multipliers are 1.

# by the module whose kernel the multipliers follow
_MULT = {'embed': 1.0, 'q_proj': 1.0, 'k_proj': 1.0, 'v_proj': 1.0,
         'o_proj': 1.0, 'gate_proj': 1.0, 'up_proj': 1.0,
         'down_proj': 1.0, 'in_proj': 1.0, 'out_proj': 1.0,
         'lm_head': 1.0}

A_LOG = (math.log(4.0), 0.7)      # a decay rate of about 1 to 16
DT_BIAS = (-4.6, 0.8)   # inverse softplus of a step of 0.002 to 0.05
SKIP_AND_SCALES = (1.0, 0.1)
CONV_BIAS = (0.0, 0.1)


def note_multipliers(cfg: dict) -> None:
    """Note the multipliers of `cfg`, which the weight rules undo."""
    s = dims(cfg)
    a_in = float(cfg['attention_in_multiplier'])
    gn = s['ssm_groups'] * s['ssm_state']
    widths = (s['d_ssm'], s['d_ssm'], gn, gn, s['ssm_heads'])
    seg = np.concatenate([np.full(w, m, np.float32) for w, m in
                          zip(widths, cfg['ssm_multipliers'])])
    _MULT.update(
        embed=float(cfg['embedding_multiplier']),
        q_proj=a_in, v_proj=a_in,
        k_proj=a_in * float(cfg['key_multiplier']),
        o_proj=float(cfg['attention_out_multiplier']),
        gate_proj=float(cfg['mlp_multipliers'][0]), up_proj=1.0,
        down_proj=float(cfg['mlp_multipliers'][1]),
        in_proj=float(cfg['ssm_in_multiplier']) * seg,
        out_proj=float(cfg['ssm_out_multiplier']),
        lm_head=float(cfg['lm_head_multiplier']))


def leaf_rule(path: tuple, unit_shape: tuple):
    """(mean, std) of every leaf of this family's tree (`std` of the
    mixer's input projection is an array over its outputs, a multiplier
    a segment)."""
    last = path[-1]
    if last == 'embedding':
        return 0.0, 1.0 / _MULT['embed']
    if last == 'scale' or last in ('norm_scale', 'D'):
        return SKIP_AND_SCALES
    if last == 'A_log':
        return A_LOG
    if last == 'dt_bias':
        return DT_BIAS
    if last == 'conv_kernel':       # depthwise: its fan-in is its taps
        return 0.0, float(unit_shape[0]) ** -0.5
    if last == 'conv_bias':
        return CONV_BIAS
    if last == 'bias':
        return 0.0, 0.1
    if last == 'kernel':
        # the output projection contracts heads x head size
        fan_in = (unit_shape[0] * unit_shape[1] if path[-2] == 'o_proj'
                  else unit_shape[0])
        return 0.0, 1.0 / (_MULT[path[-2]] * math.sqrt(fan_in))
    return None


# ---- required work -------------------------------------------------------

def qkv_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * (s['h'] + 2 * s['kv']) * s['hd']


def mixer_matmul_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['proj_width'] + s['d_ssm'] * s['d']


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that sit in a matrix multiplication."""
    s = dims(cfg)
    return (qkv_params(cfg) + s['h'] * s['hd'] * s['d']
            + mixer_matmul_params(cfg) + 3 * s['d'] * s['f'])


def layer_other_params(cfg: dict) -> int:
    """One layer's weights outside the matmuls: two norms, the
    convolution with its bias, the decay, the skip and the step bias a
    head, the gated norm's scale."""
    s = dims(cfg)
    n = 2 * s['d'] + s['ssm_conv'] * s['conv_channels'] + 3 * s['ssm_heads']
    if cfg['mamba_conv_bias']:
        n += s['conv_channels']
    if cfg['mamba_rms_norm']:
        n += s['d_ssm']
    return n


def unembed_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['v']


def scan_flops_per_position(cfg: dict) -> int:
    """The recurrence itself, a layer: per head the decay of the state,
    the outer product scaled by the step and added, and `S @ C` (5
    operations an entry of the (head, state) state), and the
    convolution's taps. The chunked form's extra products are the
    program's choice and are not counted."""
    s = dims(cfg)
    return (5 * s['ssm_heads'] * s['ssm_head_dim'] * s['ssm_state']
            + 2 * s['ssm_conv'] * s['conv_channels'])


def attention_flops(cfg: dict, key_count: int) -> int:
    """Scores and weighted sum for queries that see `key_count` keys in
    all: 2 matmuls over every layer and head."""
    s = dims(cfg)
    return 4 * s['layers'] * s['h'] * s['hd'] * key_count


def forward_flops(cfg: dict, tokens: int, key_count: int,
                  logits_for: int) -> int:
    s = dims(cfg)
    per_token = s['layers'] * (2 * layer_matmul_params(cfg)
                               + scan_flops_per_position(cfg))
    return (per_token * tokens + attention_flops(cfg, key_count)
            + 2 * unembed_params(cfg) * logits_for)


def prefill_flops(cfg: dict, start: int, stop: int, last: bool) -> int:
    return forward_flops(cfg, stop - start, keys_seen_sum(start, stop, 0),
                         1 if last else 0)


def decode_flops(cfg: dict, position: int) -> int:
    return forward_flops(cfg, 1, keys_seen(position, 0), 1)


def weight_bytes_per_step(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Every layer, the final norm and the unembedding, once. (The
    embedding is read one row a token, and left out.)"""
    s = dims(cfg)
    per_layer = layer_matmul_params(cfg) + layer_other_params(cfg)
    return bytes_per_weight * (s['layers'] * per_layer
                               + unembed_params(cfg) + s['d'])


_BYTES = {'float32': 4, 'bfloat16': 2, 'float16': 2}


def state_bytes_per_slot(cfg: dict) -> int:
    """The recurrent state one slot holds, all layers: the scan state
    and the convolution's last taps - 1 inputs, in the configuration's
    state types."""
    s = dims(cfg)
    dtypes = cfg.get('dtype') or {}
    scan = s['ssm_heads'] * s['ssm_head_dim'] * s['ssm_state'] * \
        _BYTES[dtypes.get('ssm_state', 'float32')]
    conv = (s['ssm_conv'] - 1) * s['conv_channels'] * \
        _BYTES[dtypes.get('conv_state', 'bfloat16')]
    return s['layers'] * (scan + conv)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = dims(cfg)
    return 2 * s['layers'] * s['kv'] * s['hd'] * bytes_per_value


def decode_state_bytes(cfg: dict, position: int,
                       bytes_per_value: int = 2) -> int:
    """A slot's state is two things. The recurrent state is read and
    written whole at every step, whatever the position: twice its
    bytes. K and V are read up to the position (the one row written is
    not counted)."""
    return (2 * state_bytes_per_slot(cfg)
            + keys_seen(position, 0) * kv_bytes_per_token(
                cfg, bytes_per_value))
