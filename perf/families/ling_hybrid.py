"""The Ling-3.0-flash language model (`inclusionAI/Ling-3.0-flash-VL`,
`model_type` bailing_hybrid) as the harness needs to know it (its plain
reference is `references/ling_hybrid.py`): which of the program's
parameter paths the reference takes and under which names, how each leaf
is drawn, the sizes and switches that the configuration file and the
program's `ModelConfig` have to agree on, and the work its mathematics
requires (`flops_bytes.py` says what each count is).

`cfg` is a configuration file's dict (the published config's keys);
`prog` the program's `ModelConfig`, read by attribute and not imported.

The file describes ONE CHIP'S SHARE of a deployment in which each layer
is divided over `share.chips_per_layer` chips: `num_experts` is the
experts held here (`published.num_experts` is what the router scores),
`vocab_size` the rows of the vocabulary here. Required work is the
share's. The layers run are those at `kept_layers.published_index`: a
layer's mixer is latent attention (MLA) where its published index + 1 is
a multiple of `layer_group_size`, else Kimi delta attention (KDA); the
first `first_k_dense_replace` layers run carry the dense MLP. The
program stacks them as three groups: the dense KDA layer
('dense_layers'), the KDA expert layers ('layers') and the MLA expert
layer ('layers_6', named by its first layer); this family names that
layout, the reference's names prefixed `d_`, none and `m_`.

A serving family only: no adapters (`LORA` is empty) and no training
counts, so a training mix over it is refused by name.
"""
from __future__ import annotations

from flops_bytes import keys_seen, keys_seen_sum

# ---- names: program's parameter path -> the reference's name ---------

_KDA = {
    'attn_norm/scale': 'attn_norm',
    'mixer/q_proj/kernel': 'wq',
    'mixer/k_proj/kernel': 'wk',
    'mixer/v_proj/kernel': 'wv',
    'mixer/f_proj/kernel': 'wf',
    'mixer/b_proj/kernel': 'wb',
    'mixer/g_proj/kernel': 'wg',
    'mixer/A_log': 'a_log',
    'mixer/dt_bias': 'dt_bias',
    'mixer/conv_kernel': 'conv_w',
    'mixer/o_norm': 'o_norm',
    'mixer/o_proj/kernel': 'wo',
    'mlp_norm/scale': 'mlp_norm',
}
_MLA = {
    'attn_norm/scale': 'attn_norm',
    'attn/q_proj/kernel': 'wq',
    'attn/q_norm/scale': 'q_norm',
    'attn/kv_down/kernel': 'kv_down',
    'attn/kv_norm/scale': 'kv_norm',
    'attn/kv_up': 'kv_up',
    'attn/gate_proj/kernel': 'w_attn_gate',
    'attn/o_proj/kernel': 'wo',
    'mlp_norm/scale': 'mlp_norm',
}
_DENSE_MLP = {
    'mlp/gate_proj/kernel': 'w_gate',
    'mlp/up_proj/kernel': 'w_up',
    'mlp/down_proj/kernel': 'w_down',
}
_EXPERTS = {
    'moe/router': 'router',
    'moe/expert_bias': 'expert_bias',
    'moe/shared/gate_proj/kernel': 's_gate',
    'moe/shared/up_proj/kernel': 's_up',
    'moe/shared/down_proj/kernel': 's_down',
}
LAYER = {
    **{f'dense_layers/layer/{p}': f'd_{n}'
       for p, n in {**_KDA, **_DENSE_MLP}.items()},
    **{f'layers/layer/{p}': n for p, n in {**_KDA, **_EXPERTS}.items()},
    **{f'layers_6/layer/{p}': f'm_{n}'
       for p, n in {**_MLA, **_EXPERTS}.items()},
    # every expert layer's held experts: (expert layers, held, in, out),
    # outside the layer loops, which hand them to every layer whole
    'experts/w_gate': 'w_gate',
    'experts/w_up': 'w_up',
    'experts/w_down': 'w_down',
}
WHOLE = {
    'embed/embedding': 'embed',
    'final_norm/scale': 'final_norm',
    'lm_head/kernel': 'lm_head',
}
LORA = {}
# names a tree may lack: the selection bias, the q norm
OPTIONAL = frozenset({'expert_bias', 'm_expert_bias', 'm_q_norm'})

EXPERT_BIAS_STD = 0.005
A_LOG = (0.0, 0.5)        # a rate exp(A_log) of about 0.4 to 2.7
DT_BIAS = (-3.0, 1.0)     # most channels decay by 0.7-0.99 a position
SCALES = (1.0, 0.1)


# ---- weight rules ------------------------------------------------------

def leaf_rule(path: tuple, unit_shape: tuple):
    """(mean, std) where the common rule of `weights.py` would be wrong,
    else None. A unit is one layer's leaf. An expert stack is (experts,
    in, out): its fan-in skips the expert axis; o_proj contracts heads x
    head size. The selection bias is N(0, 0.005^2) (`assumed`: levelled
    as trinity's is). The gate's A_log and dt_bias put most channels'
    decay a position between 0.7 and 0.99, so that the state carries
    tens of positions and a fault in it moves `correct`; the
    convolution's fan-in is its taps."""
    last = path[-1]
    if last == 'expert_bias':
        return 0.0, EXPERT_BIAS_STD
    if last == 'router':
        return 0.0, float(unit_shape[0]) ** -0.5
    if last in ('w_gate', 'w_up', 'w_down'):
        return 0.0, float(unit_shape[1]) ** -0.5
    if last == 'kv_up':
        return 0.0, float(unit_shape[0]) ** -0.5
    if last == 'A_log':
        return A_LOG
    if last == 'dt_bias':
        return DT_BIAS
    if last == 'conv_kernel':
        return 0.0, float(unit_shape[0]) ** -0.5
    if last == 'o_norm':
        return SCALES
    if path[-2:] == ('o_proj', 'kernel'):
        return 0.0, float(unit_shape[0] * unit_shape[1]) ** -0.5
    return None


# ---- sizes ---------------------------------------------------------------

def kept_index(cfg: dict) -> list:
    kept = cfg.get('kept_layers') or {}
    return list(kept.get('published_index',
                          range(cfg['num_hidden_layers'])))


def mixers(cfg: dict) -> list:
    """'kda' or 'mla' for every layer as it is run."""
    period = cfg['layer_group_size']
    return ['mla' if (p + 1) % period == 0 else 'kda'
            for p in kept_index(cfg)]


def limits(cfg: dict) -> list:
    """(routed, shared) SwiGLU clamps of every layer as it is run."""
    return [(float(cfg['expert_swiglu_limit_list'][p]),
             float(cfg['share_expert_swiglu_limit_list'][p]))
            for p in kept_index(cfg)]


def dims(cfg: dict) -> dict:
    share = cfg.get('share') or {}
    published = cfg.get('published') or {}
    return {'d': cfg['hidden_size'], 'h': cfg['num_attention_heads'],
            'hd': cfg['head_dim'], 'f': cfg['intermediate_size'],
            'f_expert': cfg['moe_intermediate_size'],
            'f_shared': (cfg['moe_shared_expert_intermediate_size']
                         * cfg.get('num_shared_experts', 1)),
            'v': cfg['vocab_size'], 'layers': cfg['num_hidden_layers'],
            'dense_layers': cfg['first_k_dense_replace'],
            'router_width': published.get('num_experts',
                                          cfg['num_experts']),
            'experts_held': cfg['num_experts'],
            'first_expert': share.get('first_expert', 0),
            'per_token': cfg['num_experts_per_tok'],
            'n_group': cfg['n_group'], 'topk_group': cfg['topk_group'],
            'rank': cfg['kv_lora_rank'], 'nope': cfg['qk_nope_head_dim'],
            'rope': cfg['qk_rope_head_dim'], 'v_dim': cfg['v_head_dim'],
            'conv': cfg['short_conv_kernel_size'],
            'decode_batch': share.get('decode_batch', 1)}


_BYTES = {'float32': 4, 'bfloat16': 2}


def file_sizes(cfg: dict) -> dict:
    """What the configuration file says, key for key with
    `program_sizes`: every size and switch the reference's equations
    read."""
    s = dims(cfg)
    out = {k: s[k] for k in ('d', 'h', 'hd', 'f', 'f_expert', 'f_shared',
                             'v', 'layers', 'dense_layers',
                             'router_width', 'experts_held',
                             'first_expert', 'per_token', 'n_group',
                             'topk_group', 'rank', 'nope', 'rope',
                             'v_dim', 'conv')}
    kinds = mixers(cfg)
    if len(kinds) != s['layers']:
        raise ValueError(f'{len(kinds)} layers are kept, '
                         f'num_hidden_layers is {s["layers"]}')
    for l, (kind, (routed, shared)) in enumerate(zip(kinds, limits(cfg))):
        out[f'mixer_{l}'] = kind
        out[f'expert_limit_{l}'], out[f'shared_limit_{l}'] = routed, shared
    if cfg['num_kv_heads_for_linear_attn'] not in (0, s['h']):
        raise ValueError('KDA\'s heads are the query heads')
    out.update(norm_eps=float(cfg['rms_norm_eps']),
               rope_theta=float(cfg['rope_theta']),
               gate_bound=float(cfg['kda_lower_bound']),
               score=cfg['score_function'],
               route_norm=bool(cfg['norm_topk_prob']),
               route_scale=float(cfg['routed_scaling_factor']),
               router_bias=bool(cfg['moe_router_enable_expert_bias']),
               tied=bool(cfg['tie_word_embeddings']),
               activation=cfg['hidden_act'],
               qk_norm=bool(cfg['use_qk_norm']), dropless=True,
               state_dtype=(cfg.get('dtype') or {}).get('kda_state',
                                                        'float32'))
    return out


def program_sizes(prog) -> dict:
    out = {'d': prog.d_model, 'h': prog.num_heads, 'hd': prog.head_dim,
           'f': prog.d_mlp, 'f_expert': prog.expert_width,
           'f_shared': prog.d_shared_expert, 'v': prog.vocab_size,
           'layers': prog.num_layers,
           'dense_layers': prog.num_dense_layers,
           'router_width': prog.num_experts,
           'experts_held': prog.held_experts,
           'first_expert': prog.first_expert,
           'per_token': prog.experts_per_token,
           'n_group': prog.n_group, 'topk_group': prog.topk_group,
           'rank': prog.mla_kv_rank, 'nope': prog.mla_nope_dim,
           'rope': prog.mla_rope_dim, 'v_dim': prog.mla_v_dim,
           'conv': prog.kda_conv,
           'norm_eps': float(prog.norm_eps),
           'rope_theta': float(prog.rope_theta),
           'gate_bound': float(prog.kda_gate_bound),
           'score': prog.router_score,
           'route_norm': bool(prog.route_norm),
           'route_scale': float(prog.route_scale),
           'router_bias': bool(prog.router_bias),
           'tied': bool(prog.tie_embeddings),
           'activation': prog.mlp_activation,
           'qk_norm': bool(prog.qk_norm),
           'dropless': prog.moe_impl == 'dropless',
           'state_dtype': prog.ssm_state_dtype}
    for l in range(prog.num_layers):
        out[f'mixer_{l}'] = prog.mixer_kind(l)
        out[f'expert_limit_{l}'], out[f'shared_limit_{l}'] = \
            prog.swiglu_limits(l)
    return out


def reference_config(cfg: dict) -> dict:
    """The dict the reference is handed: the file's, with each layer's
    mixer and clamps as run and the share spelt out."""
    s = dims(cfg)
    return dict(cfg, mixers=mixers(cfg), limits=limits(cfg),
                experts_held=s['experts_held'],
                first_expert=s['first_expert'],
                router_width=s['router_width'])


# ---- required work -------------------------------------------------------

def kda_matmul_params(cfg: dict) -> int:
    """q, k, v, f, g and o, a head's width each, and beta."""
    s = dims(cfg)
    return 6 * s['d'] * s['h'] * s['hd'] + s['d'] * s['h']


def kda_other_params(cfg: dict) -> int:
    """The convolution, A_log, dt_bias, the heads' norm."""
    s = dims(cfg)
    return (s['conv'] * 3 * s['h'] * s['hd'] + s['h'] + s['h'] * s['hd']
            + s['hd'])


def mla_matmul_params(cfg: dict) -> int:
    """q, the latent's down-projection, the keys' and values'
    up-projection (applied once a token in the absorbed form: to the
    query, and to the weighted latent), the gate a head and o."""
    s = dims(cfg)
    return (s['d'] * s['h'] * (s['nope'] + s['rope'])
            + s['d'] * (s['rank'] + s['rope'])
            + s['rank'] * s['h'] * (s['nope'] + s['v_dim'])
            + s['d'] * s['h'] + s['h'] * s['v_dim'] * s['d'])


def mla_other_params(cfg: dict) -> int:
    s = dims(cfg)
    return (s['nope'] + s['rope'] if cfg['use_qk_norm'] else 0) + s['rank']


def dense_mlp_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s['d'] * s['f']


def shared_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s['d'] * s['f_shared']


def expert_params(cfg: dict) -> int:
    """One routed expert."""
    s = dims(cfg)
    return 3 * s['d'] * s['f_expert']


def router_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['router_width']


def held_share(cfg: dict) -> float:
    """Of a token's chosen experts, how many are held here in
    expectation: per_token x experts_held / router_width (8 x 64 / 512
    = 1; group-limited routing keeps each expert's chance)."""
    s = dims(cfg)
    return s['per_token'] * s['experts_held'] / s['router_width']


def experts_touched(cfg: dict, batch: int) -> float:
    """Held experts that a step over `batch` tokens is expected to
    touch: each token misses a given expert with probability 1 -
    per_token / router_width."""
    s = dims(cfg)
    miss = 1.0 - s['per_token'] / s['router_width']
    return s['experts_held'] * (1.0 - miss ** batch)


def unembed_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['v']


def layer_counts(cfg: dict) -> dict:
    kinds = mixers(cfg)
    return {'kda': kinds.count('kda'), 'mla': kinds.count('mla')}


def token_matmul_flops(cfg: dict) -> float:
    """A token's matrix products over every layer: the mixers'
    projections; the dense MLP, or the router, the shared expert and the
    chosen experts' products held here in expectation."""
    s = dims(cfg)
    n = layer_counts(cfg)
    expert_layers = s['layers'] - s['dense_layers']
    per_expert_layer = (router_params(cfg) + shared_params(cfg)
                        + held_share(cfg) * expert_params(cfg))
    return 2.0 * (n['kda'] * kda_matmul_params(cfg)
                  + n['mla'] * mla_matmul_params(cfg)
                  + s['dense_layers'] * dense_mlp_params(cfg)
                  + expert_layers * per_expert_layer)


def recurrence_flops(cfg: dict) -> int:
    """The delta rule a position, over the KDA layers: per head the
    decay of the state, the delta correction's product with k, the
    rank-one add and the product with q (6 operations an entry of the
    (head_dim, head_dim) state), and the convolution's taps. The chunked
    form's extra products are the program's choice and are not
    counted."""
    s = dims(cfg)
    per_layer = (6 * s['h'] * s['hd'] * s['hd']
                 + 2 * s['conv'] * 3 * s['h'] * s['hd'])
    return layer_counts(cfg)['kda'] * per_layer


def latent_flops(cfg: dict, key_count: int) -> int:
    """The absorbed attention over `key_count` keys in all, over the
    MLA layers: a head's score against a latent and rotary key, and its
    weighted sum of latents."""
    s = dims(cfg)
    return (layer_counts(cfg)['mla'] * 2 * s['h']
            * (2 * s['rank'] + s['rope']) * key_count)


def prefill_flops(cfg: dict, start: int, stop: int, last: bool) -> float:
    return ((token_matmul_flops(cfg) + recurrence_flops(cfg))
            * (stop - start)
            + latent_flops(cfg, keys_seen_sum(start, stop, 0))
            + (2 * unembed_params(cfg) if last else 0))


def decode_flops(cfg: dict, position: int) -> float:
    return (token_matmul_flops(cfg) + recurrence_flops(cfg)
            + latent_flops(cfg, keys_seen(position, 0))
            + 2 * unembed_params(cfg))


def weight_bytes_per_step(cfg: dict, bytes_per_weight: int = 2) -> float:
    """What any dropless program has to read of the weights in one
    decode step: every non-expert weight once (the embedding is read one
    row a token, and left out), and of the expert stacks the experts
    that a full decode batch (`share.decode_batch`) is expected to
    touch."""
    s = dims(cfg)
    n = layer_counts(cfg)
    expert_layers = s['layers'] - s['dense_layers']
    touched = experts_touched(cfg, s['decode_batch'])
    weights = (n['kda'] * (kda_matmul_params(cfg) + kda_other_params(cfg))
               + n['mla'] * (mla_matmul_params(cfg) + mla_other_params(cfg))
               + s['layers'] * 2 * s['d']                   # two norms
               + s['dense_layers'] * dense_mlp_params(cfg)
               + expert_layers * (router_params(cfg) + shared_params(cfg)
                                  + touched * expert_params(cfg))
               + unembed_params(cfg) + s['d'])
    # the selection bias, A_log and dt_bias are float32
    f32 = (expert_layers * s['router_width']
           + n['kda'] * (s['h'] + s['h'] * s['hd']))
    return bytes_per_weight * weights + 2 * f32


def state_bytes_per_slot(cfg: dict) -> int:
    """The recurrent state one slot holds, over the KDA layers: a
    (head_dim, head_dim) state a head in the state's type, and the
    convolution's last taps - 1 inputs of q, k and v in bfloat16."""
    s = dims(cfg)
    state = s['h'] * s['hd'] * s['hd'] * _BYTES[
        (cfg.get('dtype') or {}).get('kda_state', 'float32')]
    conv = (s['conv'] - 1) * 3 * s['h'] * s['hd'] * 2
    return layer_counts(cfg)['kda'] * (state + conv)


def latent_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """One MLA layer's cached row of a position: the latent and the
    rotary key."""
    s = dims(cfg)
    return (s['rank'] + s['rope']) * bytes_per_value


def decode_state_bytes(cfg: dict, position: int,
                       bytes_per_value: int = 2) -> int:
    """A slot's state is two things. The KDA state is read and written
    whole at every step, whatever the position: twice its bytes. The
    latents of the MLA layers are read up to the position (the one row
    written is not counted)."""
    return (2 * state_bytes_per_slot(cfg)
            + keys_seen(position, 0) * layer_counts(cfg)['mla']
            * latent_bytes_per_token(cfg, bytes_per_value))


def kda_state_step_bytes(cfg: dict, slots: int) -> int:
    """What one call of the decode step's state kernel
    (ops/kda_state_step, one KDA layer over every slot) has to move: each
    slot's float32 state read and written, its decay, q, k, beta k and v
    read and its output written, a head's width each in float32."""
    s = dims(cfg)
    per_head = 2 * s['hd'] * s['hd'] * 4 + 6 * s['hd'] * 4
    return slots * s['h'] * per_head
