"""The AFMoE decoder (`arcee-ai/Trinity-Large-Preview`) as the harness
needs to know it (its plain reference is `references/afmoe.py`): which of
the program's parameter paths the reference takes and under which names,
how each leaf is drawn, the sizes and switches that the configuration
file and the program's `ModelConfig` have to agree on, and the work its
mathematics requires (`flops_bytes.py` says what each count is).

`cfg` is a configuration file's dict (the published config's keys);
`prog` the program's `ModelConfig`, read by attribute and not imported.

The file describes ONE CHIP'S SHARE of a deployment in which each layer
is divided over `share.chips_per_layer` chips: `num_experts` is the
experts held here (`published.num_experts` is what the router scores),
`vocab_size` the rows of the vocabulary here. Required work is the
share's: what the experts held elsewhere compute and read is theirs.

A serving family only: no adapters (`LORA` is empty) and no training
counts, so a training mix over it is refused by name.
"""
from __future__ import annotations

from flops_bytes import keys_seen, keys_seen_sum

# ---- names: program's parameter path -> the reference's name ---------

_ATTN = {
    'attn_norm/scale': 'attn_norm',
    'attn/q_proj/kernel': 'wq',
    'attn/k_proj/kernel': 'wk',
    'attn/v_proj/kernel': 'wv',
    'attn/q_norm/scale': 'q_norm',
    'attn/k_norm/scale': 'k_norm',
    'attn/gate_proj/kernel': 'w_attn_gate',
    'attn/o_proj/kernel': 'wo',
    'post_attn_norm/scale': 'post_attn_norm',
    'mlp_norm/scale': 'mlp_norm',
    'post_mlp_norm/scale': 'post_mlp_norm',
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
# Two stacked groups: the leading dense layers (the reference's names
# prefixed `d_`), then the expert layers. `layer_weights(i)` hands the
# reference entry i of both.
LAYER = {
    **{f'dense_layers/layer/{p}': f'd_{n}'
       for p, n in {**_ATTN, **_DENSE_MLP}.items()},
    **{f'layers/layer/{p}': n for p, n in {**_ATTN, **_EXPERTS}.items()},
    # the expert layers' held experts: (expert layers, held, in, out),
    # outside the layer loop, which hands them to every layer whole
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
# names a tree may lack: the dense group (a model with no leading dense
# layer), the selection bias
OPTIONAL = frozenset({n for n in LAYER.values() if n.startswith('d_')}
                     | {'expert_bias'})

EXPERT_BIAS_STD = 0.005


# ---- weight rules ------------------------------------------------------

def leaf_rule(path: tuple, unit_shape: tuple):
    """(mean, std) where the common rule of `weights.py` would be wrong,
    else None. A unit is one layer's leaf. The embedding is drawn at
    1 / sqrt(hidden), which its multiplier sqrt(hidden) undoes: the
    residual starts at the order of what every post-normed branch adds,
    so a fault in any one branch moves `correct`. An expert stack is
    (experts, in, out): its fan-in skips the expert axis. The selection
    bias is N(0, 0.005^2) (`assumed`: the published one is trained to
    level the load; this one is of the order of the top scores'
    spacing, so it decides choices and leaves the loads level. At 0.05
    the loads were uneven by a factor of 5, differently on every seed,
    and the cell's runs spread over their bounds: PERF.md section 6)."""
    last = path[-1]
    if last == 'embedding':
        return 0.0, float(unit_shape[-1]) ** -0.5
    if last == 'expert_bias':
        return 0.0, EXPERT_BIAS_STD
    if last == 'router':
        return 0.0, float(unit_shape[0]) ** -0.5
    if last in ('w_gate', 'w_up', 'w_down'):
        return 0.0, float(unit_shape[1]) ** -0.5
    if path[-2:] == ('o_proj', 'kernel'):
        return 0.0, float(unit_shape[0] * unit_shape[1]) ** -0.5
    return None


# ---- sizes ---------------------------------------------------------------

def run_layer_types(cfg: dict) -> list:
    """The layer types as run. A file cut in depth keeps the published
    list whole under `layer_types` and names the layers it runs by
    their published index (`kept_layers.published_index`); a file that
    names none runs the list as it stands."""
    kept = cfg.get('kept_layers')
    if not kept:
        return list(cfg['layer_types'])
    types = [cfg['layer_types'][i] for i in kept['published_index']]
    if kept.get('layer_types', types) != types:
        raise ValueError(
            f'kept_layers.layer_types {kept["layer_types"]} is not what '
            f'published_index picks: {types}')
    return types


def kinds(cfg: dict) -> list:
    """(window, rotary) for every layer as it is run."""
    out = []
    for kind in run_layer_types(cfg):
        if kind not in ('sliding_attention', 'full_attention'):
            raise ValueError(f'layer type {kind!r} is not the family\'s')
        sliding = kind == 'sliding_attention'
        out.append((cfg['sliding_window'] if sliding else 0, sliding))
    return out


def dims(cfg: dict) -> dict:
    share = cfg.get('share') or {}
    published = cfg.get('published') or {}
    return {'d': cfg['hidden_size'], 'h': cfg['num_attention_heads'],
            'kv': cfg['num_key_value_heads'], 'hd': cfg['head_dim'],
            'f': cfg['intermediate_size'],
            'f_expert': cfg['moe_intermediate_size'],
            'f_shared': (cfg['moe_intermediate_size']
                         * cfg['num_shared_experts']),
            'v': cfg['vocab_size'], 'layers': cfg['num_hidden_layers'],
            'dense_layers': cfg['num_dense_layers'],
            # the router scores the published experts; this chip holds
            # `num_experts` of them from `first_expert` on
            'router_width': published.get('num_experts',
                                          cfg['num_experts']),
            'experts_held': cfg['num_experts'],
            'first_expert': share.get('first_expert', 0),
            'per_token': cfg['num_experts_per_tok'],
            'decode_batch': share.get('decode_batch', 1)}


def file_sizes(cfg: dict) -> dict:
    """What the configuration file says, key for key with
    `program_sizes`: every size and switch the reference's equations
    read."""
    s = dims(cfg)
    out = {k: s[k] for k in ('d', 'h', 'kv', 'hd', 'f', 'f_expert',
                             'f_shared', 'v', 'layers', 'dense_layers',
                             'router_width', 'experts_held',
                             'first_expert', 'per_token')}
    if len(kinds(cfg)) != s['layers']:
        raise ValueError(f'{len(kinds(cfg))} layer types are run, '
                         f'num_hidden_layers is {s["layers"]}')
    for l, (window, rotary) in enumerate(kinds(cfg)):
        out[f'window_{l}'], out[f'rope_{l}'] = window, rotary
    out.update(norm_eps=float(cfg['rms_norm_eps']),
               rope_theta=float(cfg['rope_theta']),
               score=cfg['score_func'],
               route_norm=bool(cfg['route_norm']),
               route_scale=float(cfg['route_scale']),
               router_bias=True, tied=bool(cfg['tie_word_embeddings']),
               activation=cfg['hidden_act'],
               embed_by_sqrt_dim=bool(cfg['mup_enabled']),
               qk_norm=True, attn_gate=True, post_norms=True,
               dropless=True)
    return out


def program_sizes(prog) -> dict:
    out = {'d': prog.d_model, 'h': prog.num_heads,
           'kv': prog.num_kv_heads, 'hd': prog.head_dim,
           'f': prog.d_mlp, 'f_expert': prog.expert_width,
           'f_shared': prog.d_shared_expert, 'v': prog.vocab_size,
           'layers': prog.num_layers,
           'dense_layers': prog.num_dense_layers,
           'router_width': prog.num_experts,
           'experts_held': prog.held_experts,
           'first_expert': prog.first_expert,
           'per_token': prog.experts_per_token,
           'norm_eps': float(prog.norm_eps),
           'rope_theta': float(prog.rope_theta),
           'score': prog.router_score,
           'route_norm': bool(prog.route_norm),
           'route_scale': float(prog.route_scale),
           'router_bias': bool(prog.router_bias),
           'tied': bool(prog.tie_embeddings),
           'activation': prog.mlp_activation,
           'embed_by_sqrt_dim': bool(prog.scale_embed_by_dim),
           'qk_norm': bool(prog.qk_norm),
           'attn_gate': bool(prog.attn_gate),
           'post_norms': bool(prog.post_norms),
           'dropless': prog.moe_impl == 'dropless'}
    for l, (window, rotary) in enumerate(prog.layer_kinds):
        out[f'window_{l}'], out[f'rope_{l}'] = window, bool(rotary)
    return out


def reference_config(cfg: dict) -> dict:
    """The dict the reference is handed: the file's, with the layer
    types as run and the share spelt out (which experts are held, how
    many the router scores)."""
    s = dims(cfg)
    return dict(cfg, layer_types=run_layer_types(cfg),
                experts_held=s['experts_held'],
                first_expert=s['first_expert'],
                router_width=s['router_width'])


# ---- required work -------------------------------------------------------

def attn_matmul_params(cfg: dict) -> int:
    """q, k, v, the output gate and o."""
    s = dims(cfg)
    return s['d'] * (3 * s['h'] + 2 * s['kv']) * s['hd']


def layer_other_params(cfg: dict) -> int:
    """Four norms and the q/k norms."""
    s = dims(cfg)
    return 4 * s['d'] + 2 * s['hd']


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
    expectation: per_token x experts_held / router_width (uniform
    routing; 4 x 32 / 256 = 0.5)."""
    s = dims(cfg)
    return s['per_token'] * s['experts_held'] / s['router_width']


def experts_touched(cfg: dict, batch: int) -> float:
    """Held experts that a step over `batch` tokens is expected to
    touch: each token misses a given expert with probability 1 -
    per_token / router_width (uniform routing)."""
    s = dims(cfg)
    miss = 1.0 - s['per_token'] / s['router_width']
    return s['experts_held'] * (1.0 - miss ** batch)


def unembed_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['v']


def token_matmul_flops(cfg: dict) -> float:
    """A token's matrix products over every layer: attention's five
    projections; the dense MLP, or the router, the shared expert and the
    chosen experts' products held here in expectation."""
    s = dims(cfg)
    expert_layers = s['layers'] - s['dense_layers']
    per_expert_layer = (router_params(cfg) + shared_params(cfg)
                        + held_share(cfg) * expert_params(cfg))
    return 2.0 * (s['layers'] * attn_matmul_params(cfg)
                  + s['dense_layers'] * dense_mlp_params(cfg)
                  + expert_layers * per_expert_layer)


def attention_flops(cfg: dict, key_counts) -> int:
    """Scores and weighted sum, 2 matmuls a head, over the keys each
    layer's queries see (`key_counts`: one count a layer)."""
    s = dims(cfg)
    return 4 * s['h'] * s['hd'] * sum(key_counts)


def prefill_flops(cfg: dict, start: int, stop: int, last: bool) -> float:
    keys = [keys_seen_sum(start, stop, w) for w, _ in kinds(cfg)]
    return (token_matmul_flops(cfg) * (stop - start)
            + attention_flops(cfg, keys)
            + (2 * unembed_params(cfg) if last else 0))


def decode_flops(cfg: dict, position: int) -> float:
    keys = [keys_seen(position, w) for w, _ in kinds(cfg)]
    return (token_matmul_flops(cfg) + attention_flops(cfg, keys)
            + 2 * unembed_params(cfg))


def weight_bytes_per_step(cfg: dict, bytes_per_weight: int = 2) -> float:
    """What any dropless program has to read of the weights in one
    decode step: every non-expert weight once (the embedding is read one
    row a token, and left out), and of the expert stacks the experts
    that a full decode batch (`share.decode_batch`) is expected to
    touch: never all of them unconditionally, never the chosen few of
    one token alone."""
    s = dims(cfg)
    expert_layers = s['layers'] - s['dense_layers']
    touched = experts_touched(cfg, s['decode_batch'])
    weights = (s['layers'] * (attn_matmul_params(cfg)
                              + layer_other_params(cfg))
               + s['dense_layers'] * dense_mlp_params(cfg)
               + expert_layers * (router_params(cfg) + shared_params(cfg)
                                  + touched * expert_params(cfg))
               + unembed_params(cfg) + s['d'])
    # the selection bias is float32
    return bytes_per_weight * weights + 4 * expert_layers * s['router_width']


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """One layer's K and V of one position."""
    s = dims(cfg)
    return 2 * s['kv'] * s['hd'] * bytes_per_value


def decode_state_bytes(cfg: dict, position: int,
                       bytes_per_value: int = 2) -> int:
    """A slot's state is its K and V, a layer: a step reads those of
    the keys the layer's query sees, the window's on a sliding layer
    (the one row it writes is not counted). Below the window that is
    layers x 4,096 bytes a position."""
    return sum(keys_seen(position, w) for w, _ in kinds(cfg)) * \
        kv_bytes_per_token(cfg, bytes_per_value)
