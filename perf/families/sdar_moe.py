"""The SDAR decoder (`JetLM/SDAR-30B-A3B-Chat`, `model_type` sdar_moe) as
the harness needs to know it (its plain reference is
`references/sdar_moe.py`): which of the program's parameter paths the
reference takes and under which names, how each leaf is drawn, the sizes
and switches that the configuration file and the program's `ModelConfig`
have to agree on, and the work its mathematics requires under the
file's schedule of generation (`flops_bytes.py` says what each count
is).

`cfg` is a configuration file's dict (the published config's keys, and
under `assumed` what the published config does not give: block length,
denoising steps, the mask token's id); `prog` the program's
`ModelConfig`, read by attribute and not imported.

Generation is by diffusion over blocks of B positions: a block of m
masks takes the schedule's passes to clear them and one commit pass,
`steps + 1` passes a whole block, each a forward of the block's B
positions with keys to the block's end. So a generated token's required
work is its position's share of those passes, and a step (a pass) reads
every weight once whatever it yields.

A serving family only: no adapters (`LORA` is empty) and no training
counts, so a training mix over it is refused by name.
"""
from __future__ import annotations

# ---- names: program's parameter path -> the reference's name ---------

LAYER = {
    'layers/layer/attn_norm/scale': 'attn_norm',
    'layers/layer/attn/q_proj/kernel': 'wq',
    'layers/layer/attn/k_proj/kernel': 'wk',
    'layers/layer/attn/v_proj/kernel': 'wv',
    'layers/layer/attn/q_norm/scale': 'q_norm',
    'layers/layer/attn/k_norm/scale': 'k_norm',
    'layers/layer/attn/o_proj/kernel': 'wo',
    'layers/layer/mlp_norm/scale': 'mlp_norm',
    'layers/layer/moe/router': 'router',
    # every layer's experts: (layers, experts, in, out), outside the
    # layer loop, which hands them to every layer whole
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
OPTIONAL = frozenset()


# ---- weight rules ------------------------------------------------------

def leaf_rule(path: tuple, unit_shape: tuple):
    """(mean, std) where the common rule of `weights.py` would be wrong,
    else None. A unit is one layer's leaf. A kernel is N(0, 1 / fan_in):
    an expert stack is (experts, in, out), so its fan-in skips the
    expert axis; o_proj's is heads x head size. The embedding keeps the
    common N(0, 1): there is no multiplier on it, and every branch adds
    at the residual's own order."""
    last = path[-1]
    if last == 'router':
        return 0.0, float(unit_shape[0]) ** -0.5
    if last in ('w_gate', 'w_up', 'w_down'):
        return 0.0, float(unit_shape[1]) ** -0.5
    if path[-2:] == ('o_proj', 'kernel'):
        return 0.0, float(unit_shape[0] * unit_shape[1]) ** -0.5
    return None


# ---- sizes ---------------------------------------------------------------

def dims(cfg: dict) -> dict:
    assumed = cfg['assumed']
    stage = cfg.get('stage') or {}
    return {'d': cfg['hidden_size'], 'h': cfg['num_attention_heads'],
            'kv': cfg['num_key_value_heads'], 'hd': cfg['head_dim'],
            'f': cfg['intermediate_size'],
            'f_expert': cfg['moe_intermediate_size'],
            'v': cfg['vocab_size'], 'layers': cfg['num_hidden_layers'],
            'experts': cfg['num_experts'],
            'per_token': cfg['num_experts_per_tok'],
            'block': assumed['block_length'],
            'steps': assumed['denoising_steps'],
            'mask_id': assumed['mask_token_id'],
            'decode_batch': stage.get('decode_batch', 1)}


def file_sizes(cfg: dict) -> dict:
    """What the configuration file says, key for key with
    `program_sizes`: every size and switch the reference's equations
    and the schedule of generation read."""
    s = dims(cfg)
    if cfg.get('mlp_only_layers') or cfg.get('decoder_sparse_step', 1) != 1:
        raise ValueError('the family has an expert layer at every depth')
    if cfg.get('use_sliding_window') or cfg.get('rope_scaling'):
        raise ValueError('the family has no window and plain rotary')
    if cfg['assumed'].get('remasking_strategy') != 'low_confidence_static':
        raise ValueError('the family unmasks by low_confidence_static')
    out = {k: s[k] for k in ('d', 'h', 'kv', 'hd', 'f', 'f_expert', 'v',
                             'layers', 'experts', 'per_token', 'block',
                             'steps', 'mask_id')}
    out.update(norm_eps=float(cfg['rms_norm_eps']),
               rope_theta=float(cfg['rope_theta']),
               score='softmax', route_norm=bool(cfg['norm_topk_prob']),
               route_scale=1.0, router_bias=False, shared=0,
               dense_layers=0, experts_held=s['experts'], window=0,
               qkv_bias=bool(cfg['attention_bias']),
               tied=bool(cfg['tie_word_embeddings']),
               activation=cfg['hidden_act'], qk_norm=True,
               attn_gate=False, post_norms=False, layer_kinds=0,
               dropless=True)
    return out


def program_sizes(prog) -> dict:
    return {'d': prog.d_model, 'h': prog.num_heads,
            'kv': prog.num_kv_heads, 'hd': prog.head_dim,
            'f': prog.d_mlp, 'f_expert': prog.expert_width,
            'v': prog.vocab_size, 'layers': prog.num_layers,
            'experts': prog.num_experts,
            'per_token': prog.experts_per_token,
            'block': prog.block_length, 'steps': prog.denoising_steps,
            'mask_id': prog.mask_token_id,
            'norm_eps': float(prog.norm_eps),
            'rope_theta': float(prog.rope_theta),
            'score': prog.router_score,
            'route_norm': bool(prog.route_norm),
            'route_scale': float(prog.route_scale),
            'router_bias': bool(prog.router_bias),
            'shared': prog.d_shared_expert,
            'dense_layers': prog.num_dense_layers,
            'experts_held': prog.held_experts,
            'window': prog.sliding_window,
            'qkv_bias': bool(prog.qkv_bias),
            'tied': bool(prog.tie_embeddings),
            'activation': prog.mlp_activation,
            'qk_norm': bool(prog.qk_norm),
            'attn_gate': bool(prog.attn_gate),
            'post_norms': bool(prog.post_norms),
            'layer_kinds': len(prog.layer_kinds),
            'dropless': prog.moe_impl == 'dropless'}


def reference_config(cfg: dict) -> dict:
    """The dict the reference is handed: the file's, with the schedule
    of generation spelt out at the top level."""
    s = dims(cfg)
    return dict(cfg, block_length=s['block'], denoising_steps=s['steps'],
                mask_token_id=s['mask_id'])


# ---- required work -------------------------------------------------------

def attn_matmul_params(cfg: dict) -> int:
    """q, k, v and o."""
    s = dims(cfg)
    return s['d'] * (2 * s['h'] + 2 * s['kv']) * s['hd']


def layer_other_params(cfg: dict) -> int:
    """Two norms and the q/k norms."""
    s = dims(cfg)
    return 2 * s['d'] + 2 * s['hd']


def expert_params(cfg: dict) -> int:
    """One routed expert."""
    s = dims(cfg)
    return 3 * s['d'] * s['f_expert']


def router_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['experts']


def unembed_params(cfg: dict) -> int:
    s = dims(cfg)
    return s['d'] * s['v']


def experts_touched(cfg: dict, rows: int) -> float:
    """Experts that a pass over `rows` positions is expected to touch
    (uniform routing): 128 of 128 at 512 rows."""
    s = dims(cfg)
    miss = 1.0 - s['per_token'] / s['experts']
    return s['experts'] * (1.0 - miss ** rows)


def position_matmul_flops(cfg: dict) -> float:
    """One position's matrix products over every layer, one forward:
    attention's four projections, the router, the chosen experts."""
    s = dims(cfg)
    return 2.0 * s['layers'] * (attn_matmul_params(cfg) + router_params(cfg)
                                + s['per_token'] * expert_params(cfg))


def keys_to_block_end(cfg: dict, position: int) -> int:
    """Keys a query at `position` sees: to the end of its block."""
    b = dims(cfg)['block']
    return (position // b + 1) * b


def attention_flops(cfg: dict, keys: int) -> int:
    """Scores and weighted sum, 2 matmuls a head, every layer, over
    `keys` query-key pairs a layer."""
    s = dims(cfg)
    return 4 * s['h'] * s['hd'] * s['layers'] * keys


def unmask_schedule(cfg: dict) -> list:
    s = dims(cfg)
    base, extra = divmod(s['block'], s['steps'])
    return [base + (i < extra) for i in range(s['steps'])]


def masked_passes(cfg: dict) -> float:
    """Passes in which a position of a whole block is on average still
    masked, and so needs its row of logits: (steps + 1) / 2 at one
    position a pass."""
    s, left, total = dims(cfg), dims(cfg)['block'], 0
    for n in unmask_schedule(cfg):
        total += left
        left -= n
    return total / s['block']


def prefill_flops(cfg: dict, start: int, stop: int, last: bool) -> float:
    """Prompt positions start .. stop - 1, each with keys to its block's
    end. Prefill covers a prompt's whole blocks and yields no token: no
    row of logits at a prompt's end, whatever `last` says."""
    del last
    keys = sum(keys_to_block_end(cfg, p) for p in range(start, stop))
    return (position_matmul_flops(cfg) * (stop - start)
            + attention_flops(cfg, keys))


def decode_flops(cfg: dict, position: int) -> float:
    """A generated token's share of its block: steps + 1 passes of its
    position's layer operations with keys to the block's end, and the
    head's operations for the passes in which it is still masked."""
    s = dims(cfg)
    one = (position_matmul_flops(cfg)
           + attention_flops(cfg, keys_to_block_end(cfg, position)))
    return ((s['steps'] + 1) * one
            + masked_passes(cfg) * 2 * unembed_params(cfg))


def weight_bytes_per_step(cfg: dict, bytes_per_weight: int = 2) -> float:
    """What a pass cannot avoid reading of the weights: every layer's
    attention, router, norms and the experts that `stage.decode_batch`
    slots x B positions are expected to touch (all of them at 512 rows),
    the final norm and the head; of the embedding the gathered rows
    only."""
    s = dims(cfg)
    rows = s['decode_batch'] * s['block']
    weights = (s['layers'] * (attn_matmul_params(cfg)
                              + layer_other_params(cfg)
                              + router_params(cfg)
                              + experts_touched(cfg, rows)
                              * expert_params(cfg))
               + unembed_params(cfg) + s['d'] + rows * s['d'])
    return bytes_per_weight * weights


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """One layer's K and V of one position."""
    s = dims(cfg)
    return 2 * s['kv'] * s['hd'] * bytes_per_value


def decode_state_bytes(cfg: dict, position: int,
                       bytes_per_value: int = 2) -> float:
    """A generated token's share of the K/V its block's passes read:
    steps + 1 passes read the keys up to the block's end once each for
    all B positions (the rows a pass writes are not counted)."""
    s = dims(cfg)
    return ((s['steps'] + 1) / s['block'] * s['layers']
            * keys_to_block_end(cfg, position)
            * kv_bytes_per_token(cfg, bytes_per_value))
