"""Share of the tokens a decode step routed whose kept expert groups
(group-limited routing: the `topk_group` best of `n_group`) include a
group held here, so that this chip's experts are candidates at all: from
the engine's `paged_occupancy()` after the window,
`route_decode_tokens_group_held` over the real tokens routed
(`route_decode_pairs_routed` / `num_experts_per_tok`), counts of the
whole run of the steps whose tokens have landed. With one group of the
eight held and four kept, uniform routing gives 50%. A program without
those counters gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    pairs = occ.get('route_decode_pairs_routed', 0)
    if 'route_decode_tokens_group_held' not in occ or not pairs:
        return None
    per_token = ctx['config']['num_experts_per_tok']
    return 100.0 * occ['route_decode_tokens_group_held'] * per_token / pairs
