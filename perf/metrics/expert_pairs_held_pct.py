"""Share of the (token, choice) pairs the routers chose in decode steps
whose expert is held here: the share's own check (uniform routing gives
experts held over experts scored). From the engine's
`paged_occupancy()` after the window: `route_decode_pairs_held` over
`route_decode_pairs_routed` (counts of the whole run, warm-up included).
A program without those counters gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    if 'route_decode_pairs_held' not in occ or \
            not occ.get('route_decode_pairs_routed'):
        return None
    return (100.0 * occ['route_decode_pairs_held']
            / occ['route_decode_pairs_routed'])
