"""How uneven the held experts' load is in a decode step: the largest
held expert's tokens over the mean over the held experts, both summed
over the expert layers and the decode steps (1 is level; the grouped
products pay for the largest group's tiles). From the engine's
`paged_occupancy()` after the window: `route_decode_max_expert_load`
over `route_decode_pairs_held` / experts held (counts of the whole run,
warm-up included). A program without those counters gives nothing to
read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    held = occ.get('route_decode_pairs_held', 0)
    if 'route_decode_max_expert_load' not in occ or not held or \
            not occ.get('experts_held'):
        return None
    return (occ['route_decode_max_expert_load'] * occ['experts_held']
            / held)
