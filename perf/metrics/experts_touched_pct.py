"""Share of the experts held here that got a token, a decode step and an
expert layer: what a dropless step has to read of the expert stacks
(`weight_bytes_per_step` of the family assumes the share that uniform
routing gives a full batch). From the engine's `paged_occupancy()` after
the window: `route_decode_experts_touched` over decode steps x expert
layers x experts held (counts of the whole run, warm-up included, of
the steps whose tokens have landed). A program without those counters
gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    cells = (occ.get('route_decode_calls', 0) * occ.get('expert_layers', 0)
             * occ.get('experts_held', 0))
    if 'route_decode_experts_touched' not in occ or not cells:
        return None
    return 100.0 * occ['route_decode_experts_touched'] / cells
