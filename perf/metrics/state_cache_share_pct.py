"""Share of the cache's device bytes that are per-slot recurrent state
(the mixer's scan state and convolution inputs: fixed a slot, read and
written at every decode step whatever the position) and not the paged
K/V pool, from the engine's `paged_occupancy()`: `state_bytes` over
`state_bytes` + `kv_pool_bytes`. A program without those counters gives
nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    if 'state_bytes' not in occ or 'kv_pool_bytes' not in occ:
        return None
    total = occ['state_bytes'] + occ['kv_pool_bytes']
    if not total:
        return None
    return 100.0 * occ['state_bytes'] / total
