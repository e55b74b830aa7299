"""Peak share of the paged pool's blocks in use, from the engine's
`paged_occupancy()` after the window (the peak is the run's, warm-up
included: the engine has no way to reset it)."""


def read(ctx):
    occ = ctx['occupancy']
    if not occ or not occ.get('blocks_capacity'):
        return None
    return 100.0 * occ['peak_blocks_used'] / occ['blocks_capacity']
