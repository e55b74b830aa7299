"""Share of the block passes that were commits: a pass over a block with
no mask left, which writes the clean block's K/V and yields no token;
what fusing the commit with the next block's first pass would win. From
the engine's `paged_occupancy()` after the window (`block_commits` over
`block_passes`; counts of the whole run, warm-up included). A program
without those counters gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    if not occ.get('block_passes') or 'block_commits' not in occ:
        return None
    return 100.0 * occ['block_commits'] / occ['block_passes']
