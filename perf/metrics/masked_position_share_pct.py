"""Share of the positions the block passes forwarded that were still
masked, and so needed their row of logits: the head runs over every
position of a block every pass, and only a masked one's row is read.
50 at one position unmasked a pass (4, 3, 2, 1 and 0 of 4 over five
passes). From the engine's `paged_occupancy()` after the window
(`block_masked_positions` over `block_passes` x `block_length`; counts
of the whole run, warm-up included). A program without those counters
gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    rows = occ.get('block_passes', 0) * occ.get('block_length', 0)
    if not rows or 'block_masked_positions' not in occ:
        return None
    return 100.0 * occ['block_masked_positions'] / rows
