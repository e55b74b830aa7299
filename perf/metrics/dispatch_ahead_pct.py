"""Share of the decode dispatches that were queued ahead: issued off
the device's own feed while an earlier step's tokens had not been read
yet, so that the host's round trip hides behind a step. From the
engine's `paged_occupancy()` after the window: `decode_chained` over
`decode_dispatches` (counts of the whole run, warm-up included: the
engine cannot reset them). A program without those counters gives
nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    if 'decode_chained' not in occ or not occ.get('decode_dispatches'):
        return None
    return 100.0 * occ['decode_chained'] / occ['decode_dispatches']
