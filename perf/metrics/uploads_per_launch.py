"""Host-to-device uploads a program launch: `host_uploads` over
`decode_dispatches` + `prefill_chunks` of the engine's
`paged_occupancy()` after the window (counts of the whole run, warm-up
included, as `dispatch_ahead_pct`'s). A steady decode step uploads
nothing and a chunk its 4 or 5 inputs. A program without the count gives
nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    launches = occ.get('decode_dispatches', 0) + occ.get('prefill_chunks', 0)
    if 'host_uploads' not in occ or not launches:
        return None
    return occ['host_uploads'] / launches
