"""Block passes a generated token: row-passes whose results landed over
tokens emitted, in block mode (generation by diffusion over blocks). A
whole block of B takes its denoising passes and one commit: (steps + 1) /
B, 1.25 at four passes over a block of four; a prompt's tail in the
first block and the trim of the last one raise it. From the engine's
`paged_occupancy()` after the window (`block_passes` over
`block_tokens`; counts of the whole run, warm-up included). A program
without those counters gives nothing to read."""


def read(ctx):
    occ = ctx['occupancy'] or {}
    if not occ.get('block_tokens') or 'block_passes' not in occ:
        return None
    return occ['block_passes'] / occ['block_tokens']
