"""Share of the prefill chunks' positions that were a prompt's: over the
window's `engine.prefill` spans (one a request, at its first token), the
sum of `prompt_tokens` over the sum of `chunks` x the chunk's width. The
rest are right pads, which the chunk's matmuls and the mixer's scan run
over all the same and whose scan steps must leave the state alone. A
program whose span has no `chunks` gives nothing to read."""


def read(ctx):
    spans = [s['attrs'] for s in ctx['spans']
             if s['name'] == 'engine.prefill'
             and (s.get('attrs') or {}).get('chunks')]
    width = ctx['work'].get('chunk')
    positions = sum(a['chunks'] for a in spans) * (width or 0)
    if not positions:
        return None
    return 100.0 * sum(a['prompt_tokens'] for a in spans) / positions
