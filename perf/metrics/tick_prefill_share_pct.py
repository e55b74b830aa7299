"""Share of the traced stretch that the engine's thread spent inside
`engine.tick.prefill`: what every decoding slot waits through between
two of its tokens, since a tick runs its prefill chunks before its
decode step. None where the program marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.phase_share_pct(ctx['trace'], 'prefill')
