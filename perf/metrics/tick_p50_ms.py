"""Median length of the `engine.tick` spans of the traced stretch that
hold an `engine.tick.emit`: the scheduler's whole step, prefill chunks
and host work included, that separates two tokens of a decoding slot.
None where the program marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.tick_p50_ms(ctx['trace'])
