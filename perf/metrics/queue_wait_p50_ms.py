"""Median of the program's own `engine.queue_wait` spans (submit to
admission into a slot) of the requests sent and admitted inside the window; the
spans are switched on for the traced run's whole window. In a closed
loop with more clients than slots it is the time spent waiting for a
slot."""
import common


def read(ctx):
    waits = [s['dur_us'] / 1e3 for s in ctx['spans']
             if s['name'] == 'engine.queue_wait']
    return common.percentile(waits, 50) if waits else None
