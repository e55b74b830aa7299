"""Share of the traced stretch in which no operation ran on the device
while the engine's thread was inside `engine.tick.dispatch.launch` or
`engine.tick.prefill.launch` (the jitted call of the decode or chunk
program alone): a part of `idle_in_dispatch_pct` +
`idle_in_prefill_pct`. None where the program marks no sub-phase
(`perf/host_split.py`)."""
import host_split


def read(ctx):
    return host_split.idle_in(ctx, 'launch')
