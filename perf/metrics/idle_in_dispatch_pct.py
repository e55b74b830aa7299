"""Share of the traced stretch in which no operation ran on the device
while the engine's thread was inside `engine.tick.dispatch` (every
decode launch: blocks, tables, uploads, `jax.random.split`, the jitted
call). One of the five shares that add up to `device_idle_pct.serve`
(`perf/phase_idle.py`); None where the program marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.idle_in(ctx, 'dispatch')
