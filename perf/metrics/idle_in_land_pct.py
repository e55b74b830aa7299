"""Share of the traced stretch in which no operation ran on the device
while the engine's thread was inside `engine.tick.land`: the device had
finished, or had not begun, while the host waited for its answer. One
of the five shares that add up to `device_idle_pct.serve`
(`perf/phase_idle.py`); None where the program marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.idle_in(ctx, 'land')
