"""Share of the traced stretch in which no operation ran on the device
while the engine's thread was in none of the prefill, dispatch, land and
emit phases: housekeeping, admission, the engine asleep, a tick's self
time, outside any tick. If this holds most of the idle time, the phases
are in the wrong places. One of the five shares that add up to
`device_idle_pct.serve` (`perf/phase_idle.py`); None where the program
marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.idle_in(ctx, 'other')
