"""Share of the traced stretch in which no operation ran on the device
while the engine's thread was inside `engine.tick.prefill`
(`_prefill_tick`: the uploads and the table build of each chunk, its
launch, the host sync at a prompt's last chunk). One of the five shares
that add up to `device_idle_pct.serve` (`perf/phase_idle.py`); None
where the program marks no tick phase."""
import phase_idle


def read(ctx):
    return phase_idle.idle_in(ctx, 'prefill')
