"""Median, over the ticks `tick_p50_ms` takes (those of the traced
stretch that hold an `engine.tick.emit`), of the `engine.tick` span's
length less its `engine.tick.land` and `engine.tick.wait` children: the
engine thread's own work a token step, to set beside the device's step.
Where it exceeds the step, the device waits. None where the program
marks no tick phase (`perf/host_split.py`)."""
import host_split


def read(ctx):
    return host_split.host_tick_ms_p50(ctx['trace'])
