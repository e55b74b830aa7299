"""Share of the traced stretch in which no operation ran on the device
while any thread was inside a `process.gc` event (a pause of Python's
garbage collector, marked on the thread whose allocation triggered it).
It cuts across the five `idle_in_<phase>_pct` shares and is not a sixth
part of them. None where the program marks no sub-phase, 0 where it
does and no collection fell in the stretch (`perf/host_split.py`)."""
import host_split


def read(ctx):
    return host_split.idle_in(ctx, 'gc')
