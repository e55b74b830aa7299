"""Share of the decode programs' device time that the KDA state kernel
(`ops/kda_state_step`, named `kda_state_step` in the trace) holds: its
events' summed device time over the device time of the decode modules.
A trace without the kernel, or without decode programs, gives nothing to
read."""

KERNEL = r'^%?kda_state_step[\w.\-]* = '
MODULE = r'decode'


def read(ctx):
    trace = ctx['trace']
    seconds, _calls = trace.op_seconds(KERNEL)
    busy = trace.ops_within_modules(MODULE)
    if seconds <= 0 or busy <= 0:
        return None
    return 100.0 * seconds / busy
