"""Share of the device's busy time in the traced stretch that the
dropless expert layers' grouped products hold (gate, up and down a
layer): the summed device time of their events over `busy_s()`. They
are found by name in the trace: `ragged-dot` where XLA lowers
`jax.lax.ragged_dot` to a grouped-matmul kernel of its own, `moe_gmm`
where the program runs its own Pallas kernel (`ops/grouped_matmul.py`),
so a program with either reads. No share of a roofline: the bytes a
step's products touch are the family's to count. A trace with neither
gives nothing to read."""

# an `XLA Ops` event's name is the whole HLO instruction; the pattern
# holds to its own name, so that a fusion which only reads a product's
# output is not counted with it
KERNEL = r'^%?(ragged-dot|moe_gmm)[\w.\-]* = '


def read(ctx):
    trace = ctx['trace']
    seconds, _count = trace.op_seconds(KERNEL)
    busy = trace.busy_s()
    if seconds <= 0 or busy <= 0:
        return None
    return 100.0 * seconds / busy
