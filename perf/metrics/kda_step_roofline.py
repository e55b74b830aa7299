"""Share of the HBM roofline reached by the KDA decode step's state
kernel (`ops/kda_state_step`, named `kda_state_step` in the trace): what
its calls in the traced stretch had to move (each call one KDA layer
over every slot, its bytes as the configuration's family counts them:
the float32 state read and written, the step's vectors read and its
output written) over the HBM's peak, over the kernel's own device time.
Memory-bound: a call's operations need far less time than its bytes. A
trace without the kernel gives nothing to read."""
import common

# an `XLA Ops` event's name is the whole HLO instruction
KERNEL = r'^%?kda_state_step[\w.\-]* = '


def read(ctx):
    seconds, calls = ctx['trace'].op_seconds(KERNEL)
    if seconds <= 0 or not calls:
        return None
    cfg = ctx['config']
    family = common.load_family(cfg)
    bytes_ = calls * family.kda_state_step_bytes(cfg, ctx['num_slots'])
    peak = ctx['peaks']['hbm_bytes_per_s'] * ctx['chips']
    return 100.0 * bytes_ / peak / seconds
