"""Share of their roofline that the flash-attention kernels (forward,
dq, dkv) reached: the least time the chip could take for the attention
of the traced steps (operations on the causal, windowed pairs over the
bf16 peak, or the bytes that must cross HBM over its bandwidth,
whichever is longer: compute-bound at these shapes) over the summed
device time of the kernels' events, found by name in the trace."""
import flops_bytes

# The program gives its Pallas calls no name of their own: in the trace
# they carry the flax scope they were traced in (`%attn.36 = ...
# custom-call(...)`). PERF.md asks the tracing issue for real names.
KERNEL = r'^%?attn(\.\d+)? = .*custom-call'


def read(ctx):
    w = ctx['work']
    seconds, count = ctx['trace'].op_seconds(KERNEL)
    if seconds <= 0 or not w.get('steps'):
        return None
    work = flops_bytes.flash_attention_work(ctx['config'], w['rows'],
                                            w['seq'])
    least, _bound = flops_bytes.roofline_seconds(
        w['steps'] * work['flops'], w['steps'] * work['bytes'],
        ctx['peaks'])
    return 100.0 * least / seconds
