"""Share of the chip's bf16 peak reached inside the prefill programs:
the operations the prompt tokens they processed require, over the device
time of the prefill modules in the trace."""
import flops_bytes

MODULE = r'prefill'


def read(ctx):
    work = ctx['work']
    busy = ctx['trace'].ops_within_modules(MODULE)
    tokens = work['prompt_tokens_prefilled']
    if busy <= 0 or tokens <= 0:
        return None
    flops = flops_bytes.prefilled_flops(ctx['config'], work)
    peak = ctx['peaks']['bf16_flops_per_s'] * ctx['chips']
    return 100.0 * flops / busy / peak
