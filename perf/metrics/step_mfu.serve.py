"""The whole serving step's share of the chip's bf16 peak: the operations
every token processed in the traced stretch requires (prompt tokens
prefilled and tokens generated, each at its position), over stretch x
chips x peak."""
import flops_bytes


def read(ctx):
    work = ctx['work']
    cfg = ctx['config']
    flops = sum(flops_bytes.decode_flops(cfg, p)
                for p in work['decode_positions'])
    flops += flops_bytes.prefilled_flops(cfg, work)
    if flops <= 0:
        return None
    peak = ctx['peaks']['bf16_flops_per_s'] * ctx['chips']
    return 100.0 * flops / work['window_s'] / peak
