"""Share of the HBM roofline reached inside the decode programs: the
least bytes the decode steps of the traced stretch had to read (every
layer's weights and the unembedding once a step, the steps counted by
the engine's own `step_log` and not by the programs run, plus each
generated token's K and V up to its position) over 819 GB/s, over the device time
of the decode modules. Memory-bound: a step's operations need far less
time than its bytes."""
import flops_bytes

MODULE = r'decode'


def read(ctx):
    tr = ctx['trace']
    busy = tr.ops_within_modules(MODULE)
    steps = ctx['work']['decode_steps']
    positions = ctx['work']['decode_positions']
    if busy <= 0 or steps <= 0 or not positions:
        return None
    cfg = ctx['config']
    chips = ctx['chips']
    bytes_ = steps * flops_bytes.weight_bytes_per_step(cfg)
    bytes_ += sum(flops_bytes.decode_kv_bytes(cfg, p) for p in positions)
    flops = sum(flops_bytes.decode_flops(cfg, p) for p in positions)
    peaks = {k: v * chips if isinstance(v, float) else v
             for k, v in ctx['peaks'].items()}
    least, _bound = flops_bytes.roofline_seconds(flops, bytes_, peaks)
    return 100.0 * least / busy
