"""The whole training step's share of the chip's bf16 peak over the
traced stretch: the operations a LoRA step requires (forward, backward
through the activations and the adapters' gradients; no gradients of the
frozen weights, nothing recomputed) times the steps traced, over stretch
x chips x peak."""
import flops_bytes


def read(ctx):
    w = ctx['work']
    if not w.get('steps') or w['window_s'] <= 0:
        return None
    targets = tuple(ctx['mix']['lora']['targets'].split(','))
    flops = w['steps'] * flops_bytes.lora_train_flops(
        ctx['config'], w['rows'], w['seq'], w['lora_rank'], targets)
    peak = ctx['peaks']['bf16_flops_per_s'] * ctx['chips']
    return 100.0 * flops / w['window_s'] / peak
