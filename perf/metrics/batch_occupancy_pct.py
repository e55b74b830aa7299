"""Mean share of the slots that a decode step carried, over the decode
steps of the traced stretch, from the engine's `step_log` (a dispatch of
several steps counts as many times)."""


def read(ctx):
    steps = sum(k for k, _ in ctx['dispatches'])
    if not steps:
        return None
    carried = sum(k * slots for k, slots in ctx['dispatches'])
    return 100.0 * carried / steps / ctx['num_slots']
