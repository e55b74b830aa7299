"""Share of the traced stretch in which no operation ran on the device:
1 - (seconds in which an operation ran, averaged over the chips) / stretch."""


def read(ctx):
    return ctx['trace'].idle_pct(ctx['work']['window_s'])
