"""Median time of the window's steps, by the host's clock round each
step ended by block_until_ready."""
import statistics


def read(ctx):
    times = ctx.get('step_times')
    return statistics.median(times) * 1e3 if times else None
