"""Median wait from submit to the scheduler (the program's queue, route and admit spans)."""

from benchmark import readers


def reduce(ctx):
    return readers.queue_wait_percentile_ms(ctx, 50)
