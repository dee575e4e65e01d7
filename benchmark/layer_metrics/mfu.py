"""Model FLOP utilization in percent: FLOPs from shapes (no recomputation) x tokens/s over the published peak."""

from benchmark import readers


def reduce(ctx):
    return readers.mfu_percent(ctx)
