"""Median host-clock time of a training step that ends in a fetched loss."""

from benchmark import readers


def reduce(ctx):
    return readers.step_percentile_ms(ctx, 50)
