"""Median device time of the forward program at the most frequent [S, 1] bucket."""

from benchmark import readers


def reduce(ctx):
    return readers.forward_device_ms(ctx, mixed=False)
