"""Median device time of the forward program at the most frequent [S, 256] bucket."""

from benchmark import readers


def reduce(ctx):
    return readers.forward_device_ms(ctx, mixed=True)
