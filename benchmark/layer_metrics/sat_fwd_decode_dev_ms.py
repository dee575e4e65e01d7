"""Median device time of the forward program at the traced window's most frequent [S, 1] bucket, each execution matched to its dispatch by order."""

from benchmark import readers


def reduce(ctx):
    return readers.forward_device_ms(ctx, mixed=False)
