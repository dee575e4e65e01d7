"""Median device time of the forward program at the most frequent bucket of the widest chunk the traced window ran, each execution matched to its dispatch by order."""

from benchmark import readers


def reduce(ctx):
    return readers.forward_device_ms(ctx, mixed=True)
