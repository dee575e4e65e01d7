"""The flash kernels share of their FLOP-bound roofline in the train step, in percent."""

from benchmark import readers


def reduce(ctx):
    return readers.flash_attention_roofline(ctx)
