"""The paged-attention kernel's share of its roofline (bytes- or FLOP-bound, from shapes) in percent."""

from benchmark import readers


def reduce(ctx):
    return readers.paged_attention_roofline(ctx)
