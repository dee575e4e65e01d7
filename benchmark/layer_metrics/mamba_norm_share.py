"""Device self time under mamba_norm (the three RMSNorms inside an S6 layer: over the step's projection, B and C), share of busy in percent."""

from benchmark import s6_readers


def reduce(ctx):
    return s6_readers.norm_share(ctx)
