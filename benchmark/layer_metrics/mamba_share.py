"""Device self time under the Mamba-2 layers' scopes (mamba: projections, conv, recurrence, gated norm and output), share of busy in percent."""

from benchmark import ssm_readers


def reduce(ctx):
    return ssm_readers.mamba_share(ctx)
