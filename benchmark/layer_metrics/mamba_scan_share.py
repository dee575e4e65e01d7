"""Device self time under mamba_scan alone (the recurrence: the one-token step and the chunked form's tiles), share of busy in percent."""

from benchmark import ssm_readers


def reduce(ctx):
    return ssm_readers.mamba_share(ctx, ("mamba_scan",))
