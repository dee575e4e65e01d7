"""Device self time under mamba_state_io alone (the gather of the rows' recurrent state and conv tail out of the state slots and the scatter back), share of busy in percent."""

from benchmark import ssm_readers


def reduce(ctx):
    return ssm_readers.mamba_share(ctx, ("mamba_state_io",))
