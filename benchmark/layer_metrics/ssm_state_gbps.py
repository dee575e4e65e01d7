"""The recurrent state's bytes read and written by the traced window's one-token forwards over their device time under mamba_scan plus mamba_state_io, in GB/s (beside the chip's memory bandwidth)."""

from benchmark import ssm_readers


def reduce(ctx):
    return ssm_readers.state_gbps(ctx)
