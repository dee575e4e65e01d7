"""The S6 one-token step as a share of its roofline, whatever implements it: the live rows' recurrent state read once and written once (the program's ssm_state_bytes over the traced window's one-token forwards) over the chip's memory bandwidth, over the device time under mamba_scan plus mamba_state_io in those forwards, in percent."""

from benchmark import s6_readers


def reduce(ctx):
    return s6_readers.step_roofline(ctx)
