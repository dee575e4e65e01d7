"""The one-token Mamba-2 step's kernel (mamba2_step) as a share of its roofline: the live rows' recurrent state read once and written once (the program's ssm_state_bytes over the traced window's stepped forwards) over the chip's memory bandwidth, over the device time of the kernel's own events, in percent."""

from benchmark import ssm_step_readers


def reduce(ctx):
    return ssm_step_readers.step_roofline(ctx)
