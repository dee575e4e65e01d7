"""Share of scheduler.step wall time in which the device ran nothing, in percent (trace)."""

from benchmark import readers


def reduce(ctx):
    return readers.host_step_share(ctx)
