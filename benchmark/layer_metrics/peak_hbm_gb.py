"""Peak bytes in use on the fullest chip, in GB."""

from benchmark import readers


def reduce(ctx):
    return readers.peak_hbm_gb(ctx)
