"""Per request (t_last - t_first)/(n_out - 1), 90th percentile over requests due in the window."""

from benchmark import readers


def reduce(ctx):
    return readers.tpot_percentile_ms(ctx, 90)
