"""How late the load generator submitted, 99th percentile (validity of the open-loop numbers)."""

from benchmark import readers


def reduce(ctx):
    return readers.gen_late_percentile_ms(ctx, 99)
