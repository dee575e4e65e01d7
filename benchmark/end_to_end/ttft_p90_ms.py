"""Due time to first token, 90th percentile over requests due in the window (failed = missed)."""

from benchmark import readers


def reduce(ctx):
    return readers.ttft_percentile_ms(ctx, 90)
