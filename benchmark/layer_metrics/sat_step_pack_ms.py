"""Median duration of the scheduler step's pack span (admission, chunks, sampling) over the window, in ms."""

from benchmark import scopes


def reduce(ctx):
    return scopes.span_median_ms(ctx, "pack")
