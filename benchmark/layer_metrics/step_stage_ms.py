"""Median duration of the step's stage span (the host part of engine.put) over the window, in ms."""

from benchmark import scopes


def reduce(ctx):
    return scopes.span_median_ms(ctx, "stage")
