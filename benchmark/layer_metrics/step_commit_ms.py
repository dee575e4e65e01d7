"""Median duration of the step's commit span (per-row commit, finish, callbacks) over the window, in ms."""

from benchmark import scopes


def reduce(ctx):
    return scopes.span_median_ms(ctx, "commit")
