"""Device idle with nothing handed over while the worker waited for work (ds:idle_wait), share of the traced window in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.idle_share(ctx, "no_work")
