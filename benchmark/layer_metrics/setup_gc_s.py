"""Seconds of set-up spent in full (generation-2) collections of the garbage collector, as gc.callbacks reports them."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.read(ctx, "gc", "self_seconds")
