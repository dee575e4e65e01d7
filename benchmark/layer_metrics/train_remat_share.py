"""Recomputed forward (checkpoint/rematted_computation), share of device busy time in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.train_share(ctx, "remat")
