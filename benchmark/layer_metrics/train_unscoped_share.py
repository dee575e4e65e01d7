"""Micro-step operations that carry no scope of the program, share of device busy time in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.train_share(ctx, "unscoped")
