"""The update program (clip, optimizer, bookkeeping), share of device busy time in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.train_share(ctx, "opt")
