"""Forward pass of the micro step, share of device busy time in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.train_share(ctx, "fwd")
