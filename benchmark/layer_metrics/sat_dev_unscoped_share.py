"""Device self time of operations that carry no scope of the program, share of busy in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.device_share(ctx, scopes.UNSCOPED)
