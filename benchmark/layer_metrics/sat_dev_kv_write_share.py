"""Device self time under the kv_write scope (the pool scatter), share of busy in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.device_share(ctx, "kv_write")
