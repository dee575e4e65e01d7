"""Device self time under the router scope (the router's matmul over all experts), share of busy in percent."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scopes_share(ctx, ("router",))
