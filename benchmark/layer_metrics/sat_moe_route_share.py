"""Device self time under the router scope (the router's matmul over all experts, wherever the block's router reads), share of busy in percent. (the saturated cell's name)"""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scopes_share(ctx, ("router",))
