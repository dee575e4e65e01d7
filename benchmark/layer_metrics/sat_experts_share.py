"""Device self time under the experts scope (sort, grouped GEMMs, combine), share of busy in percent. (the saturated cell's name)"""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scopes_share(ctx, ("experts",))
