"""Device self time under the kv_expand scope (gathering a chunk's live latent blocks and rebuilding their K/V heads), share of busy in percent."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scopes_share(ctx, ("kv_expand",))
