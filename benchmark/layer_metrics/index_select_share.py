"""Device self time under index_select (the exact top-k over a query's scores, lax.top_k: not a kernel of ours, so a share and no roofline), share of busy in percent."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.index_share(ctx, ("index_select",))
