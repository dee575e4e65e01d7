"""Device self time under a sparse layer's indexer (the index scope: index_proj, index_score, index_select), share of busy in percent."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.index_share(ctx)
