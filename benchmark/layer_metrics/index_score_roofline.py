"""The indexer's scoring kernel (index_score) as a share of its roofline: the block's cost function over the causal query-key pairs and the index keys read, over the device time of the kernel's own events, in percent."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.index_score_roofline(ctx)
