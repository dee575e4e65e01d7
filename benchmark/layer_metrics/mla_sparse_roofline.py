"""The sparse absorbed kernel (mla_sparse_decode) as a share of its roofline: the block's cost function over the selected rows of the absorbed query positions, over the device time of the kernel's own events, in percent."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.sparse_attention_roofline(ctx)
