"""The window latent kernels (mla_window_decode, mla_window_prefill) as a share of their roofline: the block's cost function over the window group's keys and pairs by path, over the device time of the kernels' own events, in percent."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.window_roofline(ctx)
