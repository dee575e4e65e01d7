"""The grouped-matmul kernel (the held experts' GEMMs: gmm) as a share of its roofline: the block's cost function (expected pairs, expected experts hit) over the device time of the kernel's own events, in percent. (the saturated cell's name)"""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.gmm_roofline(ctx)
