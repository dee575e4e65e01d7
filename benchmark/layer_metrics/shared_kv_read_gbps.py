"""The shared K/V bytes the cross layers' walks read in the traced window's one-token forwards over their device time under cross_attn's attend, in GB/s (beside the chip's memory bandwidth)."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.shared_kv_read_gbps(ctx)
