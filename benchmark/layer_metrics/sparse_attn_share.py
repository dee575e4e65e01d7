"""Device time of the sparse layers' two attention kernels (paged_attention_select, paged_attention_mask), share of busy in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.attend_share(ctx)
