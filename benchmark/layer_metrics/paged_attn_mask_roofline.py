"""The chunk rows' kernel (paged_attention_mask) as a share of its roofline: the block's cost function over the selected query-key pairs, whatever the kernel walks, over the device time of the kernel's own events, in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.mask_roofline(ctx)
