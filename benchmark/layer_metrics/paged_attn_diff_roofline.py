"""The paged-attention kernel as a share of its roofline under differential attention on joined pairs, with an exit in front of the whole-context group's readers: the block's cost function (the definition's least work) over the program's keys and pairs, layer group by layer group, over the device time of the kernel's own events, in percent."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.paged_attention_roofline(ctx)
