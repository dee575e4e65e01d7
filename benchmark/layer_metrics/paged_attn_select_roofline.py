"""The one-token rows' kernel (paged_attention_select) as a share of its roofline: the block's cost function over the selected blocks of those rows (the program's counters), over the device time of the kernel's own events, in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.select_roofline(ctx)
