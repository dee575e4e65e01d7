"""The paged-attention kernel as a share of its roofline where layer groups differ in window: the block's cost function over the program's window-bounded keys and pairs, group by group, over the device time of the kernel's own events, in percent. (the saturated cell's name)"""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.paged_attention_roofline(ctx)
