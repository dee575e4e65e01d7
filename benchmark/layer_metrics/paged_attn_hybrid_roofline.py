"""The paged-attention kernel's share of its roofline in a hybrid block: the attention layers only, at the stated head size (the block's cost function), in percent."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.paged_attention_roofline(ctx)
