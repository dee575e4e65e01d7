"""Blocks the sparse layers attended over the blocks their query positions could see (the program's sparse_blocks_selected / sparse_blocks_live over the window's forwards)."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.select_ratio(ctx)
