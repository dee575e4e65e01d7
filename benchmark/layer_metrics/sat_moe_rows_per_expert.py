"""Rows a held expert is given in one forward of one layer, mean over the window's forwards (the program's moe_rows_held counter over layers x experts held). (the saturated cell's name)"""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.moe_rows_per_expert(ctx)
