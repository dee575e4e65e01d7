"""Device self time of the sparse layers' selection (block_compress, block_score, block_select), share of busy in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.select_share(ctx)
