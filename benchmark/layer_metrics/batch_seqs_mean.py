"""Mean sequences per forward (the program's forward spans)."""

from benchmark import readers


def reduce(ctx):
    return readers.batch_seqs_mean(ctx)
