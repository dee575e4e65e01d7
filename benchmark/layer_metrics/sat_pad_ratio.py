"""Bucket positions S*C computed per valid token, over the window's forwards."""

from benchmark import readers


def reduce(ctx):
    return readers.pad_ratio(ctx)
