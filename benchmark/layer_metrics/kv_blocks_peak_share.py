"""Largest share of the KV pool in use after a step, in percent."""

from benchmark import readers


def reduce(ctx):
    return readers.kv_blocks_peak_share(ctx)
