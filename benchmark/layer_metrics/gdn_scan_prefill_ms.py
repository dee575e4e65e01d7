"""Median device time of the gdn_scan scope (the chunked delta rule, all recurrent layers) in one mixed step (a forward with a chunk wider than one token), in milliseconds."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scope_ms_per_forward(ctx, "gdn_scan", mixed=True)
