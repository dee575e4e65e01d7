"""Device us a valid token of the traced window's forwards wider than one token, each module matched to its dispatch by order."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.dev_prefill_us_per_token(ctx)
