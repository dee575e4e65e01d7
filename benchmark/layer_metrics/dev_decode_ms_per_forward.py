"""Device ms a one-token forward (bucket_chunk = 1) of the traced window, each module matched to its dispatch by order."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.dev_decode_ms_per_forward(ctx)
