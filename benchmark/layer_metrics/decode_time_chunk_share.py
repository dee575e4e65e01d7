"""Of the traced time inside the union of the requests' decode spans, the share the device spent in forwards wider than one token, in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.decode_time_share(ctx, "chunk")
