"""Of the traced time inside the union of the requests' decode spans, the share the device was idle, in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.decode_time_share(ctx, "idle")
