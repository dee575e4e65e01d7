"""Of the traced time requests spent in their prefill span, the share the device ran forwards that held the request, in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.prefill_own_share(ctx)
