"""Device idle after the next forward's dispatch had ended (uploads, the runtime's hand-over), share of the traced window in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.idle_share(ctx, "launch")
