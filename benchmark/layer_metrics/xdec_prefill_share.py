"""Device self time under xdec (every layer behind the forward's exit) in the traced window's chunk forwards, share of those forwards' busy time in percent: a few percent where the tail runs on one row, tens where it runs on every position."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.path_share(ctx, "xdec", mixed=True)
