"""Device self time under the gated memory units' scope (gmu: the gate's projection, the product with the carried memory, the output projection), share of the traced forwards' busy time in percent."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.path_share(ctx, "gmu")
