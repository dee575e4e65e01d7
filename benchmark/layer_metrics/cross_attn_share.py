"""Device self time under the cross attention layers' scope (cross_attn: their queries, the walk of another layer's pool rows and attn_out), share of the traced forwards' busy time in percent."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.path_share(ctx, "cross_attn")
