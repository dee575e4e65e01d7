"""Device self time under the lightning layers' scopes (lightning_attn and what it holds: lightning_proj, lightning_scan, lightning_out), share of busy in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.lightning_share(ctx)
