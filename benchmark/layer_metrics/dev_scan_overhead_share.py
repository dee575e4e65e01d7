"""Device self time under layers but under none of the block's scopes (the scan's plumbing), share of busy in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.device_share(ctx, scopes.SCAN_OVERHEAD)
