"""Device self time under the Gated DeltaNet layers' scopes (linear_attn and what it holds: gdn_proj, gdn_conv, gdn_scan, gdn_out), share of busy in percent."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.scopes_share(ctx, hybrid_readers.gdn_scopes(ctx))
