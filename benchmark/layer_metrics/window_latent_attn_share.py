"""Device self time under the window latent layers' scope (window_latent_attn: qkv, kv_write, kv_expand, attend, attn_out of the window kind), share of busy in percent."""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.path_share(ctx, "window")
