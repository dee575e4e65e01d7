"""Device self time under the window attention layers' scope (window_attn: their qkv, kv_write, attend and attn_out), share of busy in percent. (the saturated cell's name)"""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.path_share(ctx, "window")
