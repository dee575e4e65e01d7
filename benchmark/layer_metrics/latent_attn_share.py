"""Device self time under the latent attention layers' scope (latent_attn: their qkv, kv_write, kv_expand, attend and attn_out), share of busy in percent."""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.path_share(ctx, "latent")
