"""The absorbed latent-attention kernel (mla_decode) as a share of its roofline: the block's cost function over the program's absorbed query rows, keys and pairs, over the device time of the kernel's own events, in percent."""

from benchmark import latent_readers


def reduce(ctx):
    return latent_readers.decode_roofline(ctx)
