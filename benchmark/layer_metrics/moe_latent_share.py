"""Device self time under latent_proj (the two projections round the routed experts' latent), share of busy in percent."""

from benchmark import ssm_readers


def reduce(ctx):
    return ssm_readers.latent_share(ctx)
