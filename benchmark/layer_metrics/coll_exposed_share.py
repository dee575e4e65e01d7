"""Share of the traced window in which a collective ran and no compute did, in percent."""

from benchmark import readers


def reduce(ctx):
    return readers.collective_share(ctx, exposed=True)
