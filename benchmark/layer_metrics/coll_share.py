"""Share of the traced window in which a collective ran on the device, in percent."""

from benchmark import readers


def reduce(ctx):
    return readers.collective_share(ctx, exposed=False)
