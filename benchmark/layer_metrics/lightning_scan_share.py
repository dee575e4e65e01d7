"""Device self time under lightning_scan alone (the recurrence: the step and the chunked form's tiles), share of busy in percent."""

from benchmark import sala_readers


def reduce(ctx):
    return sala_readers.lightning_share(ctx, ("lightning_scan",))
