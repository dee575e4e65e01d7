"""Of the window's step spans that dispatched, the share whose predecessor had already finished on the device, in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.steps_share(ctx, "starved")
