"""Device idle with nothing handed over and the host at work under a ds:* span other than idle_wait, share of the traced window in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.idle_share(ctx, "starved")
