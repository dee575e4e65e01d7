"""Of the window's step spans that dispatched, the share dispatched while the step before was unread, in percent."""

from benchmark import dispatch_readers


def reduce(ctx):
    return dispatch_readers.steps_share(ctx, "overlapped")
