"""Device self time under the logits scope (the output head's matmul over the whole vocabulary and the greedy draw over its rows), share of busy in percent: sat_logits_share's twin for the open-loop cells."""

from benchmark import scopes


def reduce(ctx):
    return scopes.device_share(ctx, "logits")
