"""Process start to window start: loading, weights, warm-up, compilation."""


def reduce(ctx):
    return ctx.result["setup_s"]
