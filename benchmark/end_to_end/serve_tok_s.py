"""Output tokens emitted inside the window per second of window."""

from benchmark import readers


def reduce(ctx):
    return readers.serve_tokens_per_s(ctx)
