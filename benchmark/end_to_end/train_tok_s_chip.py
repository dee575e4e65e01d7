"""Tokens of the steps completed in the window, per second, per chip."""

from benchmark import readers


def reduce(ctx):
    return readers.train_tokens_per_s_chip(ctx)
