"""Context positions whose K/V the expanded path rebuilt over the prompt positions prefilled, over the window's forwards (the program's latent_rows_expanded and prefill_tokens counters)."""

from benchmark import latent_readers


def reduce(ctx):
    return latent_readers.expand_ratio(ctx)
