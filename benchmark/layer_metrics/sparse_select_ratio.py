"""Keys the sparse layers attended over the keys their query positions could see (the program's sparse_keys_selected / sparse_keys_live over the window's forwards): about sum min(index_topk, L) / sum L of the traffic."""

from benchmark import sparse_readers


def reduce(ctx):
    return sparse_readers.select_ratio(ctx)
