"""K/V bytes resident over the bytes the same sequences would hold had no block been handed back behind a window, mean over the window's forwards (the program's kv_bytes_resident / kv_bytes_unreleased)."""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.resident_ratio(ctx)
