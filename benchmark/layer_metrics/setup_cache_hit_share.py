"""Of the programs set-up asked the persistent compile cache for, the share it held (hits over hits + misses), in percent: 100 in a warm run."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.cache_hit_share(ctx)
