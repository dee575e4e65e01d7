"""Largest share of the window layer group's pool in use at a forward of the window, in percent (the program's kv_g<i>_in_use of kv_g<i>_total). (the saturated cell's name)"""

from benchmark import kv_group_readers


def reduce(ctx):
    return kv_group_readers.group_peak_share(ctx, windowed=True)
