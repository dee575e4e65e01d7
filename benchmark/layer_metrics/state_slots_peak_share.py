"""Largest share of the recurrent-state slots in use at a forward of the window, in percent (the program's state_slots_used counter)."""

from benchmark import hybrid_readers


def reduce(ctx):
    return hybrid_readers.state_slots_peak_share(ctx)
