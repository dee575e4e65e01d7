"""Wall seconds of set-up in which some thread of the process was building a program (the union of every trace, lower and compile interval over all threads): what set-up would save if every program were at hand."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.read(ctx, "build_wall_seconds")
