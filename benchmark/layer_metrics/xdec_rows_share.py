"""Of the positions the traced window's chunk forwards were fed, the share that ran the layers behind the exit (the program's xdec_rows over valid_tokens), in percent."""

from benchmark import xdec_readers


def reduce(ctx):
    return xdec_readers.xdec_rows_share(ctx)
