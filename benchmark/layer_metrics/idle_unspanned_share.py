"""Device idle seconds of the traced window under no ds:* span of the program, share of all idle in percent."""

from benchmark import scopes


def reduce(ctx):
    return scopes.idle_unspanned_share(ctx)
