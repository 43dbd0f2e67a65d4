"""Growth of one cumulative counter over the window (a count)."""

from readers._paths import delta


def read(ctx, path):
    return delta(ctx, path)
