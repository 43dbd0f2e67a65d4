"""Growth of one cumulative counter over the window, scaled (bytes to
MB); None where the program does not publish it."""

from readers._paths import delta


def read(ctx, path, scale=1.0):
    d = delta(ctx, path)
    return None if d is None else d * scale
