"""Growth of one cumulative counter over the window per completed
answer (bytes per op, transfers per op)."""

from readers._paths import delta


def read(ctx, path):
    d = delta(ctx, path)
    if d is None or ctx["completed"] <= 0:
        return None
    return d / ctx["completed"]
