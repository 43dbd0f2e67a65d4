"""Growth of one cumulative counter over the window per unit of growth
of another, both from the same two snapshots (CPU seconds per second
of uptime = cores busy); None where the program publishes either not."""

from readers._paths import delta


def read(ctx, part, whole):
    p, w = delta(ctx, part), delta(ctx, whole)
    if p is None or w is None or w <= 0:
        return None
    return p / w
