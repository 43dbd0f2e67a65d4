"""hits / (hits + misses) over the window, in percent, from two
cumulative counters of a snapshot; nothing where neither moved."""

from readers._paths import delta


def read(ctx, hits, misses):
    h, m = delta(ctx, hits), delta(ctx, misses)
    if h is None or m is None or h + m <= 0:
        return None
    return 100.0 * h / (h + m)
