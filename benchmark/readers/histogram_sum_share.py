"""One cumulative histogram's sum over another's, over the window, in
percent (`/debug/vars`): what share of the whole the part took."""

from readers._paths import dig


def _sum_delta(ctx, name):
    a = dig(ctx["after"], ["vars", "histograms", name])
    if a is None:
        return None
    b = dig(ctx["before"], ["vars", "histograms", name]) or {"sum": 0.0}
    return a["sum"] - b["sum"]


def read(ctx, part, whole):
    p, w = _sum_delta(ctx, part), _sum_delta(ctx, whole)
    if p is None or w is None or w <= 0:
        return None
    return 100.0 * p / w
