"""Mean of one of the program's cumulative histograms over the window:
delta of its sum over delta of its count (`/debug/vars`)."""

from readers._paths import dig


def read(ctx, name, scale=1.0):
    a = dig(ctx["after"], ["vars", "histograms", name])
    if a is None:
        return None
    b = dig(ctx["before"], ["vars", "histograms", name]) or \
        {"sum": 0.0, "count": 0}
    n = a["count"] - b["count"]
    return (a["sum"] - b["sum"]) / n * scale if n > 0 else None
