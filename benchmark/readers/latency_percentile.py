"""Percentile (nearest rank) of the request time of every request sent
in the window, client clock around the whole HTTP exchange, in ms."""

from harness.loadgen import percentile


def read(ctx, q):
    times = [(r.t_recv - r.t_send) * 1e3 for r in ctx["requests"]]
    return percentile(times, q) if times else None
