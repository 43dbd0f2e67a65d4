"""The general traffic generator: closed-loop clients that one traffic
file describes.

A traffic file gives the number of clients, the cycle of query families
each client walks in fixed proportion (an entry is a family's name, or
an object with the family and the draws it pins, such as the field a
TopN sweeps), the skew of the row draws, and what the warm-up must
cover. Each client is one thread with one
keep-alive connection and its own generator, seeded from (`seed`,
client number): it sends its next request when the previous reply has
been read, so the server is offered what it can take. The server is
another process, so these threads never share a GIL with it.

Every request is kept — family, PQL, the reference thunk, send and
receive times on the monotonic clock, status and the reply's bytes —
and compared after the window, off the clock.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from harness.server import Client


class Request:
    __slots__ = ("client", "family", "pql", "ref", "t_send", "t_recv",
                 "status", "body", "late_s")

    def __init__(self, client, family, pql, ref):
        self.client = client
        self.family = family
        self.pql = pql
        self.ref = ref
        self.t_send = self.t_recv = 0.0
        self.status = None
        self.body = b""
        self.late_s = 0.0


def _entry(entry) -> tuple:
    """(family, pinned draws) of one entry of a cycle or a pinned list."""
    if isinstance(entry, str):
        return entry, {}
    return entry["family"], {k: v for k, v in entry.items()
                             if k != "family"}


def client_stream(dataset, data, traffic: dict, seed: int, client: int):
    """Endless (family, pql, reference thunk) of one client. Every seed
    walks the same cycle of families, from its own offset and with its
    own draws."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, client])
    draws = dataset.Draws({"grid_rows": data.grid_rows,
                           "n_days": data.n_days}, rng,
                          traffic.get("row_skew", 0.0))
    cycle = traffic["cycle"]
    i = int(rng.integers(0, len(cycle)))
    while True:
        family, pinned = _entry(cycle[i % len(cycle)])
        i += 1
        pql, ref = dataset.query(data, family, draws, **pinned)
        yield family, pql, ref


def pinned_stream(dataset, data, traffic: dict, seed: int):
    """The warm-up's shapes on purpose: each entry of the traffic
    file's `warmup.pinned` once (a family with some draws fixed — a
    time range's view count, say)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1 << 20])
    draws = dataset.Draws({"grid_rows": data.grid_rows,
                           "n_days": data.n_days}, rng,
                          traffic.get("row_skew", 0.0))
    for entry in traffic.get("warmup", {}).get("pinned", []):
        family, pinned = _entry(entry)
        pql, ref = dataset.query(data, family, draws, **pinned)
        yield family, pql, ref


def run_clients(port: int, path: str, streams: list, seconds: float,
                max_requests: int = 0) -> tuple:
    """Drive one closed loop per stream for `seconds` (or until each
    client has sent `max_requests`). Returns (requests, t_start, t_end)
    on the time.monotonic() clock, which cannot step; a request in
    flight at t_end is finished and kept, and counts as attempted but
    not as completed in the window."""
    gate = threading.Barrier(len(streams) + 1)
    out = [[] for _ in streams]
    t_end = [0.0]

    def worker(ci: int, stream) -> None:
        conn = Client(port, timeout=300)
        mine = out[ci]
        gate.wait()
        prev_recv = None
        try:
            for family, pql, ref in stream:
                if time.monotonic() >= t_end[0] or \
                        (max_requests and len(mine) >= max_requests):
                    break
                req = Request(ci, family, pql, ref)
                mine.append(req)
                body = pql.encode()
                req.t_send = time.monotonic()
                if prev_recv is not None:
                    req.late_s = req.t_send - prev_recv
                try:
                    req.status, req.body = conn.request(
                        "POST", path, body, "text/plain")
                except Exception as e:  # counted as a failed request
                    req.status, req.body = -1, repr(e).encode()
                prev_recv = req.t_recv = time.monotonic()
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, args=(i, s), daemon=True,
                                name=f"bench-client-{i}")
               for i, s in enumerate(streams)]
    for t in threads:
        t.start()
    t_start = time.monotonic()
    t_end[0] = t_start + seconds
    gate.wait()
    for t in threads:
        t.join()
    return [r for mine in out for r in mine], t_start, t_end[0]


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values, 0 < q <= 100."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(np.ceil(q / 100.0 * len(s))) - 1))
    return s[k]
