"""The NYC-taxi deployment on a mesh: `datasets/taxi.py`'s rides, loader,
queries and family references, with two things of its own.

**A check before the load.** A deployment whose banks are split over
several chips needs a program that prices a bank by what ONE device
holds of it. One that prices the whole array takes the streamed TopN
path for every query of `taxi-host4` (8 GiB > the 2 GiB resident limit;
~35 s a query on four chips, PR 23) and would warm up for hours. Such a
program cannot run the deployment, and it says so itself: it publishes
no per-device limits in `GET /info`. `load` reads `/info` first and
refuses it, in seconds and before a byte is loaded, so the run exits
non-zero with no result. The check is on the benchmark's side: no server
setting, switch or environment variable exists for it.

**The sweep families' references, a shard's rides at a time.**
`taxi.py` recomputes `TopN(<grid field>, <filter>)` as one expression
over all the rides: at a host's 67 M that is about half a GB of numpy
temporaries an answer (`np.bincount` widens the selected cells to
int64), allocated and freed by one thread per CPU. The four-chip
machines run the benchmark in a sandbox that takes freed memory back
more slowly than that: 16 such threads were counted at +2 GB/s beyond
their 5 GB resident and met the machine's 140 GiB about a minute into
the comparison, three runs out of three (PR 26, my chip runs; a
16.7 M-ride run is over before it matters). Here the same numpy
recomputation — the same filter expression, the same `np.bincount`, the
same `taxi.topn` — runs over one shard's rides after another and the
counts are added, so no temporary is larger than a shard (inside the
cell on four chips: 1,024 answers compared in 37 s, the machine's
available memory flat; PR 26, my chip run). The request
text and the draws are `taxi.query`'s own: its builder runs on a
recording copy of the client's generator, and what it drew
parameterises the filter below. Families without an entry in `FILTERS`
keep `taxi.py`'s reference as it is.
"""

import numpy as np

from datasets import taxi
from datasets.taxi import (  # noqa: F401
    INDEX, Draws, bank_bytes, equal, family_queries, fingerprint, make)
from harness.server import BenchFailure

LIMITS = ("topnBankBytesPerDevice", "bankBudgetBytesPerDevice")

# family -> filter(rides, drawn, shard slice) -> bool mask of that slice,
# from what `taxi.py`'s builder of the family drew (method name -> value).
FILTERS = {
    "topn_dist_lt":
        lambda r, g, sl: r.dist[sl] < g["threshold"],
    "topn_amount_gt":
        lambda r, g, sl: r.amount[sl] > 2 * g["threshold"],
    "topn_cab_dist":
        lambda r, g, sl: r.cab[g["cab"]][sl] & (r.dist[sl] < g["threshold"]),
    "topn_pickup_range":
        lambda r, g, sl: r.pickup[g["cab"]][sl]
        & (r.day[sl] >= g["day_range"][0]) & (r.day[sl] < g["day_range"][1]),
    "topn_miles_dollars":
        lambda r, g, sl: (r.miles[sl] == g["miles_dollars"][0])
        & (r.dollars[sl] == g["miles_dollars"][1]),
    "topn_tod":
        lambda r, g, sl: r.tod[sl] == g["tod"],
}

class _Recording:
    """A client's `Draws`, remembering what each method returned."""

    def __init__(self, draws):
        self._draws = draws
        self.drawn = {}

    def __getattr__(self, name):
        method = getattr(self._draws, name)

        def call(*args, **kwargs):
            value = self.drawn[name] = method(*args, **kwargs)
            return value
        return call


def _topn_by_shard(rides, field: str, family: str, drawn: dict) -> list:
    grid = rides.grids[field]
    counts = np.zeros(rides.grid_rows, dtype=np.int64)
    for s in range(rides.n_shards):
        sl = rides.shard(s)
        counts += np.bincount(grid[sl][FILTERS[family](rides, drawn, sl)],
                              minlength=rides.grid_rows)
    return taxi.topn(counts, 10)


def query(rides, family, draws, **pinned):
    """`taxi.query`'s request text and draws; the reference of a sweep
    family recomputed a shard at a time, any other as `taxi.py` has it."""
    recording = _Recording(draws)
    pql, whole = taxi.query(rides, family, recording, **pinned)
    if family not in FILTERS:
        return pql, whole
    field = pinned.get("field", "pickup_grid_id")
    return pql, lambda: _topn_by_shard(rides, field, family, recording.drawn)


def load(srv, rides, log=lambda m: None) -> None:
    limits = srv.get("/info").get("residentLimits") or {}
    missing = [k for k in LIMITS if k not in limits]
    if missing:
        raise BenchFailure(
            "the server's /info publishes no residentLimits "
            f"{missing}: this program prices a sharded bank as one array "
            "and would stream every TopN of a mesh deployment")
    log(f"resident limits per device: {limits}")
    taxi.load(srv, rides, log)
