"""The NYC-taxi deployment: rides, loader, queries and their plain
reference.

The schema is the source's (docs/examples.md, "Transportation"): the
seven set fields cab_type, dist_miles, total_amount_dollars,
passenger_count, drop_grid_id, pickup_grid_id and
pickup_elapsed_time_of_day, distance and amount bucketed to whole miles
and dollars as there. Three named extras ride along (the
configuration's `assumed.extras` says why): `dist` and `amount` as BSI
ints and `pickup` as a YMD time field. Generator, loader and the ten
small-query shapes started as a copy of `chip_smoke.py` (PR 21:
`Rides`, `load`, `family_queries`, `burst_query`), so that the
yardstick lives with the benchmark and a later change to the program
cannot move it; the source's fields, its two typical queries and the
parameterised families the traffic files name are added here.

Every reference answer is a numpy recomputation on the arrays of
`Rides`. Nothing here imports the program except `roaring_bytes`, which
serialises the client-side import payload the way upstream's batch
importers do (`pilosa_tpu.storage` is jax-free); a wrong serialiser
would load other bits than `Rides` holds and every answer would differ.
"""

from __future__ import annotations

from datetime import date, timedelta

import numpy as np

INDEX = "taxi"
DAY0 = date(2019, 1, 1)
TOD_BUCKETS = 48
SCHEMA = 2          # a kept data directory of another schema is reloaded


class Rides:
    """The rides, as the numpy arrays every reference answer is
    recomputed from (chip_smoke.Rides, unchanged generator)."""

    def __init__(self, seed: int, n_shards: int, grid_rows: int,
                 shard_width: int, n_days: int = 28):
        rng = np.random.default_rng(seed)
        self.shard_width = shard_width
        self.n_shards = n_shards
        self.grid_rows = grid_rows
        self.n_days = n_days
        n = self.n = n_shards * shard_width
        cab = rng.integers(0, 3, n, dtype=np.uint8)      # yellow/green/fhv
        pax = rng.integers(1, 7, n, dtype=np.uint8)
        self.dist = rng.integers(0, 300, n).astype(np.int64)  # 0.1 miles
        self.amount = self.dist * 25 // 10 + rng.integers(3, 20, n)
        self.day = rng.integers(0, n_days, n, dtype=np.uint8)
        # Pickup zones are skewed in the real data (midtown dwarfs the
        # outer boroughs): Zipf(0.8) over the grid rows.
        p = 1.0 / np.arange(1, grid_rows + 1) ** 0.8
        cdf = np.cumsum(p / p.sum())
        self.grid = np.minimum(np.searchsorted(cdf, rng.random(n)),
                               grid_rows - 1).astype(np.uint16)
        # Drawn after everything above, so that those arrays are what
        # chip_smoke.Rides draws from the same seed. Drop-offs are as
        # skewed as pickups but over another order of the cells; the
        # time of day is a half-hour bucket.
        order = rng.permutation(grid_rows)
        self.drop = order[np.minimum(np.searchsorted(cdf, rng.random(n)),
                                     grid_rows - 1)].astype(np.uint16)
        self.tod = rng.integers(0, TOD_BUCKETS, n, dtype=np.uint8)
        # The source buckets distance and amount into rows: whole miles
        # (dist is tenths of a mile) and whole dollars (amount is dimes).
        self.miles = (self.dist // 10).astype(np.uint8)
        self.dollars = (self.amount // 10).astype(np.uint8)
        self.dist = self.dist.astype(np.int16)
        self.amount = self.amount.astype(np.int16)
        self.grids = {"pickup_grid_id": self.grid, "drop_grid_id": self.drop}
        self.cab_id = cab
        self.pax_id = pax
        self.cab = {r: cab == r for r in range(3)}
        self.pax = {r: pax == r for r in range(1, 7)}
        # pickup is a time field whose row is the cab type.
        self.pickup = self.cab

    def shard(self, s: int) -> slice:
        return slice(s * self.shard_width, (s + 1) * self.shard_width)

    def in_days(self, d0: int, d1: int) -> np.ndarray:
        return (self.day >= d0) & (self.day < d1)


def make(config: dict, shard_width: int) -> Rides:
    return Rides(config["data_seed"], config["shards"],
                 config["grid_rows"], shard_width, config["n_days"])


def fingerprint(config: dict, shard_width: int) -> dict:
    """What a kept data directory must have been loaded with."""
    return {"dataset": "taxi", "schema": SCHEMA, "data_seed": config["data_seed"],
            "shards": config["shards"], "grid_rows": config["grid_rows"],
            "n_days": config["n_days"], "shard_width": shard_width}


def bank_bytes(config: dict, shard_width: int = 1 << 20) -> int:
    """Bytes of one grid field's dense device bank (pickup_grid_id and
    drop_grid_id have the same rows), from its shape: slots pad to the
    next power of two above rows+1 (one zero slot), one bit per ride
    and slot."""
    slots = 1 << int(config["grid_rows"]).bit_length()
    return slots * config["shards"] * (shard_width // 8)


# ------------------------------------------------------------------ loading


def roaring_bytes(rows: np.ndarray, cols: np.ndarray,
                  shard_width: int) -> bytes:
    """Serialized roaring bitmap of (row, column-in-shard) bits for one
    shard — what POST .../import-roaring/{shard} takes."""
    from pilosa_tpu.storage import Bitmap

    b = Bitmap()
    b.direct_add_n(rows.astype(np.uint64) * np.uint64(shard_width)
                   + cols.astype(np.uint64))
    b.optimize()
    return b.write_bytes()


def load(srv, rides: Rides, log=lambda m: None) -> None:
    """Schema + data through the public routes: import-roaring for the
    set and time fields (per-view payloads, computed client-side as
    upstream's batch importers do), JSON /import for the BSI values."""
    srv.post_json(f"/index/{INDEX}", {})
    for name, opts in (
            ("cab_type", {}), ("dist_miles", {}), ("total_amount_dollars", {}),
            ("passenger_count", {}), ("drop_grid_id", {}),
            ("pickup_grid_id", {}), ("pickup_elapsed_time_of_day", {}),
            ("dist", {"type": "int", "min": 0, "max": 300}),
            ("amount", {"type": "int", "min": 0, "max": 1000}),
            ("pickup", {"type": "time", "timeQuantum": "YMD"})):
        srv.post_json(f"/index/{INDEX}/field/{name}", {"options": opts})
    sw = rides.shard_width
    cab, pax = rides.cab_id, rides.pax_id
    every = np.arange(sw)

    def put(field: str, shard: int, rows: np.ndarray,
            cols: np.ndarray = every, view: str = "standard") -> None:
        srv.request("POST", f"/index/{INDEX}/field/{field}"
                    f"/import-roaring/{shard}?view={view}",
                    roaring_bytes(rows, cols, sw),
                    "application/octet-stream")

    for s in range(rides.n_shards):
        sl = rides.shard(s)
        put("cab_type", s, cab[sl])
        put("passenger_count", s, pax[sl])
        put("dist_miles", s, rides.miles[sl])
        put("total_amount_dollars", s, rides.dollars[sl])
        put("drop_grid_id", s, rides.drop[sl])
        put("pickup_grid_id", s, rides.grid[sl])
        put("pickup_elapsed_time_of_day", s, rides.tod[sl])
        ids = list(range(sl.start, sl.stop))
        for field, vals in (("dist", rides.dist), ("amount", rides.amount)):
            srv.post_json(f"/index/{INDEX}/field/{field}/import",
                          {"columnIDs": ids, "values": vals[sl].tolist()})
        # A YMD time field keeps each bit in its standard, year, month
        # and day views.
        for view in ("standard", "standard_2019", "standard_201901"):
            put("pickup", s, cab[sl], view=view)
        day = rides.day[sl]
        for d in range(rides.n_days):
            on = np.flatnonzero(day == d)
            put("pickup", s, cab[sl][on], on,
                view=f"standard_201901{d + 1:02d}")
        log(f"loaded shard {s + 1}/{rides.n_shards}")


# ------------------------------------------------------------------ queries


def iso(day_index: int) -> str:
    return (DAY0 + timedelta(days=day_index)).isoformat()


def topn(counts: np.ndarray, n: int) -> list:
    order = np.lexsort((np.arange(len(counts)), -counts))
    return [{"id": int(r), "count": int(counts[r])}
            for r in order[:n] if counts[r] > 0]


def family_queries(r: Rides) -> list:
    """One query per family the deployment serves: (pql, expected).
    Posted once at the start of every cell's warm-up, so that the
    server holds what a server of this deployment holds."""
    grid_counts = np.bincount(r.grid, minlength=r.grid_rows)
    grid_cab0 = np.bincount(r.grid[r.cab[0]], minlength=r.grid_rows)
    groups = [{"group": [{"field": "cab_type", "rowID": c},
                         {"field": "passenger_count", "rowID": p}],
               "count": int((r.cab[c] & r.pax[p]).sum())}
              for c in range(3) for p in range(1, 7)]
    nd = r.n_days
    return [
        ("Count(Intersect(Row(cab_type=0), Row(passenger_count=2)))",
         int((r.cab[0] & r.pax[2]).sum())),
        ("Count(Row(cab_type=2))", int(r.cab[2].sum())),
        ("Count(Row(dist < 50))", int((r.dist < 50).sum())),
        ("Sum(Row(cab_type=1), field=amount)",
         {"value": int(r.amount[r.cab[1]].sum()),
          "count": int(r.cab[1].sum())}),
        ("TopN(pickup_grid_id, n=10)", topn(grid_counts, 10)),
        ("TopN(pickup_grid_id, Row(cab_type=0), n=10)", topn(grid_cab0, 10)),
        ("GroupBy(Rows(cab_type), Rows(passenger_count))",
         [g for g in groups if g["count"]]),
        (f"Count(Row(pickup=0, from='{iso(4)}', to='{iso(11)}'))",
         int((r.pickup[0] & r.in_days(4, 11)).sum())),
        (f"Count(Row(pickup=1, from='{iso(0)}', to='{iso(nd)}'))",
         int(r.pickup[1].sum())),
        # The source's two typical queries (its 25 dollars meet no
        # 5-mile ride under the assumed fare; 13 dollars do).
        ("TopN(cab_type, Intersect(Row(dist_miles=5), "
         "Row(total_amount_dollars=13)))",
         topn(np.bincount(r.cab_id[(r.miles == 5) & (r.dollars == 13)],
                          minlength=3), 3)),
        ("GroupBy(Rows(passenger_count), Rows(cab_type))",
         _groups(r, np.ones(r.n, dtype=bool))),
    ]


class Draws:
    """The parameters one request draws, all from the client's own
    generator: grid rows by the traffic file's skew (0 = uniform,
    s > 0 = Zipf(s) over row ids, YCSB's form), the rest uniform as in
    `chip_smoke.burst_query`. `force` pins single draws — the warm-up
    uses it to cover each program shape on purpose."""

    def __init__(self, r_shape: dict, rng, row_skew: float = 0.0):
        self.rng = rng
        self.grid_rows = r_shape["grid_rows"]
        self.n_days = r_shape["n_days"]
        self._cdf = None
        if row_skew > 0:
            p = 1.0 / np.arange(1, self.grid_rows + 1) ** row_skew
            self._cdf = np.cumsum(p / p.sum())

    def grid_row(self) -> int:
        if self._cdf is None:
            return int(self.rng.integers(0, self.grid_rows))
        return int(min(np.searchsorted(self._cdf, self.rng.random()),
                       self.grid_rows - 1))

    def cab(self) -> int:
        return int(self.rng.integers(0, 3))

    def pax(self) -> int:
        return int(self.rng.integers(1, 7))

    def threshold(self) -> int:
        return int(self.rng.integers(1, 300))

    def miles_dollars(self) -> tuple:
        """A whole-mile bucket and a whole-dollar bucket that rides of
        that length pay (amount = 2.5 x dist + 0.3..1.9 dollars)."""
        a = int(self.rng.integers(0, 30))
        return a, 25 * a // 10 + int(self.rng.integers(0, 5))

    def tod(self) -> int:
        return int(self.rng.integers(0, TOD_BUCKETS))

    def day_range(self, span: int = 0) -> tuple:
        """[d0, d1) inside the data's days; `span` > 0 pins d1 - d0."""
        if span:
            d0 = int(self.rng.integers(0, self.n_days + 1 - span))
            return d0, d0 + span
        d0, d1 = sorted(self.rng.choice(self.n_days + 1, 2,
                                        replace=False).tolist())
        return int(d0), int(d1)


def _count(mask) -> int:
    return int(np.count_nonzero(mask))


def _topn_filtered(r: Rides, field: str, mask: np.ndarray) -> list:
    grid = r.grids[field]
    return topn(np.bincount(grid[mask], minlength=r.grid_rows), 10)


def _groups(r: Rides, mask: np.ndarray) -> list:
    """GroupBy(Rows(passenger_count), Rows(cab_type)) under a filter:
    the non-empty groups, passenger_count-major."""
    counts = np.bincount(r.pax_id[mask].astype(np.intp) * 3
                         + r.cab_id[mask], minlength=21)
    return [{"group": [{"field": "passenger_count", "rowID": p},
                       {"field": "cab_type", "rowID": c}],
             "count": int(counts[p * 3 + c])}
            for p in range(1, 7) for c in range(3) if counts[p * 3 + c]]


def _q_count_intersect(r, d, **_):
    g, a = d.grid_row(), d.cab()
    return (f"Count(Intersect(Row(pickup_grid_id={g}), Row(cab_type={a})))",
            lambda: _count((r.grid == g) & r.cab[a]))


def _q_bsi_lt(r, d, **_):
    t = d.threshold()
    return f"Count(Row(dist < {t}))", lambda: _count(r.dist < t)


def _q_bsi_gt(r, d, **_):
    t = d.threshold()
    return (f"Count(Row(amount > {2 * t}))",
            lambda: _count(r.amount > 2 * t))


def _q_count_union(r, d, **_):
    g, b = d.grid_row(), d.pax()
    return (f"Count(Union(Row(pickup_grid_id={g}), Row(passenger_count={b})))",
            lambda: _count((r.grid == g) | r.pax[b]))


def _q_count_difference(r, d, **_):
    g, b = d.grid_row(), d.pax()
    return (f"Count(Difference(Row(passenger_count={b}), "
            f"Row(pickup_grid_id={g})))",
            lambda: _count(r.pax[b] & ~(r.grid == g)))


def _q_count_intersect_bsi(r, d, **_):
    g, t = d.grid_row(), d.threshold()
    return (f"Count(Intersect(Row(pickup_grid_id={g}), Row(dist < {t})))",
            lambda: _count((r.grid == g) & (r.dist < t)))


def _q_rows_intersect(r, d, **_):
    g, a, b = d.grid_row(), d.cab(), d.pax()
    return (f"Intersect(Row(pickup_grid_id={g}), Row(cab_type={a}), "
            f"Row(passenger_count={b}))",
            lambda: {"columns": np.flatnonzero(
                (r.grid == g) & r.cab[a] & r.pax[b]).tolist()})


def _q_sum_filtered(r, d, **_):
    g = d.grid_row()

    def ref():
        m = r.grid == g
        return {"value": int(r.amount[m].sum()), "count": _count(m)}
    return f"Sum(Row(pickup_grid_id={g}), field=amount)", ref


def _q_time_range(r, d, span=0, **_):
    a = d.cab()
    d0, d1 = d.day_range(span)
    return (f"Count(Row(pickup={a}, from='{iso(d0)}', to='{iso(d1)}'))",
            lambda: _count(r.pickup[a] & r.in_days(d0, d1)))


def _q_count_xor(r, d, same_row=False, **_):
    # Skewed draws give the same row twice now and then, which is a
    # program of another shape (one row gathered, not two): the warm-up
    # pins it.
    g1, g2 = d.grid_row(), d.grid_row()
    if same_row:
        g2 = g1
    return (f"Count(Xor(Row(pickup_grid_id={g1}), Row(pickup_grid_id={g2})))",
            lambda: _count((r.grid == g1) ^ (r.grid == g2)))


def _q_topn_cab_miles_dollars(r, d, **_):
    a, b = d.miles_dollars()
    return (f"TopN(cab_type, Intersect(Row(dist_miles={a}), "
            f"Row(total_amount_dollars={b})))",
            lambda: topn(np.bincount(
                r.cab_id[(r.miles == a) & (r.dollars == b)], minlength=3), 3))


def _q_groupby_pax_cab(r, d, **_):
    h = d.tod()
    return (f"GroupBy(Rows(passenger_count), Rows(cab_type), "
            f"filter=Row(pickup_elapsed_time_of_day={h}))",
            lambda: _groups(r, r.tod == h))


# The sweep cell's filters. `field` is the grid field whose bank the
# TopN sweeps; the traffic file pins it per entry of its cycle.


def _q_topn_dist_lt(r, d, field="pickup_grid_id", **_):
    t = d.threshold()
    return (f"TopN({field}, Row(dist < {t}), n=10)",
            lambda: _topn_filtered(r, field, r.dist < t))


def _q_topn_amount_gt(r, d, field="pickup_grid_id", **_):
    t = d.threshold()
    return (f"TopN({field}, Row(amount > {2 * t}), n=10)",
            lambda: _topn_filtered(r, field, r.amount > 2 * t))


def _q_topn_cab_dist(r, d, field="pickup_grid_id", **_):
    a, t = d.cab(), d.threshold()
    return (f"TopN({field}, Intersect(Row(cab_type={a}), "
            f"Row(dist < {t})), n=10)",
            lambda: _topn_filtered(r, field, r.cab[a] & (r.dist < t)))


def _q_topn_pickup_range(r, d, field="pickup_grid_id", span=0, **_):
    a = d.cab()
    d0, d1 = d.day_range(span)
    return (f"TopN({field}, Row(pickup={a}, from='{iso(d0)}', "
            f"to='{iso(d1)}'), n=10)",
            lambda: _topn_filtered(r, field,
                                   r.pickup[a] & r.in_days(d0, d1)))


def _q_topn_miles_dollars(r, d, field="pickup_grid_id", **_):
    a, b = d.miles_dollars()
    return (f"TopN({field}, Intersect(Row(dist_miles={a}), "
            f"Row(total_amount_dollars={b})), n=10)",
            lambda: _topn_filtered(r, field,
                                   (r.miles == a) & (r.dollars == b)))


def _q_topn_tod(r, d, field="pickup_grid_id", **_):
    h = d.tod()
    return (f"TopN({field}, Row(pickup_elapsed_time_of_day={h}), n=10)",
            lambda: _topn_filtered(r, field, r.tod == h))


# family name -> builder(rides, draws, **pinned) -> (pql, reference thunk).
# The first ten are chip_smoke.burst_query's shapes, in its order; the
# next two are the source's typical queries with their constants drawn;
# the topn_* after them are the sweep cell's filter families.
FAMILIES = {
    "count_intersect": _q_count_intersect,
    "bsi_lt": _q_bsi_lt,
    "bsi_gt": _q_bsi_gt,
    "count_union": _q_count_union,
    "count_difference": _q_count_difference,
    "count_intersect_bsi": _q_count_intersect_bsi,
    "rows_intersect": _q_rows_intersect,
    "sum_filtered": _q_sum_filtered,
    "time_range": _q_time_range,
    "count_xor": _q_count_xor,
    "topn_cab_miles_dollars": _q_topn_cab_miles_dollars,
    "groupby_pax_cab": _q_groupby_pax_cab,
    "topn_dist_lt": _q_topn_dist_lt,
    "topn_amount_gt": _q_topn_amount_gt,
    "topn_cab_dist": _q_topn_cab_dist,
    "topn_pickup_range": _q_topn_pickup_range,
    "topn_miles_dollars": _q_topn_miles_dollars,
    "topn_tod": _q_topn_tod,
}


def query(r: Rides, family: str, draws: Draws, **pinned) -> tuple:
    return FAMILIES[family](r, draws, **pinned)


def equal(got, want) -> bool:
    """The comparison that decides one answer: exact equality of the
    decoded JSON result with the numpy recomputation (limit 0)."""
    return got == want
