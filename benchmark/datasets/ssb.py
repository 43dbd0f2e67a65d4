"""The Star Schema Benchmark deployment: one chip's share of LINEORDER
at SF = 10, its loader, the 13 queries and their plain reference.

The source is P. O'Neil, E. O'Neil, X. Chen, "Star Schema Benchmark",
revision 3 (2009): a fact table LINEORDER (~6,000,000 x SF rows) and the
dimensions CUSTOMER (30,000 x SF), SUPPLIER (2,000 x SF), PART (200,000
x floor(1 + log2 SF)) and DATE (2,556 days), with 13 queries in four
flights, Q1.1-Q4.3. The layout is the transposition upstream Pilosa
published for it (`pilosa/demo-ssb`): ONE COLUMN PER LINEORDER ROW, the
dimensions' attributes denormalised onto set fields of the column, the
measures as BSI int fields, `lo_revenue_computed` = extendedprice x
discount and `lo_profit` = revenue - supplycost precomputed at load. A
shard is therefore self-contained, and what a chip answers is its
partial `[group, count, sum]` table; the reference computes the same
thing over the same rows.

Data are made from `data_seed`, in bulk: the dimension tables at their
SF = 10 sizes (keys are drawn over all of them), then `grid_rows`
orders of 1-7 lines each, cut where the chip's shards are full (the
last order's remaining lines are the next chip's). Distributions are
dbgen's: uniform keys, quantity 1-50, discount 0-10, the lines of an
order share customer and date, a part's price is dbgen's function of
its key. Strings are row ids (the configuration's `assumed.row_ids`
gives the mapping).

**The reference** is numpy only and shares nothing with the program:
LINEORDER as one array per column; a query is a boolean mask over the
rows, evaluated a block of 2^20 rows at a time (sixteen-million-row
temporaries on one thread per CPU met the machine's memory limit
before: PERF.md section 6, PRs 26 and 30), the selected rows' group
keys and measures gathered, then `np.unique` over the keys, a count and
an int64 sum per group - the SQL of the specification transcribed, each
departure noted beside the family. Groups come out by ascending row ids,
child by child, which is the program's order; the specification's ORDER
BY is left to the client.

Nothing here imports the program except `pilosa_tpu.storage.Bitmap`
(jax-free), the client-side serialiser of an import payload.
"""

from __future__ import annotations

import numpy as np

from datasets.taxi import roaring_bytes
from harness.server import BenchFailure

INDEX = "ssb"
SCHEMA = 1              # a kept data directory of another schema is reloaded
BLOCK = 1 << 20         # rows a reference mask is evaluated over at a time

# SF = 10 (the specification's table of cardinalities).
CUSTOMERS, SUPPLIERS, PARTS = 300_000, 20_000, 800_000
DAYS = 2556             # the DATE table: 1992-01-01 .. 1998-12-30
ORDER_DAYS = 2406       # dbgen draws order dates up to 1998-08-02
YEARS = tuple(range(1992, 1999))

# Rows of each set field (what `Rows(field)` names), by the schema.
N_ROWS = {"d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53,
          "c_region": 5, "s_region": 5, "c_nation": 25, "s_nation": 25,
          "c_city": 250, "s_city": 250, "p_mfgr": 5, "p_category": 25,
          "p_brand1": 1000}
# The BSI int fields: declared (min, max), money in cents. A part's
# retail price is 900.00-2,099.00, a line has 1-50 of them.
INT_FIELDS = {"lo_quantity": (0, 50), "lo_discount": (0, 10),
              "lo_extendedprice": (0, 10_495_000),
              "lo_revenue": (0, 10_495_000),
              "lo_supplycost": (0, 125_940),
              "lo_profit": (-125_940, 10_495_000),
              "lo_revenue_computed": (0, 104_950_000)}


def bit_depth(field: str) -> int:
    lo, hi = INT_FIELDS[field]
    return max(1, (hi - lo).bit_length())


class Lineorder:
    """This chip's LINEORDER rows, one array a column: the dimensions'
    attributes as the row ids the set fields hold, the measures as the
    values the int fields hold."""

    def __init__(self, seed: int, n_shards: int, orders: int,
                 shard_width: int):
        rng = np.random.default_rng(seed)
        self.shard_width, self.n_shards = shard_width, n_shards
        self.grid_rows, self.n_days = orders, 0     # loadgen's names
        # Dimensions first, so that they are the same at every size.
        c_nation = rng.integers(0, 25, CUSTOMERS, dtype=np.uint8)
        c_city = c_nation * 10 + rng.integers(0, 10, CUSTOMERS, dtype=np.uint8)
        s_nation = rng.integers(0, 25, SUPPLIERS, dtype=np.uint8)
        s_city = s_nation * 10 + rng.integers(0, 10, SUPPLIERS, dtype=np.uint8)
        p_mfgr = rng.integers(0, 5, PARTS, dtype=np.uint8)
        p_category = p_mfgr * 5 + rng.integers(0, 5, PARTS, dtype=np.uint8)
        p_brand1 = p_category.astype(np.uint16) * 40 \
            + rng.integers(0, 40, PARTS, dtype=np.uint16)
        pk = np.arange(1, PARTS + 1, dtype=np.int64)
        retail = (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) \
            .astype(np.int32)           # dbgen's p_retailprice, in cents
        day = np.arange(DAYS).astype("timedelta64[D]") \
            + np.datetime64("1992-01-01")
        year = day.astype("datetime64[Y]").astype(np.int64) + 1970
        month = day.astype("datetime64[M]").astype(np.int64) % 12 + 1
        doy = (day - day.astype("datetime64[Y]")).astype(np.int64)
        # Orders, then their lines.
        lines = rng.integers(1, 8, orders)
        n = self.n = min(int(lines.sum()), n_shards * shard_width)
        order = np.repeat(np.arange(orders, dtype=np.int32), lines)[:n]
        cust = rng.integers(0, CUSTOMERS, orders, dtype=np.int32)[order]
        date = rng.integers(0, ORDER_DAYS, orders, dtype=np.int16)[order]
        del order
        part = rng.integers(0, PARTS, n, dtype=np.int32)
        supp = rng.integers(0, SUPPLIERS, n, dtype=np.int16)
        self.lo_quantity = rng.integers(1, 51, n, dtype=np.uint8)
        self.lo_discount = rng.integers(0, 11, n, dtype=np.uint8)
        self.c_nation, self.c_city = c_nation[cust], c_city[cust]
        self.c_region = self.c_nation // 5
        self.s_nation, self.s_city = s_nation[supp], s_city[supp]
        self.s_region = self.s_nation // 5
        self.p_mfgr, self.p_category = p_mfgr[part], p_category[part]
        self.p_brand1 = p_brand1[part]
        self.d_year = year[date].astype(np.uint16)
        self.d_yearmonthnum = (year * 100 + month)[date].astype(np.uint32)
        self.d_weeknuminyear = (doy // 7 + 1)[date].astype(np.uint8)
        price = retail[part]
        self.lo_extendedprice = price * self.lo_quantity
        self.lo_revenue = (self.lo_extendedprice.astype(np.int64)
                           * (100 - self.lo_discount) // 100) \
            .astype(np.int32)
        self.lo_supplycost = 6 * price // 10    # dbgen: 6/10 of the price
        self.lo_profit = self.lo_revenue - self.lo_supplycost
        self.lo_revenue_computed = self.lo_extendedprice * self.lo_discount

    def column(self, name: str) -> np.ndarray:
        return getattr(self, name)


def make(config: dict, shard_width: int) -> Lineorder:
    return Lineorder(config["data_seed"], config["shards"],
                     config["grid_rows"], shard_width)


def fingerprint(config: dict, shard_width: int) -> dict:
    """What a kept data directory must have been loaded with."""
    return {"dataset": "ssb", "schema": SCHEMA,
            "data_seed": config["data_seed"], "shards": config["shards"],
            "orders": config["grid_rows"], "shard_width": shard_width}


# ------------------------------------------------------------------ loading

PROBE = "ssb_aggregate_probe"


def refuse_no_aggregate(srv) -> None:
    """Before a byte is loaded: three columns in a scratch index, two
    of them in one group, asked for with `aggregate=Sum(field=v)`. A
    server that parses the argument and drops it answers 200 with
    counts alone; every GroupBy of this deployment would then read not
    correct after minutes of load, so it is refused here, in seconds, by
    its own answer."""
    srv.post_json(f"/index/{PROBE}", {})
    srv.post_json(f"/index/{PROBE}/field/g", {"options": {}})
    srv.post_json(f"/index/{PROBE}/field/v",
                  {"options": {"type": "int", "min": -10, "max": 10}})
    srv.request("POST", f"/index/{PROBE}/query",
                b"Set(0, g=1) Set(1, g=1) Set(2, g=2) "
                b"Set(0, v=-3) Set(1, v=5) Set(2, v=7)", "text/plain")
    pql = "GroupBy(Rows(g), aggregate=Sum(field=v))"
    got = srv.query(PROBE, pql)
    srv.request("DELETE", f"/index/{PROBE}")
    want = [{"group": [{"field": "g", "rowID": 1}], "count": 2, "sum": 2},
            {"group": [{"field": "g", "rowID": 2}], "count": 1, "sum": 7}]
    if got != want:
        raise BenchFailure(
            f"{pql} over three columns answers {got}, not {want}: this "
            "server has no GroupBy aggregate, the operator of ten of the "
            "deployment's thirteen queries")


def planes_bytes(values: np.ndarray, depth: int, shard_width: int) -> bytes:
    """Serialized roaring bitmap of one shard of a BSI view, from the
    base values (value - min) of its first `len(values)` columns: row i
    is bit plane i, row `depth` the not-null plane - the layout
    `/import` with `values` writes. Built from packed bits, a plane at
    a time: positions would cost a sort of thirteen million."""
    from pilosa_tpu.storage import Bitmap

    b = Bitmap()
    pad = np.zeros(shard_width, dtype=bool)
    for i in range(depth + 1):
        pad[:len(values)] = ((values >> i) & 1).astype(bool) \
            if i < depth else True
        b.set_dense_range(i * shard_width, np.packbits(
            pad, bitorder="little").view(np.uint64))
    b.optimize()
    return b.write_bytes()


def _groupsum_launches(srv) -> int:
    return srv.get("/debug/vars")["counters"].get(
        "executor.groupsum_launches", 0)


def load(srv, lo: Lineorder, log=lambda m: None) -> None:
    """Schema + data through the public routes: every field, set and
    int, as import-roaring payloads of a shard (an int field's into its
    `bsig_` view), computed client-side as upstream's batch importers
    do. Then the specification's Q2.1 once: a server whose group-sum
    counter does not move for it answers some other way than the
    deployment measures, and the run ends here."""
    refuse_no_aggregate(srv)
    srv.post_json(f"/index/{INDEX}", {})
    for name in N_ROWS:
        srv.post_json(f"/index/{INDEX}/field/{name}", {"options": {}})
    for name, (lo_v, hi_v) in INT_FIELDS.items():
        srv.post_json(f"/index/{INDEX}/field/{name}",
                      {"options": {"type": "int", "min": lo_v, "max": hi_v}})
    sw = lo.shard_width
    for s in range(-(-lo.n // sw)):
        sl = slice(s * sw, min(lo.n, (s + 1) * sw))
        cols = np.arange(sl.stop - sl.start)
        for name in N_ROWS:
            srv.request(
                "POST", f"/index/{INDEX}/field/{name}/import-roaring/{s}",
                roaring_bytes(lo.column(name)[sl], cols, sw),
                "application/octet-stream")
        for name, (lo_v, _) in INT_FIELDS.items():
            srv.request(
                "POST", f"/index/{INDEX}/field/{name}/import-roaring/{s}"
                f"?view=bsig_{name}",
                planes_bytes(lo.column(name)[sl].astype(np.int64) - lo_v,
                             bit_depth(name), sw),
                "application/octet-stream")
        log(f"loaded shard {s + 1}/{-(-lo.n // sw)}")
    before = _groupsum_launches(srv)
    pql, want = family_queries(lo)[3]       # Q2.1
    got = srv.query(INDEX, pql)
    if _groupsum_launches(srv) <= before:
        raise BenchFailure(
            f"executor.groupsum_launches did not move for {pql}: this "
            "server does not compute the group sums on the device")
    if not equal(got, want):
        raise BenchFailure(f"{pql} after the load: server {str(got)[:200]} "
                           f"reference {str(want)[:200]}")


# ------------------------------------------------------------------ queries


def _row(field: str, row: int) -> str:
    return f"Row({field}={row})"


def _any(field: str, rows) -> str:
    rows = list(rows)
    if len(rows) == 1:
        return _row(field, rows[0])
    return "Union(" + ", ".join(_row(field, r) for r in rows) + ")"


def _isin(col: np.ndarray, rows) -> np.ndarray:
    rows = list(rows)
    m = col == rows[0]
    for r in rows[1:]:
        m |= col == r
    return m


class Family:
    """One of the 13 queries: its SQL (the specification's, constants
    as names), its PQL, the rows of LINEORDER it selects, and what it
    returns - a `Sum` over `measure` (flight 1, `groups` empty) or a
    GroupBy of `groups` with the sum of `measure` a group.

    `rows(c)` names the set-field rows the filter reads, as (field,
    row) pairs, and `ranges` the int fields its range conditions read:
    `least_bytes` counts operand rows from them."""

    def __init__(self, sql, measure, groups, draw, fixed, where, mask,
                 rows, ranges=()):
        self.sql, self.measure, self.groups = sql, measure, groups
        self.draw, self.fixed, self.where = draw, fixed, where
        self.mask, self.rows, self.ranges = mask, rows, ranges

    def pql(self, c: dict) -> str:
        if not self.groups:
            return f"Sum({self.where(c)}, field={self.measure})"
        return ("GroupBy(" + ", ".join(f"Rows({g})" for g in self.groups)
                + f", filter={self.where(c)}, "
                f"aggregate=Sum(field={self.measure}))")


def _years(c) -> range:
    return range(c["year"], c["year"] + c["years"])


FAMILIES = {
    # Flight 1: restrictions on the fact table and DATE, one number.
    "q1.1": Family(
        "select sum(lo_extendedprice*lo_discount) as revenue from "
        "lineorder, date where lo_orderdate = d_datekey and d_year = "
        "[YEAR] and lo_discount between [D] and [D]+2 and lo_quantity < [Q]",
        "lo_revenue_computed", (),
        lambda d: {"year": d.year(), "discount": d.integer(0, 8),
                   "quantity": d.integer(10, 40)},
        {"year": 1993, "discount": 1, "quantity": 25},
        lambda c: ("Intersect(" + _row("d_year", c["year"])
                   + f", Row(lo_discount >< [{c['discount']}, "
                   f"{c['discount'] + 2}]), "
                   f"Row(lo_quantity < {c['quantity']}))"),
        lambda lo, sl, c: (lo.d_year[sl] == c["year"])
        & (lo.lo_discount[sl] >= c["discount"])
        & (lo.lo_discount[sl] <= c["discount"] + 2)
        & (lo.lo_quantity[sl] < c["quantity"]),
        lambda c: {("d_year", c["year"])},
        ("lo_discount", "lo_quantity")),
    "q1.2": Family(
        "... d_yearmonthnum = [YEARMONTH] and lo_discount between [D] and "
        "[D]+2 and lo_quantity between [Q] and [Q]+9",
        "lo_revenue_computed", (),
        lambda d: {"yearmonth": d.yearmonth(), "discount": d.integer(0, 8),
                   "quantity": d.integer(1, 41)},
        {"yearmonth": 199401, "discount": 4, "quantity": 26},
        lambda c: ("Intersect(" + _row("d_yearmonthnum", c["yearmonth"])
                   + f", Row(lo_discount >< [{c['discount']}, "
                   f"{c['discount'] + 2}]), Row(lo_quantity >< "
                   f"[{c['quantity']}, {c['quantity'] + 9}]))"),
        lambda lo, sl, c: (lo.d_yearmonthnum[sl] == c["yearmonth"])
        & (lo.lo_discount[sl] >= c["discount"])
        & (lo.lo_discount[sl] <= c["discount"] + 2)
        & (lo.lo_quantity[sl] >= c["quantity"])
        & (lo.lo_quantity[sl] <= c["quantity"] + 9),
        lambda c: {("d_yearmonthnum", c["yearmonth"])},
        ("lo_discount", "lo_quantity")),
    "q1.3": Family(
        "... d_weeknuminyear = [WEEK] and d_year = [YEAR] and lo_discount "
        "between [D] and [D]+2 and lo_quantity between [Q] and [Q]+9",
        "lo_revenue_computed", (),
        lambda d: {"week": d.integer(1, 52), "year": d.year(),
                   "discount": d.integer(0, 8), "quantity": d.integer(1, 41)},
        {"week": 6, "year": 1994, "discount": 5, "quantity": 26},
        lambda c: ("Intersect(" + _row("d_weeknuminyear", c["week"]) + ", "
                   + _row("d_year", c["year"])
                   + f", Row(lo_discount >< [{c['discount']}, "
                   f"{c['discount'] + 2}]), Row(lo_quantity >< "
                   f"[{c['quantity']}, {c['quantity'] + 9}]))"),
        lambda lo, sl, c: (lo.d_weeknuminyear[sl] == c["week"])
        & (lo.d_year[sl] == c["year"])
        & (lo.lo_discount[sl] >= c["discount"])
        & (lo.lo_discount[sl] <= c["discount"] + 2)
        & (lo.lo_quantity[sl] >= c["quantity"])
        & (lo.lo_quantity[sl] <= c["quantity"] + 9),
        lambda c: {("d_weeknuminyear", c["week"]), ("d_year", c["year"])},
        ("lo_discount", "lo_quantity")),
    # Flight 2: revenue by year and brand, for a class of parts and the
    # suppliers of one region.
    "q2.1": Family(
        "select sum(lo_revenue), d_year, p_brand1 from lineorder, date, "
        "part, supplier where ... p_category = [CATEGORY] and s_region = "
        "[REGION] group by d_year, p_brand1",
        "lo_revenue", ("d_year", "p_brand1"),
        lambda d: {"category": d.integer(0, 24), "region": d.integer(0, 4)},
        {"category": 1, "region": 1},       # 'MFGR#12', 'AMERICA'
        lambda c: ("Intersect(" + _row("p_category", c["category"]) + ", "
                   + _row("s_region", c["region"]) + ")"),
        lambda lo, sl, c: (lo.p_category[sl] == c["category"])
        & (lo.s_region[sl] == c["region"]),
        lambda c: {("p_category", c["category"]),
                   ("s_region", c["region"])}),
    "q2.2": Family(
        "... p_brand1 between [BRAND] and [BRAND]+7 and s_region = [REGION] "
        "group by d_year, p_brand1",
        "lo_revenue", ("d_year", "p_brand1"),
        lambda d: {"brand": d.integer(0, 24) * 40 + d.integer(0, 32),
                   "region": d.integer(0, 4)},
        {"brand": 260, "region": 2},        # 'MFGR#2221'..'2228', 'ASIA'
        lambda c: ("Intersect("
                   + _any("p_brand1", range(c["brand"], c["brand"] + 8))
                   + ", " + _row("s_region", c["region"]) + ")"),
        lambda lo, sl, c: (lo.p_brand1[sl] >= c["brand"])
        & (lo.p_brand1[sl] < c["brand"] + 8)
        & (lo.s_region[sl] == c["region"]),
        lambda c: {("p_brand1", b)
                   for b in range(c["brand"], c["brand"] + 8)}
        | {("s_region", c["region"])}),
    "q2.3": Family(
        "... p_brand1 = [BRAND] and s_region = [REGION] group by d_year, "
        "p_brand1",
        "lo_revenue", ("d_year", "p_brand1"),
        lambda d: {"brand": d.integer(0, 999), "region": d.integer(0, 4)},
        {"brand": 260, "region": 3},        # 'MFGR#2221', 'EUROPE'
        lambda c: ("Intersect(" + _row("p_brand1", c["brand"]) + ", "
                   + _row("s_region", c["region"]) + ")"),
        lambda lo, sl, c: (lo.p_brand1[sl] == c["brand"])
        & (lo.s_region[sl] == c["region"]),
        lambda c: {("p_brand1", c["brand"]), ("s_region", c["region"])}),
    # Flight 3: revenue between customers and suppliers of a place, by
    # year, the place narrowing from region to city to one month.
    "q3.1": Family(
        "select c_nation, s_nation, d_year, sum(lo_revenue) from customer, "
        "lineorder, supplier, date where ... c_region = [CREGION] and "
        "s_region = [SREGION] and d_year >= [YEAR] and d_year <= [YEAR]+5 "
        "group by c_nation, s_nation, d_year",
        "lo_revenue", ("c_nation", "s_nation", "d_year"),
        lambda d: {"c_region": d.integer(0, 4), "s_region": d.integer(0, 4),
                   "year": d.integer(1992, 1993), "years": 6},
        {"c_region": 2, "s_region": 2, "year": 1992, "years": 6},   # ASIA
        lambda c: ("Intersect(" + _row("c_region", c["c_region"]) + ", "
                   + _row("s_region", c["s_region"]) + ", "
                   + _any("d_year", _years(c)) + ")"),
        lambda lo, sl, c: (lo.c_region[sl] == c["c_region"])
        & (lo.s_region[sl] == c["s_region"])
        & (lo.d_year[sl] >= c["year"])
        & (lo.d_year[sl] < c["year"] + c["years"]),
        lambda c: {("c_region", c["c_region"]), ("s_region", c["s_region"])}
        | {("d_year", y) for y in _years(c)}),
    "q3.2": Family(
        "... c_nation = [CNATION] and s_nation = [SNATION] and d_year "
        "between [YEAR] and [YEAR]+5 group by c_city, s_city, d_year",
        "lo_revenue", ("c_city", "s_city", "d_year"),
        lambda d: {"c_nation": d.integer(0, 24), "s_nation": d.integer(0, 24),
                   "year": d.integer(1992, 1993), "years": 6},
        {"c_nation": 9, "s_nation": 9, "year": 1992, "years": 6},
        lambda c: ("Intersect(" + _row("c_nation", c["c_nation"]) + ", "
                   + _row("s_nation", c["s_nation"]) + ", "
                   + _any("d_year", _years(c)) + ")"),
        lambda lo, sl, c: (lo.c_nation[sl] == c["c_nation"])
        & (lo.s_nation[sl] == c["s_nation"])
        & (lo.d_year[sl] >= c["year"])
        & (lo.d_year[sl] < c["year"] + c["years"]),
        lambda c: {("c_nation", c["c_nation"]), ("s_nation", c["s_nation"])}
        | {("d_year", y) for y in _years(c)}),
    "q3.3": Family(
        "... (c_city = [CCITY1] or c_city = [CCITY2]) and (s_city = "
        "[SCITY1] or s_city = [SCITY2]) and d_year between [YEAR] and "
        "[YEAR]+5 group by c_city, s_city, d_year",
        "lo_revenue", ("c_city", "s_city", "d_year"),
        lambda d: {"c_cities": d.two_cities(), "s_cities": d.two_cities(),
                   "year": d.integer(1992, 1993), "years": 6},
        {"c_cities": [191, 195], "s_cities": [191, 195],    # UNITED KI1, KI5
         "year": 1992, "years": 6},
        lambda c: ("Intersect(" + _any("c_city", c["c_cities"]) + ", "
                   + _any("s_city", c["s_cities"]) + ", "
                   + _any("d_year", _years(c)) + ")"),
        lambda lo, sl, c: _isin(lo.c_city[sl], c["c_cities"])
        & _isin(lo.s_city[sl], c["s_cities"])
        & (lo.d_year[sl] >= c["year"])
        & (lo.d_year[sl] < c["year"] + c["years"]),
        lambda c: {("c_city", r) for r in c["c_cities"]}
        | {("s_city", r) for r in c["s_cities"]}
        | {("d_year", y) for y in _years(c)}),
    "q3.4": Family(
        "... the cities of Q3.3 and d_yearmonth = [YEARMONTH] group by "
        "c_city, s_city, d_year",
        "lo_revenue", ("c_city", "s_city", "d_year"),
        lambda d: {"c_cities": d.two_cities(), "s_cities": d.two_cities(),
                   "yearmonth": d.yearmonth()},
        {"c_cities": [191, 195], "s_cities": [191, 195],
         "yearmonth": 199712},              # 'Dec1997'
        lambda c: ("Intersect(" + _any("c_city", c["c_cities"]) + ", "
                   + _any("s_city", c["s_cities"]) + ", "
                   + _row("d_yearmonthnum", c["yearmonth"]) + ")"),
        lambda lo, sl, c: _isin(lo.c_city[sl], c["c_cities"])
        & _isin(lo.s_city[sl], c["s_cities"])
        & (lo.d_yearmonthnum[sl] == c["yearmonth"]),
        lambda c: {("c_city", r) for r in c["c_cities"]}
        | {("s_city", r) for r in c["s_cities"]}
        | {("d_yearmonthnum", c["yearmonth"])}),
    # Flight 4: profit (revenue - supplycost, precomputed) drilling from
    # region to nation and category to city and brand.
    "q4.1": Family(
        "select d_year, c_nation, sum(lo_revenue - lo_supplycost) from ... "
        "where c_region = [REGION] and s_region = [REGION] and (p_mfgr = "
        "[MFGR1] or p_mfgr = [MFGR2]) group by d_year, c_nation",
        "lo_profit", ("d_year", "c_nation"),
        lambda d: {"region": d.integer(0, 4), "mfgrs": d.two_of(5)},
        {"region": 1, "mfgrs": [0, 1]},     # AMERICA, MFGR#1 / MFGR#2
        lambda c: ("Intersect(" + _row("c_region", c["region"]) + ", "
                   + _row("s_region", c["region"]) + ", "
                   + _any("p_mfgr", c["mfgrs"]) + ")"),
        lambda lo, sl, c: (lo.c_region[sl] == c["region"])
        & (lo.s_region[sl] == c["region"])
        & _isin(lo.p_mfgr[sl], c["mfgrs"]),
        lambda c: {("c_region", c["region"]), ("s_region", c["region"])}
        | {("p_mfgr", m) for m in c["mfgrs"]}),
    "q4.2": Family(
        "... and (d_year = [YEAR] or d_year = [YEAR]+1) ... group by "
        "d_year, s_nation, p_category",
        "lo_profit", ("d_year", "s_nation", "p_category"),
        lambda d: {"region": d.integer(0, 4), "mfgrs": d.two_of(5),
                   "year": d.integer(1992, 1997), "years": 2},
        {"region": 1, "mfgrs": [0, 1], "year": 1997, "years": 2},
        lambda c: ("Intersect(" + _row("c_region", c["region"]) + ", "
                   + _row("s_region", c["region"]) + ", "
                   + _any("d_year", _years(c)) + ", "
                   + _any("p_mfgr", c["mfgrs"]) + ")"),
        lambda lo, sl, c: (lo.c_region[sl] == c["region"])
        & (lo.s_region[sl] == c["region"])
        & (lo.d_year[sl] >= c["year"])
        & (lo.d_year[sl] < c["year"] + c["years"])
        & _isin(lo.p_mfgr[sl], c["mfgrs"]),
        lambda c: {("c_region", c["region"]), ("s_region", c["region"])}
        | {("d_year", y) for y in _years(c)}
        | {("p_mfgr", m) for m in c["mfgrs"]}),
    "q4.3": Family(
        "... c_region = [REGION] and s_nation = [NATION] and (d_year = "
        "[YEAR] or d_year = [YEAR]+1) and p_category = [CATEGORY] group by "
        "d_year, s_city, p_brand1",
        "lo_profit", ("d_year", "s_city", "p_brand1"),
        lambda d: {"region": d.integer(0, 4), "nation": d.integer(0, 24),
                   "category": d.integer(0, 24),
                   "year": d.integer(1992, 1997), "years": 2},
        {"region": 1, "nation": 9, "category": 3,   # UNITED STATES, MFGR#14
         "year": 1997, "years": 2},
        lambda c: ("Intersect(" + _row("c_region", c["region"]) + ", "
                   + _row("s_nation", c["nation"]) + ", "
                   + _any("d_year", _years(c)) + ", "
                   + _row("p_category", c["category"]) + ")"),
        lambda lo, sl, c: (lo.c_region[sl] == c["region"])
        & (lo.s_nation[sl] == c["nation"])
        & (lo.d_year[sl] >= c["year"])
        & (lo.d_year[sl] < c["year"] + c["years"])
        & (lo.p_category[sl] == c["category"]),
        lambda c: {("c_region", c["region"]), ("s_nation", c["nation"]),
                   ("p_category", c["category"])}
        | {("d_year", y) for y in _years(c)}),
}


def answer(lo: Lineorder, family: str, c: dict):
    """The plain recomputation of one query over this chip's rows: a
    mask a block, the selected rows' keys and measures, then a count
    and an int64 sum - one (flight 1: `{"value", "count"}`) or one a
    group, groups by ascending row ids child by child."""
    fam = FAMILIES[family]
    measure = lo.column(fam.measure)
    keys, vals = [], []
    for b0 in range(0, lo.n, BLOCK):
        sl = slice(b0, min(lo.n, b0 + BLOCK))
        on = np.flatnonzero(fam.mask(lo, sl, c))
        vals.append(measure[sl][on].astype(np.int64))
        key = np.zeros(len(on), dtype=np.int64)
        for g in fam.groups:    # row ids stay under 2^20: 20 bits a child
            key = (key << 20) | lo.column(g)[sl][on]
        keys.append(key)
    keys, vals = np.concatenate(keys), np.concatenate(vals)
    if not fam.groups:
        return {"value": int(vals.sum()), "count": len(vals)}
    uniq, inverse, counts = np.unique(keys, return_inverse=True,
                                      return_counts=True)
    sums = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(sums, inverse, vals)
    out = []
    for key, n, total in zip(uniq.tolist(), counts.tolist(), sums.tolist()):
        rows = [(key >> (20 * i)) & 0xFFFFF
                for i in reversed(range(len(fam.groups)))]
        out.append({"group": [{"field": g, "rowID": r}
                              for g, r in zip(fam.groups, rows)],
                    "count": n, "sum": total})
    return out


class Reference:
    """One request's reference, computed when the comparison asks for
    it; it keeps the family and the constants for `least_bytes`."""

    def __init__(self, lo, family, constants):
        self.lo, self.family, self.constants = lo, family, constants

    def __call__(self):
        return answer(self.lo, self.family, self.constants)


def family_queries(lo: Lineorder) -> list:
    """The specification's 13 queries with its own constants: (pql,
    expected). Posted at the start of every warm-up and compared."""
    return [(fam.pql(fam.fixed), answer(lo, name, fam.fixed))
            for name, fam in FAMILIES.items()]


class Draws:
    """What one request draws, from the client's own generator: each
    constant uniform over its column's domain (`row_skew` 0: SSB's keys
    are uniform)."""

    def __init__(self, shape: dict, rng, row_skew: float = 0.0):
        self.rng = rng

    def integer(self, lo: int, hi: int) -> int:
        return int(self.rng.integers(lo, hi + 1))

    def year(self) -> int:
        return self.integer(YEARS[0], YEARS[-1])

    def yearmonth(self) -> int:
        return self.year() * 100 + self.integer(1, 12)

    def two_of(self, n: int) -> list:
        return sorted(self.rng.choice(n, 2, replace=False).tolist())

    def two_cities(self) -> list:
        """Two cities of one nation, as the specification's are."""
        return [self.integer(0, 24) * 10 + d for d in self.two_of(10)]


def query(lo: Lineorder, family: str, draws: Draws, **pinned) -> tuple:
    """One request: the family's constants drawn, except those an
    entry of the traffic file pins (the warm-up pins the year pair that
    ends in 1998, a short year: its groups are fewer, and so is the
    size of a launch)."""
    fam = FAMILIES[family]
    c = dict(fam.draw(draws), **pinned)
    return fam.pql(c), Reference(lo, family, c)


def equal(got, want) -> bool:
    """The comparison that decides one answer: exact equality of the
    decoded JSON result with the recomputation - every group, its count
    and its sum, in order (limit 0)."""
    return got == want


# ------------------------------------------------------------------ bytes


def operand_rows(family: str, c: dict) -> int:
    """Distinct operand rows the text of one query names: every row of
    each field a `Rows()` child names, every other row the filter
    names, the planes of each int field a range condition reads and of
    the aggregated field - each field's bit planes and its not-null
    plane (this program offset-encodes a signed field: there is no sign
    plane) - each once."""
    fam = FAMILIES[family]
    rows = sum(N_ROWS[g] for g in fam.groups)
    rows += sum(1 for f, _ in fam.rows(c) if f not in fam.groups)
    return rows + sum(bit_depth(f) + 1
                      for f in {*fam.ranges, fam.measure})


def least_bytes(family: str, c: dict, config: dict) -> int:
    """The bytes an answer cannot be computed without reading, whatever
    computes it: its distinct operand rows, one bit a column of this
    chip's shards each, read once."""
    return operand_rows(family, c) * config["shards"] \
        * config["shard_width"] // 8
