"""The NYC-taxi index on the chip that holds the OPEN shard: `taxi.py`'s
rides, schema and plain reference, served while rides arrive by `Set`.

Column ids are ride ids and grow, so in the 1,024-shard deployment
every arriving ride lands in the newest shard. This is the chip that
holds it: the configuration's shards, the last one half full. **Loaded**
are the rides below `n_loaded` (all of January 2019, `taxi.Rides`' own
draw). **Arriving** are columns of the last shard's upper half, each
with the generator's ten values for that column, dated 2019-02-01 (the
open day).

The rule of this yardstick: **the expected answer of every compared
request is a pure function of `data_seed` and that request's text** —
the same whatever other clients, earlier runs in the directory, the
warm-up, another tree or an aborted run have written. Nothing outside
the server's data directory carries anything from request to request
or from run to run (PR 37 kept a log of the writes it sent beside the
data directory, and the two fell out of step).

- *Reports* (the reads) are `TopN(<grid field>, Row(pickup=a, from, to)
  [∧ filter], n=10)` over a period inside January: no arriving ride is
  in any answer, while the sweep reads banks that arriving rides keep
  patching and the filter's leaves hold their bits too. Reference:
  `taxi._topn_filtered` over the loaded rides (an arriving column's
  `day` is `OPEN_DAY`, outside every period).
- *Inserts* are `Set(c, f=v)`, one bit a request (the harness decodes
  one result a request), so a ride is ten requests. The arriving rides
  are ONE stream on the run's data object (`Arrivals`): a `ride_set`
  slot of any client takes the stream's next field. Compared: HTTP 200
  and a JSON boolean — `changed` is false where an earlier run set the
  same bit.
- *Which columns arrive*: only candidates whose six set-field values
  other than the pickup cell no other candidate shares, in an order
  drawn from a nonce of the process, NOT from `--seed`: a run's `Set`s
  are real writes whichever runs came before it in the directory.
  Nothing compared depends on the order.
- *Read-backs* go through the sweep path. A ride has arrived when each
  client that sent one of its ten `Set`s has asked for its next request
  (closed loop: it has then read the reply; a stream's last request is
  generated and never sent, and such a ride never arrives). The ride's
  own six values and the open day select it alone, so `TopN(
  pickup_grid_id, RIDE)`, `Sum(RIDE, field=amount)` and `Sum(RIDE,
  field=dist)` are exact whatever else arrived: all ten fields of an
  acknowledged ride are read back.
- *Durability*: `load()` ends by inserting `LOADER_RIDES` candidates
  through `Set`; the harness then stops and re-opens the directory,
  and EVERY run's `family_queries` read eight of them back.

Two refusals, as `chem.py`'s and `ssb.py`'s: before any data is sent a
server whose `/debug/vars` has no `executor.bank_patches` (it cannot
say what a write cost the next read); after the loader's rides one
whose `executor.bank_patches` did not move on the first read-back.
"""

from __future__ import annotations

import collections
import os
import threading
import time

import numpy as np

from datasets import taxi
from datasets.taxi import INDEX, bank_bytes, iso  # noqa: F401
from harness.server import BenchFailure

SCHEMA = 1
OPEN_DAY = 255                  # `day` of a column that is not loaded
OPEN_DATE = "2019-02-01"        # the day arriving rides are dated
OPEN_NEXT = "2019-02-02"
LOADER_RIDES = 64
LOADER_READBACKS = 8
GRID_FIELDS = ("pickup_grid_id", "drop_grid_id")
# A ride's ten Sets, in the order the stream hands them out.
RIDE_FIELDS = ("cab_type", "dist_miles", "total_amount_dollars",
               "passenger_count", "drop_grid_id", "pickup_grid_id",
               "pickup_elapsed_time_of_day", "dist", "amount", "pickup")
READBACK_FORMS = ("topn", "sum_amount", "sum_dist")
PATCH_COUNTER = "executor.bank_patches"


class _Boolean:
    """What a `Set` must answer: any JSON boolean."""

    def __repr__(self):
        return "<a JSON boolean>"


BOOLEAN = _Boolean()


def equal(got, want) -> bool:
    """`taxi.equal` (exact, limit 0), and for a `Set` a JSON boolean:
    `changed` depends on what earlier runs wrote and is not compared."""
    if want is BOOLEAN:
        return isinstance(got, bool)
    return got == want


def n_loaded(config_or_rides) -> int:
    """Rides loaded: every shard but the upper half of the last."""
    if isinstance(config_or_rides, dict):
        return config_or_rides["shards"] * config_or_rides["shard_width"] \
            - config_or_rides["shard_width"] // 2
    return config_or_rides.n - config_or_rides.shard_width // 2


def candidates(r) -> np.ndarray:
    """Columns that may arrive, ascending: those of the open half whose
    (cab, drop cell, miles, dollars, passengers, half-hour) no other
    column of the open half shares."""
    lo = n_loaded(r)
    key = np.zeros(r.n - lo, dtype=np.int64)
    for values, base in ((r.cab_id, 3), (r.drop, r.grid_rows),
                         (r.miles, 256), (r.dollars, 256),
                         (r.pax_id, 8), (r.tod, taxi.TOD_BUCKETS)):
        key = key * base + values[lo:]
    _, first, counts = np.unique(key, return_index=True, return_counts=True)
    return lo + np.sort(first[counts == 1])


class Arrivals:
    """The one stream of arriving rides, shared by every client of the
    run: which field of which ride a `ride_set` slot sends next, which
    rides have arrived, which of them is read back next."""

    def __init__(self, r, nonce):
        cand = candidates(r)
        self.candidate_share = len(cand) / (r.n - n_loaded(r))
        # The loader's rides: spread over the candidates, the first and
        # the last among them, so that the open day's views are as wide
        # as the shard from the first run on.
        picks = np.unique(np.linspace(0, len(cand) - 1,
                                      LOADER_RIDES).astype(np.int64))
        self.loader = cand[picks]
        rest = np.delete(cand, picks)
        self.order = rest[np.random.default_rng(nonce).permutation(len(rest))]
        self.lock = threading.Lock()
        self.at = 0                     # fields handed out so far
        self.pending = {}               # column -> Sets not yet acknowledged
        self.arrived = collections.deque()
        self.readbacks = 0

    def next_field(self, draws) -> tuple:
        """(column, field) of the next `Set`, charged to the client
        whose generator `draws` is until it asks again."""
        with self.lock:
            ride, k = divmod(self.at, len(RIDE_FIELDS))
            self.at += 1
            col = int(self.order[ride % len(self.order)])
            self.pending.setdefault(col, len(RIDE_FIELDS))
            draws.unacknowledged.append(col)
            return col, RIDE_FIELDS[k]

    def asked(self, draws) -> None:
        """`draws`' client asks for its next request: it has read the
        reply to everything it sent."""
        if not draws.unacknowledged:
            return
        with self.lock:
            for col in draws.unacknowledged:
                left = self.pending[col] = self.pending[col] - 1
                if left == 0:
                    del self.pending[col]
                    self.arrived.append(col)
            draws.unacknowledged = []

    def next_readback(self, loader: bool) -> tuple:
        """(column or None, form): the oldest arrived ride not yet read
        back — or one of the loader's, in turn."""
        with self.lock:
            k = self.readbacks
            if loader:
                self.readbacks += 1
                return int(self.loader[k % len(self.loader)]), \
                    READBACK_FORMS[k % len(READBACK_FORMS)]
            if not self.arrived:
                return None, None
            self.readbacks += 1
            return self.arrived.popleft(), \
                READBACK_FORMS[k % len(READBACK_FORMS)]


def make(config: dict, shard_width: int, nonce=None):
    """`taxi.Rides`' own draw; the columns that are not loaded get
    `OPEN_DAY`, so every closed-period reference of `taxi.py` leaves
    them out; `.live` is the run's stream of arrivals."""
    r = taxi.make(config, shard_width)
    r.day[n_loaded(r):] = OPEN_DAY
    if nonce is None:
        nonce = [time.time_ns() & 0xFFFFFFFF, time.time_ns() >> 32,
                 os.getpid()]
    r.live = Arrivals(r, nonce)
    return r


def fingerprint(config: dict, shard_width: int) -> dict:
    return dict(taxi.fingerprint(config, shard_width), dataset="taxi_live",
                schema=[taxi.SCHEMA, SCHEMA],
                loaded=config["shards"] * shard_width - shard_width // 2)


def patch_cell_bytes(config: dict, shard_width: int = 1 << 20) -> int:
    """Bytes a bank patch cannot move fewer of, a cell: one row of one
    shard (`shard_width` bits), read from the host's copy and written
    into the bank."""
    return 2 * (shard_width // 8)


# ------------------------------------------------------------------ requests


def ride_values(r, col: int) -> dict:
    return {"cab_type": int(r.cab_id[col]), "dist_miles": int(r.miles[col]),
            "total_amount_dollars": int(r.dollars[col]),
            "passenger_count": int(r.pax_id[col]),
            "drop_grid_id": int(r.drop[col]),
            "pickup_grid_id": int(r.grid[col]),
            "pickup_elapsed_time_of_day": int(r.tod[col]),
            "dist": int(r.dist[col]), "amount": int(r.amount[col])}


def set_pql(r, col: int, field: str) -> str:
    if field == "pickup":
        return f"Set({col}, pickup={int(r.cab_id[col])}, {OPEN_DATE}T00:00)"
    return f"Set({col}, {field}={ride_values(r, col)[field]})"


def ride_pql(r, col: int) -> str:
    """The tree that selects ride `col` alone: the open day's view of
    its cab and its five other set-field values."""
    v = ride_values(r, col)
    return (f"Intersect(Row(pickup={v['cab_type']}, from='{OPEN_DATE}', "
            f"to='{OPEN_NEXT}'), Row(drop_grid_id={v['drop_grid_id']}), "
            f"Row(dist_miles={v['dist_miles']}), "
            f"Row(total_amount_dollars={v['total_amount_dollars']}), "
            f"Row(passenger_count={v['passenger_count']}), "
            "Row(pickup_elapsed_time_of_day="
            f"{v['pickup_elapsed_time_of_day']}))")


def readback(r, col: int, form: str) -> tuple:
    """(pql, expected) of one read-back of an arrived ride."""
    v = ride_values(r, col)
    if form == "topn":
        return (f"TopN(pickup_grid_id, {ride_pql(r, col)}, n=10)",
                [{"id": v["pickup_grid_id"], "count": 1}])
    field = form[len("sum_"):]
    return (f"Sum({ride_pql(r, col)}, field={field})",
            {"value": v[field], "count": 1})


class Draws(taxi.Draws):
    """`taxi.Draws`, the period of a report, and what the client this
    generator belongs to has sent of the arrivals and not yet seen
    answered."""

    RECENT = 21     # a period ends 0 ... 20 days before the data does

    def __init__(self, r_shape: dict, rng, row_skew: float = 0.0):
        super().__init__(r_shape, rng, row_skew)
        p = 1.0 / np.arange(1, self.RECENT + 1) ** 0.99
        self._recent = np.cumsum(p / p.sum())
        self.unacknowledged = []

    def period(self, span: int) -> tuple:
        """[d0, d1) of `span` days ending d1 = n_days - j, j Zipf(0.99)
        over 0 ... 20 (YCSB's `latest`: recent periods are asked for
        most)."""
        j = int(min(np.searchsorted(self._recent, self.rng.random()),
                    self.RECENT - 1))
        d1 = self.n_days - j
        return max(0, d1 - span), d1


def _report(r, d, field, span, text, mask):
    """One report: TopN over `field` under the period, and `text` /
    `mask` (filter PQL or None, its numpy mask or None)."""
    a = d.cab()
    d0, d1 = d.period(span)
    period = f"Row(pickup={a}, from='{iso(d0)}', to='{iso(d1)}')"
    tree = period if text is None else f"Intersect({period}, {text})"

    def ref():
        m = r.pickup[a] & r.in_days(d0, d1)
        return taxi._topn_filtered(r, field, m if mask is None
                                   else m & mask())
    return f"TopN({field}, {tree}, n=10)", ref


def _q_report_period(r, d, field=GRID_FIELDS[0], span=1, **_):
    return _report(r, d, field, span, None, None)


def _q_report_dist_lt(r, d, field=GRID_FIELDS[0], span=1, **_):
    t = d.threshold()
    return _report(r, d, field, span, f"Row(dist < {t})",
                   lambda: r.dist < t)


def _q_report_amount_gt(r, d, field=GRID_FIELDS[0], span=1, **_):
    t = d.threshold()
    return _report(r, d, field, span, f"Row(amount > {2 * t})",
                   lambda: r.amount > 2 * t)


def _q_report_miles_dollars(r, d, field=GRID_FIELDS[0], span=1, **_):
    a, b = d.miles_dollars()
    return _report(r, d, field, span,
                   f"Intersect(Row(dist_miles={a}), "
                   f"Row(total_amount_dollars={b}))",
                   lambda: (r.miles == a) & (r.dollars == b))


def _q_report_tod(r, d, field=GRID_FIELDS[0], span=1, **_):
    h = d.tod()
    return _report(r, d, field, span,
                   f"Row(pickup_elapsed_time_of_day={h})",
                   lambda: r.tod == h)


def _q_ride_set(r, d, **_):
    col, field = r.live.next_field(d)
    return set_pql(r, col, field), lambda: BOOLEAN


def _q_ride_readback(r, d, loader=False, **pinned):
    col, form = r.live.next_readback(loader)
    if col is None:     # nothing has arrived yet: a report
        return _q_report_period(r, d, **pinned)
    pql, want = readback(r, col, form)
    return pql, lambda: want


FAMILIES = {
    "report_period": _q_report_period,
    "report_dist_lt": _q_report_dist_lt,
    "report_amount_gt": _q_report_amount_gt,
    "report_miles_dollars": _q_report_miles_dollars,
    "report_tod": _q_report_tod,
    "ride_set": _q_ride_set,
    "ride_readback": _q_ride_readback,
}


def query(r, family: str, draws: Draws, **pinned) -> tuple:
    r.live.asked(draws)
    return FAMILIES[family](r, draws, **pinned)


def family_queries(r) -> list:
    """(pql, expected), posted at the start of every warm-up: one
    closed-period query of each kind the deployment serves — `taxi.py`'s
    two time-range counts as they are, its other shapes under a period
    — and `LOADER_READBACKS` of the loader's rides, read back from the
    re-opened directory."""
    nd = r.n_days
    week = r.in_days(nd - 7, nd)
    period = f"Row(pickup=1, from='{iso(nd - 7)}', to='{iso(nd)}')"
    m = r.pickup[1] & week
    groups = np.bincount(r.pax_id[m].astype(np.intp), minlength=7)
    out = [
        (f"Count(Row(pickup=0, from='{iso(4)}', to='{iso(11)}'))",
         int((r.pickup[0] & r.in_days(4, 11)).sum())),
        (f"Count(Row(pickup=1, from='{iso(0)}', to='{iso(nd)}'))",
         int((r.pickup[1] & r.in_days(0, nd)).sum())),
        (f"Count(Intersect({period}, Row(passenger_count=2)))",
         int((m & r.pax[2]).sum())),
        (f"Count(Intersect({period}, Row(dist < 50)))",
         int((m & (r.dist < 50)).sum())),
        (f"Sum({period}, field=amount)",
         {"value": int(r.amount[m].sum()), "count": int(m.sum())}),
        (f"GroupBy(Rows(passenger_count), filter={period})",
         [{"group": [{"field": "passenger_count", "rowID": p}],
           "count": int(groups[p])} for p in range(1, 7) if groups[p]]),
    ]
    for field in GRID_FIELDS:
        out.append((f"TopN({field}, {period}, n=10)",
                    taxi._topn_filtered(r, field, m)))
    step = len(r.live.loader) // LOADER_READBACKS
    for k in range(LOADER_READBACKS):
        out.append(readback(r, int(r.live.loader[k * step]),
                            READBACK_FORMS[k % len(READBACK_FORMS)]))
    return out


# ------------------------------------------------------------------ loading


class _FullShards:
    """The rides as `taxi.load` reads them, cut to the whole shards."""

    def __init__(self, r, n_shards: int):
        self._r = r
        self.n_shards = n_shards

    def __getattr__(self, name):
        return getattr(self._r, name)


def _load_half_shard(srv, r, s: int) -> None:
    """The lower half of shard `s`, field by field as `taxi.load` loads
    a whole one."""
    sw = r.shard_width
    sl = slice(s * sw, s * sw + sw // 2)
    cols = np.arange(sw // 2)

    def put(field, rows, on=cols, view="standard"):
        srv.request("POST", f"/index/{INDEX}/field/{field}"
                    f"/import-roaring/{s}?view={view}",
                    taxi.roaring_bytes(rows, on, sw),
                    "application/octet-stream")

    for field, rows in (("cab_type", r.cab_id), ("passenger_count", r.pax_id),
                        ("dist_miles", r.miles),
                        ("total_amount_dollars", r.dollars),
                        ("drop_grid_id", r.drop), ("pickup_grid_id", r.grid),
                        ("pickup_elapsed_time_of_day", r.tod)):
        put(field, rows[sl])
    ids = list(range(sl.start, sl.stop))
    for field, vals in (("dist", r.dist), ("amount", r.amount)):
        srv.post_json(f"/index/{INDEX}/field/{field}/import",
                      {"columnIDs": ids, "values": vals[sl].tolist()})
    cab, day = r.cab_id[sl], r.day[sl]
    for view in ("standard", "standard_2019", "standard_201901"):
        put("pickup", cab, view=view)
    for d in range(r.n_days):
        on = np.flatnonzero(day == d)
        put("pickup", cab[on], on, view=f"standard_201901{d + 1:02d}")


def refuse_no_patch_counter(srv) -> None:
    """A server that publishes no `executor.bank_patches` cannot say
    what a write cost the next read of a bank — a patch of the cells
    that moved or a 2 GiB rebuild — and the cell would time it blind.
    It is refused here, in seconds and before a byte is loaded. The
    check is on the benchmark's side: no server setting exists for it."""
    counters = srv.get("/debug/vars").get("counters", {})
    if PATCH_COUNTER not in counters:
        raise BenchFailure(
            f"the server's /debug/vars publishes no {PATCH_COUNTER}: "
            "this program cannot report what the write path does to a "
            "resident bank, and taxi-live-chip is not measured on it")


def patches(srv) -> int:
    return srv.get("/debug/vars")["counters"][PATCH_COUNTER]


def load(srv, r, log=lambda m: None) -> None:
    refuse_no_patch_counter(srv)
    full = r.n_shards - 1
    taxi.load(srv, _FullShards(r, full), log)
    _load_half_shard(srv, r, full)
    log(f"loaded the lower half of shard {full + 1}/{r.n_shards}")
    loader_rides(srv, r, log)


def loader_rides(srv, r, log=lambda m: None) -> None:
    """The loader's rides arrive by Set on the query route, after one
    read-back that finds nothing and leaves the banks it reads
    resident — so the read-back after them meets stale banks, and a
    server that does not patch them is refused."""
    first = int(r.live.loader[0])
    pql, want = readback(r, first, "topn")
    srv.query(INDEX, pql)
    for col in r.live.loader.tolist():
        for field in RIDE_FIELDS:
            got = srv.query(INDEX, set_pql(r, col, field))
            if not isinstance(got, bool):
                raise BenchFailure(f"{set_pql(r, col, field)} -> {got!r}")
    before = patches(srv)
    got = srv.query(INDEX, pql)
    if got != want:
        raise BenchFailure(f"{pql} after the loader's rides: server "
                           f"{str(got)[:200]} reference {want}")
    if patches(srv) <= before:
        raise BenchFailure(
            f"{PATCH_COUNTER} did not move on the first read-back after "
            f"{LOADER_RIDES} rides: the write path is not the one this "
            "cell measures")
    log(f"{LOADER_RIDES} rides by Set, read back; "
        f"{100 * r.live.candidate_share:.1f} % of the open half may arrive")
