"""The write path under served traffic (PR 38): the `taxi-live-chip`
deployment of `benchmark/datasets/taxi_live.py` — closed-period TopN
reports while rides arrive by `Set` — on one in-process server with the
coalescer on, at 2 shards / 15 grid rows. Every family against the plain
reference while writers insert; a ride's read-back before and after a
restart; the same directory served again under another nonce; a stream
that ends on a generated, unsent `Set`; what a staged TopN reads when a
`Set` falls between its staging and its launch; the `bank_patch`
program's lane buckets; the spans and counters; the refusals."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.ops.bitset import SHARD_WIDTH

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from datasets import taxi_live  # noqa: E402
from harness import loadgen  # noqa: E402
from harness.server import BenchFailure, Client, Server  # noqa: E402

CONFIG = {"data_seed": 20190101, "shards": 2, "grid_rows": 15,
          "n_days": 28}
INDEX = taxi_live.INDEX
SHAPE = {"grid_rows": CONFIG["grid_rows"], "n_days": CONFIG["n_days"]}


class Served:
    """One in-process server over one data directory; `restart()`
    closes the holder and serves the directory again."""

    def __init__(self, path: str):
        self.path = path
        self.rides = taxi_live.make(CONFIG, SHARD_WIDTH, nonce=[38, 1])
        self._open()

    def _open(self) -> None:
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.server import API, serve
        from pilosa_tpu.server.coalescer import QueryCoalescer
        from pilosa_tpu.utils.stats import MemStatsClient

        self.holder = Holder(self.path)
        self.holder.open()
        self.api = API(self.holder, stats=MemStatsClient())
        self.api.coalescer = QueryCoalescer(
            self.api.executor, window_s=0.0005, stats=self.api.stats)
        self.api.coalescer.start()
        self.http = serve(self.api, "localhost", 0, background=True)
        self.port = self.http.server_address[1]
        self.srv = Server.__new__(Server)
        self.srv.port, self.srv.client = self.port, Client(self.port)

    def close(self) -> None:
        self.srv.client.close()
        self.http.shutdown()
        self.http.server_close()
        self.api.coalescer.stop()
        self.holder.close()

    def restart(self) -> None:
        self.close()
        self._open()

    def counters(self, until=None) -> dict:
        """The counters; with `until`, once it holds of them: a record
        hands its counts over when it finishes, after its reply is
        written, so a client that has read the reply may be ahead."""
        deadline = time.monotonic() + 10
        while True:
            now = dict(self.api.stats.snapshot()["counters"])
            if until is None or until(now) or time.monotonic() > deadline:
                return now
            time.sleep(0.01)

    def draws(self, *seed) -> "taxi_live.Draws":
        return taxi_live.Draws(SHAPE, np.random.default_rng([38, *seed]))

    def insert(self, col: int, fields=taxi_live.RIDE_FIELDS) -> None:
        for field in fields:
            got = self.srv.query(INDEX,
                                 taxi_live.set_pql(self.rides, col, field))
            assert isinstance(got, bool)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(str(tmp_path_factory.mktemp("taxi_live")))
    taxi_live.load(s.srv, s.rides)
    yield s
    s.close()


def _fresh_ride(s: Served, k: int) -> int:
    """A candidate no test has sent yet: from the END of the order, far
    from where the stream hands them out."""
    return int(s.rides.live.order[-1 - k])


# ------------------------------------------------ the families, served


def test_the_loader_left_what_the_configuration_says(served):
    r = served.rides
    assert taxi_live.n_loaded(r) == int(1.5 * SHARD_WIDTH)
    assert (r.day[taxi_live.n_loaded(r):] == taxi_live.OPEN_DAY).all()
    assert 0.5 < r.live.candidate_share < 0.65
    assert len(r.live.loader) == taxi_live.LOADER_RIDES
    for pql, want in taxi_live.family_queries(r):
        assert taxi_live.equal(served.srv.query(INDEX, pql), want), pql


@pytest.mark.parametrize("family", [f for f in taxi_live.FAMILIES
                                    if f.startswith("report_")])
def test_a_report_equals_the_reference_while_four_writers_insert(
        served, family):
    """Both spans and both grid fields, while four threads insert rides
    field by field into the banks and leaves the reports read."""
    s, r = served, served.rides
    stop = threading.Event()
    sent = []

    def writer(k: int) -> None:
        conn, d = Client(s.port), s.draws(len(family), k)
        while not stop.is_set():
            pql, _ = taxi_live.query(r, "ride_set", d)
            status, body = conn.request("POST", f"/index/{INDEX}/query",
                                        pql.encode(), "text/plain")
            sent.append((status, json.loads(body)["results"][0]))
        conn.close()

    writers = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in writers:
        t.start()
    try:
        d = s.draws(len(family))
        some = 0
        for field in taxi_live.GRID_FIELDS:
            for span in (1, 7, 1, 7):
                pql, ref = taxi_live.query(r, family, d, field=field,
                                           span=span)
                got, want = s.srv.query(INDEX, pql), ref()
                assert taxi_live.equal(got, want), (pql, got, want)
                some += bool(want)
    finally:
        stop.set()
        for t in writers:
            t.join()
    assert some and len(sent) >= 8
    assert all(st == 200 and isinstance(res, bool) for st, res in sent)


def test_an_arrived_ride_is_read_back_exact_before_and_after_a_restart(
        served):
    s, r = served, served.rides
    col = _fresh_ride(s, 0)
    s.insert(col)
    forms = [taxi_live.readback(r, col, f) for f in taxi_live.READBACK_FORMS]
    for pql, want in forms:
        assert s.srv.query(INDEX, pql) == want, pql
    assert forms[0][1] == [{"id": int(r.grid[col]), "count": 1}]
    s.restart()
    for pql, want in forms:
        assert s.srv.query(INDEX, pql) == want, pql
    # ... and the loader's, which every run of the cell reads back.
    for pql, want in taxi_live.family_queries(r)[-taxi_live.LOADER_READBACKS:]:
        assert s.srv.query(INDEX, pql) == want, pql


def _run_cycle(s: Served, rides, clients: int, each: int, seed: int) -> list:
    with open(os.path.join(BENCH, "traffic", "report-ingest.json")) as f:
        traffic = json.load(f)
    streams = [loadgen.client_stream(taxi_live, rides, traffic, seed, c)
               for c in range(clients)]
    reqs, _, _ = loadgen.run_clients(s.port, f"/index/{INDEX}/query",
                                     streams, 600, max_requests=each)
    return reqs


def _all_equal(reqs: list) -> dict:
    by_family = {}
    for q in reqs:
        assert q.status == 200, (q.pql, q.body[:200])
        (got,) = json.loads(q.body)["results"]
        assert taxi_live.equal(got, q.ref()), (q.pql, got, q.ref())
        by_family[q.family] = by_family.get(q.family, 0) + 1
    return by_family


def test_the_same_directory_served_again_under_another_nonce_stays_exact(
        served):
    """The run-after-run case: a second process's arrivals come in
    another order over a directory that holds the first's, and every
    answer is still a function of the request's own text."""
    s = served
    first = _all_equal(_run_cycle(s, s.rides, 8, 70, 2**31 + 38))
    assert first["ride_set"] >= 24
    s.restart()
    again = taxi_live.make(CONFIG, SHARD_WIDTH, nonce=[38, 2])
    assert (again.live.loader == s.rides.live.loader).all()
    assert (again.live.order[:50] != s.rides.live.order[:50]).any()
    second = _all_equal(_run_cycle(s, again, 8, 70, 2**31 + 38))
    assert second["ride_set"] >= 24
    # The first process's order again, from its start: every Set of it
    # that was sent answers `changed` false now, and is still correct.
    replay = taxi_live.make(CONFIG, SHARD_WIDTH, nonce=[38, 1])
    third = _all_equal(_run_cycle(s, replay, 8, 70, 2**31 + 38))
    assert third == first


def test_a_stream_that_ends_on_an_unsent_set_leaves_nothing_wrong(served):
    """`loadgen.run_clients` draws a client's next request before it
    looks at the clock: the last one is generated and never sent. A ride
    left one field short never arrives, is never read back, and the
    rides around it read back exact."""
    s = served
    rides = taxi_live.make(CONFIG, SHARD_WIDTH, nonce=[38, 3])
    live = rides.live
    a, b = s.draws(1), s.draws(2)
    conn = Client(s.port)

    def send(pql):
        status, body = conn.request("POST", f"/index/{INDEX}/query",
                                    pql.encode(), "text/plain")
        assert status == 200
        return json.loads(body)["results"][0]

    # Client a sends nine fields of the first ride; client b generates
    # the tenth and stops there (its stream ended).
    for _ in range(9):
        send(taxi_live.query(rides, "ride_set", a)[0])
    short = int(live.order[0])
    taxi_live.query(rides, "ride_set", b)
    # Client a goes on: the whole second ride, then asks again.
    for _ in range(10):
        send(taxi_live.query(rides, "ride_set", a)[0])
    pql, ref = taxi_live.query(rides, "ride_readback", a)
    whole = int(live.order[1])
    assert short in live.pending and short not in live.arrived
    assert str(int(rides.drop[whole])) in pql and send(pql) == ref()
    # Nothing else has arrived: the next read-back slot is a report.
    pql, ref = taxi_live.query(rides, "ride_readback", a)
    assert pql.startswith("TopN(pickup_grid_id, Row(pickup=")
    assert send(pql) == ref()
    conn.close()


# ------------------------------------------- staging, launch and a Set


def test_a_set_between_two_topns_of_one_batch_orders_them(served):
    """One executor batch [TopN, Set, TopN]: the collector flushes the
    staged TopN before the write dispatches, so the first answers the
    state before it and the second the state after."""
    s, r = served, served.rides
    col = _fresh_ride(s, 1)
    s.insert(col, taxi_live.RIDE_FIELDS[:-1])       # all but `pickup`
    pql, want = taxi_live.readback(r, col, "topn")
    out = s.api.executor.execute_batch_shaped(
        [(INDEX, pql, None),
         (INDEX, taxi_live.set_pql(r, col, "pickup"), None),
         (INDEX, pql, None)])
    assert [o["results"] for o in out] == [[[]], [True], [want]]


def test_a_set_between_a_topns_staging_and_its_launch_reads_one_side(
        served, monkeypatch):
    """A write from another path lands after a batch's TopN has staged
    its filter and its sweep and before the collector launches them:
    the staged member holds the arrays it took — a patch makes a new
    array and donates nothing — so it answers the state before the
    write, whole, and the next read the state after."""
    from pilosa_tpu.executor import fusion
    s, r = served, served.rides
    col = _fresh_ride(s, 2)
    s.insert(col, [f for f in taxi_live.RIDE_FIELDS
                   if f != "pickup_grid_id"])
    pql, want = taxi_live.readback(r, col, "topn")
    assert s.srv.query(INDEX, pql) == []        # banks resident, no cell
    report, ref = taxi_live.query(r, "report_dist_lt", s.draws(9),
                                  field="pickup_grid_id", span=7)
    real_flush = fusion.FusionCollector.flush
    wrote = []

    def flush_after_a_write(self):
        if not wrote and (self.sweeps or self.filters):
            wrote.append(s.api.executor.execute(
                INDEX, taxi_live.set_pql(r, col, "pickup_grid_id")))
        return real_flush(self)

    monkeypatch.setattr(fusion.FusionCollector, "flush",
                        flush_after_a_write)
    out = s.api.executor.execute_batch_shaped(
        [(INDEX, pql, None), (INDEX, report, None)])
    monkeypatch.undo()
    assert wrote == [[True]]
    assert out[0]["results"] == [[]]            # the side before the write
    assert out[1]["results"] == [ref()]
    assert s.srv.query(INDEX, pql) == want      # ... and the side after


# --------------------------------------------------- the patch program


def test_bank_patch_compiles_once_a_lane_bucket(served):
    """Every lane bucket of a bank shape is compiled when its first
    patch runs: a patch of 3 cells after one of 4 compiles nothing, and
    neither does one of 7 (another bucket)."""
    from pilosa_tpu.core import view as view_mod
    from pilosa_tpu.utils.jaxenv import COMPILES
    from pilosa_tpu.utils.stats import MemStatsClient
    s, r = served, served.rides
    COMPILES.install(MemStatsClient())
    try:
        pql = "TopN(drop_grid_id, Row(dist < 100), n=3)"
        s.srv.query(INDEX, pql)                 # the bank is resident
        time.sleep(0.3)     # ... and that request's record has finished
        spare = [int(c) for c in r.live.order[-40:-20]]

        def touch(n_cells: int) -> dict:
            """Sets that move `n_cells` (row, shard) cells of the bank."""
            before = s.counters()
            rows = set()
            while len(rows) < n_cells:
                col = spare.pop()
                if int(r.drop[col]) not in rows:
                    rows.add(int(r.drop[col]))
                    s.insert(col, ["drop_grid_id"])
            s.srv.query(INDEX, pql)
            after = s.counters(lambda c: c["executor.bank_patches"]
                               > before["executor.bank_patches"])
            return {k: after[k] - before.get(k, 0) for k in (
                "executor.bank_patches", "executor.bank_patch_cells",
                "executor.bank_patch_pad_lanes", "executor.bank_rebuilds")}

        assert touch(4) == {"executor.bank_patches": 1,
                            "executor.bank_patch_cells": 4,
                            "executor.bank_patch_pad_lanes": 0,
                            "executor.bank_rebuilds": 0}
        shape = (16, CONFIG["shards"], SHARD_WIDTH // 32)
        lanes = {k for key, progs in view_mod._PATCH_PROGRAMS.items()
                 if key[0] == shape for k in progs}
        assert lanes == {(1, False), (2, False), (4, False), (8, False),
                         (16, False), (32, False), (64, False), (64, True)}
        compiled = COMPILES.snapshot()["compiles"]
        assert touch(3)["executor.bank_patch_pad_lanes"] == 1
        assert touch(7) == {"executor.bank_patches": 1,
                            "executor.bank_patch_cells": 7,
                            "executor.bank_patch_pad_lanes": 1,
                            "executor.bank_rebuilds": 0}
        assert COMPILES.snapshot()["compiles"] == compiled
    finally:
        COMPILES.stats = None


def test_a_patch_longer_than_the_top_bucket_is_a_chain(tmp_holder):
    """70 cells: one launch of 8 lanes (6 real, never donating: the
    cached array may be staged elsewhere) and one of the top bucket
    that donates the first's output; the cached array is intact."""
    from pilosa_tpu.core import view as view_mod
    idx = tmp_holder.create_index("p")
    f = idx.create_field("f")
    rows = np.arange(100, dtype=np.uint64)
    f.import_bits(rows, rows)
    v = f.view("standard")
    bank = v.device_bank([0])
    before = np.asarray(bank.array).copy()
    for row in range(70):
        f.set_bit(row, 5000 + row)
    patched = v.device_bank([0])
    assert patched is not bank and patched.slots == bank.slots
    assert (np.asarray(bank.array) == before).all()
    after = np.asarray(patched.array)
    for row in range(100):
        want = {row} | ({5000 + row} if row < 70 else set())
        words = after[patched.slots[row], 0]
        got = {32 * int(w) + b for w in np.flatnonzero(words)
               for b in range(32) if words[w] >> np.uint32(b) & 1}
        assert got == want, row
    assert view_mod.PATCH_LANES_MAX == 64


def test_what_a_patched_bank_keeps(tmp_holder):
    """The slot-ordered row array when no row was added; never the
    rows' popcounts, which belong to one bank version."""
    idx = tmp_holder.create_index("k")
    f = idx.create_field("f")
    f.import_bits(np.arange(5, dtype=np.uint64), np.arange(5, dtype=np.uint64))
    v = f.view("standard")
    bank = v.device_bank([0])
    kept = bank.slot_rows()
    bank.popcounts = np.ones(8, np.uint32)
    f.set_bit(2, 77)
    patched = v.device_bank([0])
    assert patched._slot_rows is kept and patched.popcounts is None
    f.set_bit(6, 78)                            # a new row: slots grow
    grown = v.device_bank([0])
    assert grown._slot_rows is None
    assert grown.slot_rows().tolist() == [0, 1, 2, 3, 4, 6]


# ------------------------------------------------- spans and counters


def test_the_write_paths_spans_and_counters_move(served):
    from pilosa_tpu.utils.timeline import TIMELINE
    s, r = served, served.rides
    col = _fresh_ride(s, 3)
    pql, want = taxi_live.readback(r, col, "sum_amount")
    s.srv.query(INDEX, pql)
    before = s.counters()
    s.insert(col)
    assert s.srv.query(INDEX, pql) == want
    after = s.counters(
        lambda c: c["executor.writes{call:Set}"]
        >= before["executor.writes{call:Set}"] + 10
        and c["executor.bank_patches"] >= before["executor.bank_patches"] + 3)

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    assert moved("executor.writes{call:Set}") == 10
    assert moved("executor.bank_patches") >= 3
    assert moved("executor.bank_patch_cells") >= moved("executor.bank_patches")
    assert moved("coalescer.flushes{path:direct}") >= 11
    assert moved("coalescer.flushes{reason:write}") \
        + moved("coalescer.flushes{reason:drain}") \
        + moved("coalescer.flushes{reason:idle}") >= 10
    for name in ("executor.bank_rebuilds{cause:capacity}",
                 "executor.bank_rebuilds{cause:epoch}",
                 "executor.bank_rebuilds{cause:half}",
                 "executor.bank_rebuilds{cause:width}",
                 "coalescer.flushes{path:batch}",
                 "coalescer.flushes{path:pipelined}"):
        assert name in after
    histos = s.api.stats.snapshot()["histograms"]
    assert histos["request.stage_seconds{stage:write.apply}"]["count"] >= 10
    assert histos["request.stage_seconds{stage:plan.bank_patch}"]["count"] >= 1
    spans = [sp for rec in TIMELINE.requests(last=40)
             for sp in rec.root.walk()]
    applies = [sp for sp in spans if sp.name == "write.apply"]
    patches = [sp for sp in spans if sp.name == "plan.bank_patch"]
    assert applies and applies[-1].attrs["call"] == "Set"
    assert patches and set(patches[-1].attrs) >= {"cells", "lanes", "bytes",
                                                  "bank"}
    launches = [c for sp in patches for c in sp.children
                if c.name == "dispatch"]
    assert launches and all(c.attrs["program"] == "bank_patch"
                            for c in launches)


@pytest.mark.parametrize("field, fresh", [("total_amount_dollars", 5),
                                          ("drop_grid_id", 6)])
def test_a_leaf_over_a_written_view_uploads_no_bank(served, monkeypatch,
                                                    field, fresh):
    """PR 39: a `report_miles_dollars` report after a `Set` to
    `total_amount_dollars`, and a ride's read-back after the `Set` of
    its `drop_grid_id`, at a limit the view's full bank just fits by
    the rows it has (the sum over the shards is over it): the leaf
    reads that bank, patched — no `plan.bank_upload` span, no row-subset
    bank built anew — and the answer is the reference's."""
    from pilosa_tpu.core.view import bank_capacity
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils.timeline import TIMELINE
    s, r = served, served.rides
    col = _fresh_ride(s, fresh)
    view = s.holder.index(INDEX).field(field).view()
    shards = tuple(range(CONFIG["shards"]))
    n_rows = len(view.merged_row_ids(shards))
    by_sum = sum(len(view.fragment(sh).row_ids()) for sh in shards)
    assert bank_capacity(by_sum) > bank_capacity(n_rows)
    monkeypatch.setattr(
        Executor, "BANK_MAX_BYTES",
        bank_capacity(n_rows) * len(shards) * view.trimmed_words() * 4)
    if field == "total_amount_dollars":
        # Five draws: whichever rows they name, the one bank they all
        # read is stale after the ride's Set and is patched once.
        d = s.draws(39)
        asked = [taxi_live.query(r, "report_miles_dollars", d, span=7)
                 for _ in range(5)]
        asked = [(pql, ref()) for pql, ref in asked]
        others = []
    else:
        pql, want = taxi_live.readback(r, col, "topn")
        asked = [(pql, want)]
        others = [f for f in taxi_live.RIDE_FIELDS if f != field]
    s.insert(col, others)
    for pql, _ in asked:                        # every bank is built
        s.srv.query(INDEX, pql)
    time.sleep(0.3)                 # ... and those records have finished
    before = s.counters()
    seen = {rec.trace_id for rec in TIMELINE.requests(last=256)}
    s.insert(col, [field])
    for pql, want in asked:
        got = s.srv.query(INDEX, pql)
        assert taxi_live.equal(got, want), (pql, got, want)
    after = s.counters(lambda c: c["executor.bank_patches"]
                       > before["executor.bank_patches"])
    moved = {k: after[k] - before[k] for k in (
        "executor.bank_patches", "executor.bank_subset_rebuilds",
        "executor.bank_upload_bytes")}
    assert moved == {"executor.bank_patches": 1,
                     "executor.bank_subset_rebuilds": 0,
                     "executor.bank_upload_bytes": 0}
    spans = [sp.name for rec in TIMELINE.requests(last=256)
             if rec.trace_id not in seen for sp in rec.root.walk()]
    assert "plan.bank_patch" in spans and "plan.bank_upload" not in spans
    assert [k for k in view._bank_cache if len(k) == 4] == []


def test_a_flush_that_holds_a_set_takes_the_batch_path(served):
    """Eight clients at once, one of them a Set: the flush that holds
    it barriers and runs whole on the dispatcher (`thread.batch`)."""
    s, r = served, served.rides
    col = _fresh_ride(s, 4)
    pqls = [taxi_live.query(r, "report_tod", s.draws(4, k), span=7)[0]
            for k in range(40)]
    before = s.counters()
    gate = threading.Barrier(8)

    def client(k: int) -> None:
        conn = Client(s.port)
        gate.wait()
        for i in range(5):
            pql = taxi_live.set_pql(r, col, taxi_live.RIDE_FIELDS[i]) \
                if k == 0 else pqls[5 * k + i]
            status, _ = conn.request("POST", f"/index/{INDEX}/query",
                                     pql.encode(), "text/plain")
            assert status == 200
        conn.close()

    threads = [threading.Thread(target=client, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    after = s.counters(lambda c: c["executor.writes{call:Set}"]
                       >= before["executor.writes{call:Set}"] + 5)
    by_path = {p: after[f"coalescer.flushes{{path:{p}}}"]
               - before[f"coalescer.flushes{{path:{p}}}"]
               for p in ("pipelined", "batch", "direct")}
    assert sum(by_path.values()) >= 5 and by_path["batch"] + \
        by_path["direct"] >= 1
    assert after["executor.writes{call:Set}"] \
        - before["executor.writes{call:Set}"] == 5


# ------------------------------------------------------- the refusals


class _NoPatchCounter:
    def get(self, path):
        assert path == "/debug/vars"
        return {"counters": {"executor.sweep_launches": 3}}


def test_a_server_without_the_patch_counter_is_refused_before_the_load():
    with pytest.raises(BenchFailure, match="executor.bank_patches"):
        taxi_live.refuse_no_patch_counter(_NoPatchCounter())
    with pytest.raises(BenchFailure, match="executor.bank_patches"):
        taxi_live.load(_NoPatchCounter(), None)


def test_a_server_that_has_the_counter_reads_zero_not_nothing(tmp_holder):
    from pilosa_tpu.server import API
    from pilosa_tpu.utils.stats import MemStatsClient
    api = API(tmp_holder, stats=MemStatsClient())
    counters = api.stats.snapshot()["counters"]
    assert counters["executor.bank_patches"] == 0
    assert counters["executor.writes{call:Set}"] == 0
    assert counters["executor.bank_rebuilds"] == 0


def test_a_server_that_does_not_patch_is_refused_after_the_loaders_rides(
        served, monkeypatch):
    monkeypatch.setattr(taxi_live, "patches", lambda srv: 7)
    with pytest.raises(BenchFailure, match="did not move"):
        taxi_live.loader_rides(served.srv, served.rides)


def test_a_set_is_compared_as_a_json_boolean_and_nothing_else():
    assert taxi_live.equal(True, taxi_live.BOOLEAN)
    assert taxi_live.equal(False, taxi_live.BOOLEAN)
    assert not taxi_live.equal(2, taxi_live.BOOLEAN)
    assert not taxi_live.equal(None, taxi_live.BOOLEAN)
    assert not taxi_live.equal([{"id": 1, "count": 2}],
                               [{"id": 1, "count": 1}])


def test_a_patched_bank_is_placed_as_the_bank_it_patches(tmp_holder):
    """An uploaded bank is uncommitted; a patch lowered FOR its device
    would hand back a committed array, and every program that takes the
    patched bank would compile again under that key (on the chip: the
    sweeps and filter programs, inside the window)."""
    import jax
    from pilosa_tpu.utils.jaxenv import COMPILES
    from pilosa_tpu.utils.stats import MemStatsClient
    idx = tmp_holder.create_index("c")
    f = idx.create_field("f")
    f.import_bits(np.arange(5, dtype=np.uint64), np.arange(5, dtype=np.uint64))
    v = f.view("standard")
    bank = v.device_bank([0])
    count = jax.jit(lambda a: (a != 0).sum())
    assert int(count(bank.array)) == 5
    f.set_bit(2, 77)
    patched = v.device_bank([0])
    assert patched.array.committed == bank.array.committed is False
    assert patched.array.sharding == bank.array.sharding
    COMPILES.install(MemStatsClient())
    try:
        compiled = COMPILES.snapshot()["compiles"]
        assert int(count(patched.array)) == 6
        assert COMPILES.snapshot()["compiles"] == compiled
    finally:
        COMPILES.stats = None
