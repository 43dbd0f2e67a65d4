"""A flush's GroupBy members take turns at their count fetches (PR 45;
executor.py: `_group_by_levels`, `_dispatch_query`, `_batch_begin`).

The level loop is ONE generator that stops where it is about to block
on a count fetch. Alone it is taken to its end on the spot; inside
`execute_batch` the dispatcher starts up to GROUPBY_INFLIGHT_MEMBERS
members, each to its first fetch, and then always resumes the member
whose fetch was launched earliest, so the other members' level programs
are queued on the device through every round trip.

Held here, on the Star Schema Benchmark's GroupBy shapes at rehearsal
size (`benchmark/datasets/ssb.py`, its reference) and on a small index
of its own: (a) a batch answers as each member alone and as the
reference — with and without `filter`, `aggregate`, `limit`,
`previous`, chunked and spilled levels; (b) the order of launches and
fetches; (c) a write between two GroupBys is a fence; (d) a member that
raises fails alone; (e) what a member leaves in its profile, in its
dependency capture and in the counters is what it leaves alone, and the
flush's record still tiles; (f) `executor.groupby_fetches{covered:…}`."""

import os
import sys

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.utils.profile import QueryProfile
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from datasets import ssb  # noqa: E402
from harness.server import Client, Server  # noqa: E402

ORDERS = 3000
BOUND = Executor.GROUPBY_INFLIGHT_MEMBERS
# SSB's GroupBy families whose constants select something at this size.
SHAPES = ("q2.1", "q2.2", "q2.3", "q3.1", "q3.2", "q4.1", "q4.2", "q4.3")


def _harness_server(port: int) -> Server:
    """The harness's `Server` surface (post_json / query) over a server
    this process already runs, on a connection of its own."""
    srv = Server.__new__(Server)
    srv.port, srv.client = port, Client(port)
    return srv


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The deployment's rows behind one in-process server, loaded by
    its own loader; the tests drive the server's executor."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.server import API, serve
    from pilosa_tpu.server.coalescer import QueryCoalescer

    h = Holder(str(tmp_path_factory.mktemp("ssb_overlap")))
    h.open()
    api = API(h, stats=MemStatsClient())
    api.coalescer = QueryCoalescer(api.executor, window_s=0.02,
                                   stats=api.stats)
    api.coalescer.start()
    http = serve(api, "localhost", 0, background=True)
    srv = _harness_server(http.server_address[1])
    lo = ssb.Lineorder(20090630, 1, ORDERS, SHARD_WIDTH)
    ssb.load(srv, lo)
    yield lo, api, srv.port
    srv.client.close()
    http.shutdown()
    http.server_close()
    api.coalescer.stop()
    h.close()


def _table(res) -> list:
    """A GroupBy's answer, the executor's or the reference's, as
    [(group rows, count, sum)] in its order."""
    if res and isinstance(res[0], dict):
        return [(tuple(g["rowID"] for g in w["group"]), w["count"],
                 w.get("sum")) for w in res]
    return [(tuple(fr.row_id for fr in gc.group), gc.count, gc.sum)
            for gc in res]


def _members(n: int) -> list:
    """n member queries: (pql, family or None, constants). The eight
    shapes with the specification's constants, then the same shapes
    without a filter, without the aggregate, paged and limited."""
    out = []
    for name in SHAPES:
        fam = ssb.FAMILIES[name]
        out.append((fam.pql(fam.fixed), name, fam.fixed))
    out.append(("GroupBy(Rows(d_year), Rows(c_region))", None, None))
    out.append(("GroupBy(Rows(s_region), Rows(d_year), "
                "aggregate=Sum(field=lo_revenue), limit=7)", None, None))
    out.append(("GroupBy(Rows(c_nation), Rows(d_year), "
                "filter=Row(s_region=1), previous=[3, 1994], limit=9)",
                None, None))
    return [out[(3 * i) % len(out)] for i in range(n)]


def _alone(holder, index, pql):
    (res,) = Executor(holder).execute(index, pql)
    return res


def _batch(ex, index, queries, **kw):
    out = ex.execute_batch([(index, q, None) for q in queries], **kw)
    for q, r in zip(queries, out):
        assert not isinstance(r, Exception), (q, r)
    return [r[0][0] for r in out]


# ------------------------------------------------- (a) the same answers

# case -> (members, GROUPBY_CHUNK_BYTES or None)
ANSWERS = {"two": (2, None), "four": (4, None), "nine": (9, None),
           "nine_in_chunks": (9, 1 << 17), "four_spilled": (4, 1 << 15),
           "eleven_spilled": (11, 1 << 15)}


@pytest.mark.parametrize("case", list(ANSWERS))
def test_a_batch_answers_as_each_member_alone_and_as_the_reference(
        served, monkeypatch, case):
    lo, api, _ = served
    n, chunk_bytes = ANSWERS[case]
    if chunk_bytes:
        monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", chunk_bytes)
    members = _members(n)
    before = dict(api.stats.snapshot()["counters"])
    got = _batch(api.executor, ssb.INDEX, [m[0] for m in members])
    after = api.stats.snapshot()["counters"]
    some = 0
    for (pql, family, c), res in zip(members, got):
        assert _table(res) == _table(_alone(api.holder, ssb.INDEX, pql)), pql
        if family is not None:
            want = ssb.answer(lo, family, c)
            assert _table(res) == _table(want), pql
            some += bool(want)
    assert some or n < 3
    if chunk_bytes:
        # The small limit binds: more level programs than levels, and
        # at 32 KiB a level's prefixes move to host memory.
        levels = after["executor.groupby_levels"] \
            - before["executor.groupby_levels"]
        assert levels > 3 * n
        spills = after["executor.groupby_spills"] \
            - before["executor.groupby_spills"]
        assert (spills > 0) == (chunk_bytes == 1 << 15)


# -------------------------------------------- (b) the order of events


class _Events:
    """What a run launched and fetched, in order, by member (the
    profile on the thread then): ("launch", member, program, level)
    at every `dispatch` span, ("fetch", member) at every `d2h`."""

    def __init__(self, ex, monkeypatch):
        self.log = []
        span, transfer = ex._dispatch_span, ex_mod.transfer

        def dispatch_span(program, **attrs):
            self.log.append(("launch", ex._profile(), program,
                             attrs.get("level")))
            return span(program, **attrs)

        def logged_transfer(direction, *a, **kw):
            if direction == "d2h":
                self.log.append(("fetch", ex._profile()))
            return transfer(direction, *a, **kw)

        monkeypatch.setattr(ex, "_dispatch_span", dispatch_span)
        monkeypatch.setattr(ex_mod, "transfer", logged_transfer)

    def of(self, member) -> list:
        return [e[:1] + e[2:] for e in self.log if e[1] is member]


def test_alone_every_level_program_is_fetched_before_the_next(
        served, monkeypatch):
    """The parent's sequence: launch, fetch, launch, fetch, ... and the
    group sums, which nobody waits for, last."""
    _, api, _ = served
    ex = Executor(api.holder)
    events = _Events(ex, monkeypatch)
    prof = QueryProfile(ssb.INDEX, "q")
    fam = ssb.FAMILIES["q3.1"]
    (res,) = ex.execute(ssb.INDEX, fam.pql(fam.fixed), profile=prof)
    assert len(res) > 10
    seq = [e for e in events.of(prof) if e[1:2] != ("tree_row",)]
    levels = [e for e in seq if e[:2] == ("launch", "groupby")]
    assert [lv[2] for lv in levels] == ["exp", "exp", "cntN"]
    i = 0
    for lv in levels:
        assert seq[i] == lv and seq[i + 1] == ("fetch",)
        i += 2
    assert seq[i:] and all(e[:2] == ("launch", "groupby_sum")
                           or e == ("fetch",) for e in seq[i:])
    assert seq[i][:2] == ("launch", "groupby_sum")


def test_in_a_batch_the_members_programs_are_queued_behind_each_fetch(
        served, monkeypatch):
    _, api, _ = served
    ex = Executor(api.holder)
    members = [m[0] for m in _members(9)]
    alone = []
    for pql in members:
        one = Executor(api.holder)
        ev = _Events(one, monkeypatch)
        prof = QueryProfile(ssb.INDEX, pql)
        one.execute_batch([(ssb.INDEX, pql, None)], profiles=[prof])
        alone.append(ev.of(prof))
        monkeypatch.undo()
    events = _Events(ex, monkeypatch)
    profs = [QueryProfile(ssb.INDEX, q) for q in members]
    _batch(ex, ssb.INDEX, members, profiles=profs)
    # Every member launched and fetched what it does alone, in its order.
    for prof, seq in zip(profs, alone):
        assert events.of(prof) == seq
    # In flight: a level program launched, its counts not yet fetched.
    flying, most, started = {}, 0, []
    for k, e in enumerate(events.log):
        who = e[1]
        if e[:1] == ("fetch",):
            if who in flying:
                # The dispatcher blocks on the earliest launched.
                assert who is min(flying, key=flying.get)
                del flying[who]
        elif e[2] == "groupby":
            flying[who] = k
            if who not in started:
                started.append(who)
            most = max(most, len(flying))
    assert most == BOUND and not flying
    assert started == profs, "members start in the order of the requests"
    # Between a member's first and last fetch another member launches.
    a = profs[0]
    at = [k for k, e in enumerate(events.log) if e == ("fetch", a)]
    assert len(at) >= 2
    assert any(e[0] == "launch" and e[1] is not a and e[2] == "groupby"
               for e in events.log[at[0]:at[-1]])


# ---------------------------------- a small index of the test's own


N = 2400


def _small(holder):
    """Index `ov`: set fields a (5 rows), b (6), c (4) over N columns of
    two shards, every combination populated; filter field f, row 1."""
    rng = np.random.default_rng(45)
    cols = np.sort(rng.choice(2 * SHARD_WIDTH, N, replace=False)) \
        .astype(np.uint64)
    i = np.arange(N)
    keys = {"a": i % 5, "b": (i // 5) % 6, "c": (i // 30) % 4}
    idx = holder.create_index("ov")
    for name, v in keys.items():
        idx.create_field(name).import_bits(v.astype(np.uint64), cols)
    on = i % 3 != 0
    idx.create_field("f").import_bits(
        np.ones(int(on.sum()), np.uint64), cols[on])
    idx.add_existence(cols)
    return cols


AB = "GroupBy(Rows(a), Rows(b))"
ABC = "GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(f=1))"


# ------------------------------------------------- (c) a write is a fence


def test_a_write_between_two_groupbys_is_a_fence(tmp_holder):
    cols = _small(tmp_holder)
    ex = Executor(tmp_holder)
    free = next(c for c in range(2 * SHARD_WIDTH) if c not in set(
        cols.tolist()))
    before = _table(_alone(tmp_holder, "ov", AB))
    out = ex.execute_batch([("ov", AB, None),
                            ("ov", f"Set({free}, a=2) Set({free}, b=3)",
                             None),
                            ("ov", AB, None), ("ov", ABC, None)])
    assert not any(isinstance(r, Exception) for r in out)
    first, second = _table(out[0][0][0]), _table(out[2][0][0])
    assert first == before
    want = [(g, n + (g == (2, 3)), s) for g, n, s in before]
    assert second == want != first
    assert second == _table(_alone(tmp_holder, "ov", AB))
    assert out[1][0] == [True, True]
    # A GroupBy and a write in ONE member: the member runs to its end
    # before the next one starts, and sees its own write.
    free2 = free + 1
    assert free2 not in set(cols.tolist())
    out = ex.execute_batch([("ov", ABC, None),
                            ("ov", f"{AB} Set({free2}, a=2) "
                             f"Set({free2}, b=3) {AB}", None),
                            ("ov", AB, None)])
    assert not any(isinstance(r, Exception) for r in out)
    res = out[1][0]
    assert _table(res[0]) == want
    twice = [(g, n + (g == (2, 3)), s) for g, n, s in want]
    assert _table(res[3]) == twice == _table(out[2][0][0])


# --------------------------------------- (d) a member that raises


def test_a_member_that_raises_in_its_second_level_fails_alone(
        tmp_holder, monkeypatch):
    _small(tmp_holder)
    ex = Executor(tmp_holder)
    queries = [ABC, AB, ABC, AB, ABC, AB]
    want = [_table(_alone(tmp_holder, "ov", q)) for q in queries]
    profs = [QueryProfile("ov", q) for q in queries]
    span = ex._dispatch_span

    def failing(program, **attrs):
        if ex._profile() is profs[2] and attrs.get("level") == "exp":
            raise RuntimeError("the second level of member 2")
        return span(program, **attrs)

    monkeypatch.setattr(ex, "_dispatch_span", failing)
    out = ex.execute_batch([("ov", q, None) for q in queries],
                           profiles=profs)
    assert isinstance(out[2], RuntimeError)
    for j in (0, 1, 3, 4, 5):
        assert _table(out[j][0][0]) == want[j]
    assert not getattr(ex._tls, "later_writes", False)
    assert not getattr(ex._tls, "turns", False)
    assert ex._profile() is None


# ------------------------------ (e) what a member leaves behind it


def _ops(prof) -> list:
    def names(node):
        return (node.name, [names(c) for c in node.children])
    return [names(op) for op in prof.ops]


def test_profiles_captures_and_counters_are_each_members_own(tmp_holder):
    _small(tmp_holder)
    queries = [ABC, "Count(Row(a=1))", AB, "Count(Row(b=2))", ABC,
               "Count(Intersect(Row(c=1), Row(f=1)))", AB + " " + ABC]

    def run(batch):
        ex = Executor(tmp_holder)
        ex.stats = MemStatsClient()
        profs = [QueryProfile("ov", q) for q in batch]
        deps = [{} for _ in batch]
        out = ex.execute_batch([("ov", q, None) for q in batch],
                               profiles=profs, deps=deps)
        assert not any(isinstance(r, Exception) for r in out)
        c = ex.stats.snapshot()["counters"]
        return ([_ops(p) for p in profs], deps,
                {k: c.get(f"executor.{k}", 0)
                 for k in ("groupby_levels", "groupby_groups")},
                [p.totals["dispatch"] for p in profs])

    ops, deps, counters, _ = run(queries)
    levels = groups = 0
    for j, q in enumerate(queries):
        ops1, deps1, c1, _ = run([q])
        assert ops[j] == ops1[0], q
        assert deps[j] == deps1[0], q
        levels += c1["groupby_levels"]
        groups += c1["groupby_groups"]
    assert counters == {"groupby_levels": levels, "groupby_groups": groups}
    # A capture names what ITS member read, not what ran between its
    # turns: a GroupBy poisons its own and nobody else's.
    assert all(("uncacheable" in d) == q.startswith("GroupBy")
               for q, d in zip(queries, deps))
    assert len({frozenset(map(str, d)) for q, d in zip(queries, deps)
                if q.startswith("Count")}) == 3


def test_a_members_dispatch_seconds_leave_out_the_others_turns(
        tmp_holder, monkeypatch):
    """Every fetch is made to take 20 ms: a member's op counts its own
    waits — three for ABC, as alone — and not the five other members'."""
    import time
    _small(tmp_holder)
    ex = Executor(tmp_holder)
    ex.execute("ov", ABC)
    transfer = ex_mod.transfer

    def slow(direction, *a, **kw):
        if direction == "d2h":
            time.sleep(0.02)
        return transfer(direction, *a, **kw)

    monkeypatch.setattr(ex_mod, "transfer", slow)
    profs = [QueryProfile("ov", ABC) for _ in range(6)]
    t0 = time.perf_counter()
    ex.execute_batch_begin([("ov", ABC, None)] * 6, profiles=profs)
    wall = time.perf_counter() - t0
    assert wall > 6 * 3 * 0.02
    for p in profs:
        (op,) = p.ops
        assert 3 * 0.02 <= op.attrs["dispatchS"] < wall / 2


def test_the_flush_record_still_tiles(tmp_holder):
    """`plan` + `h2d` + `dispatch` + `d2h` of a flush whose members
    took turns add up to the dispatcher's wall time: no stage stays
    open over another member's work, none is counted twice."""
    _small(tmp_holder)
    ex = Executor(tmp_holder)
    batch = [("ov", q, None) for q in (ABC, AB, ABC, AB, ABC, ABC)]
    ex.execute_batch(batch)         # compiles
    rec = TIMELINE.begin(None, name="coalescer.flush", kind="flush")
    with TIMELINE.attached(rec, "thread.begin"):
        flight = ex.execute_batch_begin(batch)
    with TIMELINE.attached(rec, "thread.finish"):
        out = ex.execute_batch_finish(flight)
    TIMELINE.finish(rec)
    assert not any(isinstance(r, Exception) for r in out)
    begin, _ = rec.sections
    mine = [s for s in rec.root.children if s.pc_end <= begin.pc_end]
    assert {s.name for s in mine} == {"plan", "h2d", "dispatch", "d2h"}
    assert all(a.pc_end <= b.pc_start + 1e-9
               for a, b in zip(mine, mine[1:])), "stages overlap"
    covered = sum(s.pc_end - s.pc_start for s in mine)
    assert covered == pytest.approx(begin.pc_end - begin.pc_start,
                                    rel=0.02, abs=2e-4)
    # 3 fetches an ABC, 2 an AB: every one a `d2h` of the begin half.
    assert sum(s.name == "d2h" for s in mine) == 4 * 3 + 2 * 2
    assert not TIMELINE._stack()


# ------------------------------------------- (f) the counter of cover


def _fetches(stats) -> tuple:
    c = stats.snapshot()["counters"]
    return (c.get("executor.groupby_fetches{covered:yes}", 0),
            c.get("executor.groupby_fetches{covered:no}", 0))


def test_fetches_are_uncovered_alone_and_mostly_covered_in_a_batch(
        served):
    _, api, _ = served
    members = [m[0] for m in _members(8)]
    ex = Executor(api.holder)
    ex.stats = MemStatsClient()
    for pql in members:
        ex.execute(ssb.INDEX, pql)
    for pql in members[:3]:
        ex.execute_batch([(ssb.INDEX, pql, None)])
    yes, no = _fetches(ex.stats)
    levels = ex.stats.snapshot()["counters"]["executor.groupby_levels"]
    assert yes == 0 and no == levels > 20
    ex.stats = MemStatsClient()
    _batch(ex, ssb.INDEX, members)
    yes, no = _fetches(ex.stats)
    assert yes + no == ex.stats.snapshot()["counters"][
        "executor.groupby_levels"]
    # Uncovered: the fetches the last member makes once it is alone.
    assert yes > 3 * no > 0


def test_a_burst_through_the_server_is_one_flush_of_members_in_turn(
        served):
    """HTTP, the coalescer's pipelined flush and its finalizer thread:
    eight clients at once (the test holds a request of its own, so the
    first to arrive is not alone and waits the window out)."""
    import threading
    lo, api, port = served
    members = _members(8)
    for pql, _, _ in members:            # compiled, so the burst is short
        api.executor.execute(ssb.INDEX, pql)
    before = _fetches(api.stats)
    got = [None] * len(members)

    def post(k):
        one = _harness_server(port)
        try:
            got[k] = one.query(ssb.INDEX, members[k][0])
        finally:
            one.client.close()

    api.held.open()
    try:
        ts = [threading.Thread(target=post, args=(k,))
              for k in range(len(members))]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in ts)
    finally:
        api.held.close()
    for (pql, family, c), res in zip(members, got):
        if family is not None:
            assert ssb.equal(res, ssb.answer(lo, family, c)), pql
        else:
            assert _table(res) == _table(_alone(api.holder, ssb.INDEX, pql))
    yes, no = _fetches(api.stats)
    assert yes - before[0] > no - before[1] >= 0


def test_the_counter_is_published_at_zero_before_any_query(tmp_holder):
    from pilosa_tpu.server import API
    api = API(tmp_holder, stats=MemStatsClient())
    assert _fetches(api.stats) == (0, 0)
    c = api.stats.snapshot()["counters"]
    assert "executor.groupby_fetches{covered:yes}" in c
    assert "executor.groupby_fetches{covered:no}" in c
