"""`GroupBy(..., aggregate=Sum(field=f))` and the deployment that needs
it (PR 32): the Star Schema Benchmark's 13 queries through a served
executor — HTTP, the coalescer, `_execute_group_by` — against the plain
reference of `benchmark/datasets/ssb.py` on a few thousand seeded
orders; the operator's rules by hand (signed values, nulls, paging,
depth, pruning, chunked launches, a mesh); and what it must refuse."""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from datasets import ssb  # noqa: E402
from harness.server import BenchFailure, Client, Server  # noqa: E402

ORDERS = 3000


def _harness_server(port: int) -> Server:
    """The harness's `Server` surface (request / get / post_json /
    query) over a server this process already runs."""
    srv = Server.__new__(Server)
    srv.port, srv.client = port, Client(port)
    return srv


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One in-process server with the coalescer on and every default,
    loaded by the deployment's own loader through the public routes."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.server import API, serve
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    h = Holder(str(tmp_path_factory.mktemp("ssb")))
    h.open()
    api = API(h, stats=MemStatsClient())
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats)
    api.coalescer.start()
    http = serve(api, "localhost", 0, background=True)
    srv = _harness_server(http.server_address[1])
    lo = ssb.Lineorder(20090630, 1, ORDERS, SHARD_WIDTH)
    ssb.load(srv, lo)
    yield srv, lo, api
    srv.client.close()
    http.shutdown()
    http.server_close()
    api.coalescer.stop()
    h.close()


# ------------------------------------------- the 13 families, served


@pytest.mark.parametrize("family", list(ssb.FAMILIES))
def test_family_equals_the_reference(served, family):
    """The specification's own constants, then drawn ones: every
    group, count and sum equal, in order."""
    srv, lo, _ = served
    fam = ssb.FAMILIES[family]
    draws = ssb.Draws({}, np.random.default_rng([32, len(family)]))
    some = 0
    for c in [fam.fixed] + [fam.draw(draws) for _ in range(4)]:
        got = srv.query(ssb.INDEX, fam.pql(c))
        want = ssb.answer(lo, family, c)
        assert ssb.equal(got, want), (fam.pql(c), got[:2] if fam.groups
                                      else got, want[:2] if fam.groups
                                      else want)
        some += bool(want) if fam.groups else want["count"] > 0
        if fam.groups:
            assert all(set(g) == {"group", "count", "sum"} for g in got)
    assert some or family in ("q1.2", "q1.3", "q3.4"), \
        "a family that selects nothing at this size proves nothing"


def test_the_loader_covers_every_lineorder_row(served):
    srv, lo, _ = served
    assert lo.n == int(np.sum(np.ones(lo.n))) and 2 * ORDERS < lo.n
    assert srv.query(ssb.INDEX, "Count(Union(" + ", ".join(
        f"Row(c_region={r})" for r in range(5)) + "))") == lo.n
    got = srv.query(ssb.INDEX, "Sum(field=lo_profit)")
    assert got == {"value": int(lo.lo_profit.astype(np.int64).sum()),
                   "count": lo.n}
    assert ssb.INT_FIELDS["lo_profit"][0] < 0 < int(lo.lo_profit.min())


def test_group_sums_add_up_to_the_sum_over_the_same_filter(served):
    srv, lo, _ = served
    filt = "Intersect(Row(s_region=1), Row(lo_quantity < 30))"
    groups = srv.query(ssb.INDEX, "GroupBy(Rows(d_year), Rows(c_nation), "
                       f"filter={filt}, aggregate=Sum(field=lo_profit))")
    total = srv.query(ssb.INDEX, f"Sum({filt}, field=lo_profit)")
    assert len(groups) > 20
    assert sum(g["sum"] for g in groups) == total["value"]
    assert sum(g["count"] for g in groups) == total["count"]


def test_the_counters_and_the_span_move(served):
    from pilosa_tpu.utils.timeline import TIMELINE
    srv, lo, api = served
    before = dict(api.stats.snapshot()["counters"])
    recorded = TIMELINE.requests_recorded
    fam = ssb.FAMILIES["q2.1"]
    got = srv.query(ssb.INDEX, fam.pql(fam.fixed))
    # The record closes in the handler's finally block, AFTER the
    # response body went out: the client can get here first.
    for _ in range(400):
        if TIMELINE.requests_recorded > recorded:
            break
        time.sleep(0.005)
    after = api.stats.snapshot()["counters"]

    def moved(name):
        return after[name] - before.get(name, 0)

    assert moved("executor.groupby_groups") == len(got) > 0
    assert moved("executor.groupby_levels") >= 2
    assert moved("executor.groupsum_launches") == 1     # not one a group
    planes = ssb.bit_depth("lo_revenue") + 1
    assert moved("executor.groupsum_plane_rows") == len(got) * planes
    spans = [s for rec in TIMELINE.requests(last=8)
             for s in rec.root.walk()]
    agg = [s for s in spans if s.name == "groupby.aggregate"]
    assert agg and agg[-1].attrs["groups"] == len(got)
    assert agg[-1].attrs["planes"] == planes
    assert agg[-1].attrs["launches"] == 1
    assert "groupby_sum" in {s.attrs.get("program") for s in spans
                             if s.name == "dispatch"}


@pytest.mark.parametrize("bad,why", [
    ("Sum(field=c_nation)", "not an int field"),
    ("Sum(field=nope)", "field not found"),
    ("Count(Row(c_nation=1))", "must be Sum(field="),
    ("Min(field=lo_profit)", "must be Sum(field="),
    ("Sum(Row(c_nation=1), field=lo_profit)", "must be Sum(field="),
    ("3", "must be Sum(field="),
])
def test_another_aggregate_is_a_400_with_a_message(served, bad, why):
    srv, _, _ = served
    req = urllib.request.Request(
        f"http://localhost:{srv.port}/index/{ssb.INDEX}/query",
        data=f"GroupBy(Rows(d_year), aggregate={bad})".encode())
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=60)
    assert e.value.code == 400
    assert why in e.value.read().decode()


def test_without_aggregate_the_json_is_the_parents(served):
    """No `sum` key, and the bytes a parent commit wrote: group, count."""
    srv, lo, _ = served
    status, body = srv.client.request(
        "POST", f"/index/{ssb.INDEX}/query",
        b"GroupBy(Rows(p_mfgr), Rows(c_region), filter=Row(d_year=1994), "
        b"limit=3)", "text/plain")
    assert status == 200
    m = lo.d_year == 1994
    want = []
    for mfgr in range(5):
        for region in range(5):
            n = int((m & (lo.p_mfgr == mfgr)
                     & (lo.c_region == region)).sum())
            if n:
                want.append({"group": [{"field": "p_mfgr", "rowID": mfgr},
                                       {"field": "c_region",
                                        "rowID": region}], "count": n})
    assert json.loads(body) == {"results": [want[:3]]}
    assert b"sum" not in body


def test_a_server_without_the_operator_is_refused():
    """The parent commit answers the probe 200 with counts alone."""
    class Parent:
        def post_json(self, *a):
            return {}

        def request(self, *a):
            return {}

        def query(self, index, pql):
            return [{"group": [{"field": "g", "rowID": 1}], "count": 2},
                    {"group": [{"field": "g", "rowID": 2}], "count": 1}]

    with pytest.raises(BenchFailure, match="no GroupBy aggregate"):
        ssb.refuse_no_aggregate(Parent())


def test_least_bytes_by_hand():
    cfg = {"shards": 16, "shard_width": 1 << 20}
    row = 16 * (1 << 20) // 8                   # 2 MiB
    # Q2.1: Rows(d_year) 7 + Rows(p_brand1) 1,000 + the filter's two
    # rows + lo_revenue's 24 planes and its not-null plane.
    c = ssb.FAMILIES["q2.1"].fixed
    assert ssb.operand_rows("q2.1", c) == 7 + 1000 + 2 + 25
    assert ssb.least_bytes("q2.1", c, cfg) == 1034 * row
    # Q3.2: two city fields, d_year (its six filter rows are among the
    # seven the group names), two nation rows, the same 25 planes.
    c = ssb.FAMILIES["q3.2"].fixed
    assert ssb.operand_rows("q3.2", c) == 250 + 250 + 7 + 2 + 25
    # Q1.1: one year row, discount's 4 + 1, quantity's 6 + 1,
    # lo_revenue_computed's 27 + 1.
    c = ssb.FAMILIES["q1.1"].fixed
    assert ssb.operand_rows("q1.1", c) == 1 + 5 + 7 + 28
    # Q4.3: 7 + 250 + 1,000, three filter rows beside the years, and
    # lo_profit's 24 + 1.
    c = ssb.FAMILIES["q4.3"].fixed
    assert ssb.least_bytes("q4.3", c, cfg) == (1257 + 3 + 25) * row


# --------------------------------------------- the operator, by hand


@pytest.fixture
def ex(tmp_holder):
    """Three set fields and a signed int field with nulls over two
    shards, beside the arrays a brute-force answer is read from."""
    rng = np.random.default_rng(32)
    n = 4000
    cols = np.sort(rng.choice(2 * SHARD_WIDTH, n, replace=False)) \
        .astype(np.uint64)
    keys = {"a": rng.integers(0, 5, n), "b": rng.integers(0, 40, n),
            "c": rng.integers(0, 3, n)}
    idx = tmp_holder.create_index("s")
    for name, v in keys.items():
        idx.create_field(name).import_bits(v.astype(np.uint64), cols)
    vals = rng.integers(-1000, 5000, n)
    has = rng.random(n) < 0.8
    idx.create_field("v", FieldOptions(type="int", min=-1000, max=5000)) \
        .import_values(cols[has], vals[has])
    # Row 1 of `null` marks the columns that have no value in `v`.
    idx.create_field("null").import_bits(
        np.ones(int((~has).sum()), np.uint64), cols[~has])
    idx.add_existence(cols)
    return Executor(tmp_holder), keys, vals, has


def _brute(keys, names, vals, has, mask):
    out = {}
    for i in np.flatnonzero(mask):
        k = tuple(int(keys[f][i]) for f in names)
        n, s = out.get(k, (0, 0))
        out[k] = (n + 1, s + (int(vals[i]) if has[i] else 0))
    return [(k, *out[k]) for k in sorted(out)]


def _groups(e, pql):
    (res,) = e.execute("s", pql)
    return [(tuple(fr.row_id for fr in gc.group), gc.count, gc.sum)
            for gc in res]


@pytest.mark.parametrize("names", [("a",), ("a", "b"), ("a", "b", "c")])
def test_signed_sums_with_nulls_at_every_depth(ex, names):
    """A column with no value counts in its group and adds nothing; the
    field's minimum is negative, and so are some sums."""
    e, keys, vals, has = ex
    rows = ", ".join(f"Rows({f})" for f in names)
    every = np.ones(len(vals), bool)
    got = _groups(e, f"GroupBy({rows}, aggregate=Sum(field=v))")
    assert got == _brute(keys, names, vals, has, every)
    assert sum(n for _, n, _ in got) == len(vals) > int(has.sum())
    got = _groups(e, f"GroupBy({rows}, filter=Row(v < 0), "
                  "aggregate=Sum(field=v))")
    assert got == _brute(keys, names, vals, has, has & (vals < 0))
    assert got and all(s < 0 for _, _, s in got)


def test_a_group_of_nulls_alone_has_sum_zero(ex):
    e, keys, vals, has = ex
    got = _groups(e, "GroupBy(Rows(a), filter=Row(null=1), "
                  "aggregate=Sum(field=v))")
    assert got == _brute(keys, ("a",), vals, has, ~has)
    assert got and all(n > 0 and s == 0 for _, n, s in got)


def test_an_empty_filter_answers_no_group(ex):
    e, *_ = ex
    assert e.execute("s", "GroupBy(Rows(a), Rows(b), filter=Row(c=7), "
                     "aggregate=Sum(field=v))") == [[]]


def test_limit_and_previous_page_the_groups_with_their_sums(ex):
    e, keys, vals, has = ex
    every = _brute(keys, ("a", "b"), vals, has, np.ones(len(vals), bool))
    got = _groups(e, "GroupBy(Rows(a), Rows(b), aggregate=Sum(field=v), "
                  "limit=7, previous=[1, 3])")
    assert got == [g for g in every if g[0] > (1, 3)][:7]
    assert _groups(e, "GroupBy(Rows(a), Rows(b), limit=2, "
                   "aggregate=Sum(field=v))") == every[:2]


def test_pruned_children_and_chunked_launches_answer_the_same(
        ex, monkeypatch):
    """Small budgets: b's stack of 40 rows is 'large', so it is swept
    against the filter and padded with zero rows; the last level goes
    five prefixes a chunk, and a launch sums at most eight groups."""
    e, keys, vals, has = ex
    pql = ("GroupBy(Rows(a), Rows(b), Rows(c), filter=Row(v > 100), "
           "aggregate=Sum(field=v))")
    want = _brute(keys, ("a", "b", "c"), vals, has, has & (vals > 100))
    assert _groups(e, pql) == want
    monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", 1 << 22)
    monkeypatch.setattr(Executor, "GROUPSUM_CHUNK_BYTES", 1 << 21)
    from pilosa_tpu.utils.stats import MemStatsClient
    e.stats = MemStatsClient()
    assert _groups(e, pql) == want
    c = e.stats.snapshot()["counters"]
    assert c["executor.groupby_groups"] == len(want)
    assert 1 < c["executor.groupsum_launches"] < len(want) / 4
    assert any(k.startswith("gb_prune:") for k in e._jit_cache)
    # A level of one prefix a chunk hands on several prefix arrays: the
    # last level's program reads them end to end ("+" in its key).
    assert any(k.startswith("gb_cntN:") and "+" in k for k in e._jit_cache)


def test_rows_that_share_columns_each_get_the_columns_value(tmp_holder):
    """A set field's rows need not be disjoint: a column in two rows
    of the last level adds to both groups."""
    idx = tmp_holder.create_index("s")
    idx.create_field("t").import_bits(
        np.array([0, 0, 1, 1, 1], np.uint64),
        np.array([1, 2, 2, 3, SHARD_WIDTH + 4], np.uint64))
    idx.create_field("v", FieldOptions(type="int", min=-9, max=9)) \
        .import_values(np.array([1, 2, 3, SHARD_WIDTH + 4], np.uint64),
                       np.array([-4, 5, 6, -9]))
    (res,) = Executor(tmp_holder).execute(
        "s", "GroupBy(Rows(t), aggregate=Sum(field=v))")
    assert [gc.to_json() for gc in res] == [
        {"group": [{"field": "t", "rowID": 0}], "count": 2, "sum": 1},
        {"group": [{"field": "t", "rowID": 1}], "count": 3, "sum": 2}]


def test_a_wide_field_is_weighed_in_python_ints(tmp_holder):
    """Past 32 planes the plane counts no longer fit an int64 product."""
    idx = tmp_holder.create_index("s")
    idx.create_field("t").import_bits(np.array([3, 3], np.uint64),
                                      np.array([0, 9], np.uint64))
    big = (1 << 44) + 12345
    idx.create_field("v", FieldOptions(type="int", min=-big, max=big)) \
        .import_values(np.array([0, 9], np.uint64), np.array([big, -7]))
    (res,) = Executor(tmp_holder).execute(
        "s", "GroupBy(Rows(t), aggregate=Sum(field=v))")
    assert (res[0].count, res[0].sum) == (2, big - 7)


def test_on_a_mesh_the_answer_is_the_one_devices(ex):
    import jax
    from pilosa_tpu.parallel.mesh import MeshContext
    e, keys, vals, has = ex
    mesh = Executor(e.holder, mesh=MeshContext(jax.devices()[:4]))
    pql = ("GroupBy(Rows(a), Rows(c), filter=Row(v < 2000), "
           "aggregate=Sum(field=v))")
    assert _groups(mesh, pql) == _groups(e, pql) \
        == _brute(keys, ("a", "c"), vals, has, has & (vals < 2000))


# ------------------------------------------------ merges and encodings


def test_a_clusters_legs_merge_their_sums():
    from pilosa_tpu.parallel.cluster_executor import merge_results
    from pilosa_tpu.pql import parse_string
    call = parse_string(
        "GroupBy(Rows(a), aggregate=Sum(field=v))").calls[0]
    g = [{"field": "a", "rowID": 1}]
    parts = [[{"group": g, "count": 2, "sum": -5}],
             [{"group": g, "count": 3, "sum": 9},
              {"group": [{"field": "a", "rowID": 2}], "count": 1,
               "sum": 4}]]
    assert merge_results(call, parts) == [
        {"group": g, "count": 5, "sum": 4},
        {"group": [{"field": "a", "rowID": 2}], "count": 1, "sum": 4}]


def test_the_protobuf_group_count_carries_the_sum():
    from pilosa_tpu.server import proto_compat
    g = [{"group": [{"field": "a", "rowID": 1}], "count": 2}]
    plain = proto_compat._encode_result(g)
    g[0]["sum"] = 77
    with_sum = proto_compat._encode_result(g)
    assert len(with_sum) == len(plain) + 2 and with_sum != plain
