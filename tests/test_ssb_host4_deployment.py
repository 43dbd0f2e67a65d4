"""The Star Schema Benchmark on a host of four chips (PR 44,
deployment `ssb-host4`): the 13 queries served by ONE server whose
executor runs under a four-device mesh, over a shard count the mesh does
not divide — seven shards, padded to eight, so the last device holds one
real shard and one absent one (the host's own last device holds 13 and
two absent). A few thousand seeded LINEORDER rows from the deployment's
generator, cut in two halves A and B and laid out A B A B A B A: every
device of the mesh holds what a lone chip over shards 0 and 1 holds (the
last one half of it), so the groups that survive on the host are the
lone chip's and their launches can be compared one for one. The
reference is `datasets/ssb.py`'s over the rows as laid out.

What is held here: every family equals the reference; the host's table
is the sum of the four devices' partial tables; a chip of the mesh cuts
its levels and its sums as a lone chip with its shards does, and a lone
chip as it always did; a spill under the mesh answers the same and is
counted; the `dispatch` spans say how a level was cut."""

import os
import sys
import time

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from datasets import ssb  # noqa: E402
from datasets.taxi import roaring_bytes  # noqa: E402
from harness.server import Client, Server  # noqa: E402

ORDERS = 1500
SHARDS = 7                  # padded to 8 on four devices
DEVICES = 4
LONE = [0, 1]               # the shards a lone chip holds: A and B


def _rows(lo, slices):
    """The rows of `slices`, end to end, as a LINEORDER of their own
    (the reference reads the columns and `n`, nothing else)."""
    out = ssb.Lineorder.__new__(ssb.Lineorder)
    for name in (*ssb.N_ROWS, *ssb.INT_FIELDS):
        setattr(out, name,
                np.concatenate([lo.column(name)[sl] for sl in slices]))
    out.n = len(out.d_year)
    return out


def _load(srv, lo, parts) -> None:
    """`ssb.load`'s schema, payloads and routes, with shard s holding
    the rows `parts[s]` of `lo` from its column 0 on."""
    srv.post_json(f"/index/{ssb.INDEX}", {})
    for name in ssb.N_ROWS:
        srv.post_json(f"/index/{ssb.INDEX}/field/{name}", {"options": {}})
    for name, (lo_v, hi_v) in ssb.INT_FIELDS.items():
        srv.post_json(f"/index/{ssb.INDEX}/field/{name}",
                      {"options": {"type": "int", "min": lo_v, "max": hi_v}})
    for s, sl in enumerate(parts):
        cols = np.arange(sl.stop - sl.start)
        for name in ssb.N_ROWS:
            srv.request(
                "POST", f"/index/{ssb.INDEX}/field/{name}/import-roaring/{s}",
                roaring_bytes(lo.column(name)[sl], cols, SHARD_WIDTH),
                "application/octet-stream")
        for name, (lo_v, _) in ssb.INT_FIELDS.items():
            srv.request(
                "POST", f"/index/{ssb.INDEX}/field/{name}/import-roaring/{s}"
                f"?view=bsig_{name}",
                ssb.planes_bytes(lo.column(name)[sl].astype(np.int64) - lo_v,
                                 ssb.bit_depth(name), SHARD_WIDTH),
                "application/octet-stream")


@pytest.fixture(scope="module")
def host(tmp_path_factory):
    """One in-process server under a four-device mesh, coalescer on,
    every default, loaded through the public routes: (harness server,
    the generator's LINEORDER, the slice of it each shard holds, api)."""
    import jax
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel.mesh import MeshContext
    from pilosa_tpu.server import API, serve
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    h = Holder(str(tmp_path_factory.mktemp("ssb4")))
    h.open()
    api = API(h, mesh=MeshContext(jax.devices()[:DEVICES]),
              stats=MemStatsClient())
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats)
    api.coalescer.start()
    http = serve(api, "localhost", 0, background=True)
    srv = Server.__new__(Server)
    srv.port = http.server_address[1]
    srv.client = Client(srv.port)
    lo = ssb.Lineorder(20090630, 1, ORDERS, SHARD_WIDTH)
    halves = [slice(0, lo.n // 2), slice(lo.n // 2, lo.n)]
    parts = [halves[s % 2] for s in range(SHARDS)]
    _load(srv, lo, parts)
    yield srv, lo, parts, api
    srv.client.close()
    http.shutdown()
    http.server_close()
    api.coalescer.stop()
    h.close()


def _table(res) -> dict:
    """An executor's GroupBy result (flight 1: its ValCount), or the
    reference's answer, as {group rows: (count, sum)}."""
    if isinstance(res, dict):
        return {(): (res["count"], res["value"])}
    if not isinstance(res, list):
        return {(): (res.count, res.value)}
    if res and isinstance(res[0], dict):
        return {tuple(g["rowID"] for g in w["group"]): (w["count"], w["sum"])
                for w in res}
    return {tuple(fr.row_id for fr in gc.group): (gc.count, gc.sum)
            for gc in res}


def _constants(family):
    fam = ssb.FAMILIES[family]
    draws = ssb.Draws({}, np.random.default_rng([44, len(family)]))
    return [fam.fixed] + [fam.draw(draws) for _ in range(3)]


# --------------------------------- (a) the 13 families under the mesh


@pytest.mark.parametrize("family", list(ssb.FAMILIES))
def test_family_on_the_host_equals_the_reference(host, family):
    srv, lo, parts, api = host
    assert api.executor.mesh_devices == DEVICES
    assert api.holder.index(ssb.INDEX).available_shards() \
        == list(range(SHARDS))
    whole = _rows(lo, parts)
    assert whole.n == 3 * lo.n + (lo.n // 2)
    fam = ssb.FAMILIES[family]
    some = 0
    for c in _constants(family):
        got = srv.query(ssb.INDEX, fam.pql(c))
        want = ssb.answer(whole, family, c)
        assert ssb.equal(got, want), (fam.pql(c), str(got)[:200],
                                      str(want)[:200])
        some += bool(want) if fam.groups else want["count"] > 0
    assert some or family in ("q1.2", "q1.3", "q3.4"), \
        "a family that selects nothing at this size proves nothing"


# ----------------------------- (b) the shares add up to the whole


@pytest.mark.parametrize("family", list(ssb.FAMILIES))
def test_the_hosts_table_is_the_sum_of_its_devices_partial_tables(
        host, family):
    """What `ssb-chip` calls "this chip's partial table": a one-device
    executor over the shards ONE device of the mesh holds equals the
    reference over that device's rows, and group for group the four add
    up to the host's answer."""
    _, lo, parts, api = host
    fam = ssb.FAMILIES[family]
    pql = fam.pql(fam.fixed)
    ex = api.executor
    padded = ex.mesh.pad_shards(list(range(SHARDS)))
    blocks = ex.mesh.placement.blocks(len(padded))
    assert len(padded) == 8 and len(blocks) == DEVICES
    # The last device: one real shard and one absent.
    assert [s < SHARDS for s in padded[blocks[-1]]] == [True, False]
    (whole,) = ex.execute(ssb.INDEX, pql)
    lone = Executor(api.holder)
    total = {}
    for block in blocks:
        held = [s for s in padded[block] if s < SHARDS]
        (part,) = lone.execute(ssb.INDEX, pql, shards=held)
        got = _table(part)
        assert got == _table(ssb.answer(
            _rows(lo, [parts[s] for s in held]), family, fam.fixed))
        for k, (n, v) in got.items():
            n0, v0 = total.get(k, (0, 0))
            total[k] = (n0 + n, v0 + v)
    if not fam.groups and total[()][0] == 0:
        total = {(): (0, 0)}
    assert total == _table(whole)


# ------------------------ (c) a chip of the mesh cuts as a lone chip


def _launches(ex, pql, shards=None) -> tuple:
    """(table, level programs, group-sum launches, spills) of a query."""
    from pilosa_tpu.utils.stats import MemStatsClient
    keep, ex.stats = ex.stats, MemStatsClient()
    try:
        (res,) = ex.execute(ssb.INDEX, pql, shards=shards)
        c = ex.stats.snapshot()["counters"]
    finally:
        ex.stats = keep
    return (_table(res), c.get("executor.groupby_levels", 0),
            c.get("executor.groupsum_launches", 0),
            c.get("executor.groupby_spills", 0))


# family -> what the PARENT (a2cf22a) launches OFF a mesh over shards 0
# and 1 under the two limits below: (level programs, group-sum
# launches), read by running `_launches` on the parent's tree.
PARENT_LONE = {"q2.1": (3, 6), "q2.2": (3, 1), "q2.3": (3, 1),
               "q3.1": (16, 16), "q3.2": (19, 8), "q4.1": (3, 4),
               "q4.2": (13, 3), "q4.3": (12, 1)}
# ... and UNDER the mesh, where it priced a chunk by the whole array:
# the same eight queries took (7, 24), (7, 4), (7, 1), (32, 60),
# (44, 11), (7, 16), (20, 11), (16, 1).
SMALL = 1 << 17     # 8 prefixes of [2, 2048] u32, 2 of [8, 2048]


@pytest.mark.parametrize("family", list(PARENT_LONE))
def test_a_chip_of_the_mesh_launches_what_a_lone_chip_would(
        host, family, monkeypatch):
    """Small limits, so that they bind at this size. Eight shards over
    four devices are two a device, each device holds what shards 0 and
    1 hold, and the prefixes and masks are cut by what ONE device holds
    of them: as many level and sum launches as a lone chip makes over
    shards 0 and 1 — which are as many as the parent made there."""
    _, lo, parts, api = host
    monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", SMALL)
    monkeypatch.setattr(Executor, "GROUPSUM_CHUNK_BYTES", SMALL)
    fam = ssb.FAMILIES[family]
    pql = fam.pql(fam.fixed)
    table, levels, sums, _ = _launches(api.executor, pql)
    assert table == _table(ssb.answer(_rows(lo, parts), family, fam.fixed))
    lone = Executor(api.holder)
    l_table, l_levels, l_sums, _ = _launches(lone, pql, shards=LONE)
    assert set(l_table) == set(table), "the same groups survive on both"
    print("LAUNCHES", family, "mesh", (levels, sums), "lone",
          (l_levels, l_sums), "groups", len(table))
    assert (l_levels, l_sums) == PARENT_LONE[family]
    assert (levels, sums) == (l_levels, l_sums)
    # Some limit binds: more than one launch of some kind a level.
    assert levels > len(fam.groups) or sums > 1


def test_the_limits_price_one_devices_share(host):
    """The arithmetic itself: a prefix [8, w] split over four devices is
    priced as the [2, w] a device holds, and off a mesh as it is."""
    *_, api = host
    w = 2048
    assert api.executor._bank_device_bytes((1, 8, w)) == 2 * w * 4
    assert Executor(api.holder)._bank_device_bytes((1, 8, w)) == 8 * w * 4


# --------------------------------------- (d) a spill under the mesh


def test_a_spill_under_the_mesh_answers_the_same_and_is_counted(
        host, monkeypatch):
    """A limit of two prefixes a device: every level that keeps more
    moves its prefix arrays to host memory, and the next level's chunks
    come back up split over the devices (`MeshContext.put_row`), never
    whole onto device 0."""
    _, lo, parts, api = host
    ex = api.executor
    fam = ssb.FAMILIES["q3.1"]
    pql = fam.pql(fam.fixed)
    want = _table(ssb.answer(_rows(lo, parts), "q3.1", fam.fixed))
    table, _, _, spills = _launches(ex, pql)
    assert table == want and spills == 0
    put = []
    monkeypatch.setattr(
        ex.mesh, "put_row",
        lambda arr, _put=ex.mesh.put_row: put.append(arr.shape) or _put(arr))
    monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", 1 << 15)
    table, levels, _, spills = _launches(ex, pql)
    assert table == want and len(table) > 50
    assert spills >= 1 and levels > 10
    assert put and all(len(s) == 3 and s[1:] == (8, 2048) for s in put)
    # A spilled chunk's prefixes are gathered on the host: its program
    # takes no index vector for them (None where the key holds it).
    assert any(k.startswith("gb_") and k.split(":")[2] == "None"
               and k.split(":")[1].startswith("(")
               and k.split(":")[1].count(",") == 2
               for k in ex._jit_cache)


def test_the_spill_counter_is_published_at_zero_before_any_query(tmp_holder):
    from pilosa_tpu.server import API
    from pilosa_tpu.utils.stats import MemStatsClient
    api = API(tmp_holder, stats=MemStatsClient())
    counters = api.stats.snapshot()["counters"]
    assert counters["executor.groupby_spills"] == 0
    assert not hasattr(api.executor, "groupby_spill_events")


# ------------------------- (e) the record says how a level was cut


def test_the_dispatch_spans_say_how_a_level_was_cut(host, monkeypatch):
    from pilosa_tpu.utils.timeline import TIMELINE
    srv, lo, parts, api = host
    monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", SMALL)
    monkeypatch.setattr(Executor, "GROUPSUM_CHUNK_BYTES", SMALL)
    fam = ssb.FAMILIES["q3.1"]
    recorded = TIMELINE.requests_recorded
    got = srv.query(ssb.INDEX, fam.pql(fam.fixed))
    assert len(got) > 50
    for _ in range(400):    # the record closes after the reply is sent
        if TIMELINE.requests_recorded > recorded:
            break
        time.sleep(0.005)
    spans = [s for rec in TIMELINE.requests(last=4)
             for s in rec.root.walk() if s.name == "dispatch"]
    levels = [s.attrs for s in spans if s.attrs.get("program") == "groupby"]
    sums = [s.attrs for s in spans
            if s.attrs.get("program") == "groupby_sum"]
    assert levels and sums
    assert {a["level"] for a in levels} == {"prune", "exp", "cntN"}
    # A pruning sweep reads its whole bank once: rows, and no chunk.
    assert all(a["rows"] == 25 and "chunk_of" not in a
               for a in levels if a["level"] == "prune")
    levels = [a for a in levels if a["level"] != "prune"]
    for a in levels + sums:
        assert a["mesh_devices"] == DEVICES and a["jit"] in ("hit", "miss")
        k, n = map(int, a["chunk_of"].split("/"))
        assert 1 <= k <= n
    assert all(a["prefixes"] >= 1 and a["rows"] >= 1 for a in levels)
    # The last level went several chunks of at most eight prefixes
    # (SMALL over the 16 KiB a device holds of one), numbered in order.
    last = [a for a in levels if a["level"] == "cntN"]
    assert len(last) > 1 and max(a["prefixes"] for a in last) <= 8
    assert [a["chunk_of"] for a in last] \
        == [f"{i + 1}/{len(last)}" for i in range(len(last))]
    assert all(a["lanes"] >= 8 and a["prefixes"] >= 1 for a in sums)


# ------------- (f) a flush's GroupBy members take turns under the mesh


# case -> (members, GROUPBY_CHUNK_BYTES or None)
TURNS = {"four": (4, None), "nine": (9, None), "nine_in_chunks": (9, SMALL),
         "six_spilled": (6, 1 << 15)}


@pytest.mark.parametrize("case", list(TURNS))
def test_a_batch_of_groupbys_on_the_host_answers_as_each_alone(
        host, monkeypatch, case):
    """PR 45: inside `execute_batch` the members' level loops are
    resumed in turn (tests/test_groupby_overlap.py); under the mesh
    every level program and every count fetch is a four-device one. The
    answers are the reference's, and the launches add up to what the
    members make one by one."""
    _, lo, parts, api = host
    n, chunk_bytes = TURNS[case]
    if chunk_bytes:
        monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", chunk_bytes)
        monkeypatch.setattr(Executor, "GROUPSUM_CHUNK_BYTES", SMALL)
    families = [list(PARENT_LONE)[(3 * i) % len(PARENT_LONE)]
                for i in range(n)]
    queries = [ssb.FAMILIES[f].pql(ssb.FAMILIES[f].fixed) for f in families]
    ex = api.executor
    alone = [_launches(ex, q) for q in queries]
    from pilosa_tpu.utils.stats import MemStatsClient
    keep, ex.stats = ex.stats, MemStatsClient()
    try:
        out = ex.execute_batch([(ssb.INDEX, q, None) for q in queries])
        c = ex.stats.snapshot()["counters"]
    finally:
        ex.stats = keep
    whole = _rows(lo, parts)
    for family, r, (table, *_) in zip(families, out, alone):
        assert not isinstance(r, Exception), (family, r)
        assert _table(r[0][0]) == table == _table(ssb.answer(
            whole, family, ssb.FAMILIES[family].fixed)), family
    assert c["executor.groupby_levels"] == sum(a[1] for a in alone)
    assert c["executor.groupsum_launches"] == sum(a[2] for a in alone)
    assert c.get("executor.groupby_spills", 0) == sum(a[3] for a in alone)
    assert (c.get("executor.groupby_spills", 0) > 0) \
        == (chunk_bytes == 1 << 15)
    yes = c.get("executor.groupby_fetches{covered:yes}", 0)
    no = c.get("executor.groupby_fetches{covered:no}", 0)
    assert yes > no > 0 and yes + no >= c["executor.groupby_levels"]
