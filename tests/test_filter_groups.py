"""A flush's TopN filters launch once a shape, not once a member
(executor/fusion.py `FusionCollector.add_filter`, `_FilterGroup`;
`Executor._filter_group_fn`): inside `execute_batch` the filter trees of
resident, non-tanimoto TopN calls wait with their sweeps, and those of
one signature launch ONE `tree_row_multi` program per group of up to
FILTER_GROUP_MAX, whose lanes the sweep groups take as they are. Held
to that here on the six filter families of the taxi deployment's
`topn-sweep` traffic (benchmark/datasets/taxi.py), at a size a test can
hold: answers bit-identical to the direct path, launches counted
through a stub on `Executor._call_program`, as tests/test_sweep_groups.py
counts them."""

import contextlib
import re
from datetime import datetime, timedelta

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.executor import fusion as ex_mod_fusion
from pilosa_tpu.executor.fusion import (FILTER_GROUP_MAX, FILTER_LANES,
                                        FusionCollector)
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE

N_SHARDS = 4
N_RIDES = 6000
N_DAYS = 28
GRID_ROWS = 15
DAY0 = datetime(2019, 1, 1)


def _fill(h: Holder) -> None:
    """The taxi schema's fields the six families read, every ride with a
    value in each (so every view trims to the same width, as the
    deployment's full-width banks do)."""
    idx = h.create_index("taxi")
    rng = np.random.default_rng(36)
    cols = np.unique(rng.integers(0, N_SHARDS, N_RIDES) * SHARD_WIDTH
                     + rng.integers(0, 3000, N_RIDES)).astype(np.uint64)
    n = len(cols)
    dist = rng.integers(0, 300, n)
    amount = dist * 25 // 10 + rng.integers(3, 20, n)
    cab = rng.integers(0, 3, n).astype(np.uint64)
    for name, rows in (
            ("cab_type", cab), ("dist_miles", dist // 10),
            ("total_amount_dollars", amount // 10),
            ("pickup_elapsed_time_of_day", rng.integers(0, 48, n)),
            ("pickup_grid_id", rng.integers(0, GRID_ROWS, n)),
            ("drop_grid_id", rng.integers(0, GRID_ROWS, n))):
        idx.create_field(name).import_bits(rows.astype(np.uint64), cols)
    for name, vals, hi in (("dist", dist, 300), ("amount", amount, 1000)):
        idx.create_field(name, FieldOptions(type="int", min=0, max=hi)) \
            .import_values(cols, vals)
    day = rng.integers(0, N_DAYS, n)
    idx.create_field("pickup", FieldOptions(
        type="time", time_quantum="YMD")).import_bits(
        cab, cols, [DAY0 + timedelta(days=int(d)) for d in day])
    idx.add_existence(cols)


def _iso(day: int) -> str:
    return f"{DAY0 + timedelta(days=day):%Y-%m-%dT%H:%M}"


# family -> i -> the i-th call of that family: the traffic's texts, its
# constants by position. Every `i` of a family is one signature, but
# for the time range, whose signature moves with its bucket of views.
FAMILIES = {
    "topn_dist_lt": lambda i: (
        f"TopN(pickup_grid_id, Row(dist < {20 + 23 * i}), n=10)"),
    "topn_amount_gt": lambda i: (
        f"TopN(drop_grid_id, Row(amount > {30 + 41 * i}), n=10)"),
    "topn_cab_dist": lambda i: (
        f"TopN(pickup_grid_id, Intersect(Row(cab_type={i % 3}), "
        f"Row(dist < {40 + 19 * i})), n=10)"),
    # Seven days each (a bucket of eight views), another start a call:
    # other view banks under one signature.
    "topn_pickup_range": lambda i: (
        f"TopN(pickup_grid_id, Row(pickup={i % 3}, from='{_iso(i)}', "
        f"to='{_iso(i + 7)}'), n=10)"),
    "topn_miles_dollars": lambda i: (
        f"TopN(drop_grid_id, Intersect(Row(dist_miles={3 + i}), "
        f"Row(total_amount_dollars={25 * (3 + i) // 10 + 1})), n=10)"),
    "topn_tod": lambda i: (
        f"TopN(drop_grid_id, Row(pickup_elapsed_time_of_day={5 + 3 * i}), "
        "n=10)"),
}


@pytest.fixture(scope="module")
def holder(tmp_path_factory):
    h = Holder(str(tmp_path_factory.mktemp("filter_groups")))
    h.open()
    _fill(h)
    yield h
    h.close()


@pytest.fixture(scope="module")
def direct(holder):
    """query text -> its answer on the direct path (no batch), kept for
    the module: what every batched answer must equal."""
    plain = Executor(holder)
    plain.result_cache.enabled = False
    memo = {}

    def answer(pql: str):
        if pql not in memo:
            memo[pql] = plain.execute("taxi", pql)[0].pairs
        return memo[pql]
    return answer


@pytest.fixture
def ex(holder):
    executor = Executor(holder)
    executor.stats = MemStatsClient()
    executor.result_cache.enabled = False
    return executor


def launches(monkeypatch, spoil_pads=False, fail=()):
    """Stub Executor._call_program: record (program name, lanes) of
    every filter and sweep program launched. `spoil_pads` overwrites the
    pad lanes of a filter group's output (the lanes whose operand row
    repeats the one before), so a sweep that read one would answer
    otherwise; `fail` names programs whose launch raises."""
    calls = []
    orig = Executor._call_program

    def stub(self, fn, *args):
        name = getattr(fn, "__name__", "")
        if name == "tree_row_multi":
            calls.append((name, len(args[1])))     # its own banks a lane
        elif name.startswith(("tree_", "topn_sweep", "range_fold",
                              "popcount_row", "groupby")):
            calls.append((name, max(1, len(args) - 1)
                          if name.startswith("topn_sweep") else 1))
        if name in fail:
            raise RuntimeError(f"launch of {name} failed")
        out = orig(self, fn, *args)
        if spoil_pads and name == "tree_row_multi":
            ops = np.asarray(args[2])
            out = tuple(
                jnp.full_like(o, 0xFFFFFFFF)
                if k and (ops[k] == ops[k - 1]).all()
                and all(a is b for a, b in zip(args[1][k], args[1][k - 1]))
                else o for k, o in enumerate(out))
        return out

    monkeypatch.setattr(Executor, "_call_program", stub)
    return calls


def _filters(calls) -> list:
    return [c for c in calls if c[0].startswith("tree_row")]


def _counters(ex) -> dict:
    return ex.stats.snapshot()["counters"]


def _answers(out) -> list:
    assert not any(isinstance(r, Exception) for r in out), out
    return [r[0][0].pairs for r in out]


def _batch(queries) -> list:
    return [("taxi", q, None) for q in queries]


# (a) members of one family in a batch -> the lanes of its launches.
@pytest.mark.parametrize("n,lanes", [
    (1, [1]), (2, [2]), (3, [4]), (5, [8]), (8, [8]), (9, [8, 1]),
])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_family_members_share_a_filter_launch(ex, direct, monkeypatch,
                                              family, n, lanes):
    queries = [FAMILIES[family](i) for i in range(n)]
    want = [direct(q) for q in queries]
    assert any(want), "the filters must meet some rides"
    calls = launches(monkeypatch, spoil_pads=True)
    out = ex.execute_batch(_batch(queries))
    # Bit-identical, with every pad lane overwritten: none was read.
    assert _answers(out) == want
    assert _filters(calls) == [
        ("tree_row_multi" if k > 1 else "tree_row", k) for k in lanes]
    c = _counters(ex)
    assert c["executor.filter_launches"] == len(lanes)
    members = [min(FILTER_GROUP_MAX, n - i * FILTER_GROUP_MAX)
               for i in range(len(lanes))]
    for k in set(lanes):
        assert c[f"executor.filter_group_members{{k:{k}}}"] == sum(
            m for m, l in zip(members, lanes) if l == k)
    assert c.get("executor.filter_pad_lanes", 0) == sum(lanes) - n
    assert c["executor.topn_sweeps{path:resident}"] == n


# (b) what a flush's filters cost in launches.
def test_twelve_of_one_family_make_two_launches(ex, direct, monkeypatch):
    queries = [FAMILIES["topn_cab_dist"](i) for i in range(12)]
    want = [direct(q) for q in queries]
    calls = launches(monkeypatch)
    out = ex.execute_batch(_batch(queries))
    assert _answers(out) == want
    assert _filters(calls) == [("tree_row_multi", 8), ("tree_row_multi", 4)]
    assert _counters(ex)["executor.filter_launches"] == 2


def _mixed(n: int) -> list:
    fams = list(FAMILIES)
    return [FAMILIES[fams[i % len(fams)]](i // len(fams)) for i in range(n)]


def test_a_mixed_flush_of_thirty_makes_a_launch_a_family(ex, direct,
                                                         monkeypatch):
    queries = _mixed(30)
    want = [direct(q) for q in queries]
    calls = launches(monkeypatch, spoil_pads=True)
    out = ex.execute_batch(_batch(queries))
    assert _answers(out) == want
    filters = _filters(calls)
    # Five of each family: one 8-lane launch a family.
    assert filters == [("tree_row_multi", 8)] * 6
    assert len(filters) <= 12
    c = _counters(ex)
    assert c["executor.filter_launches"] == len(filters)
    assert c["executor.filter_group_members{k:8}"] == 30
    # The sweeps are PR 29's: fifteen filters a grid bank, 4 + 4 + 4 + 3.
    sweeps = [k for name, k in calls if name == "topn_sweep_multi"]
    assert sorted(sweeps) == [4] * 8
    assert c["executor.sweep_launches"] == 8
    # Every filter launch precedes the sweeps that read its lanes.
    order = [name for name, _ in calls
             if name in ("tree_row_multi", "topn_sweep_multi")]
    assert order == ["tree_row_multi"] * 6 + ["topn_sweep_multi"] * 8


def test_a_full_sweep_group_launches_when_its_filters_are_in_flight(
        ex, direct, monkeypatch):
    """Eight members of one family fill their filter group; the two
    sweep groups they filled on the way launch right behind it, before
    the rest of the batch is staged."""
    queries = ([FAMILIES["topn_dist_lt"](i) for i in range(8)]
               + [FAMILIES["topn_tod"](i) for i in range(2)])
    want = [direct(q) for q in queries]
    calls = launches(monkeypatch)
    out = ex.execute_batch(_batch(queries))
    assert _answers(out) == want
    assert [c for c in calls if c[0] != "tree_count"] == [
        ("tree_row_multi", 8), ("topn_sweep_multi", 4),
        ("topn_sweep_multi", 4), ("tree_row_multi", 2),
        ("topn_sweep_multi", 2)]


# (c) no eager device op between a filter program and its sweep.
@contextlib.contextmanager
def no_eager_ops(monkeypatch):
    """tests/test_groupby_programs.py's guard, over everything inside
    the block: indexing a device array, `jnp.stack`, `jnp.concatenate`
    and `jnp.pad` over anything but tracers raise."""
    from jax._src import array as jarray

    def guarded(orig, what):
        def call(*a, **kw):
            if not any(isinstance(x, jax.core.Tracer)
                       for x in jax.tree_util.tree_leaves((a, kw))):
                raise AssertionError(f"eager {what} in a begin half")
            return orig(*a, **kw)
        return call

    with monkeypatch.context() as m:
        for name in ("stack", "concatenate", "pad"):
            m.setattr(jnp, name, guarded(getattr(jnp, name), f"jnp.{name}"))
        m.setattr(jarray.ArrayImpl, "__getitem__",
                  guarded(jarray.ArrayImpl.__getitem__, "__getitem__"))
        yield


def test_the_guard_catches_an_eager_lane_slice(monkeypatch):
    words = jnp.zeros((3, 4, 8), jnp.uint32)
    with no_eager_ops(monkeypatch):
        jax.jit(lambda w: jnp.pad(w[1], [(0, 0), (0, 8)]))(words)
        with pytest.raises(AssertionError, match="__getitem__"):
            words[1]
        with pytest.raises(AssertionError, match="jnp.stack"):
            jnp.stack([words, words])
        with pytest.raises(AssertionError, match="jnp.pad"):
            jnp.pad(words, [(0, 0), (0, 0), (0, 8)])


def test_a_begin_half_touches_no_lane_eagerly(ex, direct, monkeypatch):
    # Banks built and every program compiled by a first batch; the
    # second, of other constants, runs whole under the guard.
    ex.execute_batch(_batch(_mixed(30)))
    queries = _mixed(60)[30:]
    with no_eager_ops(monkeypatch):
        flight = ex.execute_batch_begin(_batch(queries))
    assert _answers(ex.execute_batch_finish(flight)) == [
        direct(q) for q in queries]
    assert _counters(ex)["executor.filter_launches"] == 12


# (d) every lane count of a signature compiles when it is first met.
@pytest.mark.parametrize("first", ["alone_in_a_batch", "outside_a_batch"])
def test_another_group_size_compiles_nothing(ex, monkeypatch, first):
    """... whether in a batch or, as a server's first requests come,
    one by one outside any."""
    fam = FAMILIES["topn_miles_dollars"]
    if first == "alone_in_a_batch":
        ex.execute_batch(_batch([fam(0)]))       # alone: tree_row
    else:
        ex.execute("taxi", fam(0))
    with ex._jit_cache_lock:
        fns = {k: f for k, f in ex._jit_cache.items()
               if k.startswith("filters")}
    assert sorted(int(k.split("|")[0][7:]) for k in fns) == list(
        FILTER_LANES)
    # Each has run once already: its compile is behind it.
    assert all(f._cache_size() == 1 for f in fns.values())
    jc0 = ex.jit_compiles
    for n in (2, 5, 3, 8, 4):
        ex.execute_batch(_batch([fam(i) for i in range(n)]))
    # The sweep's own lane counts (PR 29) compiled with its first group.
    assert ex.jit_compiles == jc0 + 2
    assert all(f._cache_size() == 1 for f in fns.values())
    with ex._jit_cache_lock:
        assert {k for k in ex._jit_cache if k.startswith("filters")} \
            == set(fns)


# (e) the write fence.
def test_a_write_between_two_topns_is_read_by_the_later_one(
        ex, direct, monkeypatch):
    q = FAMILIES["topn_tod"](1)
    before = direct(q)
    top_row, top_count = before[0]
    plain = Executor(ex.holder)
    tod = set(plain.execute(
        "taxi", "Row(pickup_elapsed_time_of_day=8)")[0].columns().tolist())
    top = set(plain.execute(
        "taxi", f"Row(drop_grid_id={top_row})")[0].columns().tolist())
    col = min(top - tod)        # a ride of the top cell, another hour
    calls = launches(monkeypatch)
    try:
        out = ex.execute_batch(_batch([
            q, FAMILIES["topn_tod"](2),
            f"Set({col}, pickup_elapsed_time_of_day=8)", q]))
        assert out[0][0][0].pairs == before
        assert out[2][0][0] is True
        assert out[3][0][0].pairs[0] == (top_row, top_count + 1)
        # The two reads ahead of the write share a launch; the read
        # behind it is alone, on the rebuilt filter bank.
        assert _filters(calls) == [("tree_row_multi", 2), ("tree_row", 1)]
    finally:
        plain.execute("taxi",
                      f"Clear({col}, pickup_elapsed_time_of_day=8)")


# (e2) a group's lanes share their views' banks as ONE operand each
# (PR 39); where two members may hold other arrays of one shape — the
# row-subset banks of a view past BANK_MAX_BYTES, one a row set — a lane
# brings its own, as it brings a time range's views, and the members
# still share a launch.
def test_members_over_row_subset_banks_share_a_launch(
        ex, direct, monkeypatch):
    queries = [FAMILIES["topn_tod"](i) for i in range(3)]
    queries.append(queries[0])          # the same row: the same bank
    want = [direct(q) for q in queries]
    view = ex.holder.index("taxi").field(
        "pickup_elapsed_time_of_day").view()
    view._bank_cache.clear()
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES", 4096)
    calls = launches(monkeypatch)
    try:
        assert _answers(ex.execute_batch(_batch(queries))) == want
        assert sorted(k[3] for k in view._bank_cache if len(k) == 4) \
            == [(5,), (8,), (11,)]
        assert _filters(calls) == [("tree_row_multi", 4)]
    finally:
        view._bank_cache.clear()


# (e2') which positions are a lane's own is the program's, so its key's:
# a range fold of two days and a Union of two fields' rows have ONE
# signature where the banks' shapes agree, and the fold's lanes bring
# both banks where the Union's bring none.
_SAME_SIG = {
    "fold": lambda i: (
        f"TopN(pickup_grid_id, Row(pickup={i % 3}, from='{_iso(i)}', "
        f"to='{_iso(i + 2)}'), n=10)"),
    "union": lambda i: (
        f"TopN(pickup_grid_id, Union(Row(cab_type={i % 3}), "
        f"Row(pickup={(i + 1) % 3})), n=10)"),
}


@pytest.mark.parametrize("order", [("fold", "union"), ("union", "fold")])
def test_one_signature_with_other_own_banks_is_another_program(
        ex, direct, monkeypatch, order):
    staged = []

    class Keep(FusionCollector):
        def add_filter(self, st, prof, plan_s, width):
            staged.append(st)
            return super().add_filter(st, prof, plan_s, width)

    monkeypatch.setattr(ex_mod_fusion, "FusionCollector", Keep)
    batches = [[_SAME_SIG[kind](i) for i in range(2)] for kind in order]
    want = [[direct(q) for q in queries] for queries in batches]
    calls = launches(monkeypatch)
    for queries, answers in zip(batches, want):
        assert _answers(ex.execute_batch(_batch(queries))) == answers
    assert len({st.sig for st in staged}) == 1
    assert sorted({st.own_banks for st in staged}) == [(), (0, 1)]
    assert _filters(calls) == [("tree_row_multi", 2)] * 2


# (e3) the compiled group programs outlive their first members; the
# banks those were staged against must not (PR 39: a program that closed
# over its representative kept a 2 GiB bank version alive for good).
def test_a_group_program_keeps_no_bank_of_its_first_members(ex):
    import gc
    import weakref
    queries = [FAMILIES["topn_tod"](i) for i in range(2)]
    view = ex.holder.index("taxi").field(
        "pickup_elapsed_time_of_day").view()
    col = SHARD_WIDTH + 2999
    try:
        _answers(ex.execute_batch(_batch(queries)))
        (bank,) = view._bank_cache.values()
        old = weakref.ref(bank.array)
        del bank
        ex.execute("taxi", f"Set({col}, pickup_elapsed_time_of_day=5)")
        _answers(ex.execute_batch(_batch(queries)))   # the patched bank
        gc.collect()
        assert old() is None
    finally:
        ex.execute("taxi", f"Clear({col}, pickup_elapsed_time_of_day=5)")


# (f) a failed launch is its members' alone.
def test_a_failed_filter_group_fails_its_members_only(ex, direct,
                                                      monkeypatch):
    lone = FAMILIES["topn_cab_dist"](1)      # the same grid bank, alone
    other = FAMILIES["topn_tod"](0)
    want = direct(lone), direct(other), Executor(ex.holder).execute(
        "taxi", "Count(Row(cab_type=1))")[0]
    launches(monkeypatch, fail=("tree_row_multi",))
    out = ex.execute_batch(_batch(
        [FAMILIES["topn_dist_lt"](i) for i in range(3)]
        + [lone, other, "Count(Row(cab_type=1))"]))
    for r in out[:3]:
        assert isinstance(r, RuntimeError) and "tree_row_multi" in str(r)
    # `lone` shares a sweep group with the three that failed.
    assert (out[3][0][0].pairs, out[4][0][0].pairs, out[5][0][0]) == want


# (g) who never waits: the programs launched are the direct path's.
@pytest.mark.parametrize("kind", ["tanimoto", "literal_range",
                                  "outside_a_batch", "groupby_filter",
                                  "streamed"])
def test_other_filters_launch_as_they_did(ex, direct, monkeypatch, kind):
    fam = FAMILIES["topn_pickup_range"]
    queries = {
        "tanimoto": [f"TopN(drop_grid_id, Row(cab_type={i}), n=5, "
                     "tanimotoThreshold=1)" for i in range(3)],
        "literal_range": [fam(i) for i in range(3)],
        "outside_a_batch": [FAMILIES["topn_dist_lt"](i) for i in range(3)],
        "groupby_filter": [f"GroupBy(Rows(cab_type), filter=Row(dist < "
                           f"{50 + i}))" for i in range(3)],
        "streamed": [FAMILIES["topn_tod"](i) for i in range(3)],
    }[kind]
    if kind == "literal_range":
        monkeypatch.setattr(ex_mod, "MAX_STATIC_RANGE_VIEWS", 4)
    if kind == "streamed":
        monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
        monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", 64)
    plain = Executor(ex.holder)
    plain.result_cache.enabled = False
    want = [plain.execute("taxi", q)[0] for q in queries]
    calls = launches(monkeypatch)
    if kind == "outside_a_batch":
        got = [ex.execute("taxi", q)[0] for q in queries]
    else:
        got = [r[0][0] for r in ex.execute_batch(_batch(queries))]
    for g, w in zip(got, want):
        assert getattr(g, "pairs", g) == getattr(w, "pairs", w)
    programs = [name for name, _ in calls]
    assert "tree_row_multi" not in programs
    assert programs.count("tree_row") == 3
    per_call = {
        # (the bank's own popcounts were swept by `plain`'s answers)
        "tanimoto": ["tree_row", "topn_sweep", "popcount_row"],
        # Seven views past a bound of four: two folds ahead of the tree.
        "literal_range": ["range_fold", "range_fold", "tree_row"],
        "outside_a_batch": ["tree_row", "topn_sweep"],
        "groupby_filter": ["tree_row"],
        "streamed": ["tree_row"],
    }[kind]
    tail = {"literal_range": ["topn_sweep_multi"],
            "streamed": ["topn_sweep"] * 3}.get(kind, [])
    if kind == "groupby_filter":
        programs = [p for p in programs if not p.startswith("groupby")]
    assert programs == per_call * 3 + tail
    assert "executor.filter_launches" not in _counters(ex)


# (h) under a mesh the same path.
def test_meshed_batch_equals_the_unmeshed_answers(holder, direct,
                                                  monkeypatch):
    mesh = MeshContext(jax.devices()[:4])
    meshed = Executor(holder, mesh=mesh)
    meshed.stats = MemStatsClient()
    meshed.result_cache.enabled = False
    queries = _mixed(30)
    want = [direct(q) for q in queries]
    calls = launches(monkeypatch, spoil_pads=True)
    with mesh.mesh:
        out = meshed.execute_batch(_batch(queries))
    assert _answers(out) == want
    assert _filters(calls) == [("tree_row_multi", 8)] * 6
    assert _counters(meshed)["executor.filter_launches"] == 6


# What a member's profile and the flush record say of a group launch.
def test_a_group_launch_is_attributed_to_every_member(ex, monkeypatch):
    from pilosa_tpu.utils.profile import QueryProfile
    fam = FAMILIES["topn_amount_gt"]
    ex.execute_batch(_batch([fam(i) for i in range(3)]))     # compiled
    queries = [fam(i) for i in range(3, 6)]
    profs = [QueryProfile("taxi", q) for q in queries]
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=64, sample_every=1)
    try:
        rec = TIMELINE.begin("filter-groups", index="taxi")
        with TIMELINE.attached(rec):
            ex.execute_batch(_batch(queries), profiles=profs)
        TIMELINE.finish(rec)
        spans = list(rec.root.walk())
    finally:
        TIMELINE.reset()
        TIMELINE.configure(enabled=True, ring=256, sample_every=1)
    (launch,) = [s for s in spans if s.name == "dispatch"
                 and s.attrs["program"] == "tree_row_multi"]
    assert (launch.attrs["filters"], launch.attrs["lanes"]) == (3, 4)
    assert launch.attrs["jit"] == "hit"
    # ONE operand upload for the three: [4 lanes, slots + scalars] u32.
    uploads = [s.attrs["bytes"] for s in spans if s.name == "h2d"]
    assert len(uploads) == 1 and uploads[0] % (4 * 4) == 0
    for b, p in enumerate(profs):
        assert p.fused_batch == 3
        (node,) = [n for op in p.ops for n in op.children
                   if n.name.startswith("eval:")]
        assert node.attrs["fusedBatch"] == 3
        assert node.attrs["batchIndex"] == b
        assert node.attrs["jit"] == "hit"
        assert node.attrs["h2dBytes"] == uploads[0] // 3


# The lowered program: no gather over a bank.
def _bank_gathers(text: str, banks) -> list:
    """The `stablehlo.gather` ops of a lowered module whose operand has
    a bank's type."""
    types = {"tensor<" + "x".join(map(str, a.shape)) + "xui32>"
             for lane in banks for a in lane}
    found = []
    for m in re.finditer(r'"?stablehlo\.gather"?\(.*', text):
        line = m.group(0)
        sig = line.rsplit(":", 1)[-1]
        operand = re.search(r"\(\s*(tensor<[^>]*>)", sig)
        if operand and operand.group(1) in types:
            found.append(line[:160])
    return found


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_group_program_reads_no_bank_through_a_gather(ex, monkeypatch,
                                                          family):
    """Lowered (StableHLO) text, platform-independent: lane b's leaves
    read their banks by dynamic slices. The `vmap` of the same tree,
    the body this one was chosen over, gathers — the check's control."""
    staged = []

    class Keep(FusionCollector):
        def add_filter(self, st, prof, plan_s, width):
            staged.append((st, width))
            return super().add_filter(st, prof, plan_s, width)

    monkeypatch.setattr(ex_mod_fusion, "FusionCollector", Keep)
    ex.execute_batch(_batch([FAMILIES[family](i) for i in range(2)]))
    (rep, width), _ = staged
    for lanes in FILTER_LANES:
        fn, _ = ex._filter_group_fn(rep, lanes, width)
        banks = (rep.bank_arrays,) * lanes
        ops = jnp.zeros((lanes, len(rep.idxs) + len(rep.params)),
                        jnp.uint32)
        lowered = fn.lower(rep.shared_banks,
                           (rep.owned_banks,) * lanes, ops)
        text = lowered.as_text()
        assert "stablehlo.dynamic_slice" in text
        assert _bank_gathers(text, banks) == []
        # A view's bank is ONE operand whatever the lanes; a lane
        # brings only its own range's views (PR 39: the compiler adds
        # up operands as if no two were one buffer).
        n_own = len(rep.own_banks)
        assert (n_own > 0) == (family == "topn_pickup_range")
        assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == (
            len(rep.bank_arrays) - n_own) + lanes * n_own + 1
    vmapped = jax.jit(jax.vmap(rep.runner(), in_axes=(None, 0, 0, None)))
    text = vmapped.lower(
        rep.bank_arrays, jnp.zeros((2, len(rep.idxs)), jnp.int32),
        jnp.zeros((2, len(rep.params)), jnp.uint32), None).as_text()
    assert _bank_gathers(text, banks) != []


# The counter's per-layer metric: one data file over an existing reader.
def test_the_benchmark_reads_filter_launches_per_answer():
    import json
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(repo, "benchmark")
    added = [p for p in (bench, repo) if p not in sys.path]
    sys.path[:0] = added
    try:
        from harness.manifest import Manifest
        man = Manifest(repo)
        # By name, not by position: later PRs append to the list.
        entry = next(m for m in man.doc["per_layer"]
                     if m["name"] == "filter_launches_per_op.sweep")
        assert entry == {
            "name": "filter_launches_per_op.sweep", "unit": "launches/op",
            "better": "lower", "source": "program_counter",
            "layer": "Plan / fuse", "moves": "sweep_qps",
            "workloads": ["taxi-chip.topn-sweep", "taxi-host4.topn-sweep"]}
        spec = man.metric_spec(entry["name"])
        with open(os.path.join(bench, "metrics",
                               "sweep_launches_per_op.json")) as f:
            assert spec["reader"] == json.load(f)["reader"]
        reader = man.load_module("readers", spec["reader"])

        def ctx(before, after):
            return {"before": {"vars": {"counters": before}},
                    "after": {"vars": {"counters": after}},
                    "completed": 1200}
        assert reader.read(ctx({"executor.filter_launches": 40},
                               {"executor.filter_launches": 376}),
                           **spec["args"]) == 0.28
        # The parent publishes no such counter: nothing to read.
        assert reader.read(ctx({}, {"executor.sweep_launches": 9}),
                           **spec["args"]) is None
    finally:
        for p in added:
            sys.path.remove(p)


def test_a_sparse_layout_leaf_rides_a_lane_as_it_is(ex, direct, holder,
                                                    monkeypatch):
    """A sparse bank is a tuple of arrays, expanded on the device
    (`expand_positions`): a lane hands it over unwrapped."""
    queries = [FAMILIES["topn_tod"](i) for i in range(3)]
    want = [direct(q) for q in queries]
    view = holder.index("taxi").field("pickup_elapsed_time_of_day").view()
    assert view.set_layout("sparse")
    try:
        calls = launches(monkeypatch, spoil_pads=True)
        out = ex.execute_batch(_batch(queries))
        assert _answers(out) == want
        assert _filters(calls) == [("tree_row_multi", 4)]
        assert any(k.startswith("filters4|") and "|x0|" in k
                   for k in ex._jit_cache)
    finally:
        view.set_layout("dense")
