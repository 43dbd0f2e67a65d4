"""A flush's filtered TopN sweeps over one bank share one bank pass
(executor/fusion.py `FusionCollector.add_sweep`, `Executor.
_dispatch_sweep_group`): inside `execute_batch` the filtered resident
sweeps that hold the same bank array launch ONE `topn_sweep_multi`
program per group of up to SWEEP_GROUP_MAX, answers bit-identical to
the direct path. Launches are counted through a stub on
`Executor._call_program`, as tests/test_fusion.py counts dispatches.
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.executor.fusion import (SWEEP_GROUP_MAX, SWEEP_LANES,
                                        SweepLane)
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE

N_ROWS = 24
N_SHARDS = 4


def _fill(h: Holder) -> None:
    idx = h.create_index("i")
    rng = np.random.default_rng(29)
    for name, n in (("f", 9000), ("g", 7000), ("flt", 12000)):
        rows = rng.integers(0, N_ROWS, n).astype(np.uint64)
        # Offsets inside a shard's first 3000 columns: the rows overlap.
        cols = (rng.integers(0, N_SHARDS, n) * SHARD_WIDTH
                + rng.integers(0, 3000, n)).astype(np.uint64)
        idx.create_field(name).import_bits(rows, cols)


@pytest.fixture
def ex(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    _fill(h)
    executor = Executor(h)
    executor.stats = MemStatsClient()
    executor.result_cache.enabled = False
    yield executor
    h.close()


def sweep_calls(monkeypatch, spoil_pads=False, fail=()):
    """Stub Executor._call_program: record (program name, filter
    operands) of every bank sweep launched. `spoil_pads` overwrites the
    pad lanes of a group's output (the lanes whose operand repeats the
    one before), so an answer that read one would differ; `fail` names
    programs whose launch raises."""
    calls = []
    orig = Executor._call_program

    def stub(self, fn, *args):
        name = getattr(fn, "__name__", "")
        if not name.startswith("topn_sweep"):
            return orig(self, fn, *args)
        calls.append((name, len(args) - 1))
        if name in fail:
            raise RuntimeError(f"launch of {name} failed")
        out = orig(self, fn, *args)
        if spoil_pads and name == "topn_sweep_multi":
            for k in range(2, len(args)):
                if args[k] is args[k - 1]:
                    out = out.at[k - 1].set(np.uint32(0xFFFFFFFF))
        return out

    monkeypatch.setattr(Executor, "_call_program", stub)
    return calls


def _topn(field: str, r: int) -> str:
    return f"TopN({field}, Row(flt={r}), n=6)"


def _counters(ex) -> dict:
    return ex.stats.snapshot()["counters"]


def _answers(out) -> list:
    return [r[0][0].pairs for r in out]


# n filtered TopN over one bank in one batch -> the lanes of each launch.
@pytest.mark.parametrize("n,lanes", [
    (2, [2]), (3, [4]), (4, [4]), (5, [4, 1]), (6, [4, 2]), (7, [4, 4]),
    (8, [4, 4]), (16, [4, 4, 4, 4]),
])
def test_one_bank_sweeps_share_passes(ex, monkeypatch, n, lanes):
    queries = [_topn("f", r) for r in range(n)]
    direct = [ex.execute("i", q)[0].pairs for q in queries]
    assert len({tuple(p) for p in direct}) == n, "filters must differ"
    ex.stats = MemStatsClient()
    calls = sweep_calls(monkeypatch, spoil_pads=True)
    out = ex.execute_batch([("i", q, None) for q in queries])
    # Bit-identical answers, with every pad lane overwritten: none read.
    assert _answers(out) == direct
    assert calls == [("topn_sweep_multi" if k > 1 else "topn_sweep", k)
                     for k in lanes]
    c = _counters(ex)
    assert c["executor.sweep_launches"] == len(lanes)
    members = [min(SWEEP_GROUP_MAX, n - i * SWEEP_GROUP_MAX)
               for i in range(len(lanes))]
    for k in set(lanes):
        assert c[f"executor.sweep_group_filters{{k:{k}}}"] == sum(
            m for m, l in zip(members, lanes) if l == k)
    assert c.get("executor.sweep_pad_lanes", 0) == sum(lanes) - n
    assert c["executor.topn_sweeps{path:resident}"] == n


def test_two_banks_form_separate_groups(ex, monkeypatch):
    queries = [_topn("f" if r % 2 else "g", r) for r in range(8)]
    direct = [ex.execute("i", q)[0].pairs for q in queries]
    calls = sweep_calls(monkeypatch)
    out = ex.execute_batch([("i", q, None) for q in queries])
    assert _answers(out) == direct
    assert calls == [("topn_sweep_multi", 4)] * 2


def test_lone_sweep_in_a_batch_runs_the_one_filter_program(
        ex, monkeypatch):
    """A group of one is the direct path's `topn_sweep`: same jit key,
    no multi program built."""
    (want,) = ex.execute("i", _topn("f", 3))
    with ex._jit_cache_lock:
        before = {k for k in ex._jit_cache if k.startswith("topn")}
    calls = sweep_calls(monkeypatch)
    out = ex.execute_batch([("i", _topn("f", 3), None),
                            ("i", "Count(Row(flt=1))", None)])
    assert out[0][0][0].pairs == want.pairs
    assert calls == [("topn_sweep", 1)]
    with ex._jit_cache_lock:
        after = {k for k in ex._jit_cache if k.startswith("topn")}
    assert after == before and len(after) == 1
    assert next(iter(after)).startswith("topn:True:(")
    assert _counters(ex)["executor.sweep_group_filters{k:1}"] == 2


def test_every_lane_count_is_built_when_the_first_group_forms(
        ex, monkeypatch):
    """The multi programs of a bank shape compile together, in warm-up
    (those of the filters' signature did with the first direct answer:
    tests/test_filter_groups.py): a later group of another size
    compiles nothing."""
    for r in range(4):
        ex.execute("i", _topn("f", r))
    jc0 = ex.jit_compiles
    ex.execute_batch([("i", _topn("f", r), None) for r in range(2)])
    assert ex.jit_compiles == jc0 + len(SWEEP_LANES)
    fns = []
    with ex._jit_cache_lock:
        keys = [k for k in ex._jit_cache if k.startswith("topn_multi:")]
        fns = [ex._jit_cache[k] for k in keys]
    assert len(keys) == len(SWEEP_LANES)
    # Each one has run once already: its compile is behind it.
    assert all(fn._cache_size() == 1 for fn in fns)
    ex.execute_batch([("i", _topn("f", r), None) for r in range(4)])
    assert ex.jit_compiles == jc0 + len(SWEEP_LANES)
    assert all(fn._cache_size() == 1 for fn in fns)


def test_write_between_reads_splits_the_group(ex, monkeypatch):
    """[read, write, read] over the swept field: the staged first read
    holds the bank array it read and launches before the write; the
    second sweeps the rebuilt bank."""
    q = _topn("f", 2)
    (before,) = ex.execute("i", q)
    top_row, top_count = before.pairs[0]
    # A column of filter row 2 that `top_row` lacks: setting it adds one
    # to that row's filtered count.
    flt_cols = set(ex.execute("i", "Row(flt=2)")[0].columns().tolist())
    row_cols = set(ex.execute("i", f"Row(f={top_row})")[0]
                   .columns().tolist())
    col = min(flt_cols - row_cols)
    calls = sweep_calls(monkeypatch)
    out = ex.execute_batch([
        ("i", q, None),
        ("i", _topn("f", 5), None),
        ("i", f"Set({col}, f={top_row})", None),
        ("i", q, None),
    ])
    assert out[0][0][0].pairs == before.pairs
    assert out[2][0][0] is True
    assert out[3][0][0].pairs[0] == (top_row, top_count + 1)
    # The two reads ahead of the write share a pass; the read behind it
    # is alone on the new bank array.
    assert calls == [("topn_sweep_multi", 2), ("topn_sweep", 1)]


def test_failed_group_launch_fails_its_members_only(ex, monkeypatch):
    (want_g,) = ex.execute("i", _topn("g", 1))
    (want_c,) = ex.execute("i", "Count(Row(flt=1))")
    sweep_calls(monkeypatch, fail=("topn_sweep_multi",))
    out = ex.execute_batch(
        [("i", _topn("f", r), None) for r in range(3)]
        + [("i", _topn("g", 1), None), ("i", "Count(Row(flt=1))", None)])
    for r in out[:3]:
        assert isinstance(r, RuntimeError) and "topn_sweep_multi" in str(r)
    assert out[3][0][0].pairs == want_g.pairs
    assert out[4][0][0] == want_c


@pytest.mark.parametrize("kind", ["tanimoto", "unfiltered", "streamed"])
def test_other_sweeps_never_join_a_group(ex, monkeypatch, kind):
    if kind == "streamed":
        monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
        monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", 64)
    queries = {
        "tanimoto": [f"TopN(f, Row(flt={r}), n=6, tanimotoThreshold=1)"
                     for r in range(4)],
        # A warm ranked cache would answer these on the host: the ids
        # list keeps them distinct, the rank cache is off below.
        "unfiltered": [f"TopN(f, n={n})" for n in range(3, 7)],
        "streamed": [_topn("f", r) for r in range(4)],
    }[kind]
    if kind == "unfiltered":
        from pilosa_tpu.core.cache import RANK_CACHE
        monkeypatch.setattr(RANK_CACHE, "enabled", False)
        monkeypatch.setattr(Executor, "_topn_cached_counts",
                            lambda self, view, shards: None)
    direct = [ex.execute("i", q)[0].pairs for q in queries]
    ex.stats = MemStatsClient()
    calls = sweep_calls(monkeypatch)
    out = ex.execute_batch([("i", q, None) for q in queries])
    assert _answers(out) == direct
    # The `tanimoto` kind launches one-filter `topn_sweep`s (the bank's
    # popcounts were swept by the direct answers above: all four kept).
    program = {"tanimoto": "topn_sweep",
               "unfiltered": "topn_sweep_unfiltered",
               "streamed": "topn_sweep"}[kind]
    assert calls == [(program, 1)] * 4
    c = _counters(ex)
    assert c["executor.sweep_launches"] == 4
    assert c.get("executor.bank_popcounts{path:kept}", 0) == (
        4 if kind == "tanimoto" else 0)
    assert "executor.bank_popcounts{path:swept}" not in c
    assert not any(k.startswith("executor.sweep_group_filters{k:")
                   and not k.endswith("{k:1}") for k in c)


def test_group_counters_add_up_to_the_filtered_resident_calls(
        ex, monkeypatch):
    """`sweep_group_filters` summed over k = the filtered resident TopN
    calls, in a batch or outside one, whatever else the batch holds."""
    ex.execute("i", _topn("f", 0))                      # direct: k:1
    ex.execute_batch(
        [("i", _topn("f", r), None) for r in range(7)]      # 4 + 3
        + [("i", _topn("g", r), None) for r in range(2)]    # 2
        + [("i", "TopN(f, Row(flt=1), n=3, tanimotoThreshold=1)", None),
           ("i", "Count(Row(f=1))", None)])
    c = _counters(ex)
    groups = {k: v for k, v in c.items()
              if k.startswith("executor.sweep_group_filters")}
    assert groups == {"executor.sweep_group_filters{k:1}": 2,
                      "executor.sweep_group_filters{k:2}": 2,
                      "executor.sweep_group_filters{k:4}": 7}
    assert sum(groups.values()) == c["executor.topn_sweeps{path:resident}"]
    assert c["executor.sweep_pad_lanes"] == 1
    # ... and the tanimoto call's bank is new to it: one unfiltered
    # sweep for the rows' own popcounts.
    assert c["executor.sweep_launches"] == 1 + 2 + 1 + 1 + 1
    assert c["executor.bank_popcounts{path:swept}"] == 1


def test_group_array_is_fetched_and_counted_once(ex, monkeypatch):
    """The `dispatch` span of a group names the program, its filters and
    lanes; the members' `d2h` bytes add up to the group's one array, pad
    lane included, and the lanes share one host copy."""
    for r in range(3):
        ex.execute("i", _topn("f", r))
    fetched = []
    orig = SweepLane.host

    def host(self):
        fetched.append(self.group)
        return orig(self)

    monkeypatch.setattr(SweepLane, "host", host)
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=64, sample_every=1)
    try:
        rec = TIMELINE.begin("sweep-groups", index="i")
        with TIMELINE.attached(rec):
            ex.execute_batch([("i", _topn("f", r), None) for r in range(3)])
        TIMELINE.finish(rec)
        spans = list(rec.root.walk())
    finally:
        TIMELINE.reset()
        TIMELINE.configure(enabled=True, ring=256, sample_every=1)
    (launch,) = [s for s in spans if s.name == "dispatch"
                 and s.attrs["program"] == "topn_sweep_multi"]
    assert (launch.attrs["filters"], launch.attrs["lanes"]) == (3, 4)
    slots = ex.holder.index("i").field("f").view().device_bank(
        tuple(range(N_SHARDS)), trim=True).array.shape[0]
    d2h = [s.attrs["bytes"] for s in spans if s.name == "d2h"]
    assert sorted(d2h) == [slots * 4, slots * 4, 2 * slots * 4]
    (group,) = set(fetched)
    assert group.host.shape == (4, slots)


def test_meshed_batch_equals_the_unmeshed_answers(tmp_path, monkeypatch):
    h = Holder(str(tmp_path))
    h.open()
    _fill(h)
    queries = [_topn("f" if r % 3 else "g", r) for r in range(16)]
    plain = Executor(h)
    want = [plain.execute("i", q)[0].pairs for q in queries]
    mesh = MeshContext(jax.devices()[:4])
    meshed = Executor(h, mesh=mesh)
    meshed.result_cache.enabled = False
    calls = sweep_calls(monkeypatch)
    with mesh.mesh:
        out = meshed.execute_batch([("i", q, None) for q in queries])
    h.close()
    assert _answers(out) == want
    # 10 sweeps of f's bank, 6 of g's.
    assert sorted(calls) == sorted(
        [("topn_sweep_multi", 4)] * 3 + [("topn_sweep_multi", 2)] * 2)
