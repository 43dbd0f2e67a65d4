"""One rule under a mesh: a sharded bank is priced, budgeted and built by
ONE device's share (`core/view.device_share_bytes`).

A 4-device mesh of the conftest's virtual CPU devices, every answer
compared with a plain numpy recomputation: the resident-sweep limit
(`TOPN_MAX_BANK_BYTES`) and the bank budget see a quarter of a bank split
four ways, `device_bank(mesh=)` gathers and uploads one device's block at
a time, and what the executor counts and records says which path a TopN
took and what a bank's upload cost."""

import jax
import numpy as np
import pytest

from pilosa_tpu.core import view as view_mod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.core.view import device_share_bytes
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE

N_SHARDS = 8
N_ROWS = 40          # cap 64
WIDTH_COLS = 50_000  # inside a shard's first container: 2048 words
FILTER_ROW = 0
WHOLE = 64 * N_SHARDS * 2048 * 4     # the bank as one array
SHARE = WHOLE // 4                   # what one of four devices holds


def _rows(seed: int, n_shards: int = N_SHARDS) -> dict:
    """row id -> sorted unique columns; rows 1..9 are noisy copies of the
    filter row, so that a tanimoto threshold keeps some and drops others."""
    rng = np.random.default_rng(seed)

    def draw(n):
        shard = rng.integers(0, n_shards, n).astype(np.uint64)
        return np.unique(shard * np.uint64(SHARD_WIDTH)
                         + rng.integers(0, WIDTH_COLS, n).astype(np.uint64))

    base = draw(400)
    rows = {FILTER_ROW: base}
    for r in range(1, 10):
        keep = base[rng.random(base.size) < 1.0 - 0.07 * r]
        rows[r] = np.unique(np.concatenate([keep, draw(12 * r)]))
    for r in range(10, N_ROWS):
        rows[r] = draw(int(rng.integers(20, 500)))
    return rows


def _import(field, rows: dict) -> None:
    field.import_bits(
        np.concatenate([np.full(c.size, r, np.uint64)
                        for r, c in rows.items()]),
        np.concatenate(list(rows.values())))


def _reference(rows: dict, filt, n: int, tanimoto: int = 0) -> list:
    """TopN(f[, Row(..)=filt], n[, tanimotoThreshold]) by set arithmetic."""
    pairs = []
    for r, cols in rows.items():
        inter = cols.size if filt is None else np.intersect1d(cols,
                                                              filt).size
        if tanimoto:
            denom = cols.size + filt.size - inter
            if inter * 100 <= tanimoto * denom:    # upstream: == T is out
                continue
        if inter > 0:
            pairs.append((r, inter))
    pairs.sort(key=lambda p: (-p[1], p[0]))
    return pairs[:n]


@pytest.fixture(scope="module")
def mesh4():
    return MeshContext(jax.devices()[:4])


@pytest.fixture(scope="module")
def holder(tmp_path_factory):
    """Index `i`: fields `f` and `g` (the same shape, other bits), both
    without a ranked cache so that an unfiltered TopN sweeps too."""
    h = Holder(str(tmp_path_factory.mktemp("mesh_share")))
    h.open()
    idx = h.create_index("i")
    rows = {}
    for name, seed in (("f", 26), ("g", 27)):
        rows[name] = _rows(seed)
        _import(idx.create_field(name, FieldOptions(cache_type="none")),
                rows[name])
        assert idx.field(name).view().trimmed_words() == 2048
    yield h, rows
    h.close()


def _topn_paths(stats) -> dict:
    c = stats.snapshot()["counters"]
    return {p: c.get(f"executor.topn_sweeps{{path:{p}}}", 0)
            for p in Executor.TOPN_PATHS}


def _query(kind: str) -> tuple:
    """(pql, filtered, tanimoto) of one of the three kinds of sweep call."""
    if kind == "unfiltered":
        return "TopN(f, n=12)", False, 0
    if kind == "filtered":
        return f"TopN(f, Row(f={FILTER_ROW}), n=12)", True, 0
    return (f"TopN(f, Row(f={FILTER_ROW}), n=12, tanimotoThreshold=60)",
            True, 60)


# ------------------------------------------------ (a) the resident limit


def test_the_helper_is_the_share_one_device_holds(mesh4):
    shape = (64, N_SHARDS, 2048)
    assert device_share_bytes(shape) == WHOLE
    assert device_share_bytes(shape, mesh4.bank_sharding()) == SHARE
    arr = mesh4.put_bank(np.zeros(shape, np.uint32))
    assert device_share_bytes(arr.shape, arr.sharding) == SHARE
    assert max(s.data.nbytes for s in arr.addressable_shards) == SHARE
    one = jax.device_put(np.zeros(shape, np.uint32), jax.devices()[0])
    assert device_share_bytes(one.shape, one.sharding) == WHOLE
    # A replica axis replicates banks: the share is by shard devices.
    rep = MeshContext(jax.devices()[:8], replicas=2)
    assert device_share_bytes(shape, rep.bank_sharding()) == SHARE
    assert Executor(None, mesh=mesh4)._bank_device_bytes(shape) == SHARE
    assert Executor(None)._bank_device_bytes(shape) == WHOLE


@pytest.mark.parametrize("kind", ["unfiltered", "filtered", "tanimoto"])
def test_a_bank_whose_share_fits_takes_the_resident_sweep(
        holder, mesh4, monkeypatch, kind):
    """The whole array is over the limit, a device's share is under it:
    one resident sweep under the mesh (the streamed path without one)."""
    h, rows = holder
    monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", WHOLE // 2)
    monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", 16)
    pql, filtered, tanimoto = _query(kind)
    want = _reference(rows["f"], rows["f"][FILTER_ROW] if filtered else None,
                      12, tanimoto)
    assert len(want) >= 3
    ex = Executor(h, mesh=mesh4)
    ex.stats = MemStatsClient()
    (res,) = ex.execute("i", pql)
    assert res.pairs == want
    assert _topn_paths(ex.stats) == dict.fromkeys(
        Executor.TOPN_PATHS, 0) | {"resident": 1}
    # One sweep program an answer under the mesh too; a tanimoto call
    # reads the sharded bank's own popcounts, swept for once (the bank
    # outlives this test: another may have left them).
    c = ex.stats.snapshot()["counters"]
    asked = sum(c.get(f"executor.bank_popcounts{{path:{p}}}", 0)
                for p in Executor.POPCOUNT_PATHS)
    assert asked == (1 if tanimoto else 0)
    assert c["executor.sweep_launches"] == 1 + c.get(
        "executor.bank_popcounts{path:swept}", 0)
    single = Executor(h)
    single.stats = MemStatsClient()
    (res,) = single.execute("i", pql)
    assert res.pairs == want
    assert _topn_paths(single.stats)["streamed"] == 1
    assert _topn_paths(single.stats)["resident"] == 0


@pytest.mark.parametrize("kind", ["unfiltered", "filtered", "tanimoto"])
def test_a_share_over_the_limit_still_streams_and_answers_right(
        holder, mesh4, monkeypatch, kind):
    h, rows = holder
    monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", SHARE - 1)
    monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", 16)
    pql, filtered, tanimoto = _query(kind)
    ex = Executor(h, mesh=mesh4)
    ex.stats = MemStatsClient()
    (res,) = ex.execute("i", pql)
    assert res.pairs == _reference(
        rows["f"], rows["f"][FILTER_ROW] if filtered else None, 12, tanimoto)
    paths = _topn_paths(ex.stats)
    assert (paths["streamed"], paths["resident"]) == (1, 0)


def test_row_leaves_price_the_bank_by_the_share_too(holder, mesh4,
                                                    monkeypatch):
    """`_get_bank_for` builds a row subset only when a device's share of
    the full bank is over BANK_MAX_BYTES. It prices the bank by the rows
    the view has (the union over shards: 8 shards x the same 40 rows ->
    64 slots, WHOLE; the sum over shards would say 512 slots)."""
    h, rows = holder
    want = np.intersect1d(rows["f"][1], rows["f"][2]).size
    pql = "Count(Intersect(Row(f=1), Row(f=2)))"
    view = h.index("i").field("f").view()
    assert len(view.merged_row_ids(range(N_SHARDS))) == N_ROWS
    assert sum(len(view.fragment(s).row_ids())
               for s in range(N_SHARDS)) > 4 * N_ROWS

    def subset_keys():
        return [k for k in view._bank_cache if len(k) == 4]

    view._bank_cache.clear()
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES", SHARE)
    assert Executor(h, mesh=mesh4).execute("i", pql) == [want]
    assert subset_keys() == [] and len(view._bank_cache) == 1
    # One device would have taken the row subset at that limit.
    assert Executor(h).execute("i", pql) == [want]
    assert [k[3] for k in subset_keys()] == [(1, 2)]
    view._bank_cache.clear()
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES", SHARE - 1)
    assert Executor(h, mesh=mesh4).execute("i", pql) == [want]
    assert [k[3] for k in subset_keys()] == [(1, 2)]


# ------------------------------------------------------ (b) the budget


@pytest.mark.parametrize("placement", ["mesh", "single_device"])
def test_two_banks_stay_resident_when_their_shares_fit_the_budget(
        tmp_path, mesh4, monkeypatch, placement):
    """Each bank is over half the budget as a whole array and well under
    it per device: under the mesh both stay through alternating queries,
    on one device each evicts the other, as before."""
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    rows = {}
    for name, seed in (("f", 31), ("g", 32)):
        rows[name] = _rows(seed)
        _import(idx.create_field(name), rows[name])
    budget = view_mod.BankBudget(WHOLE + WHOLE // 2)
    monkeypatch.setattr(view_mod, "BANK_BUDGET", budget)
    mesh = mesh4 if placement == "mesh" else None
    ex = Executor(h, mesh=mesh)
    ex.stats = MemStatsClient()
    for turn in range(3):
        for name in ("f", "g"):
            (res,) = ex.execute(
                "i", f"TopN({name}, Row({name}={FILTER_ROW}), n=12)")
            assert res.pairs == _reference(
                rows[name], rows[name][FILTER_ROW], 12)
    assert _topn_paths(ex.stats)["resident"] == 6
    resident = [len(idx.field(n).view()._bank_cache) for n in ("f", "g")]
    if mesh is not None:
        assert budget.evictions == 0 and resident == [1, 1]
        assert budget.total == 2 * SHARE
    else:
        assert budget.evictions == 5 and sorted(resident) == [0, 1]
        assert budget.total == WHOLE
    h.close()


# -------------------------------------------------- (c) the block build


def _whole_array_build(view, shards, rows=None) -> np.ndarray:
    """The bank as one host array, by the plain loop."""
    frags = {s: view.fragment(s) for s in shards}
    row_set = sorted(rows if rows is not None else
                     {r for f in frags.values() if f for r in f.row_ids()})
    width = view.trimmed_words()
    host = np.zeros((view_mod.bank_capacity(len(row_set)), len(shards),
                     width), np.uint32)
    for si, s in enumerate(shards):
        if frags[s] is not None:
            host[:len(row_set), si] = frags[s].rows_dense(row_set, width)
    return host


@pytest.fixture
def recorded():
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=64, sample_every=1)
    yield
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=256, sample_every=1)


def test_block_build_equals_the_whole_array_build(holder, mesh4,
                                                  monkeypatch, recorded):
    h, _ = holder
    view = h.index("i").field("g").view()
    shards = tuple(range(N_SHARDS))
    want = _whole_array_build(view, shards)
    assert want.shape == (64, N_SHARDS, 2048) and want.any()
    view._bank_cache.clear()
    staged = []
    zeros = np.zeros

    def counting_zeros(shape, *a, **kw):
        out = zeros(shape, *a, **kw)
        if np.ndim(shape) and len(shape) == 3:
            staged.append(out.nbytes)
        return out

    monkeypatch.setattr(view_mod.np, "zeros", counting_zeros)
    rec = TIMELINE.begin(None, "i", stats=(stats := MemStatsClient()))
    with TIMELINE.attached(rec), TIMELINE.phase("plan"):
        bank = view.device_bank(shards, mesh=mesh4, trim=True)
    TIMELINE.finish(rec)
    monkeypatch.undo()
    # Bit for bit the whole-array build, placed as bank_sharding places.
    assert np.array_equal(np.asarray(bank.array), want)
    assert bank.array.sharding == mesh4.bank_sharding()
    per = N_SHARDS // 4
    for d, sh in enumerate(sorted(bank.array.addressable_shards,
                                  key=lambda s: s.index[1].start)):
        assert sh.data.shape == (64, per, 2048)
        assert sh.index[1] == slice(d * per, (d + 1) * per)
        assert np.array_equal(np.asarray(sh.data),
                              want[:, d * per:(d + 1) * per])
        assert mesh4.placement.device_of(shards, shards[d * per]) == d
    # Four blocks of a device's size were staged, never the whole array.
    assert staged == [SHARE] * 4
    (up,) = [s for s in rec.root.walk() if s.name == "plan.bank_upload"]
    assert up.attrs == {"bytes": WHOLE, "devices": 4, "blocks": 4}
    (plan,) = [s for s in rec.root.children if s.name == "plan"]
    assert up in plan.children          # a child: the stages still tile
    assert stats.snapshot()["counters"]["executor.bank_upload_bytes"] \
        == WHOLE
    # The budget holds it by a device's share.
    assert view_mod.BANK_BUDGET._entries[
        (id(view), (shards, mesh4.cache_key(), True))][1] == SHARE
    # Without a mesh: one block, the whole array.
    view._bank_cache.clear()
    rec = TIMELINE.begin(None, "i", stats=MemStatsClient())
    with TIMELINE.attached(rec), TIMELINE.phase("plan"):
        one = view.device_bank(shards, trim=True)
    TIMELINE.finish(rec)
    assert np.array_equal(np.asarray(one.array), want)
    (up,) = [s for s in rec.root.walk() if s.name == "plan.bank_upload"]
    assert up.attrs == {"bytes": WHOLE, "devices": 1, "blocks": 1}


def test_shard_counts_that_do_not_divide_pad_as_before(tmp_path, mesh4):
    """Six shards on four devices: the executor pads the list to eight
    with absent shards, whose columns are zero in every block."""
    h = Holder(str(tmp_path))
    h.open()
    rows = _rows(41, n_shards=6)
    f = h.create_index("i").create_field("f")
    _import(f, rows)
    ex = Executor(h, mesh=mesh4)
    (res,) = ex.execute("i", f"TopN(f, Row(f={FILTER_ROW}), n=12)")
    assert res.pairs == _reference(rows, rows[FILTER_ROW], 12)
    ((key, bank),) = f.view()._bank_cache.items()
    assert len(key[0]) == 8 and bank.array.shape == (64, 8, 2048)
    assert np.array_equal(np.asarray(bank.array)[:, :6],
                          _whole_array_build(f.view(), tuple(range(6))))
    assert not np.asarray(bank.array)[:, 6:].any()
    with pytest.raises(ValueError, match="pad the list first"):
        f.view().device_bank(tuple(range(6)), mesh=mesh4, trim=True)
    h.close()


def test_patch_and_row_subset_builds_agree_with_a_rebuild(tmp_path, mesh4):
    h = Holder(str(tmp_path))
    h.open()
    rows = _rows(43)
    f = h.create_index("i").create_field("f")
    _import(f, rows)
    view = f.view()
    shards = tuple(range(N_SHARDS))
    ex = Executor(h, mesh=mesh4)
    q = f"TopN(f, Row(f={FILTER_ROW}), n=12)"
    ex.execute("i", q)
    first = view.device_bank(shards, mesh=mesh4, trim=True)
    # A Set on an existing row and one on a new row: the cached bank is
    # patched in place of a rebuild, and stays sharded.
    col = 5 * SHARD_WIDTH + WIDTH_COLS - 1
    assert col not in rows[3] and col not in rows[FILTER_ROW]
    ex.execute("i", f"Set({col}, f=3) Set({col}, f={N_ROWS})")
    rows[3] = np.union1d(rows[3], [col]).astype(np.uint64)
    rows[N_ROWS] = np.array([col], np.uint64)
    patched = view.device_bank(shards, mesh=mesh4, trim=True)
    assert patched is not first
    assert list(patched.slots)[-1] == N_ROWS       # appended, not rebuilt
    assert patched.array.sharding == mesh4.bank_sharding()
    view._bank_cache.clear()
    rebuilt = view.device_bank(shards, mesh=mesh4, trim=True)
    for r in rows:
        assert np.array_equal(
            np.asarray(patched.array[patched.slot(r)]),
            np.asarray(rebuilt.array[rebuilt.slot(r)]))
    (after,) = ex.execute("i", q)
    assert after.pairs == _reference(rows, rows[FILTER_ROW], 12)
    # A rows= subset build holds the same cells as the full bank's.
    subset = view.device_bank(shards, rows=[2, 3, 17], mesh=mesh4,
                              trim=True)
    assert subset.array.shape == (4, N_SHARDS, 2048)
    assert subset.array.sharding == mesh4.bank_sharding()
    assert np.array_equal(np.asarray(subset.array),
                          _whole_array_build(view, shards, rows=[2, 3, 17]))
    for r in (2, 3, 17):
        assert np.array_equal(
            np.asarray(subset.array[subset.slot(r)]),
            np.asarray(rebuilt.array[rebuilt.slot(r)]))
    h.close()


# ------------------------------------- what the server says and records


def test_info_counters_and_spans_of_a_mesh_server(holder, mesh4, recorded):
    h, rows = holder
    stats = MemStatsClient()
    api = API(h, mesh=mesh4, stats=stats)
    api.executor.result_cache.enabled = False
    info = api.info()
    assert info["meshDevices"] == 4
    assert info["residentLimits"] == {
        "topnBankBytesPerDevice": ex_mod.TOPN_MAX_BANK_BYTES,
        "bankBudgetBytesPerDevice": view_mod.BANK_BUDGET.budget}
    assert set(info["bankBudget"]) == {"bytesPerDevice", "evictions"}
    # Published from the start: a window without a streamed TopN reads
    # 0 for it, so a share of the two is a number.
    c = stats.snapshot()["counters"]
    assert _topn_paths(stats) == dict.fromkeys(Executor.TOPN_PATHS, 0)
    assert all(f"executor.topn_sweeps{{path:{p}}}" in c
               for p in Executor.TOPN_PATHS)
    assert c["executor.bank_upload_bytes"] == 0
    h.index("i").field("f").view()._bank_cache.clear()
    got = api.query("i", f"TopN(f, Row(f={FILTER_ROW}), n=3)")["results"][0]
    assert [(p["id"], p["count"]) for p in got] == _reference(
        rows["f"], rows["f"][FILTER_ROW], 3)
    rec = TIMELINE.requests()[-1]
    spans = list(rec.root.walk())
    sweeps = [s for s in spans if s.name == "dispatch"
              and s.attrs["program"] == "topn_sweep"]
    assert len(sweeps) == 1
    assert all(s.attrs["mesh_devices"] == 4 for s in spans
               if s.name == "dispatch")
    (up,) = [s for s in spans if s.name == "plan.bank_upload"]
    assert (up.attrs["bytes"], up.attrs["blocks"]) == (WHOLE, 4)
    # The record still tiles: top-level stages + unaccounted = total.
    top = sum(s.pc_end - s.pc_start for s in rec.root.children)
    total = rec.root.pc_end - rec.root.pc_start
    assert top + rec.unaccounted == pytest.approx(total, abs=1e-6)
    c = stats.snapshot()["counters"]
    assert c["executor.topn_sweeps{path:resident}"] == 1
    assert c["executor.bank_upload_bytes"] == WHOLE
    after = api.info()["bankBudget"]
    assert after["bytesPerDevice"] == view_mod.BANK_BUDGET.total >= SHARE
    assert after["evictions"] == view_mod.BANK_BUDGET.evictions
    assert API(h).info()["meshDevices"] == 1
