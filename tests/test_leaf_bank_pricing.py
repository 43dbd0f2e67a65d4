"""What a Row leaf's bank costs (`Executor._get_bank_for`, PR 39): the
view's full bank is priced by the rows the view HAS — the union over the
shards, `View.merged_row_ids` — and the row-subset bank is for a view
whose full bank really is over `BANK_MAX_BYTES`.

(a), (b): the live deployment's `total_amount_dollars` at its own shape,
16 full-width shards x the same 77 rows = a [128, 16, 32768] bank of
256 MiB at the default limit, where the sum over shards (1,232 rows ->
2,048 slots, 4 GiB) sent every leaf the row-subset way; after a `Set`
the leaf's read patches that bank. (c), (d): what still goes the
row-subset way, and a single fragment, at small shapes."""

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.view import bank_capacity
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient

N_SHARDS = 16
N_ROWS = 77
WORDS = SHARD_WIDTH // 32
COUNTERS = ("executor.bank_patches", "executor.bank_subset_rebuilds",
            "executor.bank_upload_bytes")


def _subset_keys(view) -> list:
    return [k for k in view._bank_cache if len(k) == 4]


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """Index `w`, field `f`: every one of 77 rows in every one of 16
    shards, each shard full width (a bit in its last word); field `g`,
    one row. row id -> set of columns, kept beside it."""
    h = Holder(str(tmp_path_factory.mktemp("leaf_bank_pricing")))
    h.open()
    idx = h.create_index("w")
    rng = np.random.default_rng(39)
    cols = {}
    for r in range(N_ROWS):
        per_shard = [s * SHARD_WIDTH + rng.integers(0, SHARD_WIDTH - 1, 6)
                     for s in range(N_SHARDS)]
        cols[r] = set(np.concatenate(per_shard).astype(np.uint64).tolist())
    cols[0] |= {s * SHARD_WIDTH + SHARD_WIDTH - 1 for s in range(N_SHARDS)}
    idx.create_field("f").import_bits(
        np.concatenate([np.full(len(c), r, np.uint64)
                        for r, c in cols.items()]),
        np.concatenate([np.fromiter(c, np.uint64, len(c))
                        for c in cols.values()]))
    g_cols = set(list(cols[3])[::2]) | set(list(cols[5])[::3])
    idx.create_field("g").import_bits(
        np.ones(len(g_cols), np.uint64),
        np.fromiter(g_cols, np.uint64, len(g_cols)))
    stats = MemStatsClient()
    api = API(h, stats=stats)
    api.executor.result_cache.enabled = False
    yield api, stats, cols, g_cols
    h.close()


def test_a_leaf_reads_the_full_bank_of_a_view_priced_by_its_union(wide):
    api, _, cols, g_cols = wide
    view = api.holder.index("w").field("f").view()
    shards = tuple(range(N_SHARDS))
    assert view.trimmed_words() == WORDS
    assert len(view.merged_row_ids(shards)) == N_ROWS
    by_sum = sum(len(view.fragment(s).row_ids()) for s in shards)
    assert by_sum == N_SHARDS * N_ROWS
    full = bank_capacity(N_ROWS) * N_SHARDS * WORDS * 4
    assert full == 256 << 20 <= Executor.BANK_MAX_BYTES == 2 << 30
    assert bank_capacity(by_sum) * N_SHARDS * WORDS * 4 \
        > Executor.BANK_MAX_BYTES
    got = api.query("w", "Count(Intersect(Row(f=3), Row(g=1)))")["results"]
    assert got == [len(cols[3] & g_cols)]
    assert _subset_keys(view) == []
    (key,) = view._bank_cache
    bank = view._bank_cache[key]
    assert bank is view.device_bank(shards, trim=True)
    assert bank.array.shape == (128, N_SHARDS, WORDS)


def test_a_leaf_after_a_set_patches_the_full_bank(wide):
    api, stats, cols, g_cols = wide
    pql = "Count(Intersect(Row(f=5), Row(g=1)))"
    assert api.query("w", pql)["results"] == [len(cols[5] & g_cols)]
    before = dict(stats.snapshot()["counters"])
    col = next(c for c in sorted(g_cols) if c not in cols[5])
    assert api.query("w", f"Set({col}, f=5)")["results"] == [True]
    cols[5].add(col)
    assert api.query("w", pql)["results"] == [len(cols[5] & g_cols)]
    after = stats.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    assert moved == {"executor.bank_patches": 1,
                     "executor.bank_subset_rebuilds": 0,
                     "executor.bank_upload_bytes": 0}
    view = api.holder.index("w").field("f").view()
    assert _subset_keys(view) == []
    # ... and the patched bank holds the row as numpy has it.
    (bank,) = view._bank_cache.values()
    words = np.asarray(bank.array[bank.slot(5)])
    for s in range(N_SHARDS):
        bits = np.flatnonzero(np.unpackbits(
            words[s].view(np.uint8), bitorder="little"))
        assert set((bits + s * SHARD_WIDTH).tolist()) == {
            c for c in cols[5] if c // SHARD_WIDTH == s}


def _narrow_bytes(n_rows: int, n_shards: int, view) -> int:
    return bank_capacity(n_rows) * n_shards * view.trimmed_words() * 4


def test_disjoint_rows_whose_union_is_past_the_limit_build_the_subset(
        tmp_holder, monkeypatch):
    """Four shards of ten rows each, no row in two shards: the union IS
    the sum, 40 rows -> 64 slots, and a limit under that bank sends the
    leaf the row-subset way as it always did."""
    idx = tmp_holder.create_index("d")
    f = idx.create_field("f")
    rows = np.arange(40, dtype=np.uint64)
    f.import_bits(rows, (rows // 10) * SHARD_WIDTH + rows)
    view = f.view()
    shards = (0, 1, 2, 3)
    assert len(view.merged_row_ids(shards)) == 40
    e = Executor(tmp_holder)
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES",
                        _narrow_bytes(40, 4, view) - 1)
    assert e.execute("d", "Count(Union(Row(f=7), Row(f=23)))") == [2]
    assert [k[3] for k in _subset_keys(view)] == [(7, 23)]
    assert view._bank_cache[_subset_keys(view)[0]].array.shape[0] == 4
    # At the bank's own size it is not over the limit: the full bank.
    view._bank_cache.clear()
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES",
                        _narrow_bytes(40, 4, view))
    assert e.execute("d", "Count(Union(Row(f=7), Row(f=23)))") == [2]
    assert _subset_keys(view) == [] and len(view._bank_cache) == 1


@pytest.mark.parametrize("over", [False, True])
def test_a_single_fragment_view_prices_as_before(tmp_holder, monkeypatch,
                                                 over):
    """One shard: the fragment's rows are the union. At the bank's size
    the leaf reads the full bank, a byte under it the row subset; a
    leaf that needs every row takes the full bank either way."""
    idx = tmp_holder.create_index("s")
    f = idx.create_field("f")
    rows = np.arange(20, dtype=np.uint64)
    f.import_bits(rows, rows * 3)
    view = f.view()
    assert view.merged_row_ids((0,)) == view.fragment(0).row_ids()
    monkeypatch.setattr(Executor, "BANK_MAX_BYTES",
                        _narrow_bytes(20, 1, view) - int(over))
    e = Executor(tmp_holder)
    assert e.execute("s", "Count(Row(f=4))") == [1]
    assert [k[3] for k in _subset_keys(view)] == ([(4,)] if over else [])
    view._bank_cache.clear()
    bank = e._get_bank_for(f, "standard", (0,), rows_needed=set(range(20)))
    assert _subset_keys(view) == [] and bank.array.shape[0] == 32
