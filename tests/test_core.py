"""Data model tests: fragment durability, field types, time views, holder walk."""

from datetime import datetime

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core import timeq
from pilosa_tpu.ops.bitset import SHARD_WIDTH


def test_fragment_set_clear_persist(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    assert f.set_bit(1, 100)
    assert not f.set_bit(1, 100)
    assert f.set_bit(1, SHARD_WIDTH + 5)  # second shard
    assert f.set_bit(2, 100)
    assert f.clear_bit(2, 100)
    assert f.available_shards() == [0, 1]
    h.close()

    h2 = Holder(str(tmp_path))
    h2.open()
    f2 = h2.index("i").field("f")
    frag = f2.view().fragment(0)
    assert frag.bit(1, 100)
    assert not frag.bit(2, 100)
    assert f2.view().fragment(1).bit(1, SHARD_WIDTH + 5)
    assert f2.available_shards() == [0, 1]
    h2.close()


def test_fragment_snapshot_rolls_oplog(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("f")
    frag = f.create_view_if_not_exists("standard").create_fragment_if_not_exists(0)
    frag.max_op_n = 10
    for c in range(25):
        frag.set_bit(0, c)
    assert frag.storage.op_n < 10  # snapshotted at least once
    h.close()
    h2 = Holder(str(tmp_path))
    h2.open()
    frag2 = h2.index("i").field("f").view().fragment(0)
    assert frag2.row_count(0) == 25
    h2.close()


def test_row_reads_and_bank(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("f")
    cols = np.array([1, 5, 99, SHARD_WIDTH - 1], dtype=np.uint64)
    f.import_bits(np.full(len(cols), 3, dtype=np.uint64), cols)
    frag = f.view().fragment(0)
    np.testing.assert_array_equal(frag.row_columns(3), cols)
    assert frag.row_ids() == (3,)
    bank, slots = frag.bank()
    assert bank.shape[0] == 1 and 3 in slots
    # write -> dirty -> bank refresh
    frag.set_bit(3, 42)
    bank2, slots2 = frag.bank()
    from pilosa_tpu.ops import bitset as bs
    got = bs.unpack_positions(np.asarray(bank2[slots2[3]]))
    np.testing.assert_array_equal(got, np.sort(np.append(cols, 42)))
    h.close()


def test_mutex_field(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("m", FieldOptions(type="mutex"))
    f.set_bit(1, 10)
    f.set_bit(2, 10)  # clears row 1
    frag = f.view().fragment(0)
    assert not frag.bit(1, 10)
    assert frag.bit(2, 10)
    # bulk mutex import
    f.import_bits(np.array([5, 6], np.uint64), np.array([10, 20], np.uint64))
    assert frag.mutex_vector(10) == 5
    assert frag.mutex_vector(20) == 6
    h.close()


def test_bool_field(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("b", FieldOptions(type="bool"))
    f.set_bit(1, 7)   # true
    f.set_bit(0, 7)   # flips to false
    frag = f.view().fragment(0)
    assert frag.bit(0, 7) and not frag.bit(1, 7)
    h.close()


def test_int_field_values(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field(
        "n", FieldOptions(type="int", min=-10, max=1000))
    assert f.set_value(3, -10)
    assert f.set_value(4, 1000)
    assert f.set_value(5, 0)
    assert f.value(3) == (-10, True)
    assert f.value(4) == (1000, True)
    assert f.value(5) == (0, True)
    assert f.value(6) == (0, False)
    with pytest.raises(ValueError):
        f.set_value(7, 1001)
    # bulk
    cols = np.arange(100, 200, dtype=np.uint64)
    vals = np.arange(-10, 90, dtype=np.int64)
    f.import_values(cols, vals)
    assert f.value(150) == (40, True)
    h.close()
    h2 = Holder(str(tmp_path))
    h2.open()
    f2 = h2.index("i").field("n")
    assert f2.value(150) == (40, True)
    assert f2.value(3) == (-10, True)
    h2.close()


def test_time_field_views(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field(
        "t", FieldOptions(type="time", time_quantum="YMDH"))
    ts = datetime(2018, 3, 4, 5)
    f.set_bit(1, 9, timestamp=ts)
    names = set(f.views.keys())
    assert {"standard", "standard_2018", "standard_201803",
            "standard_20180304", "standard_2018030405"} <= names
    for vn in names:
        assert f.view(vn).fragment(0).bit(1, 9)
    h.close()


def test_views_by_time_range_minimal_cover():
    views = timeq.views_by_time_range(
        "standard", datetime(2018, 1, 31, 22), datetime(2018, 3, 2, 2), "YMDH")
    assert views == [
        "standard_2018013122", "standard_2018013123",
        "standard_201802",
        "standard_20180301",
        "standard_2018030200", "standard_2018030201",
    ]
    # whole year aligns to one view
    assert timeq.views_by_time_range(
        "standard", datetime(2018, 1, 1), datetime(2019, 1, 1), "YMDH") == \
        ["standard_2018"]


def test_existence_field_tracks_columns(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("i", track_existence=True)
    idx.create_field("f")
    idx.add_existence(np.array([1, 2, 3], dtype=np.uint64))
    ef = idx.existence_field()
    frag = ef.view().fragment(0)
    np.testing.assert_array_equal(frag.row_columns(0), [1, 2, 3])
    h.close()


def test_block_checksums_and_merge(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("f")
    f.import_bits(np.array([0, 1, 250], np.uint64), np.array([5, 6, 7], np.uint64))
    frag = f.view().fragment(0)
    blocks = dict(frag.checksum_blocks())
    assert set(blocks) == {0, 2}
    # identical data on a second holder hashes identically
    h2 = Holder(str(tmp_path / "other"))
    h2.open()
    g = h2.create_index("i").create_field("f")
    g.import_bits(np.array([0, 1, 250], np.uint64), np.array([5, 6, 7], np.uint64))
    frag2 = g.view().fragment(0)
    assert dict(frag2.checksum_blocks()) == blocks
    # diverge and merge
    frag2.set_bit(1, 8)
    rows, cols = frag2.block_data(0)
    (_, _), (theirs_rows, theirs_cols) = frag.merge_block(0, rows, cols)
    assert frag.bit(1, 8)
    assert len(theirs_rows) == 0
    h.close()
    h2.close()


def test_holder_schema_and_delete(tmp_path):
    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("myindex")
    idx.create_field("f1")
    idx.create_field("n1", FieldOptions(type="int", min=0, max=100))
    schema = h.schema()
    assert schema[0]["name"] == "myindex"
    assert [f["name"] for f in schema[0]["fields"]] == ["f1", "n1"]
    with pytest.raises(ValueError):
        h.create_index("myindex")
    with pytest.raises(ValueError):
        h.create_index("BadName")
    idx.delete_field("f1")
    assert idx.field("f1") is None
    h.delete_index("myindex")
    assert h.index("myindex") is None
    h.close()


def test_import_roaring(tmp_path):
    from pilosa_tpu.storage import Bitmap

    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_field("f")
    # row 2, columns 10,11 encoded as a roaring fragment payload
    bm = Bitmap(np.array([2 * SHARD_WIDTH + 10, 2 * SHARD_WIDTH + 11],
                         dtype=np.uint64))
    frag = f.create_view_if_not_exists("standard").create_fragment_if_not_exists(0)
    frag.import_roaring(bm.write_bytes())
    assert frag.bit(2, 10) and frag.bit(2, 11)
    np.testing.assert_array_equal(frag.row_columns(2), [10, 11])
    h.close()


def test_topn_cache_persists_and_reloads(tmp_path):
    """.cache sidecar flush + reload (reference flushCache fragment.go:1858,
    openCache :252)."""
    import os
    from pilosa_tpu.core.fragment import Fragment

    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0)
    f.open()
    for row, n in [(1, 3), (2, 5), (9, 1)]:
        for c in range(n):
            f.set_bit(row, c)
    f.close()  # flushes cache
    assert os.path.exists(f.cache_path())
    g = Fragment(path, "i", "f", "standard", 0)
    g.open()
    assert g.cache.get(2) == 5
    assert g.cache.get(1) == 3
    top = g.cache.top()
    assert top[0] == (2, 5)
    g.close()


def test_time_field_bulk_import_with_timestamps(tmp_path):
    """Timestamped bulk import fans bits into quantum views (reference
    field.Import routing per RowTime, field.go:1054, time.go:91)."""
    from datetime import datetime
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.executor import Executor

    h = Holder(str(tmp_path))
    h.open()
    idx = h.create_index("t")
    f = idx.create_field("e", FieldOptions(type="time",
                                           time_quantum="YMD"))
    ts = [datetime(2018, 1, 2), datetime(2018, 1, 5), datetime(2018, 2, 1)]
    f.import_bits(np.array([1, 1, 1], np.uint64),
                  np.array([10, 11, 12], np.uint64),
                  timestamps=ts)
    names = set(f.views.keys())
    assert "standard_2018" in names and "standard_201801" in names \
        and "standard_20180102" in names
    ex = Executor(h)
    (res,) = ex.execute(
        "t", "Row(e=1, from='2018-01-01T00:00', to='2018-02-01T00:00')")
    assert res.columns().tolist() == [10, 11]
    (res,) = ex.execute(
        "t", "Row(e=1, from='2018-01-03T00:00', to='2018-03-01T00:00')")
    assert res.columns().tolist() == [11, 12]
    h.close()


def test_bulk_import_clear_flag(tmp_path):
    """Import with clear=True removes the given bits (reference
    fragment.bulkImport clear path / Import clear arg)."""
    from pilosa_tpu.core.holder import Holder

    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("c").create_field("f")
    f.import_bits(np.array([1, 1, 1], np.uint64),
                  np.array([5, 6, 7], np.uint64))
    f.import_bits(np.array([1, 1], np.uint64),
                  np.array([6, 7], np.uint64), clear=True)
    frag = f.view().fragment(0)
    assert frag.bit(1, 5) and not frag.bit(1, 6) and not frag.bit(1, 7)
    h.close()


def test_mutex_bulk_import_last_wins(tmp_path):
    """Mutex bulk import keeps one row per column — later value wins
    (reference bulkImportMutex, fragment.go:1605)."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions

    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("m").create_field("mx", FieldOptions(type="mutex"))
    f.import_bits(np.array([1, 2, 3], np.uint64),
                  np.array([7, 7, 7], np.uint64))
    frag = f.view().fragment(0)
    assert not frag.bit(1, 7) and not frag.bit(2, 7) and frag.bit(3, 7)
    # and a fresh write still clears the previous value
    f.set_bit(1, 7)
    assert frag.bit(1, 7) and not frag.bit(3, 7)
    h.close()


def test_cache_sidecar_rejected_after_unclean_shutdown(tmp_path):
    """A .cache sidecar saved before later ops reached disk must load as
    COLD on reopen — a complete-looking stale cache would let TopN's
    warm-cache shortcut serve wrong counts. The sidecar is stamped with
    the storage bytes it was computed from (size + tail checksum)."""
    from pilosa_tpu.core.fragment import Fragment

    path = str(tmp_path / "frag")
    f1 = Fragment(path, "i", "f", "standard", 0)
    f1.open()
    f1.bulk_import(np.array([1, 1, 1], np.uint64),
                   np.array([1, 2, 3], np.uint64))
    f1.close()  # clean: sidecar saved, stamp matches

    # Clean reopen loads the cache.
    f2 = Fragment(path, "i", "f", "standard", 0)
    f2.open()
    assert len(f2.cache) == 1 and f2.cache.get(1) == 3
    # More writes reach the op log on disk...
    f2.bulk_import(np.array([2, 2, 2, 2], np.uint64),
                   np.array([1, 2, 3, 4], np.uint64))
    f2._file.flush()
    # ...but the process dies without close(): no sidecar update.
    f2._file.close()
    f2.storage.op_writer = None

    f3 = Fragment(path, "i", "f", "standard", 0)
    f3.open()
    assert f3.row_count(2) == 4  # ops replayed: storage is current
    # Stale sidecar rejected — cache cold, so the TopN shortcut is
    # ineligible and the exact sweep answers.
    assert len(f3.cache) == 0
    f3.close()


def test_mutex_bulk_import_vectorized_conflicts(tmp_path):
    """Wide mutex import against pre-existing assignments: the dense
    conflict pass must clear exactly the columns whose row changes and
    keep columns re-asserting their current row (reference
    bulkImportMutex, fragment.go:1605). Cross-checked against a dict
    model."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions

    rng = np.random.default_rng(7)
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("m").create_field("mx", FieldOptions(type="mutex"))

    model = {}
    for _ in range(3):
        cols = rng.integers(0, 5000, 800, dtype=np.uint64)
        rows = rng.integers(0, 20, 800, dtype=np.uint64)
        f.import_bits(rows, cols)
        for r, c in zip(rows.tolist(), cols.tolist()):
            model[c] = r

    frag = f.view().fragment(0)
    got = {}
    for r in frag.row_ids():
        for c in frag.row_columns(r).tolist():
            assert c not in got, f"column {c} set in rows {got[c]} and {r}"
            got[c] = r
    assert got == model
    h.close()


def test_translate_replica_cursor_survives_out_of_order_adoption():
    """Incremental translate replication resumes from an explicit cursor
    into the primary's log, not the replica's own log size — replicas
    adopt out-of-order entries via primary-fallback lookups, so their
    logs are not prefixes of the primary's (reference replicate loop,
    translate.go:400)."""
    from pilosa_tpu.core.translate import TranslateStore

    primary, replica = TranslateStore(), TranslateStore()
    a = primary.translate_key("alpha")
    b = primary.translate_key("beta")
    replica.apply_entries([("beta", b)])  # out-of-order adoption
    replica.apply_log(primary.read_log_from(replica.replica_offset),
                      resume=True)
    assert replica.translate_id(a) == "alpha"  # not skipped by the offset
    assert replica.replica_offset == len(primary.log_bytes())
    # resumed pass is a no-op
    assert replica.apply_log(
        primary.read_log_from(replica.replica_offset), resume=True) == 0
    # new allocations stream incrementally
    c = primary.translate_key("gamma")
    applied = replica.apply_log(
        primary.read_log_from(replica.replica_offset), resume=True)
    assert applied == 1 and replica.translate_id(c) == "gamma"


def test_max_columns_trimmed_banks(tmp_path):
    """Declared column bound: banks trim to a 128-word granule instead of
    the 8 KiB container floor (TPU-first extension, no reference
    counterpart; motivates the 4096-bit fingerprint workload,
    docs/examples.md chem use case)."""
    import pytest as _pytest

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder

    h = Holder(str(tmp_path))
    h.open()
    try:
        idx = h.create_index("mc")
        f = idx.create_field("fp", FieldOptions(max_columns=4096))
        rows = np.repeat(np.arange(50, dtype=np.uint64), 8)
        cols = np.tile(np.arange(8, dtype=np.uint64) * 512 + 3, 50)
        f.import_bits(rows, cols)
        view = f.view()
        assert view.trimmed_words() == 128  # 4096 bits exactly
        bank = view.device_bank((0,), trim=True)
        assert bank.array.shape[-1] == 128
        # Row data survives the narrow round trip.
        got = np.asarray(bank.array[bank.slot(7)][0])
        import numpy as _np
        want = f.view().fragment(0).row_dense(7, u32_words=128)
        _np.testing.assert_array_equal(got, want)
        # Writes past the bound fail loudly.
        with _pytest.raises(ValueError, match="max_columns"):
            f.set_bit(1, 4096)
        with _pytest.raises(ValueError, match="max_columns"):
            f.import_bits(np.array([1], np.uint64),
                          np.array([5000], np.uint64))
        # In another shard the per-shard offset is what's bounded.
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        assert f.set_bit(1, SHARD_WIDTH + 100)
        # Reopen: the bound persists via .meta.
        h.close()
        h2 = Holder(str(tmp_path))
        h2.open()
        f2 = h2.index("mc").field("fp")
        assert f2.options.max_columns == 4096
        assert f2.view().trimmed_words() == 128
        h2.close()
    finally:
        try:
            h.close()
        except Exception:
            pass


def test_sub_container_row_dense_and_set_row(tmp_path):
    """row_dense/rows_dense/set_row at sub-container widths."""
    from pilosa_tpu.core.fragment import Fragment

    frag = Fragment(str(tmp_path / "f"), "i", "f", "standard", 0)
    frag.open()
    frag.bulk_import(np.array([2, 2, 3], np.uint64),
                     np.array([0, 4095, 70000], np.uint64))
    d = frag.row_dense(2, u32_words=128)
    assert d.shape == (128,) and d[0] & 1 and (d[127] >> 31) & 1
    bulk = frag.rows_dense([2, 3], 128)
    np.testing.assert_array_equal(bulk[0], d)
    assert bulk[1].any() == False  # row 3's bit is past 4096
    bulk_wide = frag.rows_dense([3], 4096)
    assert bulk_wide[0][70000 // 32] >> (70000 % 32) & 1
    # set_row with a 128-word operand clears the whole rest of the row.
    words = np.zeros(128, np.uint32)
    words[1] = 0b100
    frag.set_row(3, words)
    assert frag.bit(3, 34) and not frag.bit(3, 70000)
    frag.close()


@pytest.mark.parametrize("native_scatter", [True, False])
@pytest.mark.parametrize("u32_words", [32768, 4096, 6144])
def test_rows_dense_over_whole_containers_equals_row_by_row(
        tmp_path, monkeypatch, native_scatter, u32_words):
    """A bank-wide gather (rows x whole containers in one probe and one
    scatter) holds the cells that `row_dense` gives row by row: array
    and dense containers mixed, absent rows and absent containers, at a
    shard's width, at two containers and at a width that is no whole
    number of containers (the row-by-row path)."""
    from pilosa_tpu import native
    from pilosa_tpu.core.fragment import Fragment

    if not native_scatter:
        monkeypatch.setattr(native, "scatter_rows", lambda *a, **k: False)
    rng = np.random.default_rng(26)
    frag = Fragment(str(tmp_path / "f"), "i", "f", "standard", 0)
    frag.open()
    rows, cols = [], []
    for r in (0, 1, 5, 9, 1022):
        c = np.unique(rng.integers(0, 1 << 20, 1500).astype(np.uint64))
        rows.append(np.full(c.size, r, np.uint64))
        cols.append(c)
    # Row 7: a dense-encoded container (5,000 bits of one container)
    # beside array containers; row 3 stays absent.
    c = np.unique(np.concatenate([
        rng.choice(1 << 16, 5000, replace=False).astype(np.uint64)
        + np.uint64(2 << 16),
        rng.integers(0, 1 << 20, 300).astype(np.uint64)]))
    rows.append(np.full(c.size, 7, np.uint64))
    cols.append(c)
    frag.bulk_import(np.concatenate(rows), np.concatenate(cols))
    dtypes = {c.dtype for c in frag.storage.containers.values()}
    assert dtypes == {np.dtype(np.uint16), np.dtype(np.uint64)}
    ids = [0, 1, 3, 5, 7, 9, 1022]
    got = frag.rows_dense(ids, u32_words)
    assert got.shape == (len(ids), u32_words) and got.dtype == np.uint32
    for i, r in enumerate(ids):
        np.testing.assert_array_equal(
            got[i], frag.row_dense(r, u32_words=u32_words))
    assert not got[2].any() and got[4].any()
    frag.close()


def test_time_field_requires_quantum_and_bsi_bound(tmp_path):
    """Regressions from review: time fields must still demand a quantum,
    and max_columns binds BSI writes too."""
    import pytest as _pytest

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder

    with _pytest.raises(ValueError, match="quantum"):
        FieldOptions(type="time", time_quantum="").validate()
    h = Holder(str(tmp_path))
    h.open()
    try:
        idx = h.create_index("tb")
        f = idx.create_field("v", FieldOptions(type="int", min=0, max=100,
                                               max_columns=4096))
        f.set_value(10, 5)
        with _pytest.raises(ValueError, match="max_columns"):
            f.set_value(5000, 7)
        with _pytest.raises(ValueError, match="max_columns"):
            f.import_values(np.array([4096], np.uint64),
                            np.array([1], np.int64))
    finally:
        h.close()


def test_noop_remove_keeps_array_encoding(tmp_path):
    import numpy as np

    from pilosa_tpu.storage.roaring import Bitmap

    b = Bitmap([1, 5, 9])
    b.optimize()
    assert not b.remove(6)  # no-op: must not materialize dense
    assert b.containers[0].dtype == np.uint16
    assert b.remove(5) and b.containers[0].dtype == np.uint64


def test_bulk_import_snapshot_failure_keeps_durability(tmp_path, monkeypatch):
    """Batch imports append their op record BEFORE the amortized fold
    check, so even a snapshot that fails mid-rewrite (disk full during
    the byte-triggered fold) leaves the batch durable in the log."""
    import numpy as np
    from pilosa_tpu.core import fragment as fragment_mod
    from pilosa_tpu.core.fragment import Fragment

    # Any batch record trips the byte-based fold immediately.
    monkeypatch.setattr(fragment_mod, "OPLOG_FOLD_MIN_BYTES", 1)
    p = str(tmp_path / "f")
    f = Fragment(p, "i", "f", "standard", 0)
    f.open()
    # Fail INSIDE the real _snapshot, after it has already closed the
    # op-log append handle — the hard case: _snapshot's finally must
    # restore the handle so later appends still work.
    import os as _os
    calls = {"n": 0}
    orig_replace = _os.replace

    def failing_replace(src, dst):
        if dst.endswith("f") and "snapshotting" in src:
            calls["n"] += 1
            raise OSError("disk full (simulated)")
        return orig_replace(src, dst)

    rows = np.zeros(50, np.uint64)
    cols = np.arange(50, dtype=np.uint64)
    _os.replace = failing_replace
    try:
        f.bulk_import(rows, cols)
    except OSError:
        pass
    finally:
        _os.replace = orig_replace
    assert calls["n"] == 1  # the fold fired and failed
    f.close()
    f2 = Fragment(p, "i", "f", "standard", 0)
    f2.open()
    assert f2.row_count(0) == 50  # batch survived via its own op record
    f2.close()
