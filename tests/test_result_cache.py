"""Generation-keyed cross-request result cache + device rank cache
(executor/result_cache.py, core/cache.RANK_CACHE, ROADMAP item 3):
request/eval tier hit semantics, implicit write invalidation through
fragment generations ([read, write, read] incl. fusion and a two-node
cluster), bit-exactness against the cache-off path, the hardened
RankedCache/LRUCache/NopCache units, rank-cache hit/patch/rebuild
legs, and the ledger/metrics/hotspots/health surfaces."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.cache import (
    LRUCache, NopCache, RANK_CACHE, RankedCache,
)
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.result_cache import ResultCache
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text


@pytest.fixture(autouse=True)
def _reset_rank_cache():
    """RANK_CACHE is process-wide (the LEDGER/WORKLOAD convention):
    every test starts empty with defaults and leaves them behind."""
    RANK_CACHE.clear()
    RANK_CACHE.configure(enabled=True, max_entries=64)
    yield
    RANK_CACHE.clear()
    RANK_CACHE.configure(enabled=True, max_entries=64)


def _seed(h):
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f.import_bits(rows, cols)
    g.import_bits(rows[::2], cols[::2])
    idx.create_field("v", FieldOptions(type="int", min=0, max=10000))
    vcols = rng.integers(0, 2 * SHARD_WIDTH, 500).astype(np.uint64)
    idx.field("v").import_values(
        vcols, rng.integers(0, 10000, 500).astype(np.int64))
    idx.add_existence(cols)
    return idx


@pytest.fixture
def ex(tmp_path):
    h = Holder(str(tmp_path / "d"))
    h.open()
    _seed(h)
    executor = Executor(h)
    yield executor
    h.close()


def count_dispatches(monkeypatch):
    """Stub Executor._call_program — the single funnel every compiled
    program invocation passes through (the test_fusion idiom)."""
    calls = []
    orig = Executor._call_program

    def stub(self, fn, *args):
        calls.append(fn)
        return orig(self, fn, *args)

    monkeypatch.setattr(Executor, "_call_program", stub)
    return calls


# ------------------------------------------------- core/cache.py units


def test_ranked_cache_add_top_and_zero_removal():
    c = RankedCache(size=4)
    for r, n in [(1, 10), (2, 20), (3, 5)]:
        c.add(r, n)
    assert c.top() == [(2, 20), (1, 10), (3, 5)]
    c.add(3, 0)  # zero count removes
    assert c.top() == [(2, 20), (1, 10)]
    assert len(c) == 2


def test_ranked_cache_recalculate_prunes_to_size_and_saturates():
    c = RankedCache(size=4)  # threshold factor 1.1 -> prune above 4
    for r in range(10):
        c.add(r, r + 1)
    # The 5th add crossed the bound: _recalculate keeps exactly the
    # top-`size` by (count desc, row asc) and latches `saturated`, so
    # rows 5..9 (added after) were refused.
    assert c.top() == [(4, 5), (3, 4), (2, 3), (1, 2)]
    assert c.saturated
    c.add(50, 100)
    assert 50 not in c.counts, "saturated latch refuses further adds"
    # invalidate() resets the latch.
    c.invalidate()
    assert len(c) == 0 and not c.saturated
    c.add(50, 1)
    assert c.counts[50] == 1


def test_ranked_cache_invalidate_rebinds_not_clears():
    """invalidate() must REBIND counts (O(1)) — a lock-free reader
    holding the old dict keeps a consistent snapshot."""
    c = RankedCache(size=8)
    c.add(1, 5)
    before = c.counts
    c.invalidate()
    assert before == {1: 5}, "reader snapshot must survive invalidate"
    assert c.counts == {} and c.counts is not before


def test_ranked_cache_concurrent_adds_and_invalidates():
    c = RankedCache(size=64)
    errs = []

    def worker(base):
        try:
            for i in range(500):
                c.add(base + (i % 80), i + 1)
                if i % 97 == 0:
                    c.invalidate()
                c.top()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(b,))
          for b in (0, 100, 200, 300)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs


def test_lru_cache_recency_and_eviction():
    c = LRUCache(size=3)
    for r in (1, 2, 3):
        c.add(r, r * 10)
    assert c.get(1) == 10  # touch 1 -> 2 is now oldest
    c.add(4, 40)
    assert c.get(2) == 0, "least-recently-used entry evicted"
    assert sorted(c.ids()) == [1, 3, 4]
    assert c.top() == [(4, 40), (3, 30), (1, 10)]
    c.invalidate()
    assert len(c) == 0


def test_nop_cache_stores_nothing():
    c = NopCache()
    c.add(1, 10)
    assert c.top() == [] and len(c) == 0


# ------------------------------------------- ResultCache (store) units


def test_result_cache_hit_miss_and_generation_drop():
    rc = ResultCache(max_bytes=1 << 20)
    rc.fill("k", gen=(1,), value="v", nbytes=100)
    assert rc.lookup("k", (1,)) == "v"
    assert rc.hits["eval"] == 1
    # Stale generation: dropped immediately, not just missed.
    assert rc.lookup("k", (2,)) is None
    assert rc.invalidations == 1 and len(rc) == 0
    assert rc.lookup("k", (2,)) is None
    assert rc.misses["eval"] == 2


def test_result_cache_lru_byte_budget_and_oversized_refusal():
    rc = ResultCache(max_bytes=250)
    for i in range(3):
        rc.fill(i, (0,), i, nbytes=100)
    assert len(rc) == 2 and rc.bytes == 200, "byte budget evicts LRU"
    assert rc.evictions == 1
    assert rc.lookup(0, (0,)) is None  # 0 was the LRU victim
    # One oversized value must not flush the whole cache.
    rc.fill("big", (0,), "x", nbytes=10_000)
    assert len(rc) == 2 and rc.lookup("big", (0,)) is None
    rc.clear()
    assert rc.bytes == 0 and len(rc) == 0


def test_result_cache_configure_shrink_updates_ledger():
    from pilosa_tpu.utils.memledger import LEDGER
    c = ResultCache(max_bytes=100)
    try:
        c.fill("a", 1, "va", 40)
        c.fill("b", 1, "vb", 40)
        assert c.bytes == 80
        c.configure(max_bytes=50)
        assert c.bytes == 40 and c.evictions == 1
        ent = [e for e in LEDGER.entries("result_cache")
               if e.get("entries") is not None and e["bytes"] == c.bytes]
        assert ent, "ledger must reflect the post-shrink bytes"
    finally:
        c.clear()


def test_result_cache_request_tier_validator():
    rc = ResultCache(max_bytes=1 << 20)
    rc.fill("rk", gen={"dep": 1}, value={"results": [1]}, nbytes=50,
            tier="request")
    assert rc.lookup_request("rk", lambda d: d["dep"] == 1) \
        == {"results": [1]}
    assert rc.hits["request"] == 1
    # Failed revalidation drops the entry.
    assert rc.lookup_request("rk", lambda d: False) is None
    assert rc.invalidations == 1 and len(rc) == 0


def test_result_cache_env_kill_switch(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_RESULT_CACHE", "0")
    rc = ResultCache()
    assert not rc.enabled
    rc.configure(enabled=True)  # config can never re-enable past env
    assert not rc.enabled
    rc.fill("k", (1,), "v", 10)
    assert rc.lookup("k", (1,)) is None


def test_rank_cache_env_kill_switch(monkeypatch):
    from pilosa_tpu.core.cache import RankCacheStore
    monkeypatch.setenv("PILOSA_TPU_RANK_CACHE", "0")
    store = RankCacheStore()
    assert not store.enabled
    store.configure(enabled=True)
    assert not store.enabled


# --------------------------------------------------- eval-tier caching


def test_eval_tier_repeat_serves_without_dispatch(ex, monkeypatch):
    direct = [ex.execute("i", f"Count(Row(f={r}))")[0] for r in range(4)]
    calls = count_dispatches(monkeypatch)
    again = [ex.execute("i", f"Count(Row(f={r}))")[0] for r in range(4)]
    assert again == direct, "cached counts must be bit-identical"
    assert calls == [], "warm repeats must not dispatch anything"
    assert ex.result_cache.hits["eval"] == 4


def test_eval_tier_row_results_bit_identical(ex, monkeypatch):
    direct = ex.execute("i", "Row(f=3)")[0].columns().tolist()
    calls = count_dispatches(monkeypatch)
    cached = ex.execute("i", "Row(f=3)")[0]
    assert cached.columns().tolist() == direct
    assert cached.count() == len(direct)
    assert calls == []


def test_eval_tier_whitespace_variant_hits(ex):
    ex.execute("i", "Count(Row(f=1))")
    h0 = ex.result_cache.hits["eval"]
    # Different request text, same staged fingerprint: the eval tier
    # keys on the semantic (sig, rows, params) identity, not the PQL
    # spelling.
    ex.execute("i", "Count( Row( f = 1 ) )")
    assert ex.result_cache.hits["eval"] == h0 + 1


def test_read_write_read_generation_invalidation(ex, tmp_path):
    """The satellite invalidation contract: [read, write, read] — the
    second read must MISS (generation bump) and match the uncached
    result bit-exactly."""
    h2 = Holder(str(tmp_path / "ref"))
    h2.open()
    _seed(h2)
    ref = Executor(h2)
    ref.result_cache.enabled = False
    try:
        (c0,) = ex.execute("i", "Count(Row(f=5))")
        assert c0 == ref.execute("i", "Count(Row(f=5))")[0]
        free_col = 2 * SHARD_WIDTH - 7
        m0 = ex.result_cache.misses["eval"]
        ex.execute("i", f"Set({free_col}, f=5)")
        ref.execute("i", f"Set({free_col}, f=5)")
        (c1,) = ex.execute("i", "Count(Row(f=5))")
        assert ex.result_cache.misses["eval"] == m0 + 1, \
            "post-write read must miss, not serve the stale entry"
        assert ex.result_cache.invalidations >= 1
        assert c1 == c0 + 1 == ref.execute("i", "Count(Row(f=5))")[0]
    finally:
        h2.close()


def test_read_write_read_through_fusion_under_lock_check(
        tmp_path, monkeypatch):
    """The same contract through the FUSION path (execute_batch) with
    the lock-order checker live: the head read may serve from cache,
    the tail read must observe the write."""
    monkeypatch.setenv("PILOSA_TPU_LOCK_CHECK", "1")
    from pilosa_tpu.utils.locks import (
        lock_order_violations, reset_lock_order,
    )
    reset_lock_order()
    h = Holder(str(tmp_path / "lc"))
    h.open()
    _seed(h)
    e = Executor(h)
    try:
        (c0,) = e.execute("i", "Count(Row(f=5))")
        assert e.result_cache.hits["eval"] == 0
        free_col = 2 * SHARD_WIDTH - 11
        out = e.execute_batch([
            ("i", "Count(Row(f=5))", None),       # warm: cache hit
            ("i", f"Set({free_col}, f=5)", None),
            ("i", "Count(Row(f=5))", None),       # must miss + re-eval
        ])
        assert out[0][0][0] == c0
        assert e.result_cache.hits["eval"] == 1
        assert out[2][0][0] == c0 + 1, "tail read must observe the write"
        # And the refreshed fill is immediately servable.
        assert e.execute("i", "Count(Row(f=5))")[0] == c0 + 1
        assert e.result_cache.hits["eval"] == 2
        assert lock_order_violations() == []
    finally:
        h.close()
        reset_lock_order()


def test_fully_hitting_group_never_launches(ex, monkeypatch):
    """A fused group whose members ALL hit the eval tier never forms,
    let alone launches — zero dispatches, zero fused groups."""
    queries = [f"Count(Row(f={r}))" for r in range(6)]
    direct = [ex.execute("i", q)[0] for q in queries]  # warm the tier
    calls = count_dispatches(monkeypatch)
    fd0 = ex.fused_dispatches
    out = ex.execute_batch([("i", q, None) for q in queries])
    assert [r[0][0] for r in out] == direct
    assert calls == []
    assert ex.fused_dispatches == fd0
    assert ex.result_cache.hits["eval"] >= len(queries)


def test_eval_tier_same_named_fields_across_indexes_coexist(ex):
    """Two indexes with same-named fields and matching bank shapes
    must hold SEPARATE eval-tier entries: without the index name in
    the key they'd collide and evict each other on every lookup
    (generations always differ via process-unique fragment epochs), so
    alternating traffic would run at a 0% hit ratio."""
    h = ex.holder
    idx2 = h.create_index("j")
    f2 = idx2.create_field("f")
    rng = np.random.default_rng(7)  # the _seed layout, shifted rows
    rows = rng.integers(0, 8, 4000).astype(np.uint64)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    f2.import_bits(rows, cols)
    idx2.add_existence(cols)
    a0 = ex.execute("i", "Count(Row(f=1))")[0]
    b0 = ex.execute("j", "Count(Row(f=1))")[0]
    inv0 = ex.result_cache.invalidations
    h0 = ex.result_cache.hits["eval"]
    for _ in range(2):
        assert ex.execute("i", "Count(Row(f=1))")[0] == a0
        assert ex.execute("j", "Count(Row(f=1))")[0] == b0
    assert ex.result_cache.hits["eval"] == h0 + 4
    assert ex.result_cache.invalidations == inv0, \
        "alternating indexes must not evict each other's entries"


def test_eval_tier_shard_restriction_is_part_of_the_key(ex):
    full = ex.execute("i", "Count(Row(f=2))")[0]
    only0 = ex.execute("i", "Count(Row(f=2))", shards=[0])[0]
    assert only0 != full, "seed data spans two shards"
    # Repeat each: both must hit their OWN entry, not each other's.
    assert ex.execute("i", "Count(Row(f=2))")[0] == full
    assert ex.execute("i", "Count(Row(f=2))", shards=[0])[0] == only0


# ------------------------------------------------ request-tier caching


def test_request_tier_execute_full_hits_and_write_invalidates(
        ex, monkeypatch):
    r0 = ex.execute_full("i", "Count(Row(f=1))")
    calls = count_dispatches(monkeypatch)
    assert ex.execute_full("i", "Count(Row(f=1))") == r0
    assert ex.result_cache.hits["request"] == 1
    assert calls == []
    free_col = 2 * SHARD_WIDTH - 13
    ex.execute("i", f"Set({free_col}, f=1)")
    r1 = ex.execute_full("i", "Count(Row(f=1))")
    assert r1["results"][0] == r0["results"][0] + 1
    assert ex.result_cache.hits["request"] == 1, \
        "post-write repeat must revalidate and miss"


def test_request_tier_row_attr_mutation_invalidates(ex):
    """Row-attr writes do NOT bump fragment generations — the request
    tier must still invalidate through the attr store's own stamp."""
    ex.execute("i", 'SetRowAttrs(f, 1, cat="x")')
    r0 = ex.execute_full("i", "Row(f=1)")
    assert r0["results"][0]["attrs"] == {"cat": "x"}
    assert ex.execute_full("i", "Row(f=1)") == r0  # hit
    h0 = ex.result_cache.hits["request"]
    ex.execute("i", 'SetRowAttrs(f, 1, cat="y")')
    r1 = ex.execute_full("i", "Row(f=1)")
    assert r1["results"][0]["attrs"] == {"cat": "y"}
    assert ex.result_cache.hits["request"] == h0, \
        "attr-stale entry must not serve"


def test_request_tier_excludes_non_staged_calls(ex):
    for q in ("TopN(f, n=2)", 'Min(field="v")', 'Sum(field="v")'):
        r0 = ex.execute_full("i", q)
        assert ex.execute_full("i", q) == r0
    assert ex.result_cache.hits["request"] == 0, \
        "only the Count/bitmap family rides the request tier"


def test_forced_profile_bypasses_lookup_but_still_fills(ex):
    from pilosa_tpu.utils.profile import QueryProfile
    ex.execute_full("i", "Count(Row(f=4))")  # warm both tiers
    prof = QueryProfile("i", "Count(Row(f=4))")
    prof.forced = True
    r = ex.execute_full("i", "Count(Row(f=4))", profile=prof)
    assert ex.result_cache.hits["request"] == 0
    # The forced profile's tree must describe a REAL execution.
    evals = [n for op in prof.ops for n in op.children
             if n.name.startswith("eval:")]
    assert evals and "cacheHit" not in evals[0].attrs
    assert r["results"][0] == ex.execute("i", "Count(Row(f=4))")[0]


def test_sampled_profile_hit_gets_cache_attribution(ex):
    from pilosa_tpu.utils.profile import QueryProfile
    ex.execute_full("i", "Count(Row(f=6))")
    prof = QueryProfile("i", "Count(Row(f=6))")  # forced=False default
    ex.execute_full("i", "Count(Row(f=6))", profile=prof)
    assert ex.result_cache.hits["request"] == 1
    ops = [op for op in prof.ops if op.name == "cache"]
    assert ops and ops[0].attrs["cacheHit"] is True


# ------------------------------------------------- device rank cache


@pytest.fixture
def topn_ex(tmp_path, monkeypatch):
    """Executor over a field with known TopN standings, with the host
    fragment-cache warm path disabled so filterless TopN deterministically
    reaches the device rank cache."""
    h = Holder(str(tmp_path / "t"))
    h.open()
    idx = h.create_index("t")
    f = idx.create_field("tf")
    rows, cols = [], []
    # row r gets (20 - 2r) columns, spread over two shards.
    for r in range(8):
        for c in range(20 - 2 * r):
            rows.append(r)
            cols.append(c * 3 + (SHARD_WIDTH if c % 2 else 0))
    f.import_bits(np.asarray(rows, np.uint64),
                  np.asarray(cols, np.uint64))
    idx.add_existence(np.asarray(cols, np.uint64))
    monkeypatch.setattr(Executor, "_topn_cached_counts",
                        lambda self, view, shards: None)
    e = Executor(h)
    yield e
    h.close()


def test_rank_cache_rebuild_then_hit_bit_identical(topn_ex):
    e = topn_ex
    RANK_CACHE.configure(enabled=False)
    baseline = e.execute("t", "TopN(tf, n=3)")[0].pairs
    baseline_all = e.execute("t", "TopN(tf)")[0].pairs
    RANK_CACHE.configure(enabled=True)
    assert e.execute("t", "TopN(tf, n=3)")[0].pairs == baseline
    assert e.rank_cache_rebuilds == 1
    # Warm: the unrestricted top-k leg and the fetch leg both hit.
    assert e.execute("t", "TopN(tf, n=3)")[0].pairs == baseline
    assert e.execute("t", "TopN(tf)")[0].pairs == baseline_all
    assert e.rank_cache_hits == 2
    assert len(RANK_CACHE) == 1


def test_rank_cache_patch_after_small_write(topn_ex):
    e = topn_ex
    assert e.execute("t", "TopN(tf, n=3)")[0].pairs  # build the vector
    assert e.rank_cache_rebuilds == 1
    # One written row: versions move, rows_changed_since names it ->
    # the incremental gather+scatter patch, not a rebuild.
    e.execute("t", "Set(299, tf=7)")
    RANK_CACHE.configure(enabled=False)
    expect = e.execute("t", "TopN(tf, n=8)")[0].pairs
    RANK_CACHE.configure(enabled=True)
    got = e.execute("t", "TopN(tf, n=8)")[0].pairs
    assert got == expect
    assert e.rank_cache_patches == 1
    assert e.rank_cache_rebuilds == 1, "small churn must not rebuild"
    assert (7, 7) in got  # row 7 had 6 columns, now 7


def test_rank_cache_threshold_and_filter_paths(topn_ex):
    e = topn_ex
    RANK_CACHE.configure(enabled=False)
    thr = e.execute("t", "TopN(tf, n=8, threshold=15)")[0].pairs
    filt = e.execute("t", "TopN(tf, Row(tf=0), n=2)")[0].pairs
    RANK_CACHE.configure(enabled=True)
    assert e.execute("t", "TopN(tf, n=8, threshold=15)")[0].pairs == thr
    assert all(c >= 15 for _, c in thr) and thr
    # Filtered TopN needs real bitmaps: it must BYPASS the rank cache.
    consults0 = (e.rank_cache_hits + e.rank_cache_rebuilds
                 + e.rank_cache_patches)
    assert e.execute("t", "TopN(tf, Row(tf=0), n=2)")[0].pairs == filt
    assert (e.rank_cache_hits + e.rank_cache_rebuilds
            + e.rank_cache_patches) == consults0, \
        "filtered call must not consult the rank cache"


def test_rank_cache_lru_eviction_and_ledger_accounting(topn_ex):
    from pilosa_tpu.utils.memledger import LEDGER
    e = topn_ex
    e.execute("t", "TopN(tf, n=3)")
    ents = LEDGER.entries("rank_cache")
    assert len(ents) == 1 and ents[0]["bytes"] > 0
    assert LEDGER.snapshot()["categories"]["rank_cache"]["bytes"] \
        == ents[0]["bytes"]
    # Entry-count LRU: shrink the bound, insert another key.
    RANK_CACHE.configure(max_entries=1)
    e.execute("t", "TopN(tf, n=3)", shards=[0])
    assert len(RANK_CACHE) == 1 and RANK_CACHE.evictions == 1
    assert len(LEDGER.entries("rank_cache")) == 1, \
        "evicted vector must leave the ledger"
    # View close drops the remaining entries + ledger rows.
    e.holder.index("t").field("tf").view("standard").close()
    assert len(RANK_CACHE) == 0
    assert LEDGER.entries("rank_cache") == []


def test_rank_cache_append_grown_bank_stays_exact(tmp_path, monkeypatch):
    """An append-grown bank (_patch_bank places a NEW mid-range row at
    the END) breaks the slots-ascend-with-row-id layout: the device
    top-k leg must refuse it (its index tie-break would misattribute
    counts to sorted-position rows) and the rank entry built for the
    old layout must read as misaligned — rebuild, never a wrong-slot
    patch."""
    h = Holder(str(tmp_path / "ag"))
    h.open()
    idx = h.create_index("ag")
    f = idx.create_field("af")
    rows, cols = [], []
    for r, n_cols in ((0, 5), (5, 4), (10, 3)):
        for c in range(n_cols):
            rows.append(r)
            cols.append(c * 2)
    f.import_bits(np.asarray(rows, np.uint64),
                  np.asarray(cols, np.uint64))
    idx.add_existence(np.asarray(cols, np.uint64))
    monkeypatch.setattr(Executor, "_topn_cached_counts",
                        lambda self, view, shards: None)
    e = Executor(h)
    try:
        assert e.execute("ag", "TopN(af)")[0].pairs == \
            [(0, 5), (5, 4), (10, 3)]
        assert e.rank_cache_rebuilds == 1
        # New row 7 sorts BETWEEN cached rows but appends at the bank's
        # end: slot order is now (0, 5, 10, 7).
        e.execute("ag", "Set(100, af=7)")
        RANK_CACHE.configure(enabled=False)
        expect = e.execute("ag", "TopN(af, n=4)")[0].pairs
        RANK_CACHE.configure(enabled=True)
        assert expect == [(0, 5), (5, 4), (10, 3), (7, 1)]
        got = e.execute("ag", "TopN(af, n=4)")[0].pairs
        assert got == expect, \
            "append-grown layout must not swap rows 7 and 10"
        assert e.rank_cache_patches == 0, \
            "old-layout entry must not be patched with new-layout slots"
        assert e.rank_cache_rebuilds == 2
        # Warm repeats on the grown layout stay exact (host-merge leg).
        assert e.execute("ag", "TopN(af, n=4)")[0].pairs == expect
        assert e.rank_cache_hits == 1
    finally:
        h.close()


def test_rank_cache_fragment_recreation_forces_rebuild(topn_ex):
    """A fragment recreated in-process (pop + reload across a resize)
    starts a fresh version epoch with empty _row_versions, so
    rows_changed_since() cannot name writes made in the OLD
    incarnation. Both the rank-cache patch leg and the bank patch must
    detect the epoch change and rebuild — an attribution-based patch
    would silently keep pre-recreation counts."""
    from pilosa_tpu.core.fragment import Fragment
    e = topn_ex
    assert e.execute("t", "TopN(tf, n=8)")[0].pairs  # build the vector
    # A write the old incarnation attributes...
    e.execute("t", "Set(299, tf=7)")
    view = e.holder.index("t").field("tf").view("standard")
    for frag in view.fragments.values():
        # ...then simulate recreation: fresh epoch, attribution gone.
        frag._row_versions.clear()
        frag.version = next(Fragment._VERSION_EPOCH) << 48
    # And one post-recreation write providing a non-empty (but
    # incomplete) changed-rows set for the old-epoch entry.
    e.execute("t", "Set(301, tf=0)")
    RANK_CACHE.configure(enabled=False)
    expect = e.execute("t", "TopN(tf, n=8)")[0].pairs
    RANK_CACHE.configure(enabled=True)
    got = e.execute("t", "TopN(tf, n=8)")[0].pairs
    assert got == expect, "epoch change must rebuild, not under-patch"
    assert e.rank_cache_patches == 0
    assert (7, 7) in got and (0, 21) in got


def test_request_fill_racing_write_cannot_validate_stale(ex, monkeypatch):
    """Stamp-then-read: a write landing AFTER the dependency stamps
    are captured but BEFORE the banks are read leaves the stored stamp
    behind the current one, so the pre-write response filled into the
    cache can never validate — the repeat must miss and observe the
    write (with read-then-stamp ordering the stale response would
    validate forever)."""
    h = ex.holder
    orig = Executor._get_bank
    fired = []

    def racing(self, idx, key, shards, rows_needed=None):
        bank = orig(self, idx, key, shards, rows_needed=rows_needed)
        if not fired:
            fired.append(1)
            h.index("i").field("f").import_bits(
                np.asarray([1], np.uint64),
                np.asarray([2 * SHARD_WIDTH - 23], np.uint64))
        return bank

    monkeypatch.setattr(Executor, "_get_bank", racing)
    r0 = ex.execute_full("i", "Count(Row(f=1))")
    monkeypatch.setattr(Executor, "_get_bank", orig)
    r1 = ex.execute_full("i", "Count(Row(f=1))")
    assert ex.result_cache.hits["request"] == 0, \
        "the stale fill must fail validation, not hit"
    assert r1["results"][0] == r0["results"][0] + 1


def test_rank_cache_disabled_sweeps_identically(topn_ex):
    e = topn_ex
    warm = e.execute("t", "TopN(tf, n=4)")[0].pairs
    assert e.rank_cache_rebuilds == 1
    RANK_CACHE.configure(enabled=False)
    assert e.execute("t", "TopN(tf, n=4)")[0].pairs == warm
    assert e.rank_cache_rebuilds + e.rank_cache_hits == 1, \
        "disabled store must not be consulted"


# -------------------------------------------------- two-node cluster


def test_cluster_two_node_read_write_read(tmp_path):
    """Interleaved [read, write, read] across two real nodes: the
    second read must miss (generation bump on the owning node) and
    match the uncached result bit-exactly."""
    from tests.test_cluster import req, run_cluster
    nodes = run_cluster(tmp_path, 2)
    try:
        base = nodes[0].uri
        req(base, "POST", "/index/ci", {"options": {}})
        req(base, "POST", "/index/ci/field/f", {"options": {}})
        for col in range(0, 40, 2):
            req(base, "POST", "/index/ci/query",
                body=f"Set({col}, f=1)".encode())
        r0 = req(base, "POST", "/index/ci/query",
                 body=b"Count(Row(f=1))")
        assert r0["results"][0] == 20
        # Warm repeat: some node's eval tier serves it.
        assert req(base, "POST", "/index/ci/query",
                   body=b"Count(Row(f=1))") == r0
        hits0 = sum(n.api.executor.result_cache.hits["eval"]
                    for n in nodes)
        misses0 = sum(n.api.executor.result_cache.misses["eval"]
                      for n in nodes)
        assert hits0 >= 1
        # Write THROUGH THE OTHER NODE (routed to the shard owner).
        req(nodes[1].uri, "POST", "/index/ci/query",
            body=b"Set(41, f=1)")
        r1 = req(base, "POST", "/index/ci/query",
                 body=b"Count(Row(f=1))")
        assert r1["results"][0] == 21, "second read must see the write"
        assert sum(n.api.executor.result_cache.misses["eval"]
                   for n in nodes) > misses0, \
            "post-write read must miss the eval tier somewhere"
    finally:
        for n in nodes:
            n.stop()


# ------------------------------------------------------ HTTP surfaces


def test_http_surfaces_metrics_hotspots_health_memory(tmp_path):
    from pilosa_tpu.server import API, serve
    h = Holder(str(tmp_path / "s"))
    h.open()
    _seed(h)
    api = API(h, stats=MemStatsClient())
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"

    def get(path):
        return json.loads(urllib.request.urlopen(
            base + path, timeout=30).read())

    try:
        for _ in range(4):
            for r in range(4):
                body = f"Count(Row(f={r}))".encode()
                urllib.request.urlopen(
                    base + "/index/i/query", data=body).read()
        rc = api.executor.result_cache
        assert rc.hits["request"] + rc.hits["eval"] >= 12

        # /metrics: event-time counters + scrape-time gauges.
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "pilosa_result_cache_hits_total" in text
        assert "pilosa_result_cache_misses_total" in text
        assert "pilosa_result_cache_bytes" in text
        assert "pilosa_rank_cache_entries" in text

        # /debug/hotspots: observed hit ratio joined against the
        # estimator's predicted savings — same fingerprints, one doc.
        doc = get("/debug/hotspots")
        assert doc["resultCache"]["hits"] >= 12
        assert doc["resultCache"]["hitRatio"] > 0.5
        obs = doc["opportunity"]["observed"]
        assert obs["hits"] == doc["resultCache"]["hits"]
        assert "predictedTotalEstSavedS" in obs
        assert "rankCache" in doc

        # /internal/health: cache stanzas ride the health document.
        health = get("/internal/health")
        assert health["resultCache"]["enabled"]
        assert health["resultCache"]["hits"] >= 12
        assert {"hits", "patches", "rebuilds"} \
            <= set(health["rankCache"])

        # /debug/memory: cached host bytes are ledgered (category
        # result_cache, HOST side) and the totals stay provable.
        mem = get("/debug/memory")
        assert mem["totalBytes"] == sum(
            c["bytes"] for c in mem["categories"].values())
        # The category totals THIS cache's bytes (plus any other live
        # embedded executor's — each instance is owner-scoped).
        assert rc.bytes > 0
        assert mem["categories"]["result_cache"]["bytes"] >= rc.bytes
    finally:
        srv.shutdown()
        srv.server_close()
        h.close()


def test_prometheus_counter_names(ex):
    stats = MemStatsClient()
    ex.result_cache.stats = stats
    ex.execute("i", "Count(Row(f=1))")
    ex.execute("i", "Count(Row(f=1))")
    ex.result_cache.publish(stats)
    text = prometheus_text(stats)
    assert "pilosa_result_cache_hits_total 1" in text
    assert "pilosa_result_cache_eval_hits_total 1" in text
    assert "pilosa_result_cache_hit_ratio" in text


def test_cache_lookup_span_says_hit(ex):
    from pilosa_tpu.utils.profile import QueryProfile
    from pilosa_tpu.utils.timeline import TIMELINE
    TIMELINE.configure(enabled=True, sample_every=1)
    try:
        ex.execute_full("i", "Count(Row(f=2))")
        tl = TIMELINE.begin(None, "i")
        prof = QueryProfile("i", "Count(Row(f=2))")
        prof.timeline = tl
        with TIMELINE.attached(tl):
            ex.execute_full("i", "Count(Row(f=2))", profile=prof)
        TIMELINE.finish(tl)
        (req,) = TIMELINE.requests(last=1)
        (look,) = req.root.children      # answered from the lookup
        assert look.name == "cache.lookup" and look.attrs == {"hit": True}
        # The profile's cache op is timed by that span.
        (op,) = prof.ops
        assert op.attrs["cacheHit"] and 0 < op.attrs["dispatchS"] \
            <= look.pc_end - look.pc_start
    finally:
        TIMELINE.reset()
