"""A time-range Row is staged by the tree program (executor.py:
_plan_row_leaf): up to MAX_STATIC_RANGE_VIEWS views as an OR-fold of
slot leaves padded to a power of two, past it as grouped jitted folds
chained through an accumulator — never by eager indexing. Parity
against a numpy union on the plain executor, under a 4-device mesh and
with a sparse-layout view; no eager launch and no compile past one
query per bucket; the signature's six values; cache exactness; the
`executor.range_leaves{path:}` / `executor.range_views` counters."""

import contextlib
from datetime import datetime, timedelta

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as exmod
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.utils.jaxenv import COMPILES
from pilosa_tpu.utils.stats import MemStatsClient

DAY0 = datetime(2019, 1, 1)
N_DAYS = 44
SPANS = (1, 2, 3, 5, 8, 9, 15, 16, 17, 28, 33, 40)
G_ROWS = 6


def _ts(day: int) -> str:
    return f"{DAY0 + timedelta(days=day):%Y-%m-%dT%H:%M}"


def _row(span: int, d0: int = 0, row: int = 1) -> str:
    return f"Row(t={row}, from='{_ts(d0)}', to='{_ts(d0 + span)}')"


def _build(tmp: str):
    """Field "t" (time, quantum D: one view a day, N_DAYS of them, two
    shards, columns narrow enough for the sparse layout) with rows 1
    and 2, and a set field "g" over the same columns. Returns the
    holder and day -> columns of row 1."""
    h = Holder(tmp)
    h.open()
    idx = h.create_index("i")
    t = idx.create_field("t", FieldOptions(type="time", time_quantum="D"))
    rng = np.random.default_rng(11)
    days = {}
    for d in range(N_DAYS):
        cols = rng.choice(4096, 24, replace=False).astype(np.uint64)
        cols[12:] += SHARD_WIDTH
        days[d] = np.sort(cols)
        stamps = [DAY0 + timedelta(days=d)] * len(cols)
        t.import_bits(np.ones(len(cols), np.uint64), cols, stamps)
        t.import_bits(np.full(3, 2, np.uint64), cols[:3], stamps[:3])
    pool = np.unique(np.concatenate(list(days.values())))
    idx.create_field("g").import_bits(pool % G_ROWS, pool)
    idx.add_existence(pool)
    return h, days


def _union(days, span: int, d0: int = 0) -> np.ndarray:
    return np.unique(np.concatenate([days[d] for d in range(d0, d0 + span)]))


def _topn(cols: np.ndarray) -> list:
    counts = np.bincount((cols % G_ROWS).astype(np.intp), minlength=G_ROWS)
    return sorted(((int(r), int(c)) for r, c in enumerate(counts) if c),
                  key=lambda p: (-p[1], p[0]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """variant -> (executor, days): the plain executor, one over a
    4-device mesh, one whose day-3 view serves from its SparseBank."""
    import jax

    from pilosa_tpu.parallel.mesh import MeshContext
    out, holders = {}, []
    for variant in ("plain", "mesh4", "sparse"):
        h, days = _build(str(tmp_path_factory.mktemp(variant)))
        holders.append(h)
        mesh = MeshContext(jax.devices()[:4]) if variant == "mesh4" else None
        ex = Executor(h, mesh=mesh)
        ex.result_cache.enabled = False
        if variant == "sparse":
            view = h.index("i").field("t").view(
                f"standard_{DAY0 + timedelta(days=3):%Y%m%d}")
            assert view.set_layout("sparse")
        out[variant] = (ex, days)
    yield out
    for h in holders:
        h.close()


@pytest.mark.parametrize("variant", ["plain", "mesh4", "sparse"])
@pytest.mark.parametrize("span", SPANS)
def test_range_equals_numpy_union(served, variant, span):
    ex, days = served[variant]
    for d0 in (0, N_DAYS - span):
        want = _union(days, span, d0)
        row, count, topn = ex.execute("i", f"""
            {_row(span, d0)}
            Count({_row(span, d0)})
            TopN(g, {_row(span, d0)})""")
        np.testing.assert_array_equal(row.columns(), want)
        assert count == len(want)
        assert [(p[0], p[1]) for p in topn.pairs] == _topn(want)
    # ... inside a wider tree, beside a plain leaf of another field.
    (both,) = ex.execute(
        "i", f"Count(Intersect({_row(span)}, Row(g=1)))")
    assert both == int((_union(days, span) % G_ROWS == 1).sum())


@pytest.fixture
def ex(tmp_path):
    h, days = _build(str(tmp_path))
    executor = Executor(h)
    executor.stats = MemStatsClient()
    yield executor, days
    h.close()


class _Staging:
    """Whether Executor._stage_tree is on this thread's stack, and the
    XLA modules that compiled while it was (COMPILES' names)."""

    def __init__(self):
        self.depth = 0
        self.compiled = set()

    @staticmethod
    def _compiles():
        return {r["name"]: r["compiles"]
                for r in COMPILES.snapshot()["byName"]}

    @contextlib.contextmanager
    def __call__(self):
        before = self._compiles()
        self.depth += 1
        try:
            yield
        finally:
            self.depth -= 1
            self.compiled |= {n for n, c in self._compiles().items()
                              if c > before.get(n, 0)}


@contextlib.contextmanager
def _no_eager_ops_while_staging(monkeypatch):
    """Inside Executor._stage_tree, jnp.stack and indexing a device
    array raise, and every XLA module that compiles there is noted
    (an eager helper compiles at its first call). A jitted program's
    call (the grouped fold) and an operand upload pass. Yields the
    context that marks a block as staging."""
    import jax.numpy as jnp
    from jax._src import array as jarray

    staging = _Staging()

    def guarded(orig, what):
        def call(*a, **kw):
            if staging.depth:
                raise AssertionError(f"eager {what} inside plan.stage")
            return orig(*a, **kw)
        return call

    orig_stage = Executor._stage_tree

    def stage(self, *a, **kw):
        with staging():
            return orig_stage(self, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(jnp, "stack", guarded(jnp.stack, "jnp.stack"))
        m.setattr(jarray.ArrayImpl, "__getitem__",
                  guarded(jarray.ArrayImpl.__getitem__, "__getitem__"))
        m.setattr(Executor, "_stage_tree", stage)
        yield staging


def _count_dispatches(monkeypatch):
    calls = []
    orig = Executor._call_program

    def stub(self, fn, *args):
        calls.append(fn)
        return orig(self, fn, *args)

    monkeypatch.setattr(Executor, "_call_program", stub)
    return calls


def test_the_guard_catches_what_the_literal_branch_did(ex, monkeypatch):
    import jax.numpy as jnp
    e, _ = ex
    COMPILES.install(MemStatsClient())
    bank = e._get_bank_for(e.holder.index("i").field("g"), "standard",
                           [0, 1])
    with _no_eager_ops_while_staging(monkeypatch) as staging:
        rows = [bank.array[0], bank.array[1]]     # outside: they pass
        jnp.stack(rows)
        with staging():
            with pytest.raises(AssertionError, match="__getitem__"):
                bank.array[0]
            with pytest.raises(AssertionError, match="jnp.stack"):
                jnp.stack(rows)
            jnp.pad(rows[0], [(0, 0), (0, 7)])   # no guard of its own:
        assert staging.compiled                   # ... its compile is noted
    COMPILES.stats = None


def test_no_eager_launch_and_no_compile_past_one_query_a_bucket(
        ex, monkeypatch):
    e, days = ex
    e.result_cache.enabled = False
    COMPILES.install(MemStatsClient())
    try:
        with _no_eager_ops_while_staging(monkeypatch) as staging:
            for span in (1, 2, 4, 8, 16, 32):       # one query a bucket
                e.execute("i", f"Count({_row(span)})")
            calls = _count_dispatches(monkeypatch)
            xla0 = COMPILES.snapshot()["compiles"]
            jit0 = e.jit_compiles
            for span in range(1, 33):
                (n,) = e.execute("i", f"Count({_row(span, 44 - 33)})")
                assert n == len(_union(days, span, 44 - 33))
            assert len(calls) == 32, "one program call a range eval"
            assert e.jit_compiles == jit0
            assert COMPILES.snapshot()["compiles"] == xla0
            assert staging.compiled == set()
            # Past the fold: grouped programs, still nothing eager.
            del calls[:]
            for span in (33, 40, 44):
                (n,) = e.execute("i", f"Count({_row(span)})")
                assert n == len(_union(days, span))
            assert len(calls) == 3 * (2 + 1), \
                "two grouped folds and the tree program a query"
            assert staging.compiled == {"jit(range_fold)"}
    finally:
        COMPILES.stats = None


def test_signature_moves_with_the_bucket_only(ex):
    e, _ = ex
    idx = e.holder.index("i")
    from pilosa_tpu.pql import parse_string_cached
    sigs = {}
    for span in range(1, 33):
        call = parse_string_cached(_row(span)).calls[0]
        staged = e._stage_tree(idx, call, [0, 1], "count")
        sigs.setdefault(staged.sig, []).append(span)
        assert len(staged.bank_arrays) == exmod._pow2(span)
        assert staged.lits is None and staged.cacheable
        assert staged.ir is not None
    assert sorted(sigs.values()) == [
        [1], [2], [3, 4], [5, 6, 7, 8], list(range(9, 17)),
        list(range(17, 33))]
    call = parse_string_cached(_row(33)).calls[0]
    staged = e._stage_tree(idx, call, [0, 1], "count")
    assert len(staged.lits) == 1 and not staged.cacheable
    assert staged.bank_arrays == () and staged.ir is None


def test_cached_range_count_is_invalidated_by_its_views_only(ex):
    """A 12-view range is a fold now, so both cache tiers take it: a
    Set into any one of its day views must miss, a Set into a day
    outside it (which also writes the standard view) must not."""
    e, days = ex
    q = f"Count({_row(12, 5)})"
    want = len(_union(days, 12, 5))
    for tier, run in (("request", lambda: e.execute_full("i", q)
                       ["results"][0]),
                      ("eval", lambda: e.execute("i", q)[0])):
        if tier == "eval":
            e.result_cache.clear()
        assert run() == want
        hits = e.result_cache.hits[tier]
        assert run() == want
        assert e.result_cache.hits[tier] == hits + 1
        for day in (0, 4, 17, 30):                 # outside [5, 17)
            e.execute("i", f"Set({3000 + day}, t=1, {_ts(day)})")
            hits = e.result_cache.hits[tier]
            assert run() == want
            assert e.result_cache.hits[tier] == hits + 1, (tier, day)
        for day in range(5, 17):                   # each of the twelve
            col = 2 * SHARD_WIDTH - 100 - day - (50 if tier == "eval"
                                                 else 0)
            e.execute("i", f"Set({col}, t=1, {_ts(day)})")
            hits = e.result_cache.hits[tier]
            want += 1
            assert run() == want, (tier, day)
            assert e.result_cache.hits[tier] == hits, (tier, day)


def test_range_counters(ex):
    e, _ = ex
    e.result_cache.enabled = False

    def counters():
        c = e.stats.snapshot()["counters"]
        return (c.get("executor.range_leaves{path:fold}", 0),
                c.get("executor.range_leaves{path:grouped}", 0),
                c.get("executor.range_views", 0))

    assert counters() == (0, 0, 0)
    e.execute("i", f"Count({_row(9)})")
    assert counters() == (1, 0, 9)
    e.execute("i", f"TopN(g, {_row(32)})")
    assert counters() == (2, 0, 41)
    e.execute("i", f"Count(Union({_row(3)}, {_row(40, 2, row=2)}))")
    assert counters() == (3, 1, 84)
    e.execute("i", "Count(Row(t=1))")        # no range: not a leaf of these
    assert counters() == (3, 1, 84)


def test_counters_are_published_from_the_start(tmp_path):
    from pilosa_tpu.server.api import API
    h = Holder(str(tmp_path))
    h.open()
    try:
        c = API(h, stats=MemStatsClient()).stats.snapshot()["counters"]
        assert c["executor.range_leaves{path:fold}"] == 0
        assert c["executor.range_leaves{path:grouped}"] == 0
        assert c["executor.range_views"] == 0
    finally:
        h.close()
