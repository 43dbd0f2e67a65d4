"""A GroupBy's level loop launches its level programs and nothing else
(executor.py: _execute_group_by, _GroupSums): from the filter's words
on, row stacks and surviving prefixes are gathered INSIDE the jitted
`groupby_*` programs, by index vectors into arrays that are already
resident. Held to that here: inside the two bodies a device-array
`__getitem__`, `jnp.stack` and `jnp.concatenate` outside a trace raise,
and every XLA module that compiles is noted — over eight shapes of
query, each against a numpy recomputation, and each with a second
query of the same padded sizes and other row constants that must
compile nothing."""

import contextlib

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.bitset import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.utils.jaxenv import COMPILES
from pilosa_tpu.utils.stats import MemStatsClient

N = 4800
ROW_BYTES = 2 * WORDS_PER_SHARD * 4      # a row over the two shards


@pytest.fixture
def served(tmp_holder):
    """Two families of three set fields with the same row counts
    (a, b, c: 5, 6, 4 rows; x, y, z: the same under other row ids and
    over two thirds of the columns), every combination of rows populated; a
    filter field whose row 1 leaves out a's row 4 and whose row 2 leaves
    out a's row 0 (and x's alike), each over its own half of the
    columns; a signed int field with nulls. Returns the holder and the
    arrays a brute-force answer is read from."""
    rng = np.random.default_rng(33)
    cols = np.sort(rng.choice(2 * SHARD_WIDTH, N, replace=False)) \
        .astype(np.uint64)
    i = np.arange(N)
    keys = {"a": i % 5, "b": (i // 5) % 6, "c": (i // 30) % 4}
    keys.update(x=10 + (keys["a"] + 1) % 5, y=20 + (keys["b"] + 2) % 6,
                z=3 + (keys["c"] + 1) % 4)
    member = {f: np.ones(N, bool) for f in "abc"}
    member.update({f: (i // 120) % 3 != 0 for f in "xyz"})
    half = (i // 120) % 2 == 0
    filt = {1: (keys["a"] != 4) & half, 2: (keys["a"] != 0) & ~half}
    idx = tmp_holder.create_index("g")
    for name, v in keys.items():
        m = member[name]
        idx.create_field(name).import_bits(v[m].astype(np.uint64), cols[m])
    f = idx.create_field("f")
    for row, m in filt.items():
        f.import_bits(np.full(int(m.sum()), row, np.uint64), cols[m])
    vals = rng.integers(-1000, 5000, N)
    has = rng.random(N) < 0.8
    idx.create_field("v", FieldOptions(type="int", min=-1000, max=5000)) \
        .import_values(cols[has], vals[has])
    idx.add_existence(cols)
    return tmp_holder, dict(keys=keys, member=member, filt=filt,
                            vals=vals, has=has)


def _brute(data, names, filt=None, aggregate=False, limit=0,
           previous=None):
    mask = np.ones(N, bool) if filt is None else data["filt"][filt].copy()
    for f in names:
        mask &= data["member"][f]
    out = {}
    for i in np.flatnonzero(mask):
        k = tuple(int(data["keys"][f][i]) for f in names)
        n, s = out.get(k, (0, 0))
        out[k] = (n + 1, s + (int(data["vals"][i]) if data["has"][i]
                              else 0))
    rows = [(k, n, s if aggregate else None)
            for k, (n, s) in sorted(out.items())
            if previous is None or k > tuple(previous)]
    return rows[:limit] if limit else rows


def _pql(names, filt=None, aggregate=False, limit=0, previous=None):
    args = [f"Rows({f})" for f in names]
    if filt is not None:
        args.append(f"filter=Row(f={filt})")
    if aggregate:
        args.append("aggregate=Sum(field=v)")
    if limit:
        args.append(f"limit={limit}")
    if previous is not None:
        args.append(f"previous={list(previous)}")
    return f"GroupBy({', '.join(args)})"


def _groups(e, pql):
    (res,) = e.execute("g", pql)
    return [(tuple(fr.row_id for fr in gc.group), gc.count, gc.sum)
            for gc in res]


class _Bodies:
    """Whether `_execute_group_by` or a `_GroupSums` method is on the
    stack with none of the calls that stage the filter, the rows and
    the banks above it, and the XLA modules that compiled while one
    was (COMPILES' names)."""

    def __init__(self):
        self.depth = 0
        self.compiled = set()

    @staticmethod
    def _compiles():
        return {r["name"]: r["compiles"]
                for r in COMPILES.snapshot()["byName"]}

    def wrap(self, orig, body):
        """`orig` as a guarded body (`body` true: one level deeper) or
        as a staging call made from inside one (the guard is off until
        it returns)."""
        def call(*a, **kw):
            if not body and not self.depth:
                return orig(*a, **kw)
            before, depth = self._compiles(), self.depth
            self.depth = depth + 1 if body else 0
            try:
                return orig(*a, **kw)
            finally:
                self.depth = depth
                self.compiled |= {n for n, c in self._compiles().items()
                                  if c > before.get(n, 0)}
        return call


@contextlib.contextmanager
def _no_eager_ops_in_the_bodies(monkeypatch):
    """Inside Executor._execute_group_by and _GroupSums — but not in
    the calls that stage the filter (`_eval_tree`), list the rows
    (`_execute_rows`) or fetch a bank (`_get_bank_for`) — indexing a
    device array, `jnp.stack` and `jnp.concatenate` over anything but
    tracers raise. A jitted program's call, an operand upload and a
    fetch pass. Yields the record of what compiled meanwhile."""
    import jax
    import jax.numpy as jnp
    from jax._src import array as jarray

    bodies = _Bodies()

    def guarded(orig, what):
        def call(*a, **kw):
            traced = any(isinstance(x, jax.core.Tracer)
                         for x in jax.tree_util.tree_leaves((a, kw)))
            if bodies.depth > 0 and not traced:
                raise AssertionError(f"eager {what} inside a GroupBy body")
            return orig(*a, **kw)
        return call

    sums = Executor._GroupSums
    with monkeypatch.context() as m:
        m.setattr(jnp, "stack", guarded(jnp.stack, "jnp.stack"))
        m.setattr(jnp, "concatenate",
                  guarded(jnp.concatenate, "jnp.concatenate"))
        m.setattr(jarray.ArrayImpl, "__getitem__",
                  guarded(jarray.ArrayImpl.__getitem__, "__getitem__"))
        m.setattr(Executor, "_execute_group_by",
                  bodies.wrap(Executor._execute_group_by, True))
        for name in ("__init__", "launch", "finalize"):
            m.setattr(sums, name, bodies.wrap(getattr(sums, name), True))
        for name in ("_eval_tree", "_execute_rows", "_get_bank_for"):
            m.setattr(Executor, name,
                      bodies.wrap(getattr(Executor, name), False))
        yield bodies


def test_the_guard_catches_what_the_eager_loop_did(served, monkeypatch):
    import jax.numpy as jnp
    holder, _ = served
    e = Executor(holder)
    bank = e._get_bank_for(holder.index("g").field("a"), "standard",
                           [0, 1])
    with _no_eager_ops_in_the_bodies(monkeypatch) as bodies:
        rows = [bank.array[0], bank.array[1]]       # outside: they pass
        jnp.concatenate(rows)
        bodies.depth += 1
        try:
            with pytest.raises(AssertionError, match="__getitem__"):
                bank.array[jnp.asarray(np.arange(2))]
            with pytest.raises(AssertionError, match="jnp.concatenate"):
                jnp.concatenate(rows)
            with pytest.raises(AssertionError, match="jnp.stack"):
                jnp.stack(rows)
        finally:
            bodies.depth -= 1


# case -> (first query, second query of the same padded sizes,
#          GROUPBY_CHUNK_BYTES or None, mesh devices)
CASES = {
    "no_filter_one_child": (
        dict(names="a"), dict(names="x"), None, 0),
    "no_filter_three_children": (
        dict(names="abc"), dict(names="xyz"), None, 0),
    "filter_two_levels": (      # point-serial's groupby_pax_cab
        dict(names="ab", filt=1), dict(names="ab", filt=2), None, 0),
    "child_pruned_by_the_filter": (
        # b's six rows are "large" (pruned, padded to eight with the
        # zero slot); a level chunk is one prefix, so a level hands on
        # several prefix arrays.
        dict(names="abc", filt=1, aggregate=True),
        dict(names="abc", filt=2, aggregate=True),
        int(5.5 * ROW_BYTES), 0),
    "signed_sum_with_nulls": (
        dict(names="ac", filt=1, aggregate=True),
        dict(names="ac", filt=2, aggregate=True), None, 0),
    "limit_and_previous": (
        dict(names="ab", filt=1, aggregate=True, limit=7,
             previous=(1, 3)),
        dict(names="ab", filt=2, aggregate=True, limit=7,
             previous=(2, 2)), None, 0),
    "frontier_spilled": (
        dict(names="abc"), dict(names="xyz"), 1 << 14, 0),
    "four_forced_devices": (
        dict(names="ac", filt=1, aggregate=True),
        dict(names="ac", filt=2, aggregate=True), None, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_level_programs_and_nothing_else(served, monkeypatch, case):
    first, second, chunk_bytes, devices = CASES[case]
    holder, data = served
    mesh = None
    if devices:
        import jax

        from pilosa_tpu.parallel.mesh import MeshContext
        mesh = MeshContext(jax.devices()[:devices])
    e = Executor(holder, mesh=mesh)
    e.result_cache.enabled = False
    e.stats = MemStatsClient()
    if chunk_bytes is not None:
        monkeypatch.setattr(Executor, "GROUPBY_CHUNK_BYTES", chunk_bytes)
    COMPILES.install(MemStatsClient())
    try:
        with _no_eager_ops_in_the_bodies(monkeypatch) as bodies:
            want = _brute(data, **first)
            assert _groups(e, _pql(**first)) == want and want
            assert bodies.compiled, "the first query compiles its programs"
            stray = {n for n in bodies.compiled
                     if not n.startswith("jit(groupby_")
                     and n != "jit(tree_row)"}
            assert stray == set()
            keys0 = set(e._jit_cache)
            xla0, jit0 = COMPILES.snapshot()["compiles"], e.jit_compiles
            bodies.compiled.clear()
            want = _brute(data, **second)
            assert _groups(e, _pql(**second)) == want and want
            assert set(e._jit_cache) == keys0
            assert e.jit_compiles == jit0
            assert COMPILES.snapshot()["compiles"] == xla0
            assert bodies.compiled == set()
    finally:
        COMPILES.stats = None
    counters = e.stats.snapshot()["counters"]
    assert counters["executor.groupby_levels"] >= 2 * len(first["names"])
    if case == "frontier_spilled":
        assert counters["executor.groupby_spills"] >= 2
    if case == "child_pruned_by_the_filter":
        assert any(k.startswith("gb_prune:") for k in e._jit_cache)
        # Several prefix arrays of the level before, read end to end.
        assert any(k.startswith("gb_cntN:") and "+" in k
                   for k in e._jit_cache)
    if first.get("aggregate"):
        assert any(k.startswith("gb_sum:") for k in e._jit_cache)
