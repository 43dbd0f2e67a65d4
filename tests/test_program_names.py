"""Stable program names: every jitted closure the executor and
ops/megakernel build lowers to an XLA module named after what it is
(`jit_topn_sweep`, `jit_tree_count`, ...), never `jit_run` or
`jit__lambda_`, so a profiler trace's `XLA Modules` line and the idle
gaps named after it say which program ran."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import named
from pilosa_tpu.ops.bitset import SHARD_WIDTH


def _module(fn, *args) -> str:
    text = fn.lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def test_named_sets_module_name_and_scope():
    fn = jax.jit(named(lambda w: w + 1, "popcount_row"))
    x = jnp.zeros((4,), jnp.uint32)
    assert _module(fn, x) == "jit_popcount_row"
    assert "popcount_row" in fn.lower(x).as_text(debug_info=True)
    assert named(lambda: 0, "x").__name__ == "x"


@pytest.fixture(scope="module")
def ex(tmp_path_factory):
    holder = Holder(str(tmp_path_factory.mktemp("names")))
    holder.open()
    idx = holder.create_index("i")
    rng = np.random.default_rng(3)
    cols = rng.integers(0, 2 * SHARD_WIDTH, 4000).astype(np.uint64)
    for name in ("f", "g"):
        idx.create_field(name).import_bits(
            rng.integers(0, 6, cols.size).astype(np.uint64), cols)
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    v.import_values(cols[:500], rng.integers(0, 1000, 500))
    idx.add_existence(cols)
    ex = Executor(holder)
    yield ex
    holder.close()


def _programs(ex) -> dict:
    """jit-cache key -> the function name JAX will name the module
    after, for everything the executor has built so far."""
    with ex._jit_cache_lock:
        fns = dict(ex._jit_cache)
    return {k: getattr(f, "__name__", "?") for k, f in fns.items()}


QUERIES = [
    ("Count(Row(f=1))", "tree_count"),
    ("Row(f=1)", "tree_row"),
    ("TopN(f, Row(g=2), n=3)", "topn_sweep"),
    # A tanimoto call runs the filtered sweep, and the unfiltered one
    # for the bank's popcounts: no program of its own.
    ("TopN(f, Row(g=2), n=3, tanimotoThreshold=10)", "topn_sweep"),
    ("TopN(f, n=3)", "topn_sweep_unfiltered"),
    ("Sum(Row(f=1), field=v)", "bsi_sum"),
    ("Min(field=v)", "bsi_min"),
    ("GroupBy(Rows(f), Rows(g))", "groupby_cntN"),
]


@pytest.mark.parametrize("pql,program", QUERIES)
def test_every_built_program_has_a_stable_name(ex, pql, program):
    from pilosa_tpu.core.cache import RANK_CACHE
    was = RANK_CACHE.enabled
    RANK_CACHE.enabled = False       # the sweep, not the rank cache
    try:
        ex.execute("i", pql)
    finally:
        RANK_CACHE.enabled = was
    names = _programs(ex)
    assert program in names.values(), names
    for key, name in names.items():
        assert name not in ("run", "<lambda>", "kernel", "patch", "topk",
                            "?"), (key, name)
        assert re.fullmatch(r"[a-z][a-zA-Z0-9_]*", name), (key, name)


def test_lowered_module_names(ex):
    """What XLA will call them: lower the cached programs' twins."""
    ex.execute("i", "Count(Row(f=1))")
    ex.execute("i", "TopN(f, Row(g=2), n=3)")
    bank = jnp.zeros((8, 2, 64), jnp.uint32)
    filt = jnp.zeros((2, 64), jnp.uint32)
    sweep = ex._counts_fn(True, bank.shape)
    assert _module(sweep, bank, filt) == "jit_topn_sweep"
    ex.execute("i", "TopN(f, Row(g=2), n=3, tanimotoThreshold=10)")
    assert not any("tanimoto" in name for name in _programs(ex).values())
    assert _module(ex._counts_fn(False, bank.shape), bank, None) == \
        "jit_topn_sweep_unfiltered"
    modules = [_module(sweep, bank, filt)]
    for key, fn in list(ex._jit_cache.items()):
        if key.startswith("count|"):
            staged_name = fn.__name__
            assert staged_name == "tree_count"
            modules.append("jit_" + staged_name)
    assert "jit_tree_count" in modules
    assert not any(m in ("jit_run", "jit__lambda_") for m in modules)


def test_fused_and_mega_programs_are_named(ex):
    from pilosa_tpu.ops import megakernel as mk
    qs = [("i", f"Count(Row(f={i}))", None) for i in range(4)]
    ex.execute_batch_shaped(qs)
    names = set(_programs(ex).values())
    assert "fused_tree_count" in names, names
    prog = mk.build_program(2, 64, 8)
    assert prog.__name__ == "mega_plan"
    mixed = qs[:2] + [("i", "Count(Intersect(Row(f=1), Row(g=2)))", None),
                      ("i", "Count(Union(Row(f=1), Row(g=3)))", None)]
    from pilosa_tpu.executor import megakernel as megamod
    was = megamod.MEGAKERNEL_ENABLED
    megamod.MEGAKERNEL_ENABLED = True
    try:
        ex.execute_batch_shaped(mixed)
    finally:
        megamod.MEGAKERNEL_ENABLED = was
    assert "mega_plan" in set(_programs(ex).values())
