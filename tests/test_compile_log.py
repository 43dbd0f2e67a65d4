"""Every XLA compile counted, with its cause (utils/jaxenv.CompileLog):
the `jax.monitoring` listener sees the eager `jnp` helpers that the
executor's own `retraces` counter cannot, names them, tags the request
stage they happened in, and a forced retrace shows its jit key."""

import numpy as np
import pytest

from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.jaxenv import COMPILES, CompileLog
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE


@pytest.fixture
def log():
    """The process-wide log, attached to a fresh stats client."""
    stats = MemStatsClient()
    COMPILES.install(stats)
    COMPILES.reset()
    yield COMPILES, stats
    COMPILES.stats = None
    COMPILES.reset()


def _seed(holder):
    idx = holder.create_index("cl")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)


def test_counters_are_published_from_the_start(log, tmp_holder):
    """A window in which nothing compiled (or nothing was transferred)
    reads 0, not "no such counter": the benchmark's readers tell a
    program without the counter from one where it did not move."""
    _, stats = log
    c = stats.snapshot()["counters"]
    assert {k: c[k] for k in ("xla.compiles", "xla.compile_seconds",
                              "xla.traces", "xla.cache_hits")} == {
        "xla.compiles": 0, "xla.compile_seconds": 0.0,
        "xla.traces": 0, "xla.cache_hits": 0}
    # ... and so are the transfer counters the request records feed.
    api_stats = MemStatsClient()
    API(tmp_holder, stats=api_stats)
    c = api_stats.snapshot()["counters"]
    assert [c[f"executor.{d}_{k}"] for d in ("h2d", "d2h")
            for k in ("bytes", "transfers")] == [0, 0, 0, 0]


def test_eager_helper_compile_is_counted_and_named(log, tmp_holder):
    """An eager jnp op on a shape not seen before compiles a helper
    program. `retraces` (the executor's jit cache) cannot see it; the
    compile log counts it, names it, and says nothing was open."""
    import jax.numpy as jnp

    clog, stats = log
    _seed(tmp_holder)
    api = API(tmp_holder, stats=stats)
    before = api.executor.jit_compiles
    # A shape no other test uses, so nothing cached it in this process.
    a = jnp.arange(1237, dtype=jnp.uint32)
    np.asarray(jnp.concatenate([a, a, a[:5]]))
    assert api.executor.jit_compiles == before        # invisible there
    snap = clog.snapshot()
    by = {r["name"]: r for r in snap["byName"]}
    assert "jit(concatenate)" in by, sorted(by)
    row = by["jit(concatenate)"]
    assert row["compiles"] >= 1 and row["seconds"] > 0
    assert row["traces"] >= 1
    assert row["stage"] == "idle.no_request"
    assert snap["compiles"] >= row["compiles"]
    c = stats.snapshot()["counters"]
    assert c["xla.compiles"] == snap["compiles"] >= 1
    assert c["xla.compile_seconds"] == pytest.approx(
        snap["compileSeconds"])
    assert c["xla.traces"] == snap["traces"] >= 1
    # The health document carries the totals, /debug/queries the table.
    assert api.node_health()["xla"]["compiles"] == snap["compiles"]


def test_forced_retrace_shows_its_key_and_tags_the_open_span(
        log, tmp_holder):
    """Drop the executor's jit cache: the same query retraces. The
    compile is counted under the program's stable name, the table
    shows the readable jit key that missed, the `dispatch` span that
    paid for it says `jit=miss` with the key, and carries the
    compile."""
    clog, stats = log
    _seed(tmp_holder)
    api = API(tmp_holder, stats=stats)
    api.executor.result_cache.enabled = False
    TIMELINE.reset()
    api.query("cl", "Count(Row(f=1))")
    api.query("cl", "Count(Row(f=1))")                # cached program
    hit = TIMELINE.requests()[-1]
    (d,) = [c for c in hit.root.children if c.name == "dispatch"]
    assert d.attrs["jit"] == "hit" and "key" not in d.attrs
    retraces = api.executor.jit_compiles
    with api.executor._jit_cache_lock:
        api.executor._jit_cache.clear()               # force a retrace
    clog.reset()
    api.query("cl", "Count(Row(f=1))")
    assert api.executor.jit_compiles == retraces + 1
    rec = TIMELINE.requests()[-1]
    (d,) = [c for c in rec.root.children if c.name == "dispatch"]
    assert d.attrs["program"] == "tree_count"
    assert d.attrs["jit"] == "miss"
    assert d.attrs["key"].startswith("count|")
    assert d.attrs["compiles"] >= 1
    assert d.attrs["compiled"] == "jit(tree_count)"
    row = {r["name"]: r for r in clog.snapshot()["byName"]}[
        "jit(tree_count)"]
    assert row["retraces"] == 1 and row["key"] == d.attrs["key"]
    assert row["compiles"] >= 1 and row["stage"] == "dispatch"
    TIMELINE.reset()


def test_table_is_bounded_and_ranked():
    clog = CompileLog()
    for i in range(clog.MAX_NAMES + 40):
        clog._duration(clog.COMPILE, 0.001 * (i % 7 + 1),
                       fun_name=f"jit(f{i})")
    clog._duration(clog.COMPILE, 5.0, fun_name="jit(big)")
    clog._duration(clog.TRACE, 0.1, fun_name="big")
    clog._duration("/jax/core/compile/other", 9.0, fun_name="x")
    clog._event(clog.CACHE_HIT)
    clog._event("/jax/some/other/event")
    snap = clog.snapshot()
    assert len(snap["byName"]) <= clog.MAX_NAMES
    assert snap["byName"][0]["name"] == "jit(big)"    # by seconds
    assert snap["byName"][0]["traces"] == 1           # one row for both
    assert snap["compiles"] == clog.MAX_NAMES + 41
    assert snap["cacheHits"] == 1 and snap["traces"] == 1
