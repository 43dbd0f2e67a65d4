"""Per-query execution profiler tests (utils/profile.py + executor/
server wiring): profile tree shape, the ?profile=true-only device
fence, the slow-query ring, /debug/queries + ?profile=true HTTP surfaces, and the
pilosa_executor_* metrics feed."""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.profile import Profiler, QueryProfile
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text


def _seed_two_shards(holder, index="p"):
    """Index with two set fields holding the same bits in 2 shards."""
    idx = holder.create_index(index)
    f = idx.create_field("f")
    g = idx.create_field("g")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    f.import_bits(np.full(3, 1, np.uint64), cols)
    g.import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    return idx


def _walk(node):
    yield node
    for c in node.get("children", []):
        yield from _walk(c)


def test_profile_tree_count_intersect_two_shards(tmp_holder):
    """Acceptance: a profiled Count(Intersect(Row, Row)) over >= 2
    shards returns per-op device time, jit cache hit/miss, and
    transfer-byte fields."""
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    resp = api.query("p", "Count(Intersect(Row(f=1), Row(g=1)))",
                     profile=True)
    assert resp["results"] == [3]
    p = resp["profile"]
    assert p["deviceSampled"] is True
    assert p["durS"] > 0
    assert p["jit"]["hits"] + p["jit"]["misses"] >= 1
    assert p["ops"] and p["ops"][0]["name"] == "Count"
    op = p["ops"][0]
    assert op["dispatchS"] >= 0 and op["materializeS"] >= 0
    assert op["d2hBytes"] > 0  # the fetched per-shard counts
    evals = [n for n in _walk(op) if n["name"].startswith("eval:")]
    assert evals, op
    ev = evals[0]
    assert ev["jit"] in ("hit", "miss")
    assert ev["shards"] == 2
    assert "deviceS" in ev and ev["deviceS"] >= 0
    assert ev.get("h2dBytes", 0) >= 0
    # Warm repeat: same shape -> jit cache hit recorded.
    p2 = api.query("p", "Count(Intersect(Row(f=1), Row(g=2)))",
                   profile=True)["profile"]
    ev2 = [n for op2 in p2["ops"] for n in _walk(op2)
           if n["name"].startswith("eval:")][0]
    assert ev2["jit"] == "hit"


def test_no_fence_without_sampling_profile(tmp_holder, monkeypatch):
    """Acceptance: only ?profile=true fences. No other query adds a
    block_until_ready on the hot path, and no config key can turn a
    fence on: the config has no field that names a sampling rate and
    the profiler takes none."""
    import dataclasses
    import inspect

    import pilosa_tpu.executor.executor as ex
    from pilosa_tpu.utils.config import Config

    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    # Repeats must DISPATCH; the result cache would serve them
    # without any device work.
    api.executor.result_cache.enabled = False
    fences = []
    real = ex._fence_device
    monkeypatch.setattr(ex, "_fence_device",
                        lambda out: fences.append(1) or real(out))
    for _ in range(6):
        api.query("p", "Count(Row(f=1))")
    assert fences == []  # passive profile: zero fences
    api.query("p", "Count(Row(f=1))", profile=True)
    assert fences  # forced profile fences
    profile_keys = [f.name for f in dataclasses.fields(Config)
                    if f.name.startswith("profile_")]
    assert profile_keys == ["profile_slow_ring"]
    assert list(inspect.signature(Profiler.configure).parameters) \
        == ["self", "ring_size"]
    assert not api.profiler.begin("p", "Count(Row(f=1))").sample_device
    assert api.profiler.begin("p", "Count(Row(f=1))",
                              force=True).sample_device


def test_device_seconds_carries_sampled_label(tmp_holder):
    """pilosa_executor_device_seconds is fed ONLY by ?profile=true
    fences, so the series carries an explicit sampled="true" label: it
    is never total device time."""
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.executor.result_cache.enabled = False
    for _ in range(3):
        api.query("p", "Count(Row(f=1))")
    assert "pilosa_executor_device_seconds" not in \
        prometheus_text(api.stats)
    api.query("p", "Count(Row(f=1))", profile=True)
    prom = prometheus_text(api.stats)
    line = next(l for l in prom.splitlines()
                if l.startswith("pilosa_executor_device_seconds{"))
    assert 'sampled="true"' in line, line
    # No unlabeled twin series: one family, one label shape.
    assert "pilosa_executor_device_seconds{quantile" not in prom


def test_retrace_counter_and_metrics(tmp_holder):
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    before = api.executor.jit_compiles
    api.query("p", "Count(Row(f=1))")
    assert api.executor.jit_compiles > before  # cold shape: a retrace
    first = api.executor.jit_compiles
    api.query("p", "Count(Row(g=1))")  # same shape: no retrace
    assert api.executor.jit_compiles == first
    prom = prometheus_text(api.stats)
    assert "pilosa_executor_retrace_total" in prom
    assert "pilosa_executor_plan_seconds" in prom
    assert "pilosa_executor_materialize_seconds" in prom


def test_slow_query_ring_structured_record(tmp_holder):
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.long_query_time = 1e-9  # everything is slow
    api.query("p", "Count(Row(f=1))")
    recs = api.profiler.slow_queries()
    assert recs
    rec = recs[0]
    assert rec["index"] == "p"
    assert rec["query"] == "Count(Row(f=1))"
    assert rec["durS"] > 0 and rec["kind"] == "query"
    # Structured per-op breakdown rides along.
    assert rec["profile"]["ops"][0]["name"] == "Count"
    # Ring is bounded and most-recent-first.
    api.profiler.configure(ring_size=2)
    for i in range(4):
        api.query("p", f"Count(Row(f={i}))")
    recs = api.profiler.slow_queries()
    assert len(recs) == 2
    assert recs[0]["query"] == "Count(Row(f=3))"


def test_ring_records_errors(tmp_holder):
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.long_query_time = 1e-9
    with pytest.raises(Exception):
        api.query("p", "Count(Row(nope=1))")
    recs = api.profiler.slow_queries()
    assert any("error" in r for r in recs)


def test_http_profile_and_debug_queries(live_server):
    """?profile=true embeds the tree (through the coalescer);
    GET /debug/queries serves the structured slow-query ring."""
    base, api, holder = live_server
    _seed_two_shards(holder, index="hp")
    api.long_query_time = 1e-9

    def req(method, path, body=None):
        data = body if isinstance(body, (bytes, type(None))) \
            else json.dumps(body).encode()
        r = urllib.request.Request(base + path, data=data, method=method)
        with urllib.request.urlopen(r, timeout=30) as resp:
            return json.loads(resp.read() or b"{}")

    res = req("POST", "/index/hp/query?profile=true",
              b"Count(Intersect(Row(f=1), Row(g=1)))")
    assert res["results"] == [3]
    p = res["profile"]
    assert p["deviceSampled"] is True
    assert p["ops"][0]["name"] == "Count"
    # Through the live_server coalescer the profile records its batch.
    assert p.get("coalesced", {}).get("batch", 1) >= 1
    dbg = req("GET", "/debug/queries")
    assert isinstance(dbg["retraces"], int)
    assert dbg["queries"], dbg
    assert dbg["queries"][0]["index"] == "hp"
    # Unprofiled query: no profile key in the response.
    res = req("POST", "/index/hp/query", b"Count(Row(f=1))")
    assert "profile" not in res
    # /metrics carries the executor series.
    r = urllib.request.Request(base + "/metrics")
    with urllib.request.urlopen(r, timeout=30) as resp:
        prom = resp.read().decode()
    assert "pilosa_executor_" in prom


def test_coalesced_dedup_skips_forced_profiles(tmp_holder):
    """Forced profiles never share a deduped response dict — each gets
    its own execution."""
    from pilosa_tpu.server.coalescer import QueryCoalescer

    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    coal = QueryCoalescer(api.executor, window_s=0.02, stats=api.stats)
    coal.start()
    api.coalescer = coal
    try:
        import threading
        results = []

        def go():
            results.append(api.query_coalesced(
                "p", "Count(Row(f=1))", profile=True))

        threads = [threading.Thread(target=go) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r["results"] == [3] for r in results)
        profiles = [r["profile"] for r in results]
        assert all(p["ops"] for p in profiles)  # each really executed
    finally:
        coal.stop()


def test_profile_reused_across_executes_keeps_per_op_attribution(
        tmp_holder):
    """The cluster path runs one executor.execute() per PQL call
    against the SAME profile: finalize indices must rebase per dispatch
    run, or call 2's materialize data would overwrite call 1's op."""
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    prof = api.profiler.begin("p", "reused", force=True)
    api.executor.execute("p", "Row(f=1)", profile=prof)
    api.executor.execute("p", "Count(Row(f=1))", profile=prof)
    assert [op.name for op in prof.ops] == ["Row", "Count"]
    for op in prof.ops:
        assert "materializeS" in op.attrs, op.to_json()
    assert prof.ops[1].attrs.get("d2hBytes", 0) > 0  # Count's fetch


def test_profile_merge_node_fragments():
    p = QueryProfile("i", "Count(Row(f=1))", forced=True)
    p.add_node_fragment("node-a", {"ops": [{"name": "Count"}]})
    p.add_node_fragment("node-b", {"ops": []})
    out = p.to_json()
    assert set(out["nodes"]) == {"node-a", "node-b"}
    assert out["nodes"]["node-a"]["ops"][0]["name"] == "Count"


def test_profiler_observe_never_raises_without_sinks():
    prof = Profiler()
    p = prof.begin("i", "Count(Row(f=1))")
    prof.observe("i", "Count(Row(f=1))", 0.5, profile=p,
                 long_query_time=0.1, logger=None)
    assert prof.slow_queries()[0]["durS"] == 0.5


def test_batch_query_slow_record(tmp_holder):
    _seed_two_shards(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.long_query_time = 1e-9
    out = api.query_batch([{"index": "p", "query": "Count(Row(f=1))"}])
    assert out[0]["results"] == [3]
    assert any(r["kind"] == "batch" for r in api.profiler.slow_queries())
