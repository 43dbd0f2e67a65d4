"""The per-layer metrics that read the flush record's thread sections
and the runtime monitor's counters (ISSUE 34): each new entry of
BENCHMARK.json resolves to a metric file and a reader, the reader
returns the hand-computed value on a small pair of snapshots, and on a
program that publishes no such histogram or counter — the parent — it
returns nothing and does not raise."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

SWEEP_CELLS = ["taxi-chip.topn-sweep", "taxi-host4.topn-sweep",
               "chem-chip.tanimoto-sweep", "ssb-chip.flights",
               "taxi-live-chip.report-ingest",
               "chem-lib-chip.tanimoto-library", "ssb-host4.flights"]
POINT = ["taxi-chip.point-serial"]


def _h(total, count):
    return {"sum": total, "count": count, "buckets": {}}


BEFORE = {"vars": {
    "histograms": {
        "request.stage_seconds{stage:thread.begin}": _h(1.0, 10),
        "request.stage_cpu_seconds{stage:thread.begin}": _h(0.5, 10),
        "request.stage_seconds{stage:thread.finish}": _h(2.0, 10),
        "request.stage_cpu_seconds{stage:thread.finish}": _h(1.0, 10),
        "request.stage_seconds{stage:coalescer.handoff}": _h(1.0, 4),
        "request.stage_seconds{stage:coalescer.wait}": _h(0.5, 100)},
    "counters": {
        "runtime.gc_pause_seconds": 0.25,
        "runtime.cpu_seconds": 100.0,
        "runtime.uptime_seconds": 50.0}}}
AFTER = {"vars": {
    "histograms": {
        "request.stage_seconds{stage:thread.begin}": _h(5.0, 50),
        "request.stage_cpu_seconds{stage:thread.begin}": _h(3.5, 50),
        "request.stage_seconds{stage:thread.finish}": _h(4.0, 50),
        "request.stage_cpu_seconds{stage:thread.finish}": _h(1.5, 50),
        "request.stage_seconds{stage:coalescer.handoff}": _h(3.0, 12),
        "request.stage_seconds{stage:coalescer.wait}": _h(1.5, 600)},
    "counters": {
        "runtime.gc_pause_seconds": 0.375,
        "runtime.cpu_seconds": 145.0,
        "runtime.uptime_seconds": 80.0}}}
CTX = {"before": BEFORE, "after": AFTER, "completed": 400}
PARENT = {"before": {"vars": {"histograms": {}, "counters": {}}},
          "after": {"vars": {"histograms": {}, "counters": {}}},
          "completed": 400}

# name -> (unit, layer, moves, the cells that report it, the value)
TABLE = {
    "begin_thread_cpu_share.sweep":
        ("%", "Plan / fuse", "sweep_qps", SWEEP_CELLS, 75.0),
    "finish_thread_cpu_share.sweep":
        ("%", "Plan / fuse", "sweep_qps", SWEEP_CELLS, 25.0),
    "gc_pause_ms_in_window.sweep":
        ("ms", "HTTP front end", "sweep_qps", SWEEP_CELLS, 125.0),
    "gc_pause_ms_in_window.point":
        ("ms", "HTTP front end", "point_p95_ms", POINT, 125.0),
    "host_cpu_cores.sweep":
        ("cores", "HTTP front end", "sweep_qps", SWEEP_CELLS, 1.5),
    "host_cpu_cores.point":
        ("cores", "HTTP front end", "point_p50_ms", POINT, 1.5),
    "coalescer_handoff_mean_ms.sweep":
        ("ms", "API + coalescer", "sweep_qps", SWEEP_CELLS, 250.0),
    "coalescer_wait_mean_ms.point":
        ("ms", "API + coalescer", "point_p50_ms", POINT, 2.0),
}


@pytest.fixture(scope="module")
def man():
    added = [p for p in (BENCH, REPO) if p not in sys.path]
    sys.path[:0] = added
    from harness.manifest import Manifest
    yield Manifest(REPO)
    for p in added:
        sys.path.remove(p)


def _read(man, name, ctx):
    spec = man.metric_spec(name)
    reader = man.load_module("readers", spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def test_the_entries_are_appended_and_nothing_else_moved(man):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert len(names) == len(set(names))
    # One block, in the table's order, behind the entry that was the
    # accepted benchmark's last (later PRs append behind the block).
    first = names.index("groupby_levels_per_op.ssb") + 1
    assert names[first:first + len(TABLE)] == list(TABLE)


@pytest.mark.parametrize("name", list(TABLE))
def test_metric_reads_the_hand_computed_value(man, name):
    unit, layer, moves, cells, want = TABLE[name]
    entry = next(m for m in man.doc["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["layer"], entry["moves"]) == \
        (unit, layer, moves)
    reporting = [w["name"] for w in man.doc["workloads"]
                 if entry in man.metrics_for("per_layer", w["name"])]
    assert sorted(reporting) == sorted(cells)
    assert _read(man, name, CTX) == pytest.approx(want)
    assert _read(man, name, PARENT) is None


def test_counter_ratio_wants_both_counters_and_a_window(man):
    ratio = man.load_module("readers", "counter_ratio")
    part = ["vars", "counters", "runtime.cpu_seconds"]
    whole = ["vars", "counters", "runtime.uptime_seconds"]
    assert ratio.read(CTX, part, whole) == pytest.approx(45.0 / 30.0)
    assert ratio.read(CTX, part, ["vars", "counters", "nope"]) is None
    assert ratio.read(CTX, ["vars", "counters", "nope"], whole) is None
    still = {"before": AFTER, "after": AFTER, "completed": 1}
    assert ratio.read(still, part, whole) is None    # no time went by


def test_the_server_publishes_what_the_metric_files_name(tmp_holder):
    """The names in the data files are the names a live coalescer,
    recorder and monitor publish: one pipelined flush, one collection."""
    import gc
    import threading
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.diagnostics import RuntimeMonitor
    from pilosa_tpu.utils.stats import MemStatsClient
    from pilosa_tpu.utils.timeline import TIMELINE
    import numpy as np

    TIMELINE.reset()
    TIMELINE.configure(enabled=True, sample_every=1)
    idx = tmp_holder.create_index("tm")
    idx.create_field("f").import_bits(np.array([1, 1], np.uint64),
                                      np.array([1, 2], np.uint64))
    stats = MemStatsClient()
    mon = RuntimeMonitor(stats, interval=1000)
    mon.start()
    api = API(tmp_holder, stats=stats)
    api.executor.result_cache.enabled = False
    api.coalescer = QueryCoalescer(api.executor, window_s=0.25,
                                   max_batch=2, stats=stats)
    api.coalescer.start()
    # The test holds a request of its own: the first of the two to
    # arrive is then not alone in the server, and waits for the other.
    api.held.open()
    try:
        out = [None, None]
        ts = [threading.Thread(target=lambda i=i: out.__setitem__(
            i, api.query_coalesced("tm", f"Count(Row(f={i}))")))
            for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert [r["results"] for r in out] == [[0], [2]]
        gc.collect()
        snap = stats.snapshot()
    finally:
        api.held.close()
        api.coalescer.stop()
        mon.stop()
        TIMELINE.reset()
    for section in ("thread.begin", "thread.finish"):
        assert f"request.stage_cpu_seconds{{stage:{section}}}" in \
            snap["histograms"]
        assert f"request.stage_seconds{{stage:{section}}}" in \
            snap["histograms"]
    assert "request.stage_seconds{stage:coalescer.handoff}" in \
        snap["histograms"]
    assert "request.stage_seconds{stage:coalescer.wait}" in \
        snap["histograms"]
    for key in ("runtime.gc_pause_seconds", "runtime.cpu_seconds",
                "runtime.uptime_seconds"):
        assert key in snap["counters"]
    assert "runtime.gc_pause_seconds{gen:2}" in snap["histograms"]
