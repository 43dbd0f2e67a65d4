"""Request-lifecycle timeline plane (utils/timeline.py): the
dispatch-gap analyzer's idle-ratio math, ring bounds and sampling, the
Chrome trace-event export shape, wall-anchor skew immunity, the HTTP
surfaces (/debug/timeline, /cluster/timeline, the SLO histograms), the
memory-ledger registration, and the zero-new-fences acceptance bar."""

import json
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient, prometheus_text
from pilosa_tpu.utils.timeline import (
    LANE_DISPATCH, LANE_NAMES, LANE_PLAN, TIMELINE, TimelineRecorder,
)


@pytest.fixture(autouse=True)
def _reset_timeline():
    """The recorder is process-wide (like hotspots.WORKLOAD): every
    test starts clean and leaves defaults behind."""
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=256, sample_every=1,
                       gap_window_s=60.0)
    yield
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=256, sample_every=1,
                       gap_window_s=60.0)


def _seed(holder):
    idx = holder.create_index("tl")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    return idx


# ------------------------------------------------- dispatch-gap analyzer


def test_idle_ratio_exact_math():
    rec = TimelineRecorder(gap_window_s=100.0)
    # Three dispatches at t=0..1, 2..3, 4..5: busy 3s over span 5s.
    for s in (0.0, 2.0, 4.0):
        rec.note_dispatch(s, 1.0)
    gap = rec.gap_summary(now_pc=5.0)
    assert gap["dispatches"] == 3
    assert gap["busyS"] == pytest.approx(3.0)
    assert gap["idleS"] == pytest.approx(2.0)
    assert gap["idleRatio"] == pytest.approx(2.0 / 5.0)
    assert gap["largestGapS"] == pytest.approx(1.0)
    assert 0.0 <= gap["idleRatio"] <= 1.0


def test_idle_ratio_overlapping_dispatches_merge():
    """Overlapping enqueue intervals (pipelined dispatch) must not
    double-count busy time — coverage is an interval union."""
    rec = TimelineRecorder(gap_window_s=100.0)
    rec.note_dispatch(0.0, 2.0)
    rec.note_dispatch(1.0, 2.0)   # overlaps the first
    rec.note_dispatch(5.0, 1.0)
    gap = rec.gap_summary(now_pc=6.0)
    assert gap["busyS"] == pytest.approx(4.0)   # [0,3] + [5,6]
    assert gap["idleRatio"] == pytest.approx(2.0 / 6.0)


def test_idle_ratio_degenerate_cases():
    rec = TimelineRecorder(gap_window_s=10.0)
    assert rec.idle_ratio(now_pc=0.0) == 0.0          # no dispatches
    rec.note_dispatch(0.0, 0.5)
    assert rec.idle_ratio(now_pc=1.0) == 0.0          # one dispatch
    # Dispatches older than the window fall out of the analysis.
    rec.note_dispatch(0.6, 0.2)
    assert rec.gap_summary(now_pc=100.0)["dispatches"] == 0


def test_note_dispatch_disabled_is_noop():
    rec = TimelineRecorder()
    rec.enabled = False
    rec.note_dispatch(0.0, 1.0)
    assert rec.dispatches_total == 0
    assert rec.begin("t" * 32) is None


# -------------------------------------------------- ring / sampling / cap


def test_ring_bound_and_sampling():
    rec = TimelineRecorder(ring=4, sample_every=1)
    for i in range(10):
        req = rec.begin(f"{i:032x}")
        assert req is not None
        rec.finish(req)
    assert rec.ring_count() == 4
    assert rec.requests_recorded == 10
    # 1-in-2 sampling: roughly half skip (deterministic counter).
    rec2 = TimelineRecorder(ring=64, sample_every=2)
    got = [rec2.begin("a" * 32) for _ in range(10)]
    assert sum(1 for r in got if r is not None) == 5
    assert rec2.requests_skipped == 5


def test_note_serialize_cannot_attach_to_previous_request():
    """Review regression: if a request's serialize hook never fires
    (error path, broken pipe), the NEXT request on the thread must not
    attach its serialize slice to the already-published timeline —
    begin() invalidates the thread's post-finish handle."""
    rec = TimelineRecorder(sample_every=2)
    assert rec.begin("0" * 32) is None   # seq 1: skipped
    a = rec.begin("a" * 32)              # seq 2: sampled
    rec.finish(a)                        # serialize hook never fires
    assert rec.begin("b" * 32) is None   # seq 3: unsampled request B
    rec.note_serialize(0.0, 1.0)         # B's serialize: must go nowhere
    assert all(name != "serialize" for name, *_ in a.events)


def test_event_cap_counts_drops():
    rec = TimelineRecorder()
    req = rec.begin("b" * 32)
    for i in range(rec.MAX_EVENTS_PER_REQUEST + 10):
        rec.event(req, "plan", LANE_PLAN, float(i), 0.001)
    assert len(req.events) == rec.MAX_EVENTS_PER_REQUEST
    assert req.dropped == 10
    rec.event(None, "plan", LANE_PLAN, 0.0, 0.0)  # None handle: no-op


# ------------------------------------------------------ export shape


def test_snapshot_chrome_trace_event_shape():
    rec = TimelineRecorder()
    req = rec.begin("c" * 32, index="i1")
    rec.event(req, "plan", LANE_PLAN, req.t0_pc + 0.001, 0.002)
    rec.event(req, "dispatch", LANE_DISPATCH, req.t0_pc + 0.003, 0.004,
              shards=2)
    rec.finish(req)
    doc = rec.snapshot(node_id="node-a")
    evs = doc["traceEvents"]
    # Every event — metadata included — carries the full shape.
    for ev in evs:
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in ev, ev
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"plan", "dispatch", "request"}
    disp = next(e for e in xs if e["name"] == "dispatch")
    assert disp["tid"] == LANE_DISPATCH
    assert disp["dur"] == pytest.approx(4000.0)       # µs
    assert disp["args"]["trace"] == "c" * 32
    assert disp["args"]["shards"] == 2
    # ts is wall-anchored: within the request's wall window.
    assert abs(disp["ts"] / 1e6 - req.t0_wall) < 1.0
    # Metadata names the process and every stage lane.
    metas = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} == {e["name"] for e in metas}
    assert any(e["args"]["name"] == "node-a" for e in metas)
    assert {e["args"]["name"] for e in metas
            if e["name"] == "thread_name"} == set(LANE_NAMES.values())
    # Request-level slice nests everything under one trace.
    root = next(e for e in xs if e["name"] == "request")
    assert root["args"]["index"] == "i1"
    summary = doc["summary"]
    assert summary["requests"] == 1
    assert 0.0 <= summary["deviceIdleRatio"] <= 1.0


def test_bandwidth_sample_without_a_roofline_has_no_fraction_lane():
    """On a device with no peak on record the recorder passes None:
    the bytes/s lane is kept, no fraction is invented."""
    rec = TimelineRecorder()
    rec.note_bandwidth(2.5e9, None)
    doc = rec.snapshot()
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert [e["name"] for e in cs] == ["launch_bytes_per_s"]
    assert doc["summary"]["counterSamples"] == 1


def test_bandwidth_counter_track_shape():
    """Roofline plane counter tracks: note_bandwidth exports two
    Perfetto ph:"C" samples (launch_bytes_per_s + roofline_fraction)
    with the full event shape, bounded by MAX_COUNTER_SAMPLES."""
    rec = TimelineRecorder()
    rec.note_bandwidth(2.5e9, 0.8)
    rec.note_bandwidth(1.0e9, 0.3)
    doc = rec.snapshot()
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 4                      # 2 samples x 2 tracks
    for ev in cs:
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in ev, ev
    by_name = {}
    for ev in cs:
        by_name.setdefault(ev["name"], []).append(ev)
    assert set(by_name) == {"launch_bytes_per_s", "roofline_fraction"}
    assert [e["args"]["bytes_per_s"]
            for e in by_name["launch_bytes_per_s"]] == [2.5e9, 1.0e9]
    assert [e["args"]["fraction"]
            for e in by_name["roofline_fraction"]] == [0.8, 0.3]
    assert doc["summary"]["counterSamples"] == 2
    # Bounded ring: the counter deque never outgrows the cap.
    for _ in range(rec.MAX_COUNTER_SAMPLES + 50):
        rec.note_bandwidth(1.0, 0.5)
    assert len(rec.counter_samples()) == rec.MAX_COUNTER_SAMPLES
    assert rec.counters_total == 2 + rec.MAX_COUNTER_SAMPLES + 50
    rec.reset()
    assert len(rec.counter_samples()) == 0


def test_snapshot_filters_last_and_trace():
    rec = TimelineRecorder()
    for i in range(6):
        req = rec.begin(f"{i:032x}")
        rec.finish(req)
    assert rec.snapshot(last=2)["summary"]["requests"] == 2
    doc = rec.snapshot(trace_id=f"{3:032x}")
    assert doc["summary"]["requests"] == 1
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["args"]["trace"] == f"{3:032x}" for e in xs)


def test_wall_anchor_immune_to_clock_step(monkeypatch):
    """One wall-clock read per request: an NTP step AFTER begin() must
    not move any event timestamp or duration (they are perf_counter
    offsets from the anchor)."""
    rec = TimelineRecorder()
    real_time = time.time
    wall = [real_time()]
    monkeypatch.setattr(time, "time", lambda: wall[0])
    req = rec.begin("d" * 32)
    t = req.t0_pc
    rec.event(req, "plan", LANE_PLAN, t + 0.010, 0.005)
    wall[0] += 3600.0  # the clock steps one hour mid-request
    rec.event(req, "dispatch", LANE_DISPATCH, t + 0.020, 0.005)
    rec.finish(req)
    xs = {e["name"]: e for e in rec.snapshot()["traceEvents"]
          if e["ph"] == "X"}
    anchor_us = req.t0_wall * 1e6
    assert xs["plan"]["ts"] == pytest.approx(anchor_us + 10_000, abs=1)
    # The post-step event still exports 10ms later, not an hour later.
    assert xs["dispatch"]["ts"] - xs["plan"]["ts"] == \
        pytest.approx(10_000, abs=1)
    assert xs["request"]["dur"] < 1e6  # the request did not "take" 1h


# ------------------------------------------------------- live wiring


def test_query_records_stage_slices(tmp_holder):
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.query("tl", "Count(Row(f=1))")
    reqs = TIMELINE.requests()
    assert len(reqs) == 1
    names = [name for name, *_ in reqs[0].events]
    assert "plan" in names and "dispatch" in names \
        and "materialize" in names and "request" in names
    assert "device" not in names  # unsampled: no device slice
    assert TIMELINE.dispatches_total >= 1


def test_profiled_query_gains_device_slice(tmp_holder):
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.query("tl", "Count(Row(f=1))", profile=True)
    names = [name for name, *_ in TIMELINE.requests()[-1].events]
    assert "device" in names  # rides the profiler's sampled fence


def test_zero_new_fences_on_unsampled_path(tmp_holder, monkeypatch):
    """Acceptance: the timeline plane adds NO block_until_ready fences
    on the unsampled hot path — wall timestamps of host-side events
    only (same bar as PR 3's profiler and PR 6's recorder)."""
    import pilosa_tpu.executor.executor as ex

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    # Every repeat must DISPATCH (dispatches_total >= 8 below); the
    # result cache would serve 6 of the 8 without any device work.
    api.executor.result_cache.enabled = False
    fences = []
    monkeypatch.setattr(ex, "_fence_device",
                        lambda out: fences.append(1) or 0.0)
    for i in range(8):
        api.query("tl", f"Count(Row(f={i % 2}))")
    assert fences == []
    # ...and it recorded the full stage set while staying fence-free.
    assert TIMELINE.requests_recorded == 8
    assert TIMELINE.dispatches_total >= 8


def test_timeline_disabled_records_nothing(tmp_holder):
    _seed(tmp_holder)
    TIMELINE.configure(enabled=False)
    api = API(tmp_holder, stats=MemStatsClient())
    api.query("tl", "Count(Row(f=1))")
    assert TIMELINE.requests_recorded == 0
    assert TIMELINE.dispatches_total == 0


def test_embedded_queries_get_distinct_trace_ids(tmp_holder):
    """Review regression: library (non-HTTP) callers have no per-
    request extract() reset, so the minted trace id must be dropped at
    request end — N queries on one thread are N traces, not one."""
    from pilosa_tpu.utils.tracing import RecordingTracer

    _seed(tmp_holder)
    tracer = RecordingTracer()
    api = API(tmp_holder, stats=MemStatsClient(), tracer=tracer)
    api.query("tl", "Count(Row(f=1))")
    api.query("tl", "Count(Row(f=1))")
    assert len({r.trace_id for r in TIMELINE.requests()}) == 2
    assert len({s.trace_id for s in tracer.finished}) == 2
    assert tracer.current_trace_id() is None  # nothing sticks around


def test_endpoint_label_is_bounded():
    """Review regression: unknown paths under /internal/ and /cluster/
    fold into "other" like everything else — the known internal routes
    are a fixed whitelist, not a prefix grant."""
    from pilosa_tpu.server.http import endpoint_label

    assert endpoint_label("/internal/health") == "/internal/health"
    assert endpoint_label("/cluster/resize/abort") == \
        "/cluster/resize/abort"
    assert endpoint_label("/index/i1/query") == "/index/{index}/query"
    assert endpoint_label("/cluster/timeline/abc123") == \
        "/cluster/timeline/{trace}"
    for probe in ("/internal/zz-random", "/cluster/zz-random",
                  "/internal/fragment/bogus", "/xyz"):
        assert endpoint_label(probe) == "other", probe


def test_trace_id_links_profiler_and_timeline(tmp_holder):
    """The slow-query ring's traceId opens the same request in the
    timeline: both stamp the ONE id the tracer minted."""
    from pilosa_tpu.utils.tracing import RecordingTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=RecordingTracer())
    api.long_query_time = 1e-9  # everything is "slow"
    api.query("tl", "Count(Row(f=1))")
    rec = api.profiler.slow_queries()[0]
    assert rec["traceId"]
    doc = api.debug_timeline(trace=rec["traceId"])
    assert doc["summary"]["requests"] == 1


# ------------------------------------------------------- HTTP surfaces


@pytest.fixture
def live_api(tmp_holder):
    from pilosa_tpu.server import serve
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.tracing import RecordingTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=RecordingTracer())
    # These tests assert plan/dispatch/materialize slices on repeated
    # queries; the result cache would answer the repeats with a single
    # `cache` slice instead. Cache-ON timeline attribution is pinned
    # in tests/test_result_cache.py.
    api.executor.result_cache.enabled = False
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats, tracer=api.tracer)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    yield api, base
    srv.shutdown()
    srv.server_close()
    api.coalescer.stop()


def _get(base, path):
    return json.loads(urllib.request.urlopen(base + path,
                                             timeout=30).read())


def test_debug_timeline_http_surface(live_api):
    api, base = live_api
    for i in range(12):
        r = urllib.request.urlopen(
            base + "/index/tl/query",
            data=f"Count(Row(f={i % 3}))".encode()).read()
        assert "results" in json.loads(r)
    doc = _get(base, "/debug/timeline?last=6")
    for ev in doc["traceEvents"]:
        for k in ("ph", "ts", "dur", "pid", "tid"):
            assert k in ev, ev
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"queue", "plan", "dispatch", "materialize", "serialize",
            "request"} <= names
    s = doc["summary"]
    assert s["requests"] == 6
    assert 0.0 <= s["deviceIdleRatio"] <= 1.0
    assert s["dispatchGap"]["dispatches"] > 0
    assert s["stageMedianS"]["dispatch"] > 0
    # ?trace= narrows to one request; the single-node /cluster/timeline
    # wraps the same events with node attribution.
    tid = next(e["args"]["trace"] for e in xs if e["name"] == "request")
    one = _get(base, f"/debug/timeline?trace={tid}")
    assert one["summary"]["requests"] == 1
    merged = _get(base, f"/cluster/timeline/{tid}")
    assert merged["respondedNodes"] == merged["totalNodes"] == 1
    mx = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert mx and all(e["args"]["node"] for e in mx)
    # The idle-ratio gauge is on /metrics.
    met = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "pilosa_device_idle_ratio" in met


def test_slo_histograms_per_endpoint(live_api):
    api, base = live_api
    urllib.request.urlopen(base + "/index/tl/query",
                           data=b"Count(Row(f=1))").read()
    urllib.request.urlopen(base + "/schema").read()
    try:
        urllib.request.urlopen(base + "/definitely/not/a/route").read()
    except urllib.error.HTTPError as e:
        assert e.code == 404
        e.read()
    # The SLO observation runs in the handler's finally block, AFTER
    # the response body went out — poll until all three landed.
    met = ""
    for _ in range(200):
        met = urllib.request.urlopen(base + "/metrics").read().decode()
        if ('endpoint="/schema"' in met
                and 'endpoint="/index/{index}/query"' in met
                and 'endpoint="other",status="404"' in met):
            break
        time.sleep(0.01)
    assert '# TYPE pilosa_http_request_seconds histogram' in met
    assert 'endpoint="/index/{index}/query"' in met
    assert 'endpoint="/schema"' in met
    # Unknown paths fold into "other" with their status label — a
    # scanner cannot mint series.
    assert 'endpoint="other",status="404"' in met
    # Cumulative-bucket invariants hold for the query endpoint family.
    lines = [ln for ln in met.splitlines()
             if ln.startswith("pilosa_http_request_seconds_bucket")
             and 'endpoint="/schema"' in ln and 'status="200"' in ln]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts) and counts[-1] >= 1


def test_slow_non_query_endpoint_cross_links_ring(live_api):
    api, base = live_api
    api.long_query_time = 1e-9
    urllib.request.urlopen(base + "/schema").read()
    # The SLO observation runs in the handler's finally block, AFTER
    # the response body went out — the client can get here first.
    recs = []
    for _ in range(200):
        recs = [r for r in api.profiler.slow_queries()
                if r.get("kind") == "http"]
        if recs:
            break
        time.sleep(0.01)
    assert recs, api.profiler.slow_queries()
    assert recs[0]["query"] == "GET /schema"


def test_telemetry_rings_in_memory_ledger(live_api):
    api, base = live_api
    urllib.request.urlopen(base + "/index/tl/query",
                           data=b"Count(Row(f=1))").read()
    mem = _get(base, "/debug/memory")
    tel = mem["categories"].get("telemetry")
    assert tel is not None and tel["bytes"] > 0
    # At least two registered rings: this API's tracer span ring + the
    # process-wide timeline ring (earlier tests' tracers may not be
    # collected yet — their owner-scoped entries purge on GC).
    assert tel["count"] >= 2
    # Telemetry is host RAM: counted in totalBytes, not deviceBytes.
    assert mem["totalBytes"] == sum(
        c["bytes"] for c in mem["categories"].values())
    assert mem["deviceBytes"] <= mem["totalBytes"] - tel["bytes"]


def test_dump_and_drain(tmp_holder):
    """drain_telemetry writes the timeline + tracer rings to the log on
    shutdown (the SIGTERM post-mortem path)."""
    from pilosa_tpu.cli.main import drain_telemetry
    from pilosa_tpu.utils.tracing import RecordingTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=RecordingTracer())
    api.query("tl", "Count(Row(f=1))")

    lines = []

    class _Log:
        def printf(self, fmt, *args):
            lines.append(fmt % args if args else fmt)

    drain_telemetry(api, logger=_Log())
    assert any("timeline:" in ln for ln in lines), lines
    assert any("tracer:" in ln for ln in lines), lines


def test_config_timeline_keys(tmp_path):
    from pilosa_tpu.utils.config import load_config
    p = tmp_path / "c.toml"
    p.write_text("[timeline]\nenabled = false\nring = 64\n"
                 "sample_every = 4\ngap_window_s = 30.0\n")
    cfg = load_config(str(p))
    assert cfg.timeline_enabled is False
    assert cfg.timeline_ring == 64
    assert cfg.timeline_sample_every == 4
    assert cfg.timeline_gap_window_s == 30.0
    with pytest.raises(ValueError):
        load_config(None, {"timeline_ring": 0})
