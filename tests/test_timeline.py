"""The request record (utils/timeline.py): how spans nest, how phases
are interrupted so that stages tile, what a finished record feeds into
the cumulative stage histograms, ring bounds and sampling, the Chrome
trace-event export, wall-anchor skew immunity, the annotations that
put every span on a profiler trace's clock, the live wiring through
the API, the coalescer and the HTTP handler, the HTTP surfaces
(/debug/timeline, /cluster/timeline, the SLO histograms), the
memory-ledger registration, and the zero-new-fences acceptance bar."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import (
    STAGE_BUCKETS, TIMELINE, TimelineRecorder, union_seconds,
)

# The stages of a direct-path request, in order (PERF.md section 3);
# `h2d` only shows when operand vectors had to be uploaded.
DIRECT_STAGES = ["http.read", "pql.parse", "coalescer.wait",
                 "cache.lookup", "plan", "dispatch", "d2h", "finish",
                 "http.serialize", "http.write"]
MEMBER_STAGES = ["http.read", "pql.parse", "coalescer.wait",
                 "coalescer.flush", "http.serialize", "http.write"]
FLUSH_STAGES = ["cache.lookup", "plan", "dispatch", "d2h", "finish"]


@pytest.fixture(autouse=True)
def _reset_timeline():
    """The recorder is process-wide (like hotspots.WORKLOAD): every
    test starts clean and leaves defaults behind."""
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=256, sample_every=1)
    yield
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=256, sample_every=1)
    TIMELINE.annotation = None
    TIMELINE.exporter = None


def _seed(holder):
    idx = holder.create_index("tl")
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("f").import_bits(np.full(3, 1, np.uint64), cols)
    idx.add_existence(cols)
    return idx


def _names(span):
    return [c.name for c in span.children]


def _dedup(names):
    """Consecutive repeats collapsed, then first occurrences: the order
    in which the stages were first entered."""
    out = []
    for n in names:
        if n not in out:
            out.append(n)
    return out


def _assert_inside(span, lo=None, hi=None):
    """Every span lies inside its parent, and closed."""
    assert span.pc_end is not None and span.pc_end >= span.pc_start
    if lo is not None:
        assert lo - 1e-9 <= span.pc_start and span.pc_end <= hi + 1e-9, \
            span.name
    for c in span.children:
        _assert_inside(c, span.pc_start, span.pc_end)


# ------------------------------------------------ nesting, phases, tiling


def test_spans_nest_under_innermost_open_span():
    rec = TimelineRecorder()
    req = rec.begin("a" * 32, index="i")
    with rec.span(req, "outer") as o:
        with rec.span(req, "inner", k=1) as i:
            i.set("late", 2)
    with rec.span(req, "sibling"):
        pass
    rec.finish(req)
    assert _names(req.root) == ["outer", "sibling"]
    (inner,) = o.span.children
    assert inner.name == "inner" and inner.attrs == {"k": 1, "late": 2}
    assert all(sp.trace_id == "a" * 32 for sp in req.root.walk())
    _assert_inside(req.root)
    # A handle's duration() after exit is the span's own reading.
    assert i.duration() == inner.pc_end - inner.pc_start


def test_phase_is_interrupted_by_sibling_stage_and_resumes():
    """`dispatch` opened inside `plan` is plan's SIBLING: the plan
    segment closes, dispatch runs, a fresh plan segment resumes — so
    top-level stages never overlap and their durations add up."""
    rec = TimelineRecorder()
    req = rec.begin(None)
    with rec.span(req, "plan", phase=True, op="Count") as plan:
        time.sleep(0.002)
        with rec.span(req, "dispatch", program="tree_count") as d:
            time.sleep(0.003)
        time.sleep(0.001)
        with rec.span(req, "h2d", bytes=8, transfers=2):
            pass
    rec.finish(req)
    assert _names(req.root) == ["plan", "dispatch", "plan", "h2d", "plan"]
    segs = [c for c in req.root.children if c.name == "plan"]
    assert segs[0].attrs == {"op": "Count"}
    assert all(s.attrs == {"resumed": True} for s in segs[1:])
    # No two top-level children overlap.
    kids = req.root.children
    for a, b in zip(kids, kids[1:]):
        assert a.pc_end <= b.pc_start
    # duration() = the phase's own segments; elapsed() adds what
    # interrupted it.
    own = sum(s.pc_end - s.pc_start for s in segs)
    assert plan.duration() == pytest.approx(own)
    assert plan.elapsed() == pytest.approx(
        own + d.duration() + kids[3].pc_end - kids[3].pc_start)
    assert d.duration() >= 0.003
    assert plan.duration() < plan.elapsed()


def test_dotted_child_nests_inside_its_phase():
    rec = TimelineRecorder()
    req = rec.begin(None)
    with rec.span(req, "plan", phase=True):
        with rec.span(req, "plan.lower", entries=3):
            pass
        with rec.span(req, "plan.verify"):
            pass
    with rec.span(req, "finish", phase=True):
        with rec.span(req, "finish", phase=True):   # a phase interrupts itself
            pass
    rec.finish(req)
    assert _names(req.root) == ["plan", "finish", "finish", "finish"]
    assert _names(req.root.children[0]) == ["plan.lower", "plan.verify"]


def test_add_records_cross_thread_interval_and_link():
    rec = TimelineRecorder()
    flush = rec.begin(None, name="coalescer.flush", kind="flush", batch=2)
    rec.finish(flush)
    req = rec.begin(None)
    t0 = req.root.pc_start
    rec.add(req, "coalescer.wait", t0, t0 + 0.25, reason="window")
    rec.add(req, "coalescer.flush", flush.root.pc_start,
            flush.root.pc_end, link=flush.root)
    rec.add(None, "x", 0.0, 1.0)                  # no record: no-op
    wait, ref = req.root.children
    assert wait.pc_end - wait.pc_start == pytest.approx(0.25)
    assert wait.attrs == {"reason": "window"} and wait.link is None
    assert ref.link is flush.root
    assert (ref.pc_start, ref.pc_end) == (flush.root.pc_start,
                                          flush.root.pc_end)
    assert wait.tid == ref.tid == req.root.tid


def test_unaccounted_is_root_minus_union_of_children():
    assert union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert union_seconds([]) == 0.0
    rec = TimelineRecorder()
    req = rec.begin(None)
    t0 = req.root.pc_start
    rec.add(req, "a", t0 + 0.0, t0 + 0.010)
    rec.add(req, "b", t0 + 0.005, t0 + 0.020)     # overlaps a
    rec.add(req, "c", t0 + 0.030, t0 + 0.040)
    req.root.close(pc_end=t0 + 0.050)
    req.root.pc_end = None                        # let finish() close it
    real = time.perf_counter
    try:
        time.perf_counter = lambda: t0 + 0.050
        rec.finish(req)
    finally:
        time.perf_counter = real
    assert req.unaccounted == pytest.approx(0.050 - 0.030)


def test_disabled_begin_is_none_and_span_is_a_bare_clock():
    rec = TimelineRecorder()
    rec.enabled = False
    assert rec.begin("t" * 32) is None
    with rec.span(None, "plan", phase=True) as s:
        time.sleep(0.001)
    assert s.span is None and s.duration() >= 0.001
    assert s.elapsed() == s.duration()
    s.set("k", 1)                                 # no-op, no error
    rec.finish(None)
    assert rec.ring_count() == 0
    with rec.stage("plan", phase=True) as s2:                 # nothing attached
        pass
    assert s2.span is None


def test_attached_record_is_what_stage_writes_to():
    rec = TimelineRecorder()
    req = rec.begin(None)
    assert rec.current() is None
    with rec.attached(req):
        assert rec.current() is req
        with rec.stage("plan", phase=True):
            pass
        with rec.attached(None):
            assert rec.current() is None
        assert rec.current() is req
    assert rec.current() is None
    assert _names(req.root) == ["plan"]


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


def test_span_cap_counts_drops():
    stats = MemStatsClient()
    rec = TimelineRecorder()
    req = rec.begin("b" * 32, stats=stats)
    for _ in range(rec.MAX_EVENTS_PER_REQUEST + 10):
        with rec.span(req, "plan.stage"):
            pass
    assert req.n_spans == rec.MAX_EVENTS_PER_REQUEST
    assert len(req.root.children) == rec.MAX_EVENTS_PER_REQUEST - 1
    assert req.dropped == 11
    rec.add(req, "late", 0.0, 1.0)
    assert req.dropped == 12
    rec.finish(req)
    rec.finish(req)                               # twice: once counted
    assert rec.ring_count() == 1
    assert stats.snapshot()["counters"]["request.spans_dropped"] == 12


# ------------------------------------------- cumulative stage histograms


def _histo(stats, key):
    return stats.snapshot()["histograms"][key]


def test_stage_histograms_are_cumulative_and_equal_the_spans():
    """Each finished record adds ONE observation per stage name (the
    sum of that name's spans); sums and counts only grow, and equal
    what the ring's records hold."""
    stats = MemStatsClient()
    rec = TimelineRecorder()
    want = {}
    totals = unacc = 0.0
    for n in range(3):
        req = rec.begin(None, stats=stats)
        with rec.span(req, "plan", phase=True):
            with rec.span(req, "plan.stage"):
                pass
            with rec.span(req, "dispatch"):
                time.sleep(0.001)
        with rec.span(req, "finish", phase=True):
            pass
        rec.finish(req)
        for sp in req.root.walk():
            if sp is not req.root:
                want[sp.name] = want.get(sp.name, 0.0) \
                    + sp.pc_end - sp.pc_start
        totals += req.root.pc_end - req.root.pc_start
        unacc += req.unaccounted
        for name, secs in want.items():
            h = _histo(stats, f"request.stage_seconds{{stage:{name}}}")
            assert h["count"] == n + 1          # one per record, not
            assert h["sum"] == pytest.approx(secs)   # one per segment
        assert _histo(stats, "request.total_seconds")["count"] == n + 1
    assert set(want) == {"plan", "plan.stage", "dispatch", "finish"}
    assert _histo(stats, "request.total_seconds")["sum"] == \
        pytest.approx(totals)
    assert _histo(stats, "request.unaccounted_seconds")["sum"] == \
        pytest.approx(unacc)
    # Top-level stage sums + unaccounted = total (the stages tile).
    top = sum(want[n] for n in ("plan", "dispatch", "finish"))
    assert top + unacc == pytest.approx(totals, rel=1e-6)
    # Bounds reach down to 2^-17 s.
    h = _histo(stats, "request.stage_seconds{stage:plan.stage}")
    assert list(h["buckets"])[0] == repr(2.0 ** -17)
    assert len(STAGE_BUCKETS) == 22 and STAGE_BUCKETS[-1] == 16.0


def test_flush_record_feeds_flush_histograms_and_member_suffix():
    stats = MemStatsClient()
    rec = TimelineRecorder()
    flush = rec.begin(None, stats=stats, name="coalescer.flush",
                      kind="flush", batch=2)
    with rec.span(flush, "plan", phase=True):
        pass
    rec.finish(flush)
    for _ in range(2):
        req = rec.begin(None, stats=stats)
        rec.add(req, "coalescer.flush", flush.root.pc_start,
                flush.root.pc_end, link=flush.root)
        rec.finish(req)
    h = stats.snapshot()["histograms"]
    # The flush itself: one observation; its riders under .member.
    own = h["request.stage_seconds{stage:coalescer.flush}"]
    assert own["count"] == 1
    assert own["sum"] == pytest.approx(flush.root.duration())
    assert h["request.stage_seconds{stage:coalescer.flush.member}"][
        "count"] == 2
    assert h["flush.unaccounted_seconds"]["count"] == 1
    # A flush is not a request.
    assert h["request.total_seconds"]["count"] == 2
    assert h["request.stage_seconds{stage:plan}"]["count"] == 1


def test_span_counts_reach_the_stats_with_the_record():
    """A span's `counts` are the opener's names, not the recorder's:
    they ride the record and land in its one stats batch."""
    stats = MemStatsClient()
    rec = TimelineRecorder()
    req = rec.begin(None, stats=stats)
    with rec.span(req, "h2d", bytes=100,
                  counts=(("x.up_bytes", 100), ("x.ups", 2))):
        pass
    with rec.span(req, "h2d", bytes=20,
                  counts=(("x.up_bytes", 20), ("x.ups", 1))):
        pass
    with rec.span(None, "h2d", counts=(("x.ups", 9),)):   # no record
        pass
    assert "x.ups" not in stats.snapshot()["counters"]    # not yet
    rec.finish(req)
    c = stats.snapshot()["counters"]
    assert c["x.up_bytes"] == 120 and c["x.ups"] == 3
    assert "request.spans_dropped" not in c


def test_transfer_stages_feed_byte_and_transfer_counters():
    """profile.transfer: the executor's `h2d` / `d2h` stage, with its
    bytes and transfers as attrs and as `executor.*` counters."""
    from pilosa_tpu.utils.profile import transfer
    stats = MemStatsClient()
    req = TIMELINE.begin(None, stats=stats)
    with TIMELINE.attached(req):
        with TIMELINE.phase("plan"):
            with transfer("h2d", 100, 2):
                pass
            with transfer("h2d", 20):
                pass
        with transfer("d2h", 4096, 3):
            pass
    with transfer("d2h", 7):                    # nothing attached
        pass
    TIMELINE.finish(req)
    assert _names(req.root) == ["plan", "h2d", "plan", "h2d", "plan",
                                "d2h"]
    assert req.root.children[-1].attrs == {"bytes": 4096, "transfers": 3}
    c = stats.snapshot()["counters"]
    assert c["executor.h2d_bytes"] == 120
    assert c["executor.h2d_transfers"] == 3
    assert c["executor.d2h_bytes"] == 4096
    assert c["executor.d2h_transfers"] == 3


def test_stats_lock_taken_once_per_record_not_per_span():
    calls = []

    class _Stats(MemStatsClient):
        def batch(self, histograms=(), counts=()):
            calls.append((len(histograms), len(counts)))
            super().batch(histograms, counts)

        def histogram(self, *a, **k):
            raise AssertionError("per-span stats call")

        count = timing = gauge = histogram

    rec = TimelineRecorder()
    req = rec.begin(None, stats=_Stats())
    with rec.attached(req, "thread.batch"):
        for name in ("http.read", "pql.parse", "plan", "dispatch", "d2h",
                     "finish", "http.serialize", "http.write"):
            with rec.span(req, name):
                pass
    rec.finish(req)
    # 8 stages + the thread's section, wall and CPU seconds, + total +
    # unaccounted: once
    assert calls == [(12, 0)]


# ------------------------------------------------------ export shape


def test_snapshot_chrome_trace_event_shape():
    rec = TimelineRecorder()
    req = rec.begin("c" * 32, index="i1", calls="Count")
    with rec.span(req, "plan", phase=True):
        with rec.span(req, "plan.stage"):
            pass
        with rec.span(req, "dispatch", program="tree_count",
                      jit="hit") as d:
            time.sleep(0.004)
    rec.finish(req)
    doc = rec.snapshot(node_id="node-a")
    evs = doc["traceEvents"]
    # Every event — metadata included — carries the full shape.
    for ev in evs:
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in ev, ev
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["name"] for e in xs] == \
        ["request", "plan", "plan.stage", "dispatch", "plan"]
    disp = next(e for e in xs if e["name"] == "dispatch")
    assert disp["dur"] == pytest.approx(d.duration() * 1e6)
    assert disp["args"]["trace"] == "c" * 32
    assert disp["args"]["program"] == "tree_count"
    # Each span knows the span that caused it.
    root = xs[0]
    assert disp["args"]["parent"] == "request"
    assert disp["args"]["parentSpanId"] == root["args"]["spanId"]
    stage = next(e for e in xs if e["name"] == "plan.stage")
    assert stage["args"]["parent"] == "plan"
    assert "parent" not in root["args"]
    assert root["args"]["index"] == "i1"
    assert root["args"]["calls"] == "Count"
    assert root["args"]["kind"] == "request"
    assert root["args"]["unaccountedS"] == req.unaccounted
    # ts is wall-anchored: within the request's wall window.
    assert abs(disp["ts"] / 1e6 - req.root.start) < 1.0
    # All on the opening thread's lane, which the metadata names.
    assert {e["tid"] for e in xs} == {req.root.tid}
    metas = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} == {e["name"] for e in metas}
    assert any(e["args"]["name"] == "node-a" for e in metas)
    assert any(e["name"] == "thread_name" and e["tid"] == req.root.tid
               and e["args"]["name"] == threading.current_thread().name
               for e in metas)
    summary = doc["summary"]
    assert summary["requests"] == 1
    assert "deviceIdleRatio" not in summary
    assert "dispatchGap" not in summary
    assert summary["stageMedianS"]["dispatch"] == \
        pytest.approx(d.duration())
    by_call = summary["byCall"]["Count"]
    assert by_call["requests"] == 1
    assert by_call["stageMeanS"]["dispatch"] == \
        pytest.approx(d.duration())
    assert by_call["meanS"] == pytest.approx(req.root.duration())


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
    """One wall-clock read per record: an NTP step AFTER begin() must
    not move any event timestamp or duration (they are perf_counter
    offsets from the root's anchor)."""
    rec = TimelineRecorder()
    real_time = time.time
    wall = [real_time()]
    monkeypatch.setattr(time, "time", lambda: wall[0])
    req = rec.begin("d" * 32)
    t = req.root.pc_start
    rec.add(req, "plan", t + 0.010, t + 0.015)
    wall[0] += 3600.0  # the clock steps one hour mid-request
    rec.add(req, "dispatch", t + 0.020, t + 0.025)
    rec.finish(req)
    xs = {e["name"]: e for e in rec.snapshot()["traceEvents"]
          if e["ph"] == "X"}
    anchor_us = req.root.start * 1e6
    assert xs["plan"]["ts"] == pytest.approx(anchor_us + 10_000, abs=1)
    # The post-step event still exports 10ms later, not an hour later.
    assert xs["dispatch"]["ts"] - xs["plan"]["ts"] == \
        pytest.approx(10_000, abs=1)
    assert xs["request"]["dur"] < 1e6  # the request did not "take" 1h


# -------------------------------------------- on the profiler's clock


def test_annotation_factory_brackets_every_span():
    """The injected factory is entered and exited around every span,
    segment by segment, on the span's own thread, named
    pilosa:<stage>."""
    log = []

    class _Ann:  # what jax.profiler.TraceAnnotation looks like
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    rec = TimelineRecorder()
    rec.annotation = _Ann
    req = rec.begin(None)
    with rec.span(req, "plan", phase=True):
        with rec.span(req, "dispatch"):
            pass
    rec.add(req, "coalescer.wait", 0.0, 1.0)     # no thread: no event
    rec.finish(req)
    assert log == [("enter", "pilosa:plan"), ("exit", "pilosa:plan"),
                   ("enter", "pilosa:dispatch"),
                   ("exit", "pilosa:dispatch"),
                   ("enter", "pilosa:plan"), ("exit", "pilosa:plan")]
    # attached() puts the root on the thread's line as well.
    del log[:]
    with rec.attached(req):
        with rec.stage("finish", phase=True):
            pass
    assert log == [("enter", "pilosa:request"),
                   ("enter", "pilosa:finish"), ("exit", "pilosa:finish"),
                   ("exit", "pilosa:request")]


def test_profiler_session_holds_nested_pilosa_events(tmp_holder, tmp_path):
    """Under a jax.profiler session on the CPU backend one request's
    spans are `pilosa:` events on its thread's line of the host plane,
    nested as the spans are."""
    import glob

    import jax
    from jax.profiler import ProfileData

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.executor.result_cache.enabled = False
    api.query("tl", "Count(Row(f=1))")            # compile outside
    TIMELINE.annotation = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        api.query("tl", "Count(Row(f=1))")
    finally:
        jax.profiler.stop_trace()
    TIMELINE.annotation = None
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("pilosa:")]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines]
    (events,) = [evs for evs in lines if evs]     # one thread's line
    # The root is there too, around everything the executor did.
    (root_ev,) = [e for e in events if e[0] == "pilosa:request"]
    events = [e for e in events if e is not root_ev]
    assert all(root_ev[1] <= s and e <= root_ev[2] for n, s, e in events
               if n != "pilosa:pql.parse")
    rec = TIMELINE.requests(last=1)[0]
    spans = [sp for sp in rec.root.walk() if sp is not rec.root]
    assert [n for n, _, _ in sorted(events, key=lambda e: e[1])] == \
        ["pilosa:" + sp.name
         for sp in sorted(spans, key=lambda sp: sp.pc_start)]
    # Nested as the spans are: plan.stage inside the plan segment.
    by = {}
    for n, s, e in events:
        by.setdefault(n, []).append((s, e))
    (s0, e0), = by["pilosa:plan.stage"]
    assert any(s <= s0 and e0 <= e for s, e in by["pilosa:plan"])
    # ... and the dispatch annotation brackets JAX's own launch event.
    launches = [(e.start_ns, e.start_ns + e.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name.startswith("PjitFunction(tree_count)")]
    (d0, d1), = by["pilosa:dispatch"]
    assert any(d0 <= s and e <= d1 for s, e in launches)


# ------------------------------------------------------- live wiring


def test_query_records_stage_spans(tmp_holder):
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.query("tl", "Count(Row(f=1))")
    (rec,) = TIMELINE.requests()
    names = _dedup(_names(rec.root))
    assert [n for n in names if n != "h2d"] == \
        ["pql.parse", "cache.lookup", "plan", "dispatch", "d2h", "finish"]
    assert "device" not in names  # unsampled: no device span
    assert rec.root.attrs["calls"] == "Count"
    (disp,) = [c for c in rec.root.children if c.name == "dispatch"]
    assert disp.attrs["program"] == "tree_count"
    assert disp.attrs["jit"] == "miss" and "count|" in disp.attrs["key"]
    (d2h,) = [c for c in rec.root.children if c.name == "d2h"]
    assert d2h.attrs == {"bytes": 8, "transfers": 1}
    _assert_inside(rec.root)
    # A repeat: the program is cached, the result too.
    api.query("tl", "Count(Row(f=1))")
    again = TIMELINE.requests()[-1]
    assert _dedup(_names(again.root)) == ["pql.parse", "cache.lookup"]
    assert again.root.children[1].attrs == {"hit": True}


def test_profile_stage_seconds_are_the_spans_readings(tmp_holder):
    """One pair of clock reads per boundary: what the profile tree
    reports IS what the record's spans measured."""
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    resp = api.query("tl", "Count(Row(f=1))", profile=True)
    rec = TIMELINE.requests()[-1]
    spans = list(rec.root.walk())

    def total(name):
        return sum(s.pc_end - s.pc_start for s in spans
                   if s.name == name)

    (op,) = resp["profile"]["ops"]
    (ev,) = op["children"]
    assert ev["planS"] == pytest.approx(total("plan.stage"))
    assert ev["dispatchS"] == pytest.approx(total("dispatch"))
    assert op["materializeS"] == pytest.approx(
        total("d2h") + sum(s.pc_end - s.pc_start for s in spans
                           if s.name == "finish"
                           and s.attrs.get("op") == "Count"))
    # The op's dispatch time: its plan segments plus what interrupted
    # them (h2d, dispatch, the sampled device fence, cache lookups).
    assert op["dispatchS"] == pytest.approx(
        total("plan") - sum(s.pc_end - s.pc_start for s in spans
                            if s.name == "plan" and not s.attrs)
        + total("h2d") + total("dispatch") + total("device")
        + sum(s.pc_end - s.pc_start for s in spans
              if s.name == "cache.lookup" and "tier" in s.attrs))


@pytest.mark.parametrize("pql,program", [
    ("TopN(f, Row(f=1), n=2)", "topn_sweep"),
    ("Sum(field=v)", "bsi_sum"),
    ("GroupBy(Rows(f), Rows(g))", "groupby"),
    ("Row(f=1)", "tree_row"),
])
def test_every_upload_and_fetch_of_a_query_is_a_transfer_stage(
        tmp_holder, pql, program):
    """Whatever the call family, the operand vectors it uploads are
    `h2d` spans and the blocking fetches `d2h` spans, and the byte
    counters are the spans' sums — not only the tree path's."""
    idx = _seed(tmp_holder)
    cols = np.array([1, 2, SHARD_WIDTH + 3], np.uint64)
    idx.create_field("g").import_bits(np.full(3, 7, np.uint64), cols)
    stats = MemStatsClient()
    api = API(tmp_holder, stats=stats)
    api.create_field("tl", "v", {"type": "int", "min": 0, "max": 100})
    api.import_values("tl", "v", cols.tolist(), [5, 6, 7])
    api.executor.result_cache.enabled = False
    api.query("tl", pql)
    rec = TIMELINE.requests()[-1]
    spans = list(rec.root.walk())
    assert program in {s.attrs.get("program") for s in spans}
    up = [s for s in spans if s.name == "h2d"]
    down = [s for s in spans if s.name == "d2h"]
    assert up and down, _names(rec.root)
    # (An empty params vector is a put of 0 bytes: still a transfer.)
    assert sum(s.attrs["bytes"] for s in up) > 0
    assert all(s.attrs["bytes"] > 0 for s in down)
    c = stats.snapshot()["counters"]
    assert c["executor.h2d_bytes"] == sum(s.attrs["bytes"] for s in up)
    assert c["executor.h2d_transfers"] == sum(
        s.attrs["transfers"] for s in up)
    assert c["executor.d2h_bytes"] == sum(s.attrs["bytes"] for s in down)


def test_row_words_are_fetched_only_when_columns_are_read(tmp_holder):
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.executor.result_cache.enabled = False
    api.query("tl", "Options(Row(f=1), excludeColumns=true)")
    assert "d2h" not in {s.name
                         for s in TIMELINE.requests()[-1].root.walk()}
    assert api.query("tl", "Row(f=1)")["results"][0]["columns"] == \
        [1, 2, SHARD_WIDTH + 3]
    (d2h,) = [s for s in TIMELINE.requests()[-1].root.walk()
              if s.name == "d2h"]
    assert d2h.attrs["transfers"] == 1 and d2h.attrs["bytes"] > 0


def test_profiled_query_gains_device_span(tmp_holder):
    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    api.query("tl", "Count(Row(f=1))", profile=True)
    names = _names(TIMELINE.requests()[-1].root)
    assert "device" in names  # rides the profiler's sampled fence


def test_zero_new_fences_on_unsampled_path(tmp_holder, monkeypatch):
    """Acceptance: the request record adds NO block_until_ready fences
    on the unsampled hot path — clock readings of host-side events
    only (same bar as PR 3's profiler and PR 6's recorder)."""
    import pilosa_tpu.executor.executor as ex

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient())
    # Every repeat must DISPATCH; the result cache would serve 6 of
    # the 8 without any device work.
    api.executor.result_cache.enabled = False
    fences = []
    monkeypatch.setattr(ex, "_fence_device",
                        lambda out: fences.append(1) or 0.0)
    for i in range(8):
        api.query("tl", f"Count(Row(f={i % 2}))")
    assert fences == []
    # ...and it recorded the full stage set while staying fence-free.
    assert TIMELINE.requests_recorded == 8
    assert sum(1 for r in TIMELINE.requests()
               for c in r.root.children if c.name == "dispatch") >= 8


def test_timeline_disabled_records_nothing(tmp_holder):
    _seed(tmp_holder)
    TIMELINE.configure(enabled=False)
    stats = MemStatsClient()
    api = API(tmp_holder, stats=stats)
    resp = api.query("tl", "Count(Row(f=1))", profile=True)
    assert TIMELINE.requests_recorded == 0
    assert not any(k.startswith("request.")
                   for k in stats.snapshot()["histograms"])
    # The profile still gets its seconds: a bare clock stands in.
    assert resp["profile"]["totals"]["dispatchS"] > 0


def test_embedded_queries_get_distinct_trace_ids(tmp_holder):
    """Review regression: library (non-HTTP) callers have no per-
    request extract() reset, so the minted trace id must be dropped at
    request end — N queries on one thread are N traces, not one."""
    from pilosa_tpu.utils.tracing import ContextTracer

    _seed(tmp_holder)
    tracer = ContextTracer()
    api = API(tmp_holder, stats=MemStatsClient(), tracer=tracer)
    api.query("tl", "Count(Row(f=1))")
    api.query("tl", "Count(Row(f=1))")
    assert len({r.trace_id for r in TIMELINE.requests()}) == 2
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
    from pilosa_tpu.utils.tracing import ContextTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=ContextTracer())
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
    from pilosa_tpu.utils.tracing import ContextTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=ContextTracer())
    # These tests assert plan/dispatch/d2h/finish spans on repeated
    # queries; the result cache would answer the repeats from
    # `cache.lookup` alone. Cache-ON attribution is pinned in
    # tests/test_result_cache.py.
    api.executor.result_cache.enabled = False
    api.coalescer = QueryCoalescer(api.executor, window_s=0.0005,
                                   stats=api.stats)
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


def _post(base, pql):
    return json.loads(urllib.request.urlopen(
        base + "/index/tl/query", data=pql.encode(), timeout=60).read())


def _wait_recorded(n):
    """The record closes in the handler's finally block, AFTER the
    response body went out — the client can get here first."""
    for _ in range(400):
        if TIMELINE.requests_recorded >= n:
            return
        time.sleep(0.005)
    raise AssertionError(f"{TIMELINE.requests_recorded} < {n} records")


def test_http_direct_request_tiles(live_api):
    """A lone query through the HTTP handler (a single-item flush is
    the direct path): ONE record whose children are the stages in
    order, each inside its parent, and little left unaccounted."""
    api, base = live_api
    for _ in range(3):                            # warm: compile, pools
        _post(base, "Count(Row(f=1))")
    _wait_recorded(3)
    TIMELINE.reset()
    shares = []
    for i in range(30):
        assert _post(base, "Count(Row(f=1))") == {"results": [3]}
        _wait_recorded(i + 1)
        (rec,) = TIMELINE.requests(last=1)
        assert rec.kind == "request"
        names = [n for n in _dedup(_names(rec.root)) if n != "h2d"]
        assert names == DIRECT_STAGES, names
        _assert_inside(rec.root)
        kids = rec.root.children
        # On every thread the top-level stages follow one another.
        for a, b in zip(kids, kids[1:]):
            assert a.pc_start <= b.pc_start
        total = rec.root.pc_end - rec.root.pc_start
        assert rec.unaccounted == pytest.approx(total - union_seconds(
            [(c.pc_start, c.pc_end) for c in kids]))
        shares.append(rec.unaccounted / total)
        wait = next(c for c in kids if c.name == "coalescer.wait")
        assert wait.attrs["batch"] == 1
        read = next(c for c in kids if c.name == "http.read")
        assert read.attrs["bytes"] == len("Count(Row(f=1))")
    # On a quiet CPU backend well under 15 % (thread hand-offs between
    # the request's thread and the dispatcher's are what is left; the
    # best of 30 so that a loaded test machine's scheduler is not what
    # the test measures).
    assert min(shares) < 0.15, shares
    assert TIMELINE.ring_count() == 30            # one record each


def test_http_coalesced_request_tiles(tmp_holder):
    """Concurrent queries share a flush: the flush is a record of its
    own whose children are plan ... finish, once; every member holds
    its wait and a reference to the flush over the same interval."""
    from pilosa_tpu.server import serve
    from pilosa_tpu.server.coalescer import QueryCoalescer

    _seed(tmp_holder)
    stats = MemStatsClient()
    api = API(tmp_holder, stats=stats)
    api.executor.result_cache.enabled = False
    api.coalescer = QueryCoalescer(api.executor, window_s=0.25,
                                   max_batch=4, stats=stats)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    flush_shares = []
    # The test holds a request of its own: the first of four to arrive
    # is then not alone in the server, and waits for its batch-mates.
    api.held.open()
    try:
        for warm in range(4):
            TIMELINE.reset()
            out = [None] * 4
            ts = [threading.Thread(
                target=lambda i=i: out.__setitem__(
                    i, _post(base, f"Count(Row(f={i % 2}))")))
                for i in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert out == [{"results": [0]}, {"results": [3]}] * 2
            _wait_recorded(5)                     # 4 requests + 1 flush
            flush_shares += [r.unaccounted / r.root.duration()
                             for r in TIMELINE.requests()
                             if r.kind == "flush"]
    finally:
        api.held.close()
        srv.shutdown()
        srv.server_close()
        api.coalescer.stop()
    recs = TIMELINE.requests()
    (flush,) = [r for r in recs if r.kind == "flush"]
    members = [r for r in recs if r.kind == "request"]
    assert len(members) == 4
    assert flush.root.name == "coalescer.flush"
    assert flush.root.attrs["batch"] == 4
    assert flush.root.attrs["unique"] == 2        # identical reads dedup
    assert flush.root.attrs["reason"] == "size"
    assert [n for n in _dedup(_names(flush.root))
            if n not in ("h2d", "coalescer.handoff")] == FLUSH_STAGES
    _assert_inside(flush.root)
    # A 2 ms flush on the CPU backend: the best of four, so that a
    # loaded test machine's scheduler is not what the test measures.
    assert len(flush_shares) == 4 and min(flush_shares) < 0.15, \
        flush_shares
    for m in members:
        assert _dedup(_names(m.root)) == MEMBER_STAGES
        _assert_inside(m.root)
        ref = next(c for c in m.root.children
                   if c.name == "coalescer.flush")
        assert ref.link is flush.root
        assert (ref.pc_start, ref.pc_end) == (flush.root.pc_start,
                                              flush.root.pc_end)
        wait = next(c for c in m.root.children
                    if c.name == "coalescer.wait")
        assert wait.pc_end == flush.root.pc_start
        assert wait.attrs == {"batch": 4, "reason": "size"}
    # What a member cannot account for is its thread being woken (four
    # of them at once here, on a 2 ms request): small for the luckiest.
    assert min(m.unaccounted / m.root.duration() for m in members) < 0.15
    # Once per flush in the histograms, once per rider under .member.
    h = stats.snapshot()["histograms"]
    assert h["request.stage_seconds{stage:coalescer.flush.member}"][
        "count"] == 16
    assert h["request.stage_seconds{stage:coalescer.flush}"]["count"] == 4
    assert h["request.stage_seconds{stage:plan}"]["count"] == 4
    assert h["request.stage_seconds{stage:coalescer.wait}"]["count"] == 16
    # The flush's two halves, each a section of the thread that ran it
    # (dispatcher, finalizer) with that thread's readings; the members,
    # whose threads were parked, have none.
    assert [sp.name for sp in flush.sections] == ["thread.begin",
                                                  "thread.finish"]
    begin, fin = flush.sections
    assert begin.tid != fin.tid
    assert begin.pc_end <= fin.pc_start
    for sp in flush.sections:
        assert 0.0 <= sp.cpu <= sp.duration() + 1e-3
        assert flush.root.pc_start <= sp.pc_start <= sp.pc_end \
            <= flush.root.pc_end
        assert h[f"request.stage_cpu_seconds{{stage:{sp.name}}}"][
            "count"] == 4
        assert h[f"request.stage_seconds{{stage:{sp.name}}}"]["count"] == 4
    assert all(m.sections == [] for m in members)


def test_debug_timeline_http_surface(live_api):
    api, base = live_api
    for i in range(12):
        assert "results" in _post(base, f"Count(Row(f={i % 3}))")
    _wait_recorded(12)
    doc = _get(base, "/debug/timeline?last=6")
    for ev in doc["traceEvents"]:
        for k in ("ph", "ts", "dur", "pid", "tid"):
            assert k in ev, ev
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert set(DIRECT_STAGES) | {"request"} <= names
    s = doc["summary"]
    assert s["requests"] == 6
    assert s["stageMedianS"]["dispatch"] > 0
    assert s["byCall"]["Count"]["requests"] == 6
    assert "deviceIdleRatio" not in s and "dispatchGap" not in s
    # A request's stages ran on two threads: its own and the
    # dispatcher's — two lanes, both named.
    tid = next(e["args"]["trace"] for e in xs if e["name"] == "request")
    one = _get(base, f"/debug/timeline?trace={tid}")
    assert one["summary"]["requests"] == 1
    lanes = {e["tid"] for e in one["traceEvents"] if e["ph"] == "X"}
    assert len(lanes) == 2
    named = {e["tid"]: e["args"]["name"] for e in one["traceEvents"]
             if e["name"] == "thread_name"}
    assert lanes <= set(named)
    assert "query-coalescer" in named.values()
    # ?trace= narrows to one request; the single-node /cluster/timeline
    # wraps the same events with node attribution.
    merged = _get(base, f"/cluster/timeline/{tid}")
    assert merged["respondedNodes"] == merged["totalNodes"] == 1
    mx = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert mx and all(e["args"]["node"] for e in mx)
    # The host-clock idle gauge is gone from every surface.
    met = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "device_idle_ratio" not in met
    assert 'pilosa_request_stage_seconds_bucket{stage="plan",' in met
    assert "deviceIdleRatio" not in json.dumps(
        _get(base, "/internal/health"))


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
    _post(base, "Count(Row(f=1))")
    _wait_recorded(1)
    mem = _get(base, "/debug/memory")
    tel = mem["categories"].get("telemetry")
    assert tel is not None and tel["bytes"] > 0
    # The one per-request ring: the process-wide timeline's (there
    # is no tracer ring beside it any more).
    assert "tracer_ring" not in json.dumps(mem)
    assert TIMELINE.ring_nbytes() > 0
    # Telemetry is host RAM: counted in totalBytes, not deviceBytes.
    assert mem["totalBytes"] == sum(
        c["bytes"] for c in mem["categories"].values())
    assert mem["deviceBytes"] <= mem["totalBytes"] - tel["bytes"]


def test_dump_and_drain(tmp_holder):
    """drain_telemetry writes the last request records to the log on
    shutdown (the SIGTERM post-mortem path)."""
    from pilosa_tpu.cli.main import drain_telemetry
    from pilosa_tpu.utils.tracing import ContextTracer

    _seed(tmp_holder)
    api = API(tmp_holder, stats=MemStatsClient(),
              tracer=ContextTracer())
    api.query("tl", "Count(Row(f=1))")

    lines = []

    class _Log:
        def printf(self, fmt, *args):
            lines.append(fmt % args if args else fmt)

    drain_telemetry(api, logger=_Log())
    rows = [ln for ln in lines if ln.startswith("timeline: request")]
    assert len(rows) == 1, lines
    assert "plan=" in rows[0] and "dispatch=" in rows[0]
    # ... and the longest kept beside the ring.
    slow = [ln for ln in lines if ln.startswith("timeline: slowest request")]
    assert len(slow) == 1 and "plan=" in slow[0], lines


def test_config_timeline_keys(tmp_path):
    from pilosa_tpu.utils.config import Config, load_config
    p = tmp_path / "c.toml"
    p.write_text("[timeline]\nenabled = false\nring = 64\n"
                 "sample_every = 4\n")
    cfg = load_config(str(p))
    assert cfg.timeline_enabled is False
    assert cfg.timeline_ring == 64
    assert cfg.timeline_sample_every == 4
    # The gap analyzer's window went with it.
    assert not hasattr(Config(), "timeline_gap_window_s")
    with pytest.raises(ValueError):
        load_config(None, {"timeline_ring": 0})


# ------------------------------------ whether a record's threads ran

def _thread_clock_step():
    """The step of this kernel's thread CPU clock, probed once: under a
    microsecond on a plain Linux, 10 ms under a sandboxed kernel that
    accounts CPU time by the scheduler tick (gVisor: the benchmark's
    machines). The smallest step seen in 50 ms of reading it; the whole
    50 ms where it never moved."""
    steps = []
    last = time.thread_time()
    deadline = time.perf_counter() + 0.05
    while time.perf_counter() < deadline:
        t = time.thread_time()
        if t != last:
            steps.append(t - last)
            last = t
    return min(steps, default=0.05)


# What a reading of `cpu` may be off by at each of a section's ends.
_TICK = _thread_clock_step()


def _spin(seconds):
    """Burn `seconds` of this thread's CPU (not of the wall clock: a
    loaded test machine takes the CPU away meanwhile) — by the clock
    the sections read, so at least its next step."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _section(req, name):
    return next(sp for sp in req.sections if sp.name == name)


def test_sleeping_section_reads_little_cpu():
    rec = TimelineRecorder()
    req = rec.begin(None)
    with rec.attached(req, "thread.finish"):
        with rec.stage("d2h"):
            time.sleep(0.05)
    sp = _section(req, "thread.finish")
    assert sp.duration() >= 0.05
    assert 0.0 <= sp.cpu < 0.010 + _TICK


def test_spinning_section_reads_its_cpu():
    rec = TimelineRecorder()
    req = rec.begin(None)
    with rec.attached(req, "thread.begin"):
        with rec.stage("plan"):
            _spin(0.05)
    sp = _section(req, "thread.begin")
    assert 0.05 <= sp.cpu < 0.06 + _TICK
    assert sp.cpu <= sp.duration() + 1e-3 + _TICK


def test_section_covers_every_stage_its_thread_ran_inside_it():
    rec = TimelineRecorder()
    req = rec.begin(None)
    with rec.attached(req, "thread.begin"):
        with rec.phase("plan") as plan:
            _spin(0.02)
            with rec.stage("dispatch") as d:     # interrupts the phase
                _spin(0.02)
            with rec.stage("d2h"):
                time.sleep(0.03)
            _spin(0.01)
    sp = _section(req, "thread.begin")
    # 50 ms of CPU and a 30 ms sleep: the stages' wall adds up to the
    # section's, the section's CPU leaves the sleep out.
    # (each spin runs on to the clock's next step).
    assert 0.05 <= sp.cpu < 0.065 + 3 * _TICK
    assert sp.duration() >= plan.duration() + d.duration() + 0.03 - 1e-3
    assert sp.duration() >= sp.cpu + 0.03 - 1e-3 - _TICK
    # The stages themselves carry no reading: one pair a thread a
    # record, not one a span.
    assert not any(hasattr(c, "cpu") for c in req.root.walk())
    assert sp not in list(req.root.walk())


def test_stages_and_added_spans_feed_no_cpu_histogram():
    stats = MemStatsClient()
    rec = TimelineRecorder()
    req = rec.begin(None, stats=stats)
    t0 = time.perf_counter()
    rec.add(req, "coalescer.wait", t0, t0 + 0.004)
    with rec.attached(req, "thread.batch"):
        with rec.stage("plan"):
            pass
    rec.finish(req)
    h = stats.snapshot()["histograms"]
    assert h["request.stage_seconds{stage:coalescer.wait}"]["count"] == 1
    assert h["request.stage_seconds{stage:plan}"]["count"] == 1
    assert sorted(k for k in h if k.startswith("request.stage_cpu")) == \
        ["request.stage_cpu_seconds{stage:thread.batch}"]


def test_cpu_histograms_count_as_the_wall_ones_and_never_sum_above():
    stats = MemStatsClient()
    rec = TimelineRecorder()
    for _ in range(5):
        req = rec.begin(None, stats=stats, kind="flush",
                        name="coalescer.flush")
        with rec.attached(req, "thread.begin"):
            with rec.phase("plan"):
                _spin(0.002)
                with rec.stage("dispatch"):
                    _spin(0.001)
        with rec.attached(req, "thread.finish"):
            with rec.stage("d2h"):
                time.sleep(0.004 + 2 * _TICK)
            with rec.phase("finish"):
                _spin(0.001)
        rec.finish(req)
    h = stats.snapshot()["histograms"]
    for section in ("thread.begin", "thread.finish"):
        wall = h[f"request.stage_seconds{{stage:{section}}}"]
        cpu = h[f"request.stage_cpu_seconds{{stage:{section}}}"]
        assert cpu["count"] == wall["count"] == 5
        assert 0.0 <= cpu["sum"] <= wall["sum"] + 1e-3 + 5 * _TICK
    # The half that slept ran for a small share of its wall time.
    assert h["request.stage_cpu_seconds{stage:thread.finish}"]["sum"] < \
        0.5 * h["request.stage_seconds{stage:thread.finish}"]["sum"]
    # ... and the half that spun read the CPU it burnt (5 x 3 ms).
    assert h["request.stage_cpu_seconds{stage:thread.begin}"]["sum"] >= \
        0.015


@pytest.mark.parametrize("how", ["disabled", "unsampled", "unnamed"])
def test_no_section_reads_no_thread_clock(monkeypatch, how):
    from pilosa_tpu.utils import timeline as tl_mod
    calls = []
    real = tl_mod._thread_time
    monkeypatch.setattr(tl_mod, "_thread_time",
                        lambda: calls.append("tt") or real())
    rec = TimelineRecorder()
    if how == "disabled":
        rec.configure(enabled=False)
    elif how == "unsampled":
        rec.configure(sample_every=1000)
    req = rec.begin(None)
    assert (req is None) == (how != "unnamed")
    section = None if how == "unnamed" else "thread.begin"
    with rec.attached(req, section):
        with rec.span(req, "pql.parse"), rec.phase("plan"), \
                rec.stage("dispatch"):
            pass
    rec.finish(req)
    assert calls == []
    # ... and a named section of a record reads it twice, however many
    # stages run inside it.
    rec.configure(enabled=True, sample_every=1)
    req = rec.begin(None)
    with rec.attached(req, "thread.begin"):
        for _ in range(10):
            with rec.stage("dispatch"):
                pass
    assert len(calls) == 2


def test_section_cpu_rides_the_chrome_export_and_the_dump():
    rec = TimelineRecorder()
    req = rec.begin("ab" * 16)
    with rec.attached(req, "thread.begin"):
        with rec.stage("plan"):
            _spin(0.002)
    rec.finish(req)
    evs = {e["name"]: e for e in rec.snapshot()["traceEvents"]
           if e["ph"] == "X"}
    sec, plan = evs["thread.begin"], evs["plan"]
    assert "cpu" not in plan["args"] and "cpu" not in evs["request"]["args"]
    assert sec["args"]["cpu"] > 0.001
    assert sec["args"]["parentSpanId"] == evs["request"]["args"]["spanId"]
    # It lies over the stage its thread ran, on that thread's lane.
    assert sec["tid"] == plan["tid"]
    assert sec["ts"] <= plan["ts"]
    assert sec["ts"] + sec["dur"] >= plan["ts"] + plan["dur"]
    lines = []

    class _Log:
        def printf(self, fmt, *args):
            lines.append(fmt % args)

    rec.dump(_Log(), last=1)
    row = next(ln for ln in lines if ln.startswith("timeline: request"))
    assert "plan=" in row and "thread.begin=" in row
    assert "/" in row.split("thread.begin=")[1]
    assert rec.ring_nbytes() > req.root.nbytes()


# ------------------------------------------ the slowest outlive the ring

def _finish_lasting(rec, seconds, kind="request", **begin):
    req = rec.begin(None, kind=kind, **begin)
    req.root.pc_start -= seconds             # as if begun that long ago
    rec.finish(req)
    return req


def test_slowest_records_outlive_the_ring_eight_a_kind():
    rec = TimelineRecorder(ring=4)
    slow = [_finish_lasting(rec, 2.0 + i) for i in range(3)]
    slow_flush = _finish_lasting(rec, 1.5, kind="flush",
                                 name="coalescer.flush")
    for i in range(40):
        _finish_lasting(rec, 0.001 * (i % 7), kind="request")
        _finish_lasting(rec, 0.001 * (i % 5), kind="flush",
                        name="coalescer.flush")
    assert all(r not in rec.requests() for r in slow + [slow_flush])
    kept = rec.requests(slowest=True)
    assert sum(r.kind == "request" for r in kept) == 8
    assert sum(r.kind == "flush" for r in kept) == 8
    assert set(map(id, slow + [slow_flush])) <= set(map(id, kept))
    assert [r.seq for r in kept] == sorted(r.seq for r in kept)
    doc = rec.snapshot(slowest=True)
    assert doc["summary"]["requests"] == 16
    roots = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and "kind" in e["args"]]
    assert max(e["dur"] for e in roots) == pytest.approx(4.0e6, rel=0.01)
    assert rec.ring_nbytes() > 0
    rec.reset()
    assert rec.requests(slowest=True) == []


def test_full_collection_is_drawn_into_the_record_it_fell_in():
    rec = TimelineRecorder()
    before = _finish_lasting(rec, 0.01)
    req = rec.begin(None)
    t0 = time.perf_counter()
    rec.gc_pauses.append((t0, t0 + 0.25))     # the pause is inside it
    time.sleep(0.001)
    rec.finish(req)
    after = _finish_lasting(rec, 0.0)
    evs = [e for e in rec.snapshot()["traceEvents"] if e["name"] == "gc"]
    assert len(evs) == 1                     # once, however many overlap
    assert evs[0]["dur"] == pytest.approx(0.25e6)
    assert evs[0]["args"] == {"gen": 2}
    assert evs[0]["ts"] == pytest.approx(req.root.start * 1e6, abs=5e3)
    only = [e for e in rec._export_events([before], 0) if e["name"] == "gc"]
    assert only == [] and after is not None


def test_debug_timeline_slowest_http_surface(live_api):
    api, base = live_api
    TIMELINE.configure(ring=4)
    for i in range(12):
        assert "results" in _post(base, f"Count(Row(f={i % 3}))")
    _wait_recorded(12)
    assert len(TIMELINE.requests()) == 4
    doc = _get(base, "/debug/timeline?slowest=1")
    assert doc["summary"]["requests"] == 8
    assert any(e["name"] == "plan" for e in doc["traceEvents"])
    longest = max(r.root.duration() for r in TIMELINE.requests(slowest=True))
    assert max(e["dur"] for e in doc["traceEvents"]
               if e["name"] == "request") == pytest.approx(longest * 1e6)
    with pytest.raises(urllib.error.HTTPError):
        _get(base, "/debug/timeline?slow=1")
