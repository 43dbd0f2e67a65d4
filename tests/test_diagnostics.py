"""Diagnostics (utils/diagnostics.py) and slow-query logging tests."""

import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from pilosa_tpu.utils.diagnostics import DiagnosticsCollector, RuntimeMonitor
from pilosa_tpu.utils.stats import MemStatsClient
# The step of the thread CPU clock that times the collector: 10 ms under
# a sandboxed kernel (gVisor, as on the benchmark's machines), where a
# short collection reads 0 — lengths are held above 0 only below 1 ms.
from tests.test_timeline import _TICK


def test_disabled_by_default():
    d = DiagnosticsCollector()
    assert not d.enabled()
    assert d.flush() is False  # no URL → never POSTs


def test_payload_shape(tmp_path):
    from pilosa_tpu.core.holder import Holder
    holder = Holder(str(tmp_path))
    holder.open()
    idx = holder.create_index("d1")
    idx.create_field("f1")
    idx.create_field("f2")
    d = DiagnosticsCollector(holder=holder)
    d.set("ClusterID", "abc")
    p = d.payload()
    assert p["NumIndexes"] == 1 and p["NumFields"] >= 2
    assert p["Version"] and p["OS"] and p["ClusterID"] == "abc"
    holder.close()


def test_flush_posts_json():
    received = []

    class H(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        d = DiagnosticsCollector(
            url=f"http://127.0.0.1:{srv.server_port}/diagnostics")
        assert d.flush() is True
        assert received and received[0]["Version"]
    finally:
        srv.shutdown()


def test_flush_survives_unreachable_endpoint():
    d = DiagnosticsCollector(url="http://127.0.0.1:1/nope")
    assert d.flush() is False  # no raise


@pytest.mark.parametrize("latest,expect_update", [
    ("v9.9.9", True),
    ("0.0.1", False),
    ("garbage", False),
])
def test_check_version(latest, expect_update):
    d = DiagnosticsCollector()
    msg = d.check_version(latest)
    assert (msg is not None) == expect_update
    assert d.server_version == latest


def test_runtime_monitor_samples_gauges():
    stats = MemStatsClient()
    mon = RuntimeMonitor(stats, interval=1000)
    mon.sample()
    snap = stats.snapshot()
    assert snap["gauges"]["threads"] >= 1
    assert snap["gauges"].get("heapInuse", 0) > 0  # /proc available on linux


def test_runtime_monitor_no_longer_samples_the_collector():
    stats = MemStatsClient()
    RuntimeMonitor(stats, interval=1000).sample()
    assert {"gcGen0", "garbageCollection"}.isdisjoint(
        stats.snapshot()["gauges"])


@pytest.fixture
def monitor():
    import gc
    stats = MemStatsClient()
    mon = RuntimeMonitor(stats, interval=1000)
    before = list(gc.callbacks)
    mon.start()
    try:
        yield mon, stats
    finally:
        mon.stop()
    assert gc.callbacks == before        # the hook went at shutdown
    assert "runtime.gc_pause_seconds" not in stats.snapshot()["counters"]


def _gc_numbers(stats):
    snap = stats.snapshot()
    c = snap["counters"]
    return (c["runtime.gc_pause_seconds"],
            [c[f"runtime.gc_collections{{gen:{g}}}"] for g in range(3)],
            snap["histograms"].get("runtime.gc_pause_seconds{gen:2}",
                                   {"count": 0, "sum": 0.0}))


def test_full_collection_is_timed_counted_and_bucketed(monitor):
    import gc
    from pilosa_tpu.utils.timeline import STAGE_BUCKETS, TIMELINE
    mon, stats = monitor
    pause0, n0, h0 = _gc_numbers(stats)
    marks = []

    class _Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            marks.append(("in", self.name))

        def __exit__(self, *exc):
            marks.append(("out", self.name))

    TIMELINE.annotation = _Ann
    n_pauses = len(TIMELINE.gc_pauses)
    try:
        gc.collect()
    finally:
        TIMELINE.annotation = None
    pause1, n1, h1 = _gc_numbers(stats)
    assert n1[2] == n0[2] + 1
    assert h1["count"] == h0["count"] + 1
    length = h1["sum"] - h0["sum"]
    assert length > 0 or _TICK > 1e-3
    # Its length is in the cumulative counter too (younger collections
    # may have run beside it: never less).
    assert pause1 - pause0 >= length - 1e-9
    assert len(h1["buckets"]) == len(STAGE_BUCKETS) + 1
    assert marks == [("in", "pilosa:gc"), ("out", "pilosa:gc")]
    t0, t1 = TIMELINE.gc_pauses[-1]
    assert len(TIMELINE.gc_pauses) == n_pauses + 1
    assert t1 - t0 == pytest.approx(length)


def test_young_collection_adds_to_counters_and_to_no_histogram(monitor):
    import gc
    from pilosa_tpu.utils.timeline import TIMELINE
    mon, stats = monitor
    pause0, n0, h0 = _gc_numbers(stats)
    n_pauses = len(TIMELINE.gc_pauses)
    was = gc.isenabled()
    gc.disable()                 # nothing but the asked-for collection
    try:
        gc.collect(0)
    finally:
        if was:
            gc.enable()
    pause1, n1, h1 = _gc_numbers(stats)
    assert n1 == [n0[0] + 1, n0[1], n0[2]]
    assert pause1 > pause0 or _TICK > 1e-3
    assert h1 == h0
    assert len(TIMELINE.gc_pauses) == n_pauses


def test_process_cpu_and_uptime_are_read_when_a_snapshot_is_built(monitor):
    import time
    mon, stats = monitor
    a = stats.snapshot()["counters"]
    t0 = time.thread_time()          # 30 ms of CPU, whatever the load
    while time.thread_time() - t0 < 0.03:
        pass
    b = stats.snapshot()["counters"]
    up = b["runtime.uptime_seconds"] - a["runtime.uptime_seconds"]
    cpu = b["runtime.cpu_seconds"] - a["runtime.cpu_seconds"]
    assert 0.03 <= up < 5.0
    assert cpu >= 0.03 - _TICK
    import gc
    from pilosa_tpu.utils.stats import prometheus_text
    gc.collect()        # the histogram is there once one has run
    text = prometheus_text(stats)
    assert "pilosa_runtime_cpu_seconds_total" in text
    assert 'pilosa_runtime_gc_collections_total{gen="2"}' in text
    assert 'pilosa_runtime_gc_pause_seconds_bucket{gen="2",le="+Inf"}' \
        in text


def test_monitor_stop_leaves_the_totals_in_the_log():
    import gc
    stats = MemStatsClient()
    mon = RuntimeMonitor(stats, interval=1000)
    mon.start()
    gc.collect()
    lines = []

    class _Log:
        def printf(self, fmt, *args):
            lines.append(fmt % args)

    mon.stop(_Log())
    assert len(lines) == 1 and lines[0].startswith("runtime: gc pauses")
    assert "process cpu" in lines[0]
    assert f"longest full collection {mon.gc_longest:.4f}s" in lines[0]
    assert mon.gc_longest > 0 or _TICK > 1e-3
    # ... and the collections no snapshot had asked for are observed.
    assert stats.snapshot()["histograms"][
        "runtime.gc_pause_seconds{gen:2}"]["count"] >= 1
    mon.stop(_Log())                     # a second stop says nothing
    assert len(lines) == 1


def test_slow_query_logged(tmp_path):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.server.api import API

    logged = []

    class FakeLogger:
        def printf(self, fmt, *args):
            logged.append(fmt % args)

        def debugf(self, fmt, *args):
            pass

    holder = Holder(str(tmp_path))
    holder.open()
    holder.create_index("q").create_field("f")
    api = API(holder)
    api.logger = FakeLogger()
    api.long_query_time = 0.0000001  # everything is slow
    api.query("q", "Set(1, f=2)")
    assert any("SLOW QUERY" in line for line in logged)
    logged.clear()
    api.long_query_time = 0.0  # disabled
    api.query("q", "Count(Row(f=2))")
    assert not any("SLOW QUERY" in line for line in logged)
    holder.close()


def test_drain_telemetry_order_once_and_reentrant(tmp_holder):
    """One drain dumps every ring exactly once, in plane order
    (watchdog -> profiler -> workload -> timeline -> tracer); a second
    call is a no-op."""
    from pilosa_tpu.cli.main import drain_telemetry
    from pilosa_tpu.server.api import API
    from tests.test_memledger import _LogStub

    api = API(tmp_holder, stats=MemStatsClient())
    api.profiler.record_slow("i", "Count(Row(f=1))", 2.5)

    class _Tracer:
        stops = 0

        def stop(self):
            self.stops += 1

    api.tracer = _Tracer()
    log = _LogStub()
    drain_telemetry(api, watchdog=None, logger=log)
    # Ordering: the profiler's slow-query line precedes the workload
    # summary.
    slow = next(i for i, l in enumerate(log.lines)
                if "Count(Row(f=1))" in l)
    first_workload = next(i for i, l in enumerate(log.lines)
                          if l.startswith("workload:"))
    assert slow < first_workload
    assert api.tracer.stops == 1
    # Re-entrant second drain: nothing dumps twice, tracer not
    # re-stopped.
    n = len(log.lines)
    drain_telemetry(api, watchdog=None, logger=log)
    assert len(log.lines) == n
    assert api.tracer.stops == 1


def test_metrics_carry_uptime_and_build_info(live_server):
    import urllib.request
    base, _api, _h = live_server
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        met = r.read().decode()
    assert "pilosa_process_uptime_seconds" in met
    assert 'pilosa_build_info{' in met and 'version="' in met \
        and 'backend="' in met


def test_statsd_client_wire_format():
    """DataDog-flavored statsd datagrams over UDP (reference
    statsd/statsd.go:41: prefix 'pilosa.', |c/|g/|ms types, #tags)."""
    import socket

    from pilosa_tpu.utils.stats import StatsdStatsClient

    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.bind(("localhost", 0))
    srv.settimeout(5)
    port = srv.getsockname()[1]

    c = StatsdStatsClient(f"localhost:{port}")
    tagged = c.with_tags("index:i", "field:f")
    tagged.count("query", 3)
    c.gauge("goroutines", 12.5)
    c.timing("exec", 0.25)  # seconds -> 250 ms
    c.flush()
    tagged.flush()

    data = b""
    while b"exec" not in data or b"query" not in data:
        data += srv.recv(65536) + b"\n"
    lines = data.decode().split("\n")
    assert any(l == "pilosa.query:3|c|#field:f,index:i" for l in lines), lines
    assert any(l == "pilosa.goroutines:12.5|g" for l in lines), lines
    assert any(l == "pilosa.exec:250|ms" for l in lines), lines
    srv.close()


def test_statsd_send_failure_never_raises():
    from pilosa_tpu.utils.stats import StatsdStatsClient

    c = StatsdStatsClient("localhost:1")  # nothing listening; UDP is
    for _ in range(64):                   # fire-and-forget either way
        c.count("x")
    c.flush()
