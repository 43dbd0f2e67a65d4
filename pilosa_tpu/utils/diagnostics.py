"""Diagnostics (phone-home) and runtime monitoring.

Reference: /root/reference/diagnostics.go:42-263 (diagnosticsCollector —
periodic JSON POST of version/OS/CPU/memory/schema-shape plus a version
check against the latest release) driven by server.go:675-724, and the
runtime monitor loop server.go:726-770 (goroutine/heap/open-FD gauges on
GC notifications, gcnotify/gcnotify.go:30).

Rebuild divergences: reporting is OFF unless an interval AND endpoint are
configured (the reference defaults to pilosa.com; this build runs in
zero-egress environments, so the default must be inert), and the runtime
monitor samples its gauges on a plain timer but times every garbage
collection where it happens (one `gc.callbacks` hook: a full collection
stops every Python thread for its length)."""

from __future__ import annotations

from collections import deque
import gc
import json
import os
import platform
import threading
import time
from pilosa_tpu.utils.locks import make_lock
import urllib.request
from typing import Any, Dict, Optional

from pilosa_tpu import __version__
from pilosa_tpu.utils.timeline import STAGE_BUCKETS, TIMELINE


class DiagnosticsCollector:
    """Periodic anonymous usage report (reference diagnosticsCollector,
    diagnostics.go:42). `set(...)` accumulates fields; `flush()` POSTs
    them; `start()` runs flush on an interval. Inert without an URL."""

    def __init__(self, url: str = "", interval: float = 0.0,
                 holder=None, logger=None):
        self.url = url
        self.interval = interval
        self.holder = holder
        self.logger = logger
        self._fields: Dict[str, Any] = {}
        self._lock = make_lock("DiagnosticsCollector._lock")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.server_version: Optional[str] = None  # from version check

    def set(self, name: str, value: Any) -> None:
        with self._lock:
            # graftlint: disable=GL008 — closed key space: callers set
            # a fixed handful of report fields (version, schema shape),
            # mirroring the reference's diagnosticsCollector.
            self._fields[name] = value

    def enabled(self) -> bool:
        return bool(self.url) and self.interval > 0

    def payload(self) -> Dict[str, Any]:
        """The report body (reference diagnostics.go:80-135: version, OS,
        arch, uptime, schema shape — never data or keys)."""
        with self._lock:
            fields = dict(self._fields)
        fields.update({
            "Version": __version__,
            "OS": platform.system(),
            "Arch": platform.machine(),
            "PythonVersion": platform.python_version(),
            "NumCPU": os.cpu_count(),
        })
        if self.holder is not None:
            schema = self.holder.schema()
            fields["NumIndexes"] = len(schema)
            fields["NumFields"] = sum(len(ix.get("fields", []))
                                      for ix in schema)
        return fields

    def flush(self) -> bool:
        """POST one report; never raises (diagnostics must not disturb
        serving)."""
        if not self.url:
            return False
        try:
            body = json.dumps(self.payload()).encode("utf-8")
            req = urllib.request.Request(
                self.url, data=body, method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10):
                pass
            return True
        except Exception as e:  # noqa: BLE001 — best-effort by design
            if self.logger is not None:
                self.logger.debugf("diagnostics flush failed: %r", e)
            return False

    def check_version(self, latest: str) -> Optional[str]:
        """Compare a reported latest version against ours (reference
        compareVersions, diagnostics.go:183-229). Returns a human message
        when an update exists, else None."""
        self.server_version = latest
        try:
            ours = [int(x) for x in __version__.split("-")[0]
                    .lstrip("v").split(".")]
            theirs = [int(x) for x in latest.split("-")[0]
                      .lstrip("v").split(".")]
        except ValueError:
            return None
        if theirs > ours:
            return (f"an update is available: {latest} "
                    f"(running {__version__})")
        return None

    def start(self) -> None:
        if not self.enabled() or self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="diagnostics")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self.flush()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


class RuntimeMonitor:
    """Samples process/runtime gauges into the stats client (reference
    monitorRuntime, server.go:726-770: goroutines, heap, open FDs,
    mmaps) and, from start() to stop(), times the collector.

    The hook runs hundreds of times a second on a busy server, on
    whichever thread tripped the collector and whatever lock that
    thread holds, so it takes no lock and calls no stats client: the
    interpreter runs one collection at a time, and the hook adds to
    plain attributes that `published` hands the stats client when a
    snapshot is built (`/debug/vars`, `/metrics`):

        runtime.gc_pause_seconds           every generation, cumulative
        runtime.gc_collections{gen:0|1|2}
        runtime.gc_pause_seconds{gen:2}    histogram, one observation a
                                           full collection (observed
                                           when the snapshot is built)
        runtime.cpu_seconds                time.process_time()
        runtime.uptime_seconds             monotonic, since start()

    A full collection — the one that walks the whole tracked heap — is
    also a `pilosa:gc` event on the profiler's trace and an interval of
    `TIMELINE.gc_pauses`, drawn into the records it fell in."""

    def __init__(self, stats, interval: float = 10.0, holder=None):
        self.stats = stats
        self.interval = interval
        self.holder = holder
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.gc_pause_seconds = 0.0
        self.gc_collections = [0, 0, 0]
        # Full collections' lengths the stats client has not seen yet
        # (a deque append takes no lock; drained every sample and every
        # snapshot), and the longest since start.
        self._gen2_pending: deque = deque(maxlen=4096)
        self.gc_longest = 0.0
        self._gc_t0 = 0.0
        self._gc_pc0 = 0.0
        self._gc_ann: Any = None
        self._t_start = time.monotonic()

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # The collecting thread's CPU clock, not the wall clock: the
        # interpreter may hand the GIL over on entry to the `stop` call
        # (a Python function like any other), and a wall pair would
        # then count other threads' turns as the collector's — three
        # times over with three busy threads. The collection itself
        # holds the GIL and blocks on nothing, so its CPU seconds are
        # how long it stopped everyone.
        if phase == "start":
            if info["generation"] == 2:
                factory = TIMELINE.annotation
                if factory is not None:
                    ann = self._gc_ann = factory("pilosa:gc")
                    ann.__enter__()
                self._gc_pc0 = time.perf_counter()
            self._gc_t0 = time.thread_time()
            return
        dt = time.thread_time() - self._gc_t0
        gen = info["generation"]
        self.gc_pause_seconds += dt
        # graftlint: disable=GL008 — three counts, one a generation.
        self.gc_collections[gen] += 1
        if gen == 2:
            self._gen2_pending.append(dt)
            self.gc_longest = max(self.gc_longest, dt)
            TIMELINE.gc_pauses.append((self._gc_pc0, self._gc_pc0 + dt))
            ann, self._gc_ann = self._gc_ann, None
            if ann is not None:
                ann.__exit__(None, None, None)

    def published(self) -> Dict[str, float]:
        """What a stats snapshot reads from here (StatsClient.add_source):
        cumulative counters; the full collections since the last call
        go to their histogram on the way."""
        self._observe_full_collections()
        counters: Dict[str, float] = {
            "runtime.gc_pause_seconds": self.gc_pause_seconds,
            "runtime.cpu_seconds": time.process_time(),
            "runtime.uptime_seconds": time.monotonic() - self._t_start}
        for gen, n in enumerate(self.gc_collections):
            counters[f"runtime.gc_collections{{gen:{gen}}}"] = n
        return counters

    def _observe_full_collections(self) -> None:
        # The sampler and any number of snapshots drain this with no
        # lock between them: a popleft is atomic, and the one who finds
        # the deque emptied under it stops.
        pending = self._gen2_pending
        if pending:
            gen2 = self.stats.with_tags("gen:2")
            try:
                while True:
                    gen2.histogram("runtime.gc_pause_seconds",
                                   pending.popleft(),
                                   buckets=STAGE_BUCKETS)
            except IndexError:
                pass

    def sample(self) -> None:
        self._observe_full_collections()
        self.stats.gauge("threads", threading.active_count())
        if self.holder is not None:
            # Torn op-log tails sidecarred at open: operators must see
            # dropped-data events in metrics, not only a log line.
            self.stats.gauge("tailDroppedBytes",
                             self.holder.tail_dropped_bytes())
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        self.stats.gauge(
                            "heapInuse", int(line.split()[1]) * 1024)
                        break
        except OSError:
            pass
        try:
            self.stats.gauge("openFiles", len(os.listdir("/proc/self/fd")))
        except OSError:
            pass

    def start(self) -> None:
        if self._thread is not None:
            return
        self._t_start = time.monotonic()
        gc.callbacks.append(self._on_gc)
        self.stats.add_source(self.published)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="runtime-monitor")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.sample()
            except Exception:  # noqa: BLE001 — monitoring must not crash
                pass

    def stop(self, logger=None) -> None:
        """Stop sampling and take the hook out; with a logger, leave the
        collector's and the process's totals in the log (a benchmark run
        stops its server before anyone can ask `/debug/vars`)."""
        self._stop.set()
        if self._thread is None:
            return
        self._thread.join(timeout=5)
        self._thread = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.published()        # the last full collections, observed
        self.stats.remove_source(self.published)
        if logger is not None:
            logger.printf(
                "runtime: gc pauses %.4fs in %s collections (gen 0/1/2); "
                "longest full collection %.4fs; process cpu %.2fs in "
                "%.2fs up", self.gc_pause_seconds, self.gc_collections,
                self.gc_longest, time.process_time(),
                time.monotonic() - self._t_start)
