"""Process-level JAX set-up shared by every entry point that compiles
(cmd_server, benches/*): where the persistent compile cache lives, the
description of the devices the process ended up on, and the log of
every XLA compile the process makes."""

from __future__ import annotations

import os
from typing import Any, Dict, List

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Arm JAX's persistent compilation cache before the first compile
    and return its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is the only placement: JAX
    reads it itself and this function names no other directory.
    Otherwise the cache sits at <checkout>/.jax_cache — a fixed path,
    because the directory is part of what a later process must find
    again; a temp name, pid or timestamp would never hit. The server
    compiles many sub-second programs (one per query signature), so
    the keep-thresholds drop to zero: a cold start that recompiled only
    the "cheap" ones would still pay for hundreds of them."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def describe_devices() -> List[Dict[str, Any]]:
    """One entry per device THIS process addresses: id, platform, kind
    and the allocator's byte counters (None where the backend keeps
    none — the CPU backend's memory_stats() is None). Under
    jax.distributed, jax.devices() also lists the other hosts' devices,
    whose memory_stats() raises; each host's server answers for its own
    (jax.device_count() is the global figure). Initialises the backend;
    a platform that cannot start raises here."""
    import jax

    out = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        out.append({
            "id": int(d.id),
            "platform": d.platform,
            "kind": d.device_kind,
            "bytesInUse": ms.get("bytes_in_use"),
            "peakBytesInUse": ms.get("peak_bytes_in_use"),
            "bytesLimit": ms.get("bytes_limit"),
        })
    return out


class CompileLog:
    """Every XLA compile the process makes, with its cause.

    `install()` registers one `jax.monitoring` listener pair; from then
    on each `/jax/core/compile/backend_compile_duration`,
    `/jax/core/compile/jaxpr_trace_duration` and
    `/jax/compilation_cache/cache_hits` event adds to the cumulative
    counters `xla.compiles`, `xla.compile_seconds`, `xla.traces` and
    `xla.cache_hits` of the attached stats client, to a bounded table
    by JAX's `fun_name` (compiles, seconds, traces, the request stage
    open on the compiling thread), and tags that open span. The
    executor's own jit-cache misses (`Executor._note_jit_compile`) show
    in the same table with their readable cache key. An eager `jnp`
    helper that compiles per new shape (`jit(concatenate)`,
    `jit(squeeze)`, ...) is counted here and nowhere else: it never
    passes the executor's jit cache, so `retraces` cannot see it."""

    MAX_NAMES = 256
    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from pilosa_tpu.utils.locks import make_lock
        self._lock = make_lock("CompileLog._lock")
        self.stats: Any = None
        self.installed = False
        self.compiles = 0
        self.compile_seconds = 0.0
        self.traces = 0
        self.cache_hits = 0
        self._by_name: Dict[str, Dict[str, Any]] = {}

    def install(self, stats: Any = None) -> None:
        """Attach `stats` (last attached wins, as with the workload
        recorder) and register the listeners, once per process."""
        if stats is not None:
            self.stats = stats
            # Published from the start, so a window in which nothing
            # compiled reads 0 and not "no such counter".
            stats.batch((), [("xla.compiles", 0),
                             ("xla.compile_seconds", 0.0),
                             ("xla.traces", 0), ("xla.cache_hits", 0)])
        with self._lock:
            if self.installed:
                return
            self.installed = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _entry(self, name: str) -> Dict[str, Any]:
        e = self._by_name.get(name)
        if e is None:
            if len(self._by_name) >= self.MAX_NAMES:
                # Drop the least-compiled name: the table is a ranking.
                del self._by_name[min(
                    self._by_name,
                    key=lambda k: self._by_name[k]["compiles"])]
            e = self._by_name[name] = {"compiles": 0, "seconds": 0.0,
                                       "traces": 0}
        return e

    def _duration(self, event: str, duration: float,
                  fun_name: str = "", **_: Any) -> None:
        if event != self.COMPILE and event != self.TRACE:
            return
        from pilosa_tpu.utils.timeline import TIMELINE
        span = TIMELINE.open_span()
        stage = span.name if span is not None else "idle.no_request"
        # The trace event names the Python function ("concatenate"),
        # the compile event the jitted one ("jit(concatenate)"): one
        # row for both.
        name = fun_name if fun_name.startswith("jit(") \
            else f"jit({fun_name})"
        stats = self.stats
        with self._lock:
            e = self._entry(name)
            if event == self.COMPILE:
                self.compiles += 1
                self.compile_seconds += duration
                e["compiles"] += 1
                e["seconds"] += duration
                e["stage"] = stage
            else:
                self.traces += 1
                e["traces"] += 1
        if event == self.COMPILE:
            if span is not None:
                span.attrs["compiles"] = span.attrs.get("compiles", 0) + 1
                span.attrs["compiled"] = name
            if stats is not None:
                stats.count("xla.compiles", 1)
                stats.count("xla.compile_seconds", duration)
        elif stats is not None:
            stats.count("xla.traces", 1)

    def _event(self, event: str, **_: Any) -> None:
        if event != self.CACHE_HIT:
            return
        with self._lock:
            self.cache_hits += 1
        if self.stats is not None:
            self.stats.count("xla.cache_hits", 1)

    def note_key(self, program: str, key: str) -> None:
        """An executor jit-cache miss: `program` is about to be traced
        and compiled under cache key `key`."""
        with self._lock:
            e = self._entry(f"jit({program})")
            e["retraces"] = e.get("retraces", 0) + 1
            e["key"] = str(key)[:300]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            rows = [dict(v, name=k) for k, v in self._by_name.items()]
            out = {"compiles": self.compiles,
                   "compileSeconds": self.compile_seconds,
                   "traces": self.traces,
                   "cacheHits": self.cache_hits}
        rows.sort(key=lambda r: (-r["seconds"], r["name"]))
        out["byName"] = rows
        return out

    def reset(self) -> None:
        """Tests only."""
        with self._lock:
            self.compiles = self.traces = self.cache_hits = 0
            self.compile_seconds = 0.0
            self._by_name.clear()


COMPILES = CompileLog()
