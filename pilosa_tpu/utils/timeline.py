"""The request record: one tree of spans per request, tiling it.

Every query request (and every coalesced flush) owns ONE record: a
root ``tracing.Span`` whose children are the stages the request went
through, named ``<layer>.<stage>`` after the layers of PERF.md §3:

    http.read · pql.parse · coalescer.wait · coalescer.flush ·
    cache.lookup · plan (plan.lower / plan.verify / plan.optimise) ·
    h2d · dispatch · d2h · finish · http.serialize · http.write

A stage is opened with ``TIMELINE.span(rec, name, **attrs)`` — one
``perf_counter`` pair per boundary — and everything else renders from
the record: the profile tree's stage seconds are the span's one
reading, ``GET /debug/timeline`` is its Chrome trace-event export,
the OTLP exporter ships its root (``tracing.spans_to_otlp``), and
each finished record adds its stage durations to the cumulative
``request.stage_seconds{stage:<name>}`` histograms of ``/debug/vars``
(one stats-lock acquisition per record, not per span).

Whether the thread ran. Where a thread attaches to a record under a
section name (``TIMELINE.attached(rec, "thread.begin")``: the
dispatcher's half of a coalesced flush, the finalizer's) it reads its
own CPU clock (``time.thread_time()``) at the section's two ends, and
the record keeps a span of that name beside its tree
(``rec.sections``) with ``cpu``: the user + system seconds between.
wall − cpu is the time that thread was off the CPU while it held the
record's work — waiting for the GIL, for a lock, in a blocking call. A
finished record feeds a section like a stage — ``request.stage_seconds
{stage:<section>}`` — and beside it ``request.stage_cpu_seconds{stage:
<section>}``, in the same batch. One pair of readings a thread a
record, not one a span: a reading is a system call, 0.4 us on a plain
kernel and ~15 us under the sandboxed one the benchmark's machines run,
where ~1,200 of them a flush cost a fifth of the throughput (PERF.md
section 6, PR 34). The stages themselves carry no such reading, nor
does a span laid in with ``add``; a None record and an attachment
without a name make no such call.

Tiling. A span opened with ``TIMELINE.phase(name)`` (the executor's
``plan`` and ``finish``) is a *phase*: a span opened inside it whose
name is not ``<phase>.<x>`` (``h2d``, ``dispatch``, ``d2h``,
``cache.lookup``) interrupts it — the phase segment is closed, the new
span becomes its sibling, and the phase resumes in a fresh segment
afterwards. So a record's top-level children never overlap on one
thread and their durations add up: the root's ``unaccounted`` time is
its duration minus the union of its children.

Counters. An opener may hand a span ``counts`` — (counter name, delta)
pairs, e.g. a transfer's bytes — which the record adds to the stats
client in the same one batch as its stage durations. This module
knows no stage or counter name of its own.

On the device trace's clock. Each span also opens the annotation the
server injected at start (``jax.profiler.TraceAnnotation``, named
``pilosa:<name>``): under a profiler session it lands in the xplane's
host plane on its thread's line, beside the ``XLA Ops`` lines, with no
clock arithmetic (``tools/trace_gaps.py`` joins the two); with no
session it is a no-op well under a microsecond. This module stays
jax-free.

A coalesced flush is a record of its own (kind ``flush``, root
``coalescer.flush``); ``plan`` … ``finish`` happen once per flush and
are ITS children, and every member request holds a child
``coalescer.flush`` over the same interval whose ``link`` is the
flush's root. Linked spans feed the histograms under
``<name>.member`` so ``stage:coalescer.flush`` counts flushes, not
riders.

Pure host-side module: NO jax imports, no device interaction —
recording is list appends on thread-confined objects plus one leaf
lock at finish (graftlint GL003 clean by construction).
"""

from __future__ import annotations

import heapq
import threading
import time
import uuid
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from pilosa_tpu.utils.locks import make_lock
from pilosa_tpu.utils.tracing import Span

# request.stage_seconds bucket bounds: 2^-17 s (7.6 us) .. 16 s.
STAGE_BUCKETS = tuple(2.0 ** e for e in range(-17, 5))

_thread_time = time.thread_time


class ThreadSection(Span):
    """A thread's section of a record: the interval it was attached to
    it under a name, with ``cpu`` — the seconds (user + system) of the
    thread's CPU clock between its two ends."""

    __slots__ = ("cpu",)


class _TimelineRequest:
    """One record: a root span plus what finish() needs. ``stats`` is
    the client its stage durations go to (the opener's); ``n_spans``
    bounds the tree; ``sections`` are the threads' named attachments,
    each a span with the thread's CPU seconds, kept beside the tree (they
    overlap the stages their thread ran inside them)."""

    __slots__ = ("trace_id", "index", "seq", "kind", "root", "stats",
                 "n_spans", "dropped", "counts", "error", "unaccounted",
                 "sections")

    def __init__(self, trace_id: str, index: str, seq: int, kind: str,
                 name: str, stats: Any, attrs: dict) -> None:
        self.trace_id = trace_id
        self.index = index
        self.seq = seq
        self.kind = kind
        self.root = Span(name, trace_id, attrs, wall=True)
        self.stats = stats
        self.n_spans = 1
        self.dropped = 0
        self.counts: Dict[str, int] = {}
        self.error: Optional[str] = None
        self.unaccounted = 0.0
        self.sections: List[ThreadSection] = []


class _Clock:
    """What span() hands back when there is no record to write to: the
    same surface, two clock reads, nothing kept — callers that feed a
    span's duration onward (the profile tree) need not care."""

    __slots__ = ("t0", "t1")
    span = None

    def __init__(self) -> None:
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Clock":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        self.t1 = time.perf_counter()

    def set(self, key: str, value: Any) -> None:
        pass

    def duration(self) -> float:
        return (self.t1 or time.perf_counter()) - self.t0

    elapsed = duration


class _Open:
    """An open span of a record on this thread: context manager and
    handle. ``duration()`` after exit is the stage's own time — for a
    phase, the sum of its segments."""

    __slots__ = ("tl", "rec", "name", "attrs", "is_phase", "counts",
                 "span", "parent", "ann", "resume", "total",
                 "interrupted")

    def __init__(self, tl: "TimelineRecorder", rec: _TimelineRequest,
                 name: str, attrs: dict, is_phase: bool = False,
                 counts: Any = ()) -> None:
        self.tl = tl
        self.rec = rec
        self.name = name
        self.attrs = attrs
        self.is_phase = is_phase
        self.counts = counts
        self.span: Optional[Span] = None
        self.parent: Optional[Span] = None
        self.ann: Any = None
        self.resume: Optional["_Open"] = None
        self.total = 0.0
        self.interrupted = 0.0

    def _start(self, attrs: dict) -> None:
        rec = self.rec
        sp = self.span = Span(self.name, rec.trace_id, attrs)
        sp.tid = self.tl._lane()
        if rec.n_spans < self.tl.MAX_EVENTS_PER_REQUEST:
            rec.n_spans += 1
            # graftlint: disable=GL008 — bounded by n_spans above; the
            # tree lives for one request and then in the bounded ring.
            self.parent.children.append(sp)
        else:
            rec.dropped += 1
        factory = self.tl.annotation
        if factory is not None:
            ann = self.ann = factory("pilosa:" + self.name)
            ann.__enter__()

    def _stop(self) -> None:
        sp = self.span
        sp.close()
        self.total += sp.pc_end - sp.pc_start
        ann, self.ann = self.ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def __enter__(self) -> "_Open":
        stack = self.tl._stack()
        top = stack[-1] if stack else None
        if top is not None and top.rec is self.rec:
            if top.is_phase and \
                    not self.name.startswith(top.name + "."):
                top._stop()
                self.resume = top
                self.parent = top.parent
            else:
                self.parent = top.span
        else:
            self.parent = self.rec.root
        self._start(self.attrs)
        stack.append(self)
        if self.counts:
            totals = self.rec.counts
            for key, n in self.counts:
                totals[key] = totals.get(key, 0) + n
        return self

    def __exit__(self, *exc: object) -> None:
        self._stop()
        stack = self.tl._stack()
        if stack and stack[-1] is self:
            stack.pop()
        top, self.resume = self.resume, None
        if top is not None:
            top.interrupted += self.total + self.interrupted
            top._start({"resumed": True})

    def set(self, key: str, value: Any) -> None:
        self.span.attrs[key] = value

    def duration(self) -> float:
        sp = self.span
        if sp is None or sp.pc_end is not None:
            return self.total
        return self.total + sp.duration()

    def elapsed(self) -> float:
        """duration() plus what interrupted this phase: the whole time
        from its first open to now (or to its close)."""
        return self.duration() + self.interrupted


class _Attached:
    __slots__ = ("tl", "rec", "prev", "ann", "section", "tt")

    def __init__(self, tl: "TimelineRecorder", rec: Any,
                 section: Optional[str] = None) -> None:
        self.tl = tl
        self.rec = rec
        self.prev = None
        self.ann = None
        self.section: Optional[ThreadSection] = None
        if rec is not None and section is not None:
            self.section = ThreadSection(section, rec.trace_id, {})
        self.tt = 0.0

    def __enter__(self) -> Any:
        tls = self.tl._tls
        self.prev = getattr(tls, "rec", None)
        tls.rec = rec = self.rec
        factory = self.tl.annotation
        if rec is not None and factory is not None:
            # The root on this thread's trace line too, so the time
            # between two stages reads as the request's (or the
            # flush's), not as nobody's.
            self.ann = factory("pilosa:" + rec.root.name)
            self.ann.__enter__()
        sp = self.section
        if sp is not None:
            sp.tid = self.tl._lane()
            sp.pc_start = time.perf_counter()
            self.tt = _thread_time()
        return rec

    def __exit__(self, *exc: object) -> None:
        sp = self.section
        if sp is not None:
            sp.cpu = _thread_time() - self.tt
            sp.close()
            rec = self.rec
            if rec.n_spans < self.tl.MAX_EVENTS_PER_REQUEST:
                rec.n_spans += 1
                # graftlint: disable=GL008 — bounded by n_spans above.
                rec.sections.append(sp)
            else:
                rec.dropped += 1
        ann, self.ann = self.ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        self.tl._tls.rec = self.prev


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    covered = 0.0
    end = float("-inf")
    for s, e in sorted(intervals):
        if s > end:
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    return covered


class TimelineRecorder:
    """Process-wide ring of request records (the timeline analog of
    hotspots.WORKLOAD / memledger.LEDGER).

    ``begin`` is on the path of every query: it decides sampling and
    hands back a record (or None — every call on a None record is a
    no-op or a bare clock, so the unsampled/disabled path costs two
    clock reads a stage)."""

    # Spans kept per record: enough for a realistic multi-call query
    # or a 64-wide flush of filtered TopNs that all miss the arg cache
    # (~11 spans a request: plan.stage, two h2d, two dispatch, the plan
    # segments between, d2h, finish) without letting a 1024-call query
    # bloat the ring. What is past it is counted, not kept.
    MAX_EVENTS_PER_REQUEST = 1024
    # Longest records kept per kind beside the ring, which at hundreds
    # of answers a second has turned over before anyone can ask it
    # which stage was open in a stall.
    SLOWEST_PER_KIND = 8
    # Generation-2 collections kept (a few a minute): drawn into the
    # export of the records they fell in.
    MAX_GC_PAUSES = 64

    def __init__(self, ring: int = 256, sample_every: int = 1) -> None:
        self.enabled = True
        self.sample_every = max(1, int(sample_every))
        self._lock = make_lock("TimelineRecorder._lock")
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self._seq = 0
        self.requests_recorded = 0
        self.requests_skipped = 0
        self._tls = threading.local()
        # Injected by the server at start (this module imports no jax):
        # a context-manager factory taking the event name, i.e.
        # jax.profiler.TraceAnnotation. None = spans only.
        self.annotation: Optional[Callable[[str], Any]] = None
        # A tracer with offer(root) — the OTLP exporter — or None.
        self.exporter: Any = None
        # Thread lanes of the Chrome export: ident -> (lane, name).
        self._lanes: Dict[int, Tuple[int, str]] = {}
        # kind -> min-heap of (seconds, seq, record): the longest
        # SLOWEST_PER_KIND records of each kind since reset().
        self._slowest: Dict[str, list] = {}
        # (pc_start, pc_end) of the newest full collections, appended
        # by the collector's hook (utils/diagnostics.RuntimeMonitor)
        # with no lock: a deque append is atomic, and the hook may run
        # while any lock of this process is held.
        self.gc_pauses: deque = deque(maxlen=self.MAX_GC_PAUSES)

    # ------------------------------------------------------------ configure

    def configure(self, enabled: Optional[bool] = None,
                  ring: Optional[int] = None,
                  sample_every: Optional[int] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if ring is not None:
                self._ring = deque(self._ring, maxlen=max(1, int(ring)))
            if sample_every is not None:
                self.sample_every = max(1, int(sample_every))

    def reset(self) -> None:
        """Tests only: drop every recorded timeline."""
        with self._lock:
            self._ring.clear()
            self._slowest.clear()
            self._seq = 0
            self.requests_recorded = 0
            self.requests_skipped = 0
        self.gc_pauses.clear()

    # -------------------------------------------------------- thread state

    def _stack(self) -> List[_Open]:
        try:
            return self._tls.stack
        except AttributeError:
            stack = self._tls.stack = []
            return stack

    def _lane(self) -> int:
        try:
            return self._tls.lane
        except AttributeError:
            t = threading.current_thread()
            with self._lock:
                lane, name = self._lanes.get(t.ident, (0, ""))
                if lane and name != t.name:
                    # The ident of a dead thread, reused.
                    self._lanes[t.ident] = (lane, t.name)
                if not lane:
                    if len(self._lanes) >= 1024:
                        # Threads come and go (one per connection):
                        # forget the lanes of the dead before growing.
                        live = {x.ident for x in threading.enumerate()}
                        for ident in [i for i in self._lanes
                                      if i not in live]:
                            del self._lanes[ident]
                    lane = 1 + max((v[0] for v in self._lanes.values()),
                                   default=0)
                    self._lanes[t.ident] = (lane, t.name)
            self._tls.lane = lane
            return lane

    def attached(self, rec: Optional[_TimelineRequest],
                 section: Optional[str] = None) -> _Attached:
        """``with TIMELINE.attached(rec):`` — `rec` is what
        ``current()`` returns on this thread inside the block: how the
        executor finds the record (a request's, or the flush's) without
        a parameter on every call. With a `section` name the block is
        also timed as this thread's section of the record, with the
        thread's CPU clock read at its two ends (module docstring)."""
        return _Attached(self, rec, section)

    def current(self) -> Optional[_TimelineRequest]:
        return getattr(self._tls, "rec", None)

    def open_span(self) -> Optional[Span]:
        """The innermost span open on this thread, of any record (what
        the compile log tags when XLA compiles inside a stage)."""
        stack = self._stack()
        return stack[-1].span if stack else None

    def phase(self, name: str, **attrs: Any) -> Any:
        """A stage of the attached record that sibling stages may
        interrupt (module docstring) — unless this thread is already
        inside phase `name` of that record: then a bare clock, whose
        elapsed() is the same wall interval. The executor brackets
        every call of a query with `plan` and `finish`; inside a
        flush's one `plan` (or `finish`) those are readings, not spans
        of their own."""
        rec = getattr(self._tls, "rec", None)
        if rec is None:
            return _Clock()
        stack = self._stack()
        if stack and stack[-1].name == name and stack[-1].rec is rec:
            return _Clock()
        return self.span(rec, name, phase=True, **attrs)

    # ------------------------------------------------------------ recording

    def begin(self, trace_id: Optional[str], index: str = "",
              stats: Any = None, name: str = "request",
              kind: str = "request",
              **attrs: Any) -> Optional[_TimelineRequest]:
        """Open a record (None = not sampled / disabled). ``trace_id``
        should be the id the tracer propagates (W3C traceparent) so
        cross-node legs stitch by it; ``stats`` receives the stage
        durations at finish()."""
        if not self.enabled:
            return None
        with self._lock:
            self._seq += 1
            seq = self._seq
            if self.sample_every > 1 and seq % self.sample_every:
                self.requests_skipped += 1
                return None
        if index:
            attrs["index"] = index
        rec = _TimelineRequest(trace_id or uuid.uuid4().hex, index, seq,
                               kind, name, stats, attrs)
        rec.root.tid = self._lane()
        return rec

    def span(self, rec: Optional[_TimelineRequest], name: str,
             phase: bool = False, counts: Any = (),
             **attrs: Any) -> Any:
        """``with TIMELINE.span(rec, "pql.parse", ...) as s:`` — time
        one stage of `rec` on this thread. The parent is the innermost
        span of `rec` open on this thread, else its root; see the
        module docstring for how phases are interrupted. `s.set(k, v)`
        adds an attribute, `s.duration()` is the stage's seconds;
        `phase` makes it one that sibling stages interrupt; `counts`
        are (counter name, delta) pairs the record hands to its stats
        client when it finishes."""
        if rec is None:
            return _Clock()
        return _Open(self, rec, name, attrs, phase, counts)

    def stage(self, name: str, **kw: Any) -> Any:
        """span() on the record attached to this thread."""
        return self.span(getattr(self._tls, "rec", None), name, **kw)

    def count(self, name: str, n: int = 1) -> None:
        """Add to a counter of the record attached to this thread (as a
        span's `counts` do), where no span of its own marks the event."""
        rec = getattr(self._tls, "rec", None)
        if rec is not None:
            rec.counts[name] = rec.counts.get(name, 0) + n

    def add(self, rec: Optional[_TimelineRequest], name: str,
            pc_start: float, pc_end: float,
            link: Optional[Span] = None, own_lane: bool = False,
            **attrs: Any) -> None:
        """An interval no single thread brackets — a queue wait ends
        on the dispatcher's thread while its request's own thread is
        parked — or one that is only kept once its outcome is known (a
        fan-out leg that won its hedge race): recorded from two
        existing clock readings as a child of the root, drawn on the
        root's lane, or on the calling thread's with `own_lane`
        (concurrent legs would overlap on one). `link` makes it a
        reference to a span of another record."""
        if rec is None:
            return
        if rec.n_spans >= self.MAX_EVENTS_PER_REQUEST:
            rec.dropped += 1
            return
        sp = Span(name, rec.trace_id, attrs, pc_start=pc_start)
        sp.pc_end = max(pc_start, pc_end)
        sp.link = link
        sp.tid = self._lane() if own_lane else rec.root.tid
        rec.n_spans += 1
        rec.root.children.append(sp)

    def finish(self, rec: Optional[_TimelineRequest],
               error: Optional[BaseException] = None) -> None:
        """Close a record: stamp the root, take its unaccounted time,
        add every span to the cumulative stage histograms (one lock),
        publish it into the ring and offer it to the exporter."""
        if rec is None:
            return
        root = rec.root
        if root.pc_end is not None:
            return   # finished already (an error path ran twice)
        if error is not None:
            rec.error = f"{type(error).__name__}: {error}"
            root.attrs["error"] = rec.error
        root.close()
        total = root.pc_end - root.pc_start
        rec.unaccounted = max(0.0, total - union_seconds(
            [(max(c.pc_start, root.pc_start),
              min(c.pc_end if c.pc_end is not None else root.pc_end,
                  root.pc_end))
             for c in root.children]))
        if rec.stats is not None:
            self._feed(rec, total)
        with self._lock:
            self._ring.append(rec)
            self.requests_recorded += 1
            heap = self._slowest.setdefault(rec.kind, [])
            if len(heap) < self.SLOWEST_PER_KIND:
                heapq.heappush(heap, (total, rec.seq, rec))
            elif total > heap[0][0]:
                heapq.heapreplace(heap, (total, rec.seq, rec))
        exporter = self.exporter
        if exporter is not None:
            exporter.offer(root)

    def _feed(self, rec: _TimelineRequest, total: float) -> None:
        """One observation per stage name per record (the sum of that
        name's spans), so a stage's histogram count is the number of
        requests — or flushes — that went through it, and a record's
        top-level sums plus its unaccounted time equal its total. A
        thread's section feeds the same family under its own name and
        its CPU seconds go beside it, so Σcpu ÷ Σwall of a section is
        the share of it that its thread ran."""
        sums: Dict[str, float] = {}
        cpus: Dict[str, float] = {}
        for sp in rec.root.walk():
            if sp is rec.root:
                continue
            name = sp.name + ".member" if sp.link is not None else sp.name
            sums[name] = sums.get(name, 0.0) + (
                (sp.pc_end if sp.pc_end is not None
                 else rec.root.pc_end) - sp.pc_start)
        for sp in rec.sections:
            name = sp.name
            sums[name] = sums.get(name, 0.0) + sp.pc_end - sp.pc_start
            cpus[name] = cpus.get(name, 0.0) + sp.cpu
        histos = [("request.stage_seconds", (f"stage:{n}",), v,
                   STAGE_BUCKETS) for n, v in sums.items()]
        histos += [("request.stage_cpu_seconds", (f"stage:{n}",), v,
                    STAGE_BUCKETS) for n, v in cpus.items()]
        if rec.kind == "flush":
            histos.append(("request.stage_seconds",
                           ("stage:" + rec.root.name,), total,
                           STAGE_BUCKETS))
            histos.append(("flush.unaccounted_seconds", (),
                           rec.unaccounted, STAGE_BUCKETS))
        else:
            histos.append(("request.total_seconds", (), total,
                           STAGE_BUCKETS))
            histos.append(("request.unaccounted_seconds", (),
                           rec.unaccounted, STAGE_BUCKETS))
        counts = list(rec.counts.items())
        if rec.dropped:
            # Spans past MAX_EVENTS_PER_REQUEST left the tree: their
            # time reads as the parent's, or as unaccounted.
            counts.append(("request.spans_dropped", rec.dropped))
        rec.stats.batch(histos, counts)

    # -------------------------------------------------------------- reading

    def _export_events(self, reqs: List[_TimelineRequest], pid: int
                       ) -> List[Dict[str, Any]]:
        """Chrome ``ph:"X"`` slices, one per span, on the lane of the
        thread that opened it: spans of one thread nest, so the UI
        stacks a request's stages under its root."""
        events: List[Dict[str, Any]] = []
        for req in reqs:
            root = req.root
            anchor_us = root.start * 1e6

            def emit(sp: Span, parent: Optional[Span]) -> None:
                end = sp.pc_end if sp.pc_end is not None else root.pc_end
                args: Dict[str, Any] = dict(sp.attrs)
                if isinstance(sp, ThreadSection):
                    args["cpu"] = sp.cpu
                args["trace"] = req.trace_id
                args["spanId"] = sp.span_id
                if parent is not None:
                    args["parent"] = parent.name
                    args["parentSpanId"] = parent.span_id
                else:
                    args["kind"] = req.kind
                    args["unaccountedS"] = req.unaccounted
                if sp.link is not None:
                    args["linkSpanId"] = sp.link.span_id
                events.append({
                    "name": sp.name, "ph": "X", "cat": "pilosa",
                    "ts": anchor_us + (sp.pc_start - root.pc_start) * 1e6,
                    "dur": max(0.0, end - sp.pc_start) * 1e6,
                    "pid": pid, "tid": sp.tid, "args": args})
                for c in list(sp.children):
                    emit(c, sp)

            emit(root, None)
            # A thread's section lies over the stages it ran: on its
            # lane the UI stacks them under it.
            for sec in req.sections:
                emit(sec, root)
        # Full collections stop every thread: each is drawn once, on
        # lane 0, anchored on the first exported record it fell in.
        for t0, t1 in list(self.gc_pauses):
            for req in reqs:
                root = req.root
                end = root.pc_end if root.pc_end is not None else t1
                if t0 < end and t1 > root.pc_start:
                    events.append({
                        "name": "gc", "ph": "X", "cat": "pilosa",
                        "ts": (root.start + t0 - root.pc_start) * 1e6,
                        "dur": (t1 - t0) * 1e6, "pid": pid, "tid": 0,
                        "args": {"gen": 2}})
                    break
        return events

    @staticmethod
    def process_metadata(pid: int, node_name: str
                         ) -> List[Dict[str, Any]]:
        """The Chrome ``ph:"M"`` event naming one process (node)."""
        return [{"name": "process_name", "ph": "M", "ts": 0, "dur": 0,
                 "pid": pid, "tid": 0, "args": {"name": node_name}}]

    def metadata_events(self, pid: int, node_name: str
                        ) -> List[Dict[str, Any]]:
        """Chrome ``ph:"M"`` naming events for one process (node) and
        its thread lanes. ``ts``/``dur`` ride along as 0 so every event
        in the document carries the full ph/ts/dur/pid/tid shape (the
        CI smoke validates exactly that)."""
        meta = self.process_metadata(pid, node_name)
        with self._lock:
            lanes = sorted(self._lanes.values())
        for lane, lname in lanes:
            meta.append({"name": "thread_name", "ph": "M", "ts": 0,
                         "dur": 0, "pid": pid, "tid": lane,
                         "args": {"name": lname}})
        return meta

    def requests(self, last: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 slowest: bool = False) -> List[_TimelineRequest]:
        """Most-recent-last records, optionally filtered by trace id
        and bounded to the last N; with `slowest`, the longest records
        of each kind since reset() instead of the ring's."""
        with self._lock:
            if slowest:
                reqs = sorted((r for heap in self._slowest.values()
                               for _, _, r in heap), key=lambda r: r.seq)
            else:
                reqs = list(self._ring)
        if trace_id:
            reqs = [r for r in reqs if r.trace_id == trace_id]
        if last is not None and last >= 0:
            reqs = reqs[-last:]
        return reqs

    @staticmethod
    def _stage_sums(req: _TimelineRequest) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sp in req.root.walk():
            if sp is not req.root and sp.pc_end is not None:
                out[sp.name] = out.get(sp.name, 0.0) \
                    + sp.pc_end - sp.pc_start
        return out

    @classmethod
    def _stage_line(cls, req: _TimelineRequest) -> str:
        """`stage=ms` of a record for the log, then its threads'
        sections as `section=wall/cpu ms`."""
        return ",".join(
            [f"{name}={s * 1e3:.2f}ms"
             for name, s in cls._stage_sums(req).items()]
            + [f"{sp.name}={sp.duration() * 1e3:.2f}/{sp.cpu * 1e3:.2f}ms"
               for sp in req.sections])

    def _stage_medians(self, reqs: List[_TimelineRequest]
                       ) -> Dict[str, float]:
        per: Dict[str, List[float]] = {}
        for req in reqs:
            for name, s in self._stage_sums(req).items():
                per.setdefault(name, []).append(s)
        return {name: sorted(vals)[len(vals) // 2]
                for name, vals in per.items()}

    def _by_call(self, reqs: List[_TimelineRequest]) -> Dict[str, Any]:
        """Per top-level PQL call name (the root's ``calls`` attr):
        requests, mean seconds, mean unaccounted seconds and mean
        seconds per stage — which shapes make the time."""
        acc: Dict[str, Dict[str, Any]] = {}
        for req in reqs:
            if req.kind != "request" or req.root.pc_end is None:
                continue
            a = acc.setdefault(str(req.root.attrs.get("calls", "-")),
                               {"n": 0, "total": 0.0, "un": 0.0,
                                "stages": {}})
            a["n"] += 1
            a["total"] += req.root.pc_end - req.root.pc_start
            a["un"] += req.unaccounted
            for name, s in self._stage_sums(req).items():
                a["stages"][name] = a["stages"].get(name, 0.0) + s
        return {call: {"requests": a["n"],
                       "meanS": a["total"] / a["n"],
                       "unaccountedMeanS": a["un"] / a["n"],
                       "stageMeanS": {k: v / a["n"] for k, v in
                                      sorted(a["stages"].items())}}
                for call, a in sorted(acc.items())}

    def snapshot(self, last: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 node_id: str = "local", pid: int = 0,
                 slowest: bool = False) -> Dict[str, Any]:
        """The ``GET /debug/timeline`` document: trace-event JSON
        (``traceEvents`` — the Chrome JSON object format, loadable
        directly in Perfetto/chrome://tracing) plus a summary with
        per-stage medians and per-call-name means. ``slowest`` =
        ``?slowest=1``: the longest records kept beside the ring."""
        reqs = self.requests(last=last, trace_id=trace_id,
                             slowest=slowest)
        events = self.metadata_events(pid, node_id) \
            + self._export_events(reqs, pid)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "node": node_id,
            "summary": {
                "requests": len(reqs),
                "requestsRecorded": self.requests_recorded,
                "requestsSkipped": self.requests_skipped,
                "ringCapacity": self._ring.maxlen,
                "sampleEvery": self.sample_every,
                "stageMedianS": self._stage_medians(reqs),
                "byCall": self._by_call(reqs),
            },
        }

    def ring_count(self) -> int:
        with self._lock:
            return len(self._ring)

    def ring_nbytes(self) -> int:
        """Estimated bytes held by the timeline ring (the memory-ledger
        ``telemetry`` registration; O(ring) under the lock)."""
        with self._lock:
            held = {id(r): r for r in self._ring}
            for heap in self._slowest.values():
                held.update((id(r), r) for _, _, r in heap)
            return sum(r.root.nbytes() + 160
                       + sum(sp.nbytes() for sp in r.sections)
                       for r in held.values())

    def register_memory(self, ledger: Optional[Any] = None) -> None:
        """Register the ring's bytes with the memory ledger (category
        ``telemetry``) so /debug/memory totals stay provable."""
        if ledger is None:
            from pilosa_tpu.utils.memledger import LEDGER as ledger
        ledger.register("telemetry", "timeline_ring", self.ring_nbytes(),
                        owner=self, kind="timeline",
                        entries=self.ring_count())

    def dump(self, logger: Optional[Any], last: int = 5) -> int:
        """Write the most recent `last` records and the slowest kept
        to the log, stage by stage, their threads' sections as wall/cpu
        ms — the SIGTERM drain calls
        this so buffered timelines survive a graceful shutdown.
        Returns records written."""
        groups = [("", self.requests(last=max(0, int(last)))),
                  ("slowest ", self.requests(slowest=True))]
        n = sum(len(reqs) for _, reqs in groups)
        if logger is not None and n:
            logger.printf("timeline: dumping %d request timeline(s) on "
                          "shutdown", n)
            for which, reqs in groups:
                for r in reqs:
                    logger.printf(
                        "timeline: %s%s trace=%s index=%s %.2fms %s",
                        which, r.kind, r.trace_id, r.index or "-",
                        r.root.duration() * 1e3, self._stage_line(r))
        return n


# The process-wide recorder every serving-path seam reports into (the
# timeline analog of hotspots.WORKLOAD — one process, one timeline).
TIMELINE = TimelineRecorder()
