"""Cross-request query coalescing: continuous batching for the serving
path.

The executor already amortizes device dispatch *within* a batch
(`Executor.execute_batch`'s overlapped drain), but only clients that
explicitly POST to /batch/query benefit. The north-star workload is
thousands of *independent* single-query requests, each paying its own
host->device dispatch and result-fetch round trip. This module sits
between the HTTP layer and the executor and transparently collects
concurrent `POST /index/{i}/query` requests into one stacked device
sweep — the serving-layer analogue of continuous batching in inference
stacks.

Mechanics:
- A request thread enqueues its query and blocks on a per-item event.
- A single dispatcher thread collects items arriving within a short
  batching window (default ~1.5 ms), flushing early when the batch hits
  the size cap or a write-containing query arrives, and without a
  window at all when the items queued behind a running flush (they have
  waited already) or when the queued request is alone in the server:
  the window is a wait for batch-mates, and the server's count of the
  query requests it holds (`API.held`, begin_request to end_request)
  says there are none — reason `alone`. A count of two or more waits
  (somebody is reading a body or parsing and may submit in time), and
  so does a caller that submits without a count: it may be one of many.
- The batch runs through `Executor.execute_batch` (one pipelined
  dispatch-then-drain) with per-request error isolation: one bad query
  resolves to ITS exception without failing its batchmates, the same
  contract as /batch/query.
- Identical read-only queries in one write-free flush execute ONCE and
  fan the shaped response out to every requester (results are
  byte-identical by construction).
- The distinct remainder passes through to `execute_batch` UNCHANGED:
  the executor's fusion pass (executor/fusion.py) then collapses
  *similar* queries — same tree shape, different row ids / predicates —
  into one vmapped XLA dispatch per signature group, where read-dedup
  only collapses *equal* ones. The flush span records how many of the
  batch's queries fused (`fusedQueries`).

Robustness pieces a production front door needs:
- Admission control: a bounded pending queue; past capacity, submit
  raises CoalescerOverload -> HTTP 429 + Retry-After.
- Per-request deadlines: an expired request is ejected from the window
  (its dispatch skipped) and fails with 408 instead of occupying a
  batch slot.
- Observability: queue depth, batch occupancy and flush-reason
  counters via utils/stats.py; every flush is a record of its own in
  utils/timeline.py (root `coalescer.flush`, its stages beneath it),
  and every member request holds a `coalescer.wait` span and a
  `coalescer.flush` span that links to the flush it rode.

Coalescing is semantically invisible: single-item flushes run the exact
direct path (`Executor.execute_full`), write-containing queries flush
the window immediately (preserving the existing `batch_tail_writes`
ordering inside `execute_batch`), and the API layer degrades to the
direct path whenever the coalescer is absent, stopped, or ineligible
(cluster fan-out, remote legs, protobuf surface).
"""

from __future__ import annotations

import os
import threading
from pilosa_tpu.utils.locks import make_condition
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from pilosa_tpu.server.api import ApiError, HeldRequests
from pilosa_tpu.utils.fingerprint import request_key
from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.timeline import TIMELINE

# Item lifecycle: PENDING (queued, still ejectable) -> CLAIMED (taken by
# the dispatcher; result imminent) or EJECTED (deadline passed while
# queued; the dispatcher must skip it).
_PENDING, _CLAIMED, _EJECTED = 0, 1, 2

# RTT-hiding pipelined dispatch (kill switch): while batch K's results
# drain on the finalizer thread, the dispatcher plans + launches batch
# K+1 — the plan-build and H2D that otherwise sit serially inside
# every flush. Depth is exactly one in-flight
# batch (double buffering); write-containing or single-item flushes
# barrier and run the exact serial path, so results are always
# identical to PILOSA_TPU_PIPELINE=0.
PIPELINE_ENABLED = os.environ.get("PILOSA_TPU_PIPELINE", "1") != "0"

# A flush answers its members when the LAST of them is finalised, so a
# flush is held to about this long: the next one claims no more members
# than the last pipelined flush's wall time a member lets finish inside
# it (never fewer than two: a lone member leaves the pipeline). Flushes
# of cheap queries never meet it — 64 members inside it are 15.6 ms
# each — while 64 bank sweeps of 126 ms each held every answer for 8 s
# and let them go in one lump (PERF.md §6, PR 30).
FLUSH_TARGET_S = 1.0


class CoalescerStopped(RuntimeError):
    """Raised by submit() when the coalescer is stopped (or its
    dispatcher died) — the ONLY condition the API layer may answer by
    re-running the query on the direct path. A dedicated type so
    genuine executor RuntimeErrors (device OOM, transfer failures)
    surface to the client instead of being silently retried."""


class CoalescerOverload(ApiError):
    """Pending queue at capacity — HTTP 429 with a Retry-After hint."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg, 429)
        self.retry_after = retry_after
        self.headers = {"Retry-After": str(max(1, int(retry_after)))}


class DeadlineExceeded(ApiError):
    """Request expired while queued; its dispatch was skipped."""

    def __init__(self, msg: str):
        super().__init__(msg, 408)


class _Item:
    __slots__ = ("index", "query", "shards", "is_write", "deadline",
                 "state", "event", "result", "enqueued_at", "profile",
                 "held")

    def __init__(self, index: str, query: Any,
                 shards: Optional[Sequence[int]], is_write: bool,
                 deadline: Optional[float], profile: Any = None,
                 held: Optional[HeldRequests] = None):
        self.index = index
        self.query = query
        self.shards = shards
        self.is_write = is_write
        self.deadline = deadline
        self.state = _PENDING
        self.event = threading.Event()
        self.result: Any = None
        self.enqueued_at = time.perf_counter()
        # utils/profile QueryProfile the executor fills in while this
        # item's request executes (None on non-profiled paths).
        self.profile = profile
        # The submitting server's count of the query requests it holds,
        # this one among them; None from a caller that announces nothing.
        self.held = held


class QueryCoalescer:
    """Collects concurrent single-query requests into executor batches.

    `submit()` is the only entry point for request threads; `start()`/
    `stop()` bracket the dispatcher thread's lifetime. A request waits
    at most `window_s` for batch-mates, and not at all when the server
    that submitted it holds no other query request (`_collect_window`,
    flush reason `alone`). `stop()` drains:
    everything already queued still executes before the thread exits, so
    a SIGTERM'd server answers its admitted requests (in-flight HTTP
    handlers block in submit until their batch completes)."""

    def __init__(self, executor, window_s: float = 0.0015,
                 max_batch: int = 64, max_queue: int = 256,
                 deadline_s: float = 0.0, stats=None, logger=None,
                 pipeline: Optional[bool] = None):
        from pilosa_tpu.utils.stats import NopStatsClient
        self.executor = executor
        self.window_s = max(0.0, float(window_s))
        self.max_batch = max(1, int(max_batch))
        self.max_queue = max(1, int(max_queue))
        self.deadline_s = max(0.0, float(deadline_s))
        self.stats = stats or NopStatsClient()
        self.logger = logger
        # Pipelined dispatch: config default (None -> on) gated by the
        # PILOSA_TPU_PIPELINE env kill switch, and by the executor
        # actually exposing the begin/finish split (stub executors in
        # tests don't).
        self.pipeline = (PIPELINE_ENABLED
                         and (pipeline is None or bool(pipeline))
                         and hasattr(executor,
                                     "execute_batch_shaped_begin"))
        self._queue: List[_Item] = []
        # Items claimed out of _queue for the batch being built or
        # executed — tracked on self so the dispatcher-death handler
        # can resolve them too (they are no longer in _queue).
        self._inflight: List[_Item] = []
        self._cond = make_condition("QueryCoalescer._cond")
        self._flush_now: Optional[str] = None  # early-flush reason
        self._stop = False
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # True while the dispatcher executes a batch: arrivals during
        # that span have already "waited" (continuous batching), so the
        # next flush takes them without re-running the window timer.
        self._busy = False
        # Pipelined-dispatch plumbing: the (depth-1) hand-off slot to
        # the finalizer thread plus its lifecycle flag. `_pl_pending`
        # holds exactly one in-flight batch's finalize work; the
        # dispatcher blocks on the slot before handing off the next —
        # that IS the double buffer.
        self._pl_cond = make_condition("QueryCoalescer._pl_cond")
        self._pl_pending: Optional[tuple] = None
        self._pl_stop = False
        self._pl_thread: Optional[threading.Thread] = None
        self.pipelined_flushes = 0
        # Wall seconds a member of the last pipelined flush, begin to
        # last answer (0 until one has finished): what FLUSH_TARGET_S
        # is divided by. Written by the finalizer, read by the
        # dispatcher.
        self._member_s = 0.0
        # Flushes by the path they took and by why the window closed,
        # published from the start: a share of them is read over a
        # window in which one path may never be taken.
        for tag in [f"path:{p}" for p in self.FLUSH_PATHS] + \
                [f"reason:{r}" for r in self.FLUSH_REASONS]:
            self.stats.with_tags(tag).count("coalescer.flushes", 0)

    # `pipelined`: read-only, two threads, overlapping the next flush;
    # `batch`: barriered and run whole on the dispatcher (`thread.batch`)
    # — every flush that holds a write; `direct`: a lone request.
    FLUSH_PATHS = ("pipelined", "batch", "direct")
    FLUSH_REASONS = ("window", "size", "write", "idle", "drain",
                     "shutdown", "alone")

    def _count_flush(self, path: str, reason: str, size: int) -> None:
        self.stats.count(f"coalescer.flush.{reason}", 1)
        self.stats.with_tags(f"path:{path}").count("coalescer.flushes", 1)
        self.stats.with_tags(f"reason:{reason}").count(
            "coalescer.flushes", 1)
        self.stats.histogram("coalescer.batch_size", size)

    # ------------------------------------------------------------ lifecycle

    @property
    def running(self) -> bool:
        return self._running

    def queue_depth(self) -> int:
        """Live pending-queue depth (the health plane reads this; the
        coalescer.queue_depth gauge only updates on queue churn)."""
        with self._cond:
            return len(self._queue)

    def start(self) -> None:
        if self._running or (self._thread is not None
                             and self._thread.is_alive()):
            # Second guard: a stop() whose drain timed out leaves the
            # old dispatcher running — never spawn a second one over
            # the same queue.
            return
        with self._cond:
            self._stop = False
            self._running = True
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="query-coalescer")
        self._thread.start()
        if self.pipeline and (self._pl_thread is None
                              or not self._pl_thread.is_alive()):
            with self._pl_cond:
                self._pl_stop = False
            self._pl_thread = threading.Thread(
                target=self._finalize_loop, daemon=True,
                name="query-coalescer-finalize")
            self._pl_thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        """Graceful drain: stop admitting, execute everything queued,
        join the dispatcher. Safe to call twice. If the dispatcher is
        wedged in a batch past `timeout`, says so and keeps the thread
        handle — callers proceed with teardown knowing the drain did
        not complete, and start() refuses to double-dispatch."""
        with self._cond:
            if not self._running and self._thread is None:
                return
            self._running = False  # submit() now degrades to direct
            self._stop = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                if self.logger is not None:
                    self.logger.printf(
                        "coalescer drain timed out after %.0fs; "
                        "dispatcher still executing a batch", timeout)
                return
            with self._cond:
                self._thread = None
        # The dispatcher barriers its own in-flight batch before
        # exiting, so the finalizer is idle here — stop it too.
        with self._pl_cond:
            self._pl_stop = True
            self._pl_cond.notify_all()
        ft = self._pl_thread
        if ft is not None:
            ft.join(timeout=timeout)
            if not ft.is_alive():
                self._pl_thread = None

    # --------------------------------------------------------------- submit

    def submit(self, index: str, query: Any,
               shards: Optional[Sequence[int]] = None,
               profile: Any = None,
               is_write: Optional[bool] = None,
               held: Optional[HeldRequests] = None) -> Dict[str, Any]:
        """Queue one query and block until its batch resolves. Returns
        the shaped response dict; raises the per-request exception
        (executor errors, CoalescerOverload, DeadlineExceeded).
        `profile` (a utils/profile QueryProfile) rides along and is
        filled in by the executor when this item's request runs; forced
        profiles are excluded from read-dedup so their tree describes
        exactly this request's execution. `is_write`, when the caller
        already parsed the query, saves parsing it again here. `held`
        is the server's count of the query requests it holds, this one
        included (API.held): a request it shows to be alone does not
        wait out the window (see _collect_window).

        The caller (API.query_coalesced) checks `running` first and
        falls back to the direct path, but the check races with stop():
        RuntimeError from a just-stopped coalescer is re-routed by the
        caller, never surfaced to the client."""
        from pilosa_tpu.executor.executor import query_is_write
        deadline = (time.monotonic() + self.deadline_s
                    if self.deadline_s > 0 else None)
        if is_write is None:
            is_write = query_is_write(query)
        item = _Item(index, query, shards, is_write, deadline,
                     profile=profile, held=held)
        with self._cond:
            if not self._running:
                raise CoalescerStopped("coalescer stopped")
            if len(self._queue) >= self.max_queue:
                self.stats.count("coalescer.rejected", 1)
                raise CoalescerOverload(
                    f"query queue at capacity ({self.max_queue} pending)",
                    retry_after=max(1.0, self.window_s * 2))
            self._queue.append(item)
            self.stats.count("coalescer.admitted", 1)
            self.stats.gauge("coalescer.queue_depth", len(self._queue))
            if is_write and self._flush_now is None:
                # Writes must not sit in a window: flush immediately so
                # the batch (with its batch_tail_writes snapshotting)
                # starts now.
                self._flush_now = "write"
            elif len(self._queue) >= self.max_batch and \
                    self._flush_now is None:
                self._flush_now = "size"
            self._cond.notify_all()
        return self._await(item)

    def _await(self, item: _Item) -> Dict[str, Any]:
        if item.deadline is not None:
            if not item.event.wait(max(0.0, item.deadline
                                       - time.monotonic())):
                with self._cond:
                    if item.state == _PENDING:
                        # Still in the window: eject so the dispatcher
                        # skips its dispatch entirely.
                        item.state = _EJECTED
                        try:
                            self._queue.remove(item)
                        except ValueError:
                            pass
                        self.stats.gauge("coalescer.queue_depth",
                                         len(self._queue))
                        self.stats.count("coalescer.deadline_ejected", 1)
                        raise DeadlineExceeded(
                            f"deadline exceeded after "
                            f"{self.deadline_s * 1e3:.0f} ms in queue")
                # Claimed by the dispatcher in the race: the result is
                # being computed — deliver it (the deadline bounds QUEUE
                # time, not execution).
                item.event.wait()
        else:
            item.event.wait()
        if isinstance(item.result, Exception):
            raise item.result
        return item.result

    # ----------------------------------------------------------- dispatcher

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    while not self._queue and not self._stop:
                        self._busy = False
                        self._cond.wait()
                    if not self._queue and self._stop:
                        break  # drain the pipeline below, then exit
                    reason = self._collect_window()
                    batch = self._claim_batch()
                    busy_next = bool(self._queue)
                if batch:
                    if self._can_pipeline(batch):
                        self._execute_pipelined(batch, reason)
                    else:
                        # Writes (and the single-item direct path)
                        # run serially AFTER the in-flight batch fully
                        # drains: a write must not mutate fragment
                        # state a draining read could still lazily
                        # consult (TopN chunking) — the pipelined path
                        # keeps exactly the sequential semantics.
                        self._pipeline_barrier()
                        self._execute(batch, reason)
                self._inflight = []
                with self._cond:
                    # Items that arrived while executing have waited
                    # their window already: take them on the next loop
                    # pass without re-arming the timer.
                    # graftlint: disable=GL015 — busy_next snapshots
                    # the queue at claim time ON PURPOSE and is OR-ed
                    # with a fresh read: staleness can only err toward
                    # one extra busy pass, never a lost wakeup.
                    self._busy = busy_next or bool(self._queue)
            self._pipeline_barrier()
        except BaseException as e:  # dispatcher died: strand nobody
            if self.logger is not None:
                self.logger.printf("coalescer dispatcher died: %r", e)
            with self._cond:
                self._running = False  # submits degrade to direct
                pending, self._queue = self._queue, []
            # _inflight covers items already claimed out of the queue
            # (batch being built/executed when the exception hit).
            for item in pending + self._inflight:
                if not item.event.is_set():
                    item.result = CoalescerStopped(
                        f"coalescer dispatcher died: {e!r}")
                    item.event.set()
            raise

    def _collect_window(self) -> str:
        """Hold the window open for more arrivals (lock held). Returns
        the flush reason."""
        if self._stop:
            return "shutdown"
        if self._busy:
            # The device just finished a batch and these items queued
            # behind it — flush without further delay.
            return "drain"
        if self.window_s <= 0:
            return "idle"
        if self._flush_now is None and len(self._queue) == 1:
            held = self._queue[0].held
            if held is not None and held.count() == 1:
                # The window is a wait for batch-mates, and the queued
                # request is the only query request its server holds:
                # there can be none. Two held means somebody is still
                # reading a body or parsing and may submit in time, so
                # that waits; so does a caller that announces nothing
                # (held is None) — it may be one of many.
                return "alone"
        deadline = time.monotonic() + self.window_s
        while (self._flush_now is None and not self._stop
               and len(self._queue) < self.max_batch):
            left = deadline - time.monotonic()
            if left <= 0:
                return "window"
            self._cond.wait(left)
        if self._stop:
            return "shutdown"
        return self._flush_now or "window"

    def _claim_batch(self) -> List[_Item]:
        """Move up to max_batch pending items into CLAIMED (lock held),
        dropping expired ones with a DeadlineExceeded result."""
        self._flush_now = None
        now = time.monotonic()
        batch = self._inflight = []
        limit = self.max_batch
        if self._member_s > 0:
            limit = min(limit, max(2, int(FLUSH_TARGET_S / self._member_s)))
        while self._queue and len(batch) < limit:
            item = self._queue.pop(0)
            if item.state != _PENDING:  # ejected by its requester
                continue
            if item.deadline is not None and now >= item.deadline:
                item.state = _EJECTED
                item.result = DeadlineExceeded(
                    f"deadline exceeded after "
                    f"{self.deadline_s * 1e3:.0f} ms in queue")
                self.stats.count("coalescer.deadline_ejected", 1)
                item.event.set()
                continue
            item.state = _CLAIMED
            batch.append(item)
        self.stats.gauge("coalescer.queue_depth", len(self._queue))
        return batch

    def _note_workload(self, batch: List[_Item]) -> None:
        """Record every read-only request's identity with the workload
        recorder's rolling window: cross-REQUEST duplicate reads (the
        ones in-batch dedup cannot see — identical queries arriving in
        different flushes) feed the cache-opportunity report and the
        coalescer.window_repeat counter."""
        if not WORKLOAD.enabled:
            return
        repeats = 0
        for item in batch:
            if item.is_write:
                continue
            # The ONE canonical request identity
            # (utils/fingerprint.request_key) — the same key the
            # in-flush dedup groups on and the executor's request-tier
            # result cache caches under, so window_repeat predicts
            # exactly what the cache will later serve.
            key = request_key(item.index, item.query, item.shards)
            if WORKLOAD.record_request(key):
                repeats += 1
        if repeats:
            self.stats.count("coalescer.window_repeat", repeats)

    def _execute(self, batch: List[_Item], reason: str) -> None:
        self._count_flush("direct" if len(batch) == 1 else "batch",
                          reason, len(batch))
        self._note_workload(batch)
        try:
            if len(batch) == 1:
                self._execute_direct(batch[0], reason)
            else:
                self._execute_batched(batch, reason)
        except Exception as e:  # dispatcher must never die
            if self.logger is not None:
                self.logger.printf("coalescer flush failed: %r", e)
            for item in batch:
                if not item.event.is_set():
                    item.result = e
                    item.event.set()

    def _execute_direct(self, item: _Item, reason: str = "idle") -> None:
        """Batch of one: run the EXACT direct path (execute_full), so a
        lone request degrades to uncoalesced behavior."""
        rec = getattr(item.profile, "timeline", None)
        if item.profile is not None:
            now = time.perf_counter()
            item.profile.set_coalesced(1, now - item.enqueued_at)
            TIMELINE.add(rec, "coalescer.wait", item.enqueued_at, now,
                         batch=1, reason=reason)
        # The stages run on this thread but belong to the request: a
        # single-item flush has no flush record of its own.
        with TIMELINE.attached(rec):
            try:
                item.result = self.executor.execute_full(
                    item.index, item.query, shards=item.shards,
                    profile=item.profile)
            except Exception as e:
                item.result = e
        item.event.set()

    def _dedup(self, batch: List[_Item]) -> Tuple[
            List[Tuple[str, Any, Optional[Sequence[int]]]],
            List[Any], List[List[_Item]]]:
        """Collapse identical read-only queries when the flush carries
        no writes (a write in the batch orders against its batchmates,
        so reads that would straddle it must each run in position).
        Forced profiles (?profile=true) never dedup: their tree must
        describe this request's own execution, not a batchmate's."""
        dedup_ok = not any(it.is_write for it in batch)
        groups: Dict[Tuple[str, str, Optional[Tuple[int, ...]]],
                     List[int]] = {}
        reqs: List[Tuple[str, Any, Optional[Sequence[int]]]] = []
        profiles: List[Any] = []
        owner: List[List[_Item]] = []
        for item in batch:
            key = None
            forced = item.profile is not None and item.profile.forced
            if dedup_ok and not forced and isinstance(item.query, str):
                key = request_key(item.index, item.query, item.shards)
            if key is not None and key in groups:
                owner[groups[key][0]].append(item)
                continue
            if key is not None:
                groups[key] = [len(reqs)]
            reqs.append((item.index, item.query, item.shards))
            profiles.append(item.profile)
            owner.append([item])
        if len(reqs) < len(batch):
            self.stats.count("coalescer.deduped", len(batch) - len(reqs))
        return reqs, profiles, owner

    def _open_flush(self, batch: List[_Item], reason: str,
                    pipelined: bool):
        """The flush's own record (root `coalescer.flush`). Each
        member's queue wait ends where it starts — stamped before the
        batch runs, so window/queue time and execution time separate."""
        rec = TIMELINE.begin(None, batch[0].index, stats=self.stats,
                             name="coalescer.flush", kind="flush",
                             batch=len(batch), reason=reason,
                             pipelined=pipelined)
        start = rec.root.pc_start if rec is not None \
            else time.perf_counter()
        for item in batch:
            if item.profile is not None:
                item.profile.set_coalesced(len(batch),
                                           start - item.enqueued_at)
                TIMELINE.add(getattr(item.profile, "timeline", None),
                             "coalescer.wait", item.enqueued_at, start,
                             batch=len(batch), reason=reason)
        return rec

    def _close_flush(self, rec, batch: List[_Item], profiles,
                     err: Optional[BaseException] = None) -> None:
        """Finish the flush's record and hand every member its
        reference to it: a `coalescer.flush` child over the same
        interval whose link is the flush's root. Runs BEFORE the
        members are released, so each sees it when it wakes."""
        if rec is None:
            return
        # Fusion attribution from this flush's OWN profiles (the
        # process-wide executor counters also move under concurrent
        # /batch/query traffic, so a before/after delta would claim
        # work this flush never did).
        rec.root.attrs["fusedQueries"] = sum(
            1 for p in profiles
            if p is not None and getattr(p, "fused_batch", None))
        TIMELINE.finish(rec, error=err)
        root = rec.root
        for item in batch:
            if item.profile is not None:
                TIMELINE.add(getattr(item.profile, "timeline", None),
                             "coalescer.flush", root.pc_start,
                             root.pc_end, link=root,
                             batch=len(batch))

    def _execute_batched(self, batch: List[_Item],
                         reason: str = "window") -> None:
        """One executor batch for N requests, identical reads deduped
        (see _dedup)."""
        reqs, profiles, owner = self._dedup(batch)
        rec = self._open_flush(batch, reason, pipelined=False)
        if rec is not None:
            rec.root.attrs["unique"] = len(reqs)
        err = None
        try:
            with TIMELINE.attached(rec, "thread.batch"):
                shaped = self.executor.execute_batch_shaped(
                    reqs, profiles=profiles)
        except Exception as e:
            err = e
            raise
        finally:
            self._close_flush(rec, batch, profiles, err)
        for res, items in zip(shaped, owner):
            for item in items:
                item.result = res
                item.event.set()

    # ------------------------------------------------------- pipelined path

    def _can_pipeline(self, batch: List[_Item]) -> bool:
        """Read-only multi-item flushes pipeline; anything else (a
        write that must order against in-flight reads, or a singleton
        that takes the exact direct path) barriers and runs serially."""
        return (self.pipeline and self._pl_thread is not None
                and self._pl_thread.is_alive() and len(batch) > 1
                and not any(it.is_write for it in batch))

    def _pipeline_barrier(self) -> None:
        """Wait until no batch is in flight on the finalizer."""
        if self._pl_thread is None:
            return
        with self._pl_cond:
            while self._pl_pending is not None:
                self._pl_cond.wait()

    def _execute_pipelined(self, batch: List[_Item], reason: str) -> None:
        """Dispatch half on this (dispatcher) thread — parse, plan,
        fuse, LAUNCH, start prefetch — then hand the in-flight handle
        to the finalizer and return to collecting the next window.
        While the previous batch drains device->host, this one's plan
        build and H2D run concurrently: the overlap that buys back the
        per-flush host time. Both halves are stages of ONE flush
        record; `coalescer.handoff` is the wait for the finalizer."""
        began = time.perf_counter()
        self._count_flush("pipelined", reason, len(batch))
        self._note_workload(batch)
        rec = None
        profiles: List[Any] = []
        try:
            reqs, profiles, owner = self._dedup(batch)
            rec = self._open_flush(batch, reason, pipelined=True)
            if rec is not None:
                rec.root.attrs["unique"] = len(reqs)
            # This thread's section of the flush, and the finalizer's
            # below: whether each ran while it held the flush's work.
            with TIMELINE.attached(rec, "thread.begin"):
                sh = self.executor.execute_batch_shaped_begin(
                    reqs, profiles=profiles)
        except Exception as e:  # dispatch failed: resolve everyone now
            if self.logger is not None:
                self.logger.printf("coalescer pipelined dispatch "
                                   "failed: %r", e)
            self._close_flush(rec, batch, profiles, e)
            for item in batch:
                if not item.event.is_set():
                    item.result = e
                    item.event.set()
            return
        self.pipelined_flushes += 1
        self.stats.count("coalescer.pipelined", 1)
        handoff = time.perf_counter()
        with self._pl_cond:
            # Depth-1 double buffer: wait for the PREVIOUS batch's
            # drain slot, then occupy it. The wait happens AFTER this
            # batch dispatched, so its device work already overlaps
            # the predecessor's drain.
            while self._pl_pending is not None:
                self._pl_cond.wait()
            self._pl_pending = (batch, owner, sh, rec, profiles,
                                handoff, began)
            self._pl_cond.notify_all()

    def _finalize_loop(self) -> None:
        """Finalizer thread: drain in-flight batches' device->host
        transfers, shape responses, resolve requesters. Never dies on
        a batch failure — the error resolves to that batch's items."""
        while True:
            with self._pl_cond:
                while self._pl_pending is None and not self._pl_stop:
                    self._pl_cond.wait()
                if self._pl_pending is None:
                    return
                work = self._pl_pending
            try:
                self._finish_pipelined(*work)
            except BaseException as e:  # strand nobody, keep draining
                if self.logger is not None:
                    self.logger.printf("coalescer pipelined finalize "
                                       "failed: %r", e)
                for item in work[0]:
                    if not item.event.is_set():
                        item.result = (e if isinstance(e, Exception)
                                       else CoalescerStopped(repr(e)))
                        item.event.set()
            finally:
                with self._pl_cond:
                    self._pl_pending = None
                    self._pl_cond.notify_all()

    def _finish_pipelined(self, batch: List[_Item],
                          owner: List[List[_Item]], sh: Any, rec: Any,
                          profiles: List[Any], handoff: float,
                          began: float) -> None:
        # The wait for the finalizer's slot (the previous flush still
        # draining): one more stage of the flush, so that it tiles.
        TIMELINE.add(rec, "coalescer.handoff", handoff,
                     time.perf_counter())
        err = None
        try:
            with TIMELINE.attached(rec, "thread.finish"):
                shaped = self.executor.execute_batch_shaped_finish(sh)
        except BaseException as e:
            err = e
            raise
        finally:
            self._close_flush(rec, batch, profiles, err)
            self._member_s = (time.perf_counter() - began) / len(batch)
        for res, items in zip(shaped, owner):
            for item in items:
                item.result = res
                item.event.set()
