"""API facade: the programmatic surface between transports and the engine.

Reference: /root/reference/api.go:40 (API struct; Query :103, schema CRUD
:130-393, Import :814, ImportValue :922, ImportRoaring :291, fragment/
block/attr-diff sync endpoints :517-812, cluster admin :1084). Transport
handlers (HTTP here, like the reference's gorilla/mux layer) stay thin and
call this.
"""

from __future__ import annotations

import threading
import time as _time
from datetime import datetime
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core import timeq
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import (ALL_WRITE_CALLS,
                                          write_call_count)
from pilosa_tpu.executor.results import result_to_json
from pilosa_tpu.ops.bitset import SHARD_WIDTH
from pilosa_tpu.pql import Call, Query, parse_string_cached
from pilosa_tpu.utils.failpoints import FAILPOINTS
from pilosa_tpu.utils.locks import make_lock
from pilosa_tpu.utils.timeline import TIMELINE
from pilosa_tpu import __version__

# Fault-injection sites on the server seams (utils/failpoints.py
# catalog). `api.status` is what heartbeat probes hit — arming error
# there makes THIS node look dead to every prober while its data plane
# keeps running; `api.query` fails every query leg routed here (the
# failpoint "kill": coordinators must fail over); `resize.job.rpc` is
# the coordinator's per-node pull RPC inside the resize job.
_FP_STATUS = FAILPOINTS.register("api.status")
_FP_QUERY = FAILPOINTS.register("api.query")
_FP_RESIZE_RPC = FAILPOINTS.register("resize.job.rpc")


def export_fragment_lines(idx, field_name: str, shard: int):
    """Yield CSV 'row,col' lines (with trailing newline) for one
    (field, standard-view, shard): keys translated on keyed
    fields/indexes with a decimal-id fallback for unmapped ids,
    csv-module quoting for keys containing delimiters (reference
    api.ExportCSV, api.go:430-500). A generator so the CLI can stream
    shard after shard without buffering; the HTTP handler joins (it
    needs the body for Content-Length anyway)."""
    import csv as _csv
    import io as _io

    f = idx.field(field_name) if idx is not None else None
    if f is None:
        raise ApiError(f"field not found: {field_name}", 404)
    view = f.view()
    frag = view.fragment(shard) if view is not None else None
    if frag is None:
        return
    row_tx = (f.row_translator.translate_id if f.options.keys and
              f.row_translator is not None else None)
    col_tx = (idx.column_translator.translate_id if idx.keys and
              idx.column_translator is not None else None)
    buf = _io.StringIO()
    w = _csv.writer(buf, lineterminator="\n")
    for row in frag.row_ids():
        r = row_tx(row) if row_tx else row
        if r is None:
            r = row
        for col in frag.row_columns(row):
            c = col_tx(int(col)) if col_tx else col
            if c is None:
                c = int(col)
            buf.seek(0)
            buf.truncate()
            w.writerow([r, c])
            yield buf.getvalue()


class ApiError(ValueError):
    def __init__(self, msg: str, status: int = 400):
        super().__init__(msg)
        self.status = status


class HeldRequests:
    """The query requests a server holds: one opens where its request
    record opens (`API.begin_request` — the HTTP handler calls it before
    it reads the body) and closes where that closes (`end_request`,
    after the reply is on the socket). The coalescer asks it whether a
    queued request is alone in the server: a window is a wait for
    batch-mates, and a request nobody else is being read, parsed,
    executed or written beside has none to wait for.

    A thread serves one request at a time, so opens nest per thread and
    count once: query() under a handler whose record the timeline does
    not keep (switched off, or sampling) opens the request again."""

    def __init__(self) -> None:
        self._lock = make_lock("HeldRequests._lock")
        self._tls = threading.local()
        self._n = 0

    def open(self) -> None:
        depth = getattr(self._tls, "depth", 0)
        self._tls.depth = depth + 1
        if depth == 0:
            with self._lock:
                self._n += 1

    def close(self) -> None:
        self._tls.depth -= 1
        if self._tls.depth == 0:
            with self._lock:
                self._n -= 1

    def count(self) -> int:
        """One attribute read, no lock: the coalescer's dispatcher asks
        under its own condition."""
        return self._n


class API:
    def __init__(self, holder: Holder, mesh=None, cluster=None,
                 stats=None, tracer=None, client_ssl_context=None):
        from pilosa_tpu.utils.logger import Logger
        from pilosa_tpu.utils.profile import Profiler
        from pilosa_tpu.utils.stats import NopStatsClient
        from pilosa_tpu.utils.tracing import NopTracer
        self.logger = Logger()
        self._translate_negative: Dict[Any, set] = {}
        self._started_at = _time.time()
        self.holder = holder
        self.executor = Executor(holder, mesh=mesh)
        self.cluster = cluster
        self.stats = stats or NopStatsClient()
        # Batch-scoped executor signals (fusion counters/group sizes)
        # have no per-query profile to ride — feed them straight in.
        self.executor.stats = self.stats
        # The transfer counters the request records' h2d / d2h spans
        # feed (utils/timeline.py), published from the start so a
        # window without a transfer reads 0, not "no such counter".
        self.stats.batch((), [(f"executor.{d}_{k}", 0)
                              for d in ("h2d", "d2h")
                              for k in ("bytes", "transfers")]
                         + [("executor.bank_upload_bytes", 0)])
        # ... and so are the TopN path, time-range leaf and bank
        # popcount counters: a share of them is read over a window in
        # which one path may never be taken.
        for path in Executor.TOPN_PATHS:
            self.stats.with_tags(f"path:{path}").count(
                "executor.topn_sweeps", 0)
        for path in Executor.RANGE_PATHS:
            self.stats.with_tags(f"path:{path}").count(
                "executor.range_leaves", 0)
        self.stats.count("executor.range_views", 0)
        for path in Executor.POPCOUNT_PATHS:
            self.stats.with_tags(f"path:{path}").count(
                "executor.bank_popcounts", 0)
        # ... and the bank-sweep launches and the filter programs
        # launched for them inside a batch, read per answer.
        self.stats.count("executor.sweep_launches", 0)
        self.stats.count("executor.filter_launches", 0)
        # ... and the positions bank's (a field past the resident
        # limit): its segment programs launched, a call without `n`
        # whose segment ran again wider, the bank's builds by kind, and
        # which membership form a filtered launch's gate took.
        for name in ("pbank_launches", "pbank_overflow_reruns",
                     "pbank_builds"):
            self.stats.count(f"executor.{name}", 0)
        for kind in ("full", "patch"):
            self.stats.with_tags(f"kind:{kind}").count(
                "executor.pbank_builds", 0)
        for form in ("compare", "gather"):
            self.stats.with_tags(f"form:{form}").count(
                "executor.pbank_form", 0)
        # ... and a GroupBy's: groups answered, level programs, levels
        # whose prefixes moved to host memory, the group-sum launches
        # of `aggregate=Sum(field=f)` with the (group, plane) rows they
        # counted and the operand rows their kernel fetched for them, and
        # its level loop's blocking fetches by whether
        # another member of the flush had a program queued meanwhile.
        for name in ("groupby_groups", "groupby_levels", "groupby_spills",
                     "groupsum_launches", "groupsum_plane_rows",
                     "groupsum_operand_rows"):
            self.stats.count(f"executor.{name}", 0)
        for covered in ("yes", "no"):
            self.stats.with_tags(f"covered:{covered}").count(
                "executor.groupby_fetches", 0)
        # ... and the write path's: writes applied by call, what each
        # stale bank cost the next read of it (a `bank_patch` of the
        # cells that moved, or a rebuild and why), read per write and
        # per window — 0 in a window without a write, never absent.
        for call in sorted(ALL_WRITE_CALLS):
            self.stats.with_tags(f"call:{call}").count(
                "executor.writes", 0)
        for name in ("bank_patches", "bank_patch_cells",
                     "bank_patch_pad_lanes", "bank_rebuilds",
                     "bank_subset_rebuilds"):
            self.stats.count(f"executor.{name}", 0)
        for cause in ("capacity", "epoch", "half", "width"):
            self.stats.with_tags(f"cause:{cause}").count(
                "executor.bank_rebuilds", 0)
        # The process-wide workload recorder (utils/hotspots.py)
        # increments its counters (pilosa_fragment_reads_total, ...)
        # straight into the stats client at record time so the
        # exported counters stay true monotone counters. Last-attached
        # wins, same as the ledger's scrape-time publish target.
        from pilosa_tpu.utils.hotspots import WORKLOAD
        WORKLOAD.stats = self.stats
        # Result-cache hit/miss/eviction counters increment at event
        # time through the same last-attached-wins convention.
        self.executor.result_cache.stats = self.stats
        self.tracer = tracer or NopTracer()
        self.long_query_time = 0.0  # seconds; 0 disables slow-query logs
        # Per-query execution profiler (utils/profile.py): every query
        # path reports through it (executor.* stats, the slow-query ring
        # at GET /debug/queries); ?profile=true additionally embeds the
        # profile tree in the response with device fencing on.
        self.profiler = Profiler(stats=self.stats, tracer=self.tracer)
        # Serving-path query coalescer (server/coalescer.py), attached
        # by the server wiring (cli/main.py) or a test harness; None
        # means every request takes the direct path.
        self.coalescer = None
        # The query requests in the server, begin_request to
        # end_request; every submit to the coalescer carries it.
        self.held = HeldRequests()
        # Always-on memory watchdog (utils/memledger.MemoryWatchdog),
        # attached by cli/main.py; the health plane reports its state.
        self.watchdog = None
        # Cached backend label for pilosa_build_info: resolved from an
        # already-imported jax only (never forces backend init from a
        # metrics scrape).
        self._build_backend: Optional[str] = None
        # Adaptive hybrid bank layout (core/layout.py): the background
        # re-layout pass. Constructed unconditionally (its counters
        # and the layout stanza must exist even when the thread is
        # off); cli/main.py configures thresholds and starts the loop.
        from pilosa_tpu.core.layout import LayoutManager
        self.layout = LayoutManager(holder, stats=self.stats,
                                    logger=self.logger)
        self.cluster_executor = None
        self.syncer = None
        self.resize_puller = None
        self.broadcaster = None
        if cluster is not None:
            from pilosa_tpu.parallel.client import InternalClient
            from pilosa_tpu.parallel.cluster_executor import ClusterExecutor
            from pilosa_tpu.parallel.syncer import HolderSyncer, ResizePuller
            from pilosa_tpu.parallel.broadcast import AsyncBroadcaster
            client = InternalClient(tracer=self.tracer,
                                    ssl_context=client_ssl_context)
            # Membership/cache messages ride a queued, retried async
            # path so a briefly-down peer doesn't miss them (reference
            # SendAsync over the gossip retransmit queue,
            # broadcast.go:30, gossip/gossip.go:306).
            self.broadcaster = AsyncBroadcaster(client, logger=self.logger)
            self.cluster_executor = ClusterExecutor(
                self.executor, cluster, client,
                broadcaster=self.broadcaster, stats=self.stats)
            self.syncer = HolderSyncer(holder, cluster, client)
            self.resize_puller = ResizePuller(holder, cluster, client)
            self.executor.key_resolver = self._resolve_key_via_primary
            self.executor.id_resolver = self._resolve_ids_via_primary
            self._client = client

    # -------------------------------------------------- translation primary

    def _translate_primary(self):
        """The pinned primary allocates all keys (default: lexically-
        first member; pinned before any dynamic membership change so a
        joiner cannot steal primacy with an empty store — the reference
        pins the translate source by ring position,
        cluster.go:1908-1935)."""
        return self.cluster.translate_primary()

    def _resolve_key_via_primary(self, index: str, field: Optional[str],
                                 keys: List[str]) -> List[int]:
        """Batch key allocation on the primary — one round trip per call,
        however many keys (the bulk-import path resolves thousands)."""
        primary = self._translate_primary()
        if primary.id == self.cluster.local.id:
            return self.translate_keys_local(index, field, keys)
        import json as _json
        body = _json.dumps({"index": index, "field": field,
                            "keys": list(keys)}).encode()
        res = self._client._req(
            "POST", f"{primary.uri}/internal/translate/keys", body)
        # Adopt the primary's allocation locally so result translation and
        # replicas stay consistent.
        store = self._translate_store(index, field)
        store.apply_entries(zip(res["keys"], res["ids"]))
        return [int(i) for i in res["ids"]]

    def _translate_store(self, index: str, field: Optional[str]):
        idx = self._index(index)
        if field is None:
            return idx.column_translator
        return self._field(idx, field).row_translator

    def translate_keys_local(self, index: str, field: Optional[str],
                             keys: List[str]) -> List[int]:
        """Allocate ids locally (primary side of /internal/translate/keys,
        reference http/handler.go:274)."""
        store = self._translate_store(index, field)
        return [int(i) for i in store.translate_keys(keys)]

    def translate_ids_local(self, index: str, field: Optional[str],
                            ids: List[int]) -> List[Optional[str]]:
        """Reverse lookup (primary side of /internal/translate/ids)."""
        store = self._translate_store(index, field)
        return store.translate_ids([int(i) for i in ids])

    def _resolve_ids_via_primary(self, index: str, field: Optional[str],
                                 ids: List[int]) -> List[Optional[str]]:
        """ids -> keys with primary fallback: the local replica of the
        translate log streams asynchronously (reference translate.go:400
        replicate loop), so a read landing between allocation and replay
        would otherwise miss. Local hits stay local; misses take one batch
        round trip to the primary and are adopted into the local store."""
        store = self._translate_store(index, field)
        keys = store.translate_ids([int(i) for i in ids])
        # The negative cache is only valid for the store state it was
        # built against: any local growth (write, replication catch-up,
        # adoption below) may have allocated a previously-missing id, so
        # drop the cache and re-ask the primary once.
        size = store.size()
        cached_size, neg = self._translate_negative.get(
            (index, field), (-1, set()))
        if cached_size != size:
            neg = set()
            self._translate_negative[(index, field)] = (size, neg)
        missing = [int(i) for i, k in zip(ids, keys)
                   if k is None and int(i) not in neg]
        if not missing:
            return keys
        primary = self._translate_primary()
        if primary.id == self.cluster.local.id:
            return keys
        import json as _json
        body = _json.dumps({"index": index, "field": field,
                            "ids": missing}).encode()
        try:
            res = self._client._req(
                "POST", f"{primary.uri}/internal/translate/ids", body)
            fetched = dict(zip(missing, res["keys"]))
        except Exception as e:
            self.logger.printf(
                "translate-id fallback to primary %s failed: %r",
                primary.uri, e)
            return keys
        store.apply_entries((k, i) for i, k in fetched.items()
                            if k is not None)
        # The primary is the allocator: an id it cannot resolve does not
        # exist anywhere, so cache the miss (bounded) instead of re-asking
        # on every query (raw-id imports into a keyed index hit this).
        if len(neg) < 100_000:
            neg.update(i for i, k in fetched.items() if k is None)
        # Re-version against the post-adoption store size so the adoption
        # itself doesn't invalidate the misses just cached.
        # graftlint: disable=GL008 — keyed by (index, field): schema-
        # bounded, and each value's miss-set is capped above.
        self._translate_negative[(index, field)] = (store.size(), neg)
        return [k if k is not None else fetched.get(int(i))
                for i, k in zip(ids, keys)]

    # ----------------------------------------------------------------- query

    def _observe_query(self, index: str, query, dur: float,
                       profile=None, error=None,
                       kind: str = "query") -> None:
        """The single slow-query/stats sink for every query path —
        slow-query logging (reference api.LongQueryTime api.go:1048) +
        the structured ring at GET /debug/queries + the executor.*
        stats feed, in one place instead of per-path printf copies."""
        self.profiler.observe(index, query, dur, profile=profile,
                              error=error,
                              long_query_time=self.long_query_time,
                              logger=self.logger, kind=kind)
        # Cheap (one len() under a lock) and refreshed on the query
        # path, so /metrics tracks compile-cache pressure live.
        self.stats.gauge("executor.jit_cache_size",
                         self.executor.jit_cache_size())

    def begin_request(self, index: str):
        """Open a request record under the SAME trace id outgoing legs
        and the profiler carry (minting one when the request arrived
        without a traceparent), so /debug/queries, exported spans and
        /debug/timeline all cross-link by it. The HTTP handler calls
        this before it reads the body and end_request() after the
        socket write, so the record tiles the whole exchange; query()
        opens its own for callers that hand it none."""
        tid = getattr(self.tracer, "ensure_trace_id", lambda: None)()
        rec = TIMELINE.begin(tid, index, stats=self.stats)
        if rec is not None and hasattr(self.tracer, "adopt"):
            # Outgoing node-to-node legs name the root as their parent.
            self.tracer.adopt(rec.trace_id, rec.root)
        self.held.open()
        return rec

    def end_request(self, rec, err=None) -> None:
        """Close what begin_request opened. `rec` is None for a request
        the timeline keeps no record of; the server held it all the
        same, so whoever called begin_request calls this."""
        try:
            TIMELINE.finish(rec, error=err)
            # The request is over: drop the thread-adopted trace id so
            # an embedded (non-HTTP) caller's next query on this thread
            # mints a fresh id instead of stitching every query into
            # one trace. (The HTTP layer already resets per request via
            # extract(); library callers have no such reset.)
            adopt = getattr(self.tracer, "adopt", None)
            if adopt is not None:
                adopt(None)
        finally:
            self.held.close()

    def _parse_stage(self, rec, query) -> Optional[bool]:
        """The `pql.parse` stage: parse the text once on the request's
        own thread (later stages take the parser's cached tree), name
        the record after its top-level calls, and say whether it
        writes. None = unparseable; the dispatch path reports that."""
        with TIMELINE.span(rec, "pql.parse"):
            try:
                q = parse_string_cached(query) \
                    if isinstance(query, str) else query
                if isinstance(q, Call):
                    q = Query([q])
                is_write = write_call_count(q) > 0
            except Exception:
                return None
        if rec is not None:
            rec.root.attrs["calls"] = ",".join(
                (c.children[0].name if c.name == "Options" and c.children
                 else c.name) for c in q.calls[:4])
        return is_write

    def query(self, index: str, query: str,
              shards: Optional[Sequence[int]] = None,
              remote: bool = False, profile: bool = False,
              record=None) -> Dict[str, Any]:
        """(reference API.Query, api.go:103). Returns the JSON-shaped
        response {"results": [...]}. `remote=True` marks a node-to-node
        sub-query: execute locally only, no re-fan-out (the reference's
        opt.Remote, executor.go:2236). `profile=True` (the
        ?profile=true surface) embeds the execution profile tree in the
        response with device-time fencing on. `record` is the request
        record the caller opened (and will finish); without one the
        query opens and finishes its own."""
        _FP_QUERY.fire(index=index, remote=remote)
        tl = record if record is not None else self.begin_request(index)
        prof = self.profiler.begin(index, query, shards,
                                   force=bool(profile))
        prof.timeline = tl
        t0 = _time.perf_counter()
        err = None
        try:
            self._parse_stage(tl, query)
            with TIMELINE.attached(tl):
                resp = self._query(index, query, shards, remote, prof)
            if profile:
                prof.close(_time.perf_counter() - t0)
                resp = dict(resp)
                resp["profile"] = prof.to_json()
            return resp
        except Exception as e:
            err = e
            raise
        finally:
            dur = _time.perf_counter() - t0
            self._observe_query(index, query, dur, prof, err)
            if record is None:
                self.end_request(tl, err)

    def query_coalesced(self, index: str, query,
                        shards: Optional[Sequence[int]] = None,
                        remote: bool = False, profile: bool = False,
                        record=None) -> Dict[str, Any]:
        """query() that rides the serving-path coalescer when one is
        attached and the request is eligible: concurrent single-query
        HTTP requests share one stacked executor batch (see
        server/coalescer.py). Degrades to the direct path when the
        coalescer is absent/stopped, on cluster deployments (the
        fan-out legs already pipeline per node), and for remote
        node-to-node legs (different response shaping)."""
        coal = self.coalescer
        if (coal is None or not coal.running or remote
                or self.cluster_executor is not None):
            return self.query(index, query, shards=shards, remote=remote,
                              profile=profile, record=record)
        _FP_QUERY.fire(index=index, remote=remote)
        from pilosa_tpu.server.coalescer import CoalescerStopped
        tl = record if record is not None else self.begin_request(index)
        prof = self.profiler.begin(index, query, shards,
                                   force=bool(profile))
        prof.timeline = tl
        t0 = _time.perf_counter()
        err = None
        try:
            is_write = self._parse_stage(tl, query)
            self.stats.count("query", 1)
            try:
                resp = coal.submit(index, query, shards=shards,
                                   profile=prof, is_write=is_write,
                                   held=self.held)
            except CoalescerStopped:
                # Lost the race with coalescer.stop(): serve the
                # request directly rather than failing it. (Only
                # this exception retries — a genuine executor
                # RuntimeError must surface, not re-run.) Inline
                # direct path, not self._query: "query" was already
                # counted above and must not double-count.
                with TIMELINE.attached(tl):
                    resp = self.executor.execute_full(
                        index, query, shards=shards, profile=prof)
            if tl is not None:
                prof.annotate_span(tl.root)
            if profile:
                # Forced profiles are excluded from coalescer dedup,
                # so resp is this request's own dict — still copy
                # before mutating (defense against future sharing).
                prof.close(_time.perf_counter() - t0)
                resp = dict(resp)
                resp["profile"] = prof.to_json()
            return resp
        except Exception as e:
            err = e
            raise
        finally:
            dur = _time.perf_counter() - t0
            self._observe_query(index, query, dur, prof, err)
            if record is None:
                self.end_request(tl, err)

    def _query(self, index: str, query: str,
               shards: Optional[Sequence[int]] = None,
               remote: bool = False, prof=None) -> Dict[str, Any]:
        self.stats.count("query", 1)
        try:
            if remote:
                # Node-to-node leg: results only; the coordinator owns
                # response shaping (columnAttrs etc).
                results = self.executor.execute(index, query,
                                                shards=shards,
                                                profile=prof)
                return {"results": [result_to_json(r)
                                    for r in results]}
            if self.cluster_executor is not None:
                from pilosa_tpu.pql import parse_string
                q = parse_string(query) if isinstance(query, str) \
                    else query
                resp = {"results": self.cluster_executor.execute(
                    index, q, shards=shards, profile=prof)}
                self._attach_column_attrs(index, q, resp)
                return resp
            return self.executor.execute_full(index, query,
                                              shards=shards,
                                              profile=prof)
        finally:
            tl = getattr(prof, "timeline", None)
            if tl is not None:
                prof.annotate_span(tl.root)

    def query_batch(self, items: Sequence[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
        """Execute N independent queries in one request with one
        pipelined device drain (Executor.execute_batch). Each item is
        {"index": str, "query": str, "shards"?: [int]}; the response
        list carries {"results": [...]} or {"error": "..."} per item —
        one bad query does not fail its batchmates.

        This is the serving-layer amortization of the per-request
        round trip: the reference's protocol already batches CALLS in
        one query string (executor.go:84); this batches QUERIES, so a
        client pays one HTTP round trip and the executor pays one
        device->host drain for N small queries. On the single-node
        path the dispatch/finalize pipeline spans the whole batch; on
        the cluster path items execute sequentially (fan-out legs
        already pipeline per node) — the HTTP round trip is still
        amortized."""
        if self.cluster_executor is not None:
            # self.query() counts the "query" stat per item.
            out = []
            for it in items:
                try:
                    out.append(self.query(it["index"], it["query"],
                                          shards=it.get("shards")))
                except Exception as e:
                    out.append({"error": str(e)})
            return out
        self.stats.count("query", len(items))
        t0 = _time.perf_counter()
        # Malformed items degrade per-item, same as execution errors.
        reqs = []
        shaped_err = {}
        for pos, it in enumerate(items):
            try:
                reqs.append((it["index"], it["query"],
                             it.get("shards")))
            except (KeyError, TypeError) as e:
                shaped_err[pos] = {"error": f"bad batch item: {e!r}"}
                reqs.append(None)
        shaped = self.executor.execute_batch_shaped(
            [r for r in reqs if r is not None])
        out = []
        bi = iter(shaped)
        for pos, r in enumerate(reqs):
            if r is None:
                out.append(shaped_err[pos])
                continue
            res = next(bi)
            out.append({"error": str(res)}
                       if isinstance(res, Exception) else res)
        dur = _time.perf_counter() - t0
        self._observe_query("*", f"{len(items)} queries", dur,
                            kind="batch")
        return out

    def _attach_column_attrs(self, index: str, q, resp: Dict[str, Any]
                             ) -> None:
        """Coordinator-side columnAttrs for the cluster path: if the query
        carries Options(columnAttrs=true), read attrs for every merged row
        column from the local (anti-entropy-replicated) attr store
        (reference executor.go:134-165)."""
        from pilosa_tpu.executor.executor import column_attr_sets
        if not any(c.name == "Options" and c.args.get("columnAttrs")
                   for c in q.calls):
            return
        idx = self.holder.index(index)
        if idx is None:
            return
        ids = sorted({int(c) for r in resp["results"]
                      if isinstance(r, dict) for c in r.get("columns", [])})
        resp["columnAttrs"] = column_attr_sets(
            idx, ids,
            resolve=lambda xs: self._resolve_ids_via_primary(index, None, xs))

    # ---------------------------------------------------------------- schema

    def schema(self) -> Dict[str, Any]:
        return {"indexes": self.holder.schema()}

    def _validate_normal(self, method: str) -> None:
        """Schema mutations are not allowed while RESIZING (reference
        api.validate against methodsNormal, api.go:76-99: only cluster
        messages, fragment streaming and abort run in that state; queries
        and imports additionally stay available here because reads route
        via the pre-change placement and writes go to the owner union)."""
        if self.cluster is None:
            return
        from pilosa_tpu.parallel.cluster import STATE_RESIZING
        if self.cluster.state == STATE_RESIZING:
            raise ApiError(
                f"api method {method} not allowed in state RESIZING", 409)

    def create_index(self, name: str, keys: bool = False,
                     track_existence: bool = True,
                     remote: bool = False) -> Dict[str, Any]:
        self._validate_normal("CreateIndex")
        try:
            idx = self.holder.create_index(name, keys=keys,
                                           track_existence=track_existence)
        except ValueError as e:
            raise ApiError(str(e), 409 if "exists" in str(e) else 400)
        self._broadcast_schema(remote, lambda uri: self._client
                               .create_index_node(uri, name,
                                                  {"keys": keys,
                                                   "trackExistence":
                                                   track_existence}))
        return {"name": idx.name}

    def _broadcast_schema(self, remote: bool, send) -> None:
        """Schema mutations replicate to every node (reference SendSync of
        create messages, server.go:485-620)."""
        if remote or self.cluster is None:
            return
        from pilosa_tpu.parallel.client import ClientError
        for node in self.cluster.nodes():
            if node.id == self.cluster.local.id:
                continue
            try:
                send(node.uri)
            except ClientError:
                pass  # healed by resize pull / anti-entropy

    def delete_index(self, name: str) -> None:
        self._validate_normal("DeleteIndex")
        try:
            self.holder.delete_index(name)
        except KeyError as e:
            raise ApiError(str(e), 404)

    def create_field(self, index: str, name: str,
                     options: Optional[dict] = None,
                     remote: bool = False) -> Dict[str, Any]:
        self._validate_normal("CreateField")
        idx = self._index(index)
        opts = FieldOptions()
        options = dict(options or {})
        mapping = {"type": "type", "cacheType": "cache_type",
                   "cacheSize": "cache_size", "min": "min", "max": "max",
                   "timeQuantum": "time_quantum", "keys": "keys",
                   "noStandardView": "no_standard_view",
                   "maxColumns": "max_columns"}
        for k, v in options.items():
            if k not in mapping:
                raise ApiError(f"unknown field option {k!r}")
            setattr(opts, mapping[k], v)
        try:
            f = idx.create_field(name, opts)
        except ValueError as e:
            raise ApiError(str(e), 409 if "exists" in str(e) else 400)
        self._broadcast_schema(remote, lambda uri: self._client
                               .create_field_node(uri, index, name,
                                                  dict(options)))
        return {"name": f.name}

    def delete_field(self, index: str, name: str) -> None:
        self._validate_normal("DeleteField")
        idx = self._index(index)
        try:
            idx.delete_field(name)
        except KeyError as e:
            raise ApiError(str(e), 404)

    # --------------------------------------------------------------- imports

    def import_bits(self, index: str, field: str, rows=None, columns=None,
                    row_keys=None, column_keys=None, timestamps=None,
                    clear: bool = False, remote: bool = False,
                    ignore_key_check: bool = False) -> None:
        """Bulk bit import (reference API.Import, api.go:814): translate
        keys, group bits by shard, forward to owner nodes, write the local
        subset, feed the existence field. Keyed index/field rejects raw
        ids unless ignore_key_check (reference api.go:836-860; forwarded
        legs are pre-translated, so remote implies it)."""
        idx = self._index(index)
        f = self._field(idx, field)
        if not remote and not ignore_key_check:
            if f.options.keys and row_keys is None and rows is not None:
                raise ApiError("row ids cannot be used because field uses "
                               "string keys")
            if idx.keys and column_keys is None and columns is not None:
                raise ApiError("column ids cannot be used because index "
                               "uses string keys")
        if column_keys is not None:
            if not idx.keys:
                raise ApiError(f"index {index} does not use column keys")
            columns = self.executor._resolve_col_keys(idx, list(column_keys))
        if row_keys is not None:
            if not f.options.keys:
                raise ApiError(f"field {field} does not use row keys")
            rows = self.executor._resolve_row_keys(idx, f, list(row_keys))
        rows = np.asarray(rows, dtype=np.uint64)
        columns = np.asarray(columns, dtype=np.uint64)
        if len(rows) != len(columns):
            raise ApiError("rows and columns length mismatch")
        ts = None
        if timestamps is not None:
            ts = [datetime.fromtimestamp(t) if isinstance(t, (int, float))
                  else (timeq.parse_timestamp(t) if isinstance(t, str) else t)
                  for t in timestamps]

        touched = np.unique(columns // np.uint64(SHARD_WIDTH)).tolist()
        if self.cluster is not None and not remote:
            self._import_fanout(index, field, rows, columns, timestamps,
                                clear, values=None)
            # AFTER the fan-out: peers invalidated now will re-discover
            # lists that already include the new shards.
            self.cluster_executor.note_written_shards(index, touched)
            return
        f.import_bits(rows, columns, timestamps=ts, clear=clear)
        if not clear:
            idx.add_existence(columns)
        if self.cluster_executor is not None:
            # Remote leg: local cache only; the coordinator pushes.
            self.cluster_executor.invalidate_shards_cache(index)

    def _import_fanout(self, index, field, rows, columns, timestamps,
                       clear, values) -> None:
        """Group bits by owning node and forward (reference api.go:838-888,
        errgroup-parallel per node)."""
        from pilosa_tpu.parallel.client import ClientError
        shards = columns // np.uint64(SHARD_WIDTH)
        by_node: Dict[str, List[int]] = {}
        for i, shard in enumerate(shards.tolist()):
            # write_nodes: current ∪ pre-resize owners while RESIZING.
            for node in self.cluster.write_nodes(index, int(shard)):
                by_node.setdefault(node.id, []).append(i)
        for node_id, idxs in by_node.items():
            node = self.cluster.node_by_id(node_id)
            body: Dict[str, Any] = {
                "columnIDs": [int(columns[i]) for i in idxs]}
            if values is not None:
                body["values"] = [int(values[i]) for i in idxs]
            else:
                body["rowIDs"] = [int(rows[i]) for i in idxs]
                if timestamps is not None:
                    body["timestamps"] = [timestamps[i] for i in idxs]
            if node_id == self.cluster.local.id:
                if values is not None:
                    self.import_values(index, field,
                                       columns=body["columnIDs"],
                                       values=body["values"], clear=clear,
                                       remote=True)
                else:
                    self.import_bits(index, field, rows=body["rowIDs"],
                                     columns=body["columnIDs"],
                                     timestamps=body.get("timestamps"),
                                     clear=clear, remote=True)
            else:
                try:
                    self._client.import_node(node.uri, index, field, body,
                                             clear=clear)
                except ClientError:
                    pass  # healed by anti-entropy

    def import_values(self, index: str, field: str, columns=None,
                      values=None, column_keys=None,
                      clear: bool = False, remote: bool = False,
                      ignore_key_check: bool = False) -> None:
        """(reference API.ImportValue, api.go:922; key check :944)."""
        idx = self._index(index)
        f = self._field(idx, field)
        if not remote and not ignore_key_check and idx.keys \
                and column_keys is None and columns is not None:
            raise ApiError("column ids cannot be used because index uses "
                           "string keys")
        if column_keys is not None:
            if not idx.keys:
                raise ApiError(f"index {index} does not use column keys")
            columns = self.executor._resolve_col_keys(idx, list(column_keys))
        columns = np.asarray(columns, dtype=np.uint64)
        values = np.asarray(values, dtype=np.int64)
        if len(columns) != len(values):
            raise ApiError("columns and values length mismatch")
        touched = np.unique(columns // np.uint64(SHARD_WIDTH)).tolist()
        if self.cluster is not None and not remote:
            self._import_fanout(index, field, None, columns, None, clear,
                                values=values)
            self.cluster_executor.note_written_shards(index, touched)
            return
        try:
            f.import_values(columns, values, clear=clear)
        except ValueError as e:
            raise ApiError(str(e))
        if not clear:
            idx.add_existence(columns)
        if self.cluster_executor is not None:
            self.cluster_executor.invalidate_shards_cache(index)

    def import_roaring(self, index: str, field: str, shard: int,
                       data: bytes, clear: bool = False,
                       view: str = "standard",
                       remote: bool = False) -> None:
        """Pre-serialized roaring import — the fastest path (reference
        API.ImportRoaring, api.go:291)."""
        idx = self._index(index)
        f = self._field(idx, field)
        frag = f.create_view_if_not_exists(view) \
            .create_fragment_if_not_exists(shard)
        try:
            payload = frag.import_roaring(data, clear=clear)
        except ValueError as e:
            raise ApiError(f"invalid roaring payload: {e}")
        # A union marks the payload's columns, not the fragment's: a
        # shard imported in row blocks holds 100 M bits by the last body.
        # A clear names bits that went, so it reads what stayed.
        bits = frag.storage if clear else payload
        cols = bits.slice() % np.uint64(SHARD_WIDTH) \
            + np.uint64(shard * SHARD_WIDTH)
        if len(cols):
            idx.add_existence(np.unique(cols))
        if self.cluster_executor is not None:
            if remote:
                self.cluster_executor.invalidate_shards_cache(index)
            else:
                self.cluster_executor.note_written_shards(index,
                                                          [int(shard)])

    # ---------------------------------------------------------------- export

    def export_csv(self, index: str, field: str, shard: int) -> str:
        """CSV rows 'row,col' for one shard, ids translated to keys on
        keyed fields/indexes (reference api.ExportCSV, api.go:430-500 —
        the per-bit translate in its write fn). Proper CSV quoting (the
        reference uses encoding/csv); untranslatable ids fall back to
        the decimal id, matching _translate_result's convention."""
        return "".join(export_fragment_lines(self._index(index), field,
                                             shard))

    # ------------------------------------------------------- sync primitives

    def fragment_blocks(self, index: str, field: str, view: str, shard: int):
        frag = self._fragment(index, field, view, shard)
        return [{"block": b, "checksum": c.hex()}
                for b, c in frag.checksum_blocks()]

    def fragment_block_data(self, index: str, field: str, view: str,
                            shard: int, block: int):
        frag = self._fragment(index, field, view, shard)
        rows, cols = frag.block_data(block)
        return {"rows": rows.tolist(), "columns": cols.tolist()}

    def fragment_data(self, index: str, field: str, view: str, shard: int
                      ) -> bytes:
        """Full fragment stream (reference GET /internal/fragment/data)."""
        return self._fragment(index, field, view, shard).write_bytes()

    def _attr_store(self, index: str, field: Optional[str]):
        """Column attrs (field=None) or a field's row attrs (reference
        index/field AttrStore split, index.go:35, field.go:62)."""
        idx = self._index(index)
        if field is None:
            return idx.column_attr_store
        return self._field(idx, field).row_attr_store

    def attr_blocks(self, index: str, field: Optional[str] = None):
        """(reference api.IndexAttrDiff/FieldAttrDiff block lists,
        api.go:716-812; attr.go:80-119)."""
        return [{"block": b, "checksum": c.hex()}
                for b, c in self._attr_store(index, field).blocks()]

    def attr_block_data(self, index: str, field: Optional[str],
                        block: int) -> Dict[str, Any]:
        store = self._attr_store(index, field)
        return {"attrs": {str(i): a
                          for i, a in store.block_data(block).items()}}

    def attr_merge(self, index: str, field: Optional[str],
                   attrs: Dict[str, Dict[str, Any]]) -> None:
        """Adopt attrs pulled from a replica during anti-entropy."""
        self._attr_store(index, field).set_bulk(
            {int(i): a for i, a in attrs.items()})

    def translate_data(self, index: str, field: Optional[str] = None,
                       offset: int = 0) -> bytes:
        idx = self._index(index)
        store = idx.column_translator if field is None \
            else self._field(idx, field).row_translator
        if self.cluster is not None \
                and self._translate_primary().id != self.cluster.local.id:
            # Restarted replica that hasn't re-streamed this boot: its
            # disk log may hold out-of-band adopted entries (holes in
            # the id order), which must not be spliced into a chained
            # successor's stream. Serve nothing until our own pull
            # re-establishes the streamed prefix. Check-and-set under
            # the store lock: a concurrent apply_log(resume) may have
            # just re-established the prefix and must not be clobbered.
            with store._lock:
                if store.served_limit is None:
                    store.served_limit = 0
        return store.read_log_from(offset)

    def recalculate_caches(self) -> None:
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        frag.cache.invalidate()
                        for r in frag.row_ids():
                            frag.cache.add(r, frag.row_count(r))

    # ------------------------------------------------- memory / health plane

    def refresh_memory_gauges(self) -> None:
        """Publish the memory-ledger gauges (pilosa_memory_bytes{category},
        pilosa_memory_padding_bytes{category}) plus the jit-cache size
        into the stats client. Called by the watchdog every sample and
        by the /metrics handler so a scrape is never staler than one
        request. Pure host-side dict reads — no device interaction."""
        import sys as _sys
        from pilosa_tpu.utils.hotspots import WORKLOAD
        from pilosa_tpu.utils.memledger import LEDGER
        from pilosa_tpu.utils.timeline import TIMELINE
        # Telemetry rings register their own bytes (category
        # "telemetry") before the ledger publishes, so /debug/memory
        # totals cover the observability plane itself.
        TIMELINE.register_memory(LEDGER)
        LEDGER.publish(self.stats)
        WORKLOAD.publish(self.stats)
        # Result-cache live gauges (hit/miss/eviction counters
        # increment at event time); the rank-cache store publishes its
        # entry/byte gauges the same way.
        from pilosa_tpu.core.cache import RANK_CACHE
        self.executor.result_cache.publish(self.stats)
        rsnap = RANK_CACHE.snapshot()
        self.stats.gauge("rank_cache.entries", rsnap["entries"])
        self.stats.gauge("rank_cache.bytes", rsnap["bytes"])
        # Hybrid-layout gauges (pilosa_layout_*): sparse-view count,
        # resident sparse-bank bytes, cumulative reclaimed bytes.
        self.layout.publish(self.stats)
        self.stats.gauge("executor.jit_cache_size",
                         self.executor.jit_cache_size())
        # Process identity on /metrics: uptime (previously only in the
        # node_health JSON) and the build-info constant gauge every
        # Prometheus setup joins version rollouts against.
        self.stats.gauge("process_uptime_seconds",
                         _time.time() - self._started_at)
        if self._build_backend in (None, "none"):
            backend = "none"
            jaxmod = _sys.modules.get("jax")
            if jaxmod is not None:
                try:
                    backend = str(jaxmod.default_backend())
                except Exception:
                    backend = "error"
            self._build_backend = backend
        self.stats.with_tags(f"version:{__version__}",
                             f"backend:{self._build_backend}").gauge(
            "build_info", 1)

    def debug_memory(self, top_k: int = 10) -> Dict[str, Any]:
        """The GET /debug/memory document: per-category live/padded
        bytes + the top-K largest resident banks (utils/memledger.py).
        `totalBytes` equals the sum of the per-category byte totals by
        construction (pinned by test)."""
        from pilosa_tpu.utils.memledger import LEDGER
        self.refresh_memory_gauges()
        doc = LEDGER.snapshot(top_k=top_k)
        # The hybrid-layout stanza rides the memory document (capacity
        # is exactly what re-layout acts on); a separate key, so the
        # totalBytes == sum(categories) invariant is untouched.
        doc["layout"] = self.layout.snapshot()
        return doc

    def debug_hotspots(self, top_k: Optional[int] = None
                       ) -> Dict[str, Any]:
        """The GET /debug/hotspots document (utils/hotspots.py):
        fragment/row/signature heatmaps, write churn, rolling-window
        repeat ratios, and the cache-opportunity report — signature
        saved-seconds estimates joined against profiler timings, bank
        density-vs-access quadrants joined against the memory ledger.
        Totals are provable from the document: totals.X == tracked.X +
        evicted.X (pinned by test)."""
        from pilosa_tpu.core.cache import RANK_CACHE
        from pilosa_tpu.utils.hotspots import WORKLOAD
        from pilosa_tpu.utils.memledger import LEDGER
        self.refresh_memory_gauges()
        doc = WORKLOAD.snapshot(
            top_k=top_k,
            bank_entries=LEDGER.entries("bank", "fragment_bank"))
        # The estimator finally gets validated: OBSERVED result-cache
        # hit ratios sit next to the PREDICTED estSavedS ranking built
        # from the same fingerprints, so over- or under-prediction is
        # one document read apart.
        rc = self.executor.result_cache.snapshot()
        doc["resultCache"] = rc
        doc["rankCache"] = RANK_CACHE.snapshot()
        doc["rankCache"]["hits"] = self.executor.rank_cache_hits
        doc["rankCache"]["patches"] = self.executor.rank_cache_patches
        doc["rankCache"]["rebuilds"] = self.executor.rank_cache_rebuilds
        doc["opportunity"]["observed"] = {
            "hits": rc["hits"],
            "misses": rc["misses"],
            "hitRatio": rc["hitRatio"],
            "predictedTotalEstSavedS":
                doc["opportunity"]["totalEstSavedS"],
        }
        return doc

    def _node_ident(self):
        if self.cluster is not None:
            return self.cluster.local.id, self.cluster.local.uri
        return self.holder.node_id, ""

    def debug_timeline(self, last: Optional[int] = None,
                       trace: Optional[str] = None,
                       slowest: bool = False) -> Dict[str, Any]:
        """The GET /debug/timeline document (utils/timeline.py):
        Chrome trace-event JSON for the last N recorded requests (or
        one trace id, or with `slowest` the longest records since
        start), loadable directly in Perfetto/chrome://tracing, plus
        per-stage medians and per-call-name stage means."""
        from pilosa_tpu.utils.timeline import TIMELINE
        node_id, _ = self._node_ident()
        self.refresh_memory_gauges()
        return TIMELINE.snapshot(last=last, trace_id=trace,
                                 node_id=node_id, slowest=slowest)

    @staticmethod
    def _merge_timeline_events(pid: int, node_id: str,
                               doc: Dict[str, Any]) -> List[Dict[str, Any]]:
        """One node's trace events re-based under a merged pid, each
        slice stamped with the node id it came from (down in `args` —
        Perfetto's process track already shows it, but the JSON must be
        self-describing too)."""
        from pilosa_tpu.utils.timeline import TimelineRecorder
        evs = TimelineRecorder.process_metadata(pid, node_id)
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "X" and ev.get("name") != "thread_name":
                continue  # the process is re-named per pid above
            ev = dict(ev)
            ev["pid"] = pid
            args = dict(ev.get("args") or {})
            args["node"] = node_id
            ev["args"] = args
            evs.append(ev)
        return evs

    def cluster_timeline(self, trace_id: str) -> Dict[str, Any]:
        """The GET /cluster/timeline/{trace} document: every member's
        timeline slices for one trace id assembled into a single
        trace-event JSON — the coordinator is pid 0, each remote node
        its own pid (legs joined by the W3C traceparent the cluster
        already propagates, so a cross-node query reads as one
        timeline). An unreachable node is REPORTED with its error,
        never dropped — its missing leg is exactly the blind spot an
        operator must see."""
        import threading as _threading
        node_id, uri = self._node_ident()
        local = self.debug_timeline(trace=trace_id)
        if self.cluster is None:
            nodes = [{"id": node_id, "uri": uri, "healthy": True,
                      "down": False,
                      "events": local["summary"]["requests"]}]
            return {"traceId": trace_id, "totalNodes": 1,
                    "respondedNodes": 1, "nodes": nodes,
                    "displayTimeUnit": "ms",
                    "traceEvents": self._merge_timeline_events(
                        0, node_id, local)}
        docs: Dict[str, Dict[str, Any]] = {}
        down = set(getattr(self.cluster, "down_ids", set()))

        def fetch(node):
            if node.id == self.cluster.local.id:
                docs[node.id] = local
                return
            try:
                doc = self._client.node_timeline(node.uri, trace_id)
                if not isinstance(doc, dict):
                    raise ValueError(f"bad timeline body: {doc!r}")
                docs[node.id] = doc
            except Exception as e:
                docs[node.id] = {"error": f"{type(e).__name__}: {e}"}

        # Coordinator first, then cluster order — pid 0 is always the
        # node that assembled the document.
        members = sorted(self.cluster.nodes(),
                         key=lambda n: n.id != self.cluster.local.id)
        threads = [_threading.Thread(target=fetch, args=(n,))
                   for n in members]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        nodes = []
        events: List[Dict[str, Any]] = []
        for pid, node in enumerate(members):
            doc = docs.get(node.id, {"error": "no response"})
            entry: Dict[str, Any] = {"id": node.id, "uri": node.uri,
                                     "pid": pid,
                                     "healthy": "error" not in doc,
                                     "down": node.id in down}
            if entry["down"]:
                entry["healthy"] = False
            if "error" in doc:
                entry["error"] = doc["error"]
            else:
                entry["events"] = doc.get("summary", {}).get(
                    "requests", 0)
                events.extend(self._merge_timeline_events(pid, node.id,
                                                          doc))
            nodes.append(entry)
        return {"traceId": trace_id, "totalNodes": len(nodes),
                "respondedNodes": sum(1 for n in nodes
                                      if "error" not in n),
                "nodes": nodes, "displayTimeUnit": "ms",
                "traceEvents": events}

    def node_health(self) -> Dict[str, Any]:
        """This node's health document (GET /internal/health): memory
        ledger totals, coalescer queue depth, jit-cache/retrace/fusion
        counters, slow-query count, watchdog state. The coordinator's
        cluster_health() merges one of these per node."""
        from pilosa_tpu.executor import megakernel as _megamod
        from pilosa_tpu.utils.hotspots import WORKLOAD
        from pilosa_tpu.utils.jaxenv import COMPILES as _COMPILES
        from pilosa_tpu.utils.memledger import LEDGER
        from pilosa_tpu.utils.timeline import TIMELINE as _TIMELINE
        now = _time.time()
        if self.cluster is not None:
            node_id, uri = self.cluster.local.id, self.cluster.local.uri
            state = self.cluster.state
        else:
            node_id, uri, state = self.holder.node_id, "", "NORMAL"
        mem = LEDGER.snapshot(top_k=3)
        coal = self.coalescer
        wd = self.watchdog
        workload = WORKLOAD.summary()
        return {
            "id": node_id,
            "uri": uri,
            "state": state,
            "healthy": True,
            "time": now,
            "uptimeS": now - self._started_at,
            "memory": {
                "totalBytes": mem["totalBytes"],
                "deviceBytes": mem["deviceBytes"],
                "paddingBytes": mem["paddingBytes"],
                "categories": {c: t["bytes"]
                               for c, t in mem["categories"].items()},
            },
            "coalescer": {
                "attached": coal is not None,
                "running": bool(coal is not None and coal.running),
                "queueDepth": coal.queue_depth() if coal is not None
                else 0,
            },
            # Every XLA compile of the process (utils/jaxenv.py
            # CompileLog): totals here, the table by function name at
            # GET /debug/queries. `executor.retraces` below only sees
            # the executor's own jit cache.
            "xla": {k: v for k, v in _COMPILES.snapshot().items()
                    if k != "byName"},
            "executor": {
                "jitCacheSize": self.executor.jit_cache_size(),
                "retraces": self.executor.jit_compiles,
                "fusedDispatches": self.executor.fused_dispatches,
                "fusedQueries": self.executor.fused_queries,
                # Heterogeneous megakernel (executor/megakernel.py):
                # mixed-signature flushes collapsed to single
                # plan-buffer launches, and what those plans cost.
                "megakernelEnabled": _megamod.MEGAKERNEL_ENABLED,
                "megaLaunches": self.executor.mega_launches,
                "megaQueries": self.executor.mega_queries,
                "megaPlanEntries": self.executor.mega_plan_entries,
                "megaPlanBytes": self.executor.mega_plan_bytes,
                # Mesh cohort path (executor/megakernel.py under a
                # MeshContext, PILOSA_TPU_MESH): one plan buffer SPMD
                # over the shard axis, in-kernel collective reduce.
                "meshLaunches": self.executor.mesh_launches,
                "meshCollectiveBytes":
                    self.executor.mesh_collective_bytes,
                # Plan-IR verification gate (PILOSA_TPU_PLAN_VERIFY):
                # a nonzero reject count means a lowering bug raised
                # instead of executing — page-worthy.
                "planVerifyPasses": self.executor.plan_verify_passes,
                "planVerifyRejects": self.executor.plan_verify_rejects,
                # Plan optimizer (ops/plan_opt.py, PILOSA_TPU_PLAN_OPT):
                # how much work CSE / fold reordering / DCE shaved off
                # launched megakernel plans.
                "opt": {
                    "plans": self.executor.opt_plans,
                    "cseHits": self.executor.opt_cse_hits,
                    "entriesEliminated":
                        self.executor.opt_entries_eliminated,
                    "foldsReordered": self.executor.opt_folds_reordered,
                    "bytesSaved": self.executor.opt_bytes_saved,
                },
                # What the launched megakernel plans moved: the sum
                # of plan_cost's cumulative byte splits (a count from
                # shapes; the splits are in GET /debug/queries).
                "launchBytes": (self.executor.launch_bytes_gather
                                + self.executor.launch_bytes_compute
                                + self.executor.launch_bytes_expand
                                + self.executor.launch_bytes_pad),
            },
            # Cross-request cache tier (executor/result_cache.py +
            # core/cache.RANK_CACHE): hit ratios and live bytes in the
            # same health document capacity is judged from.
            "resultCache": self.executor.result_cache.snapshot(),
            "rankCache": {
                "hits": self.executor.rank_cache_hits,
                "patches": self.executor.rank_cache_patches,
                "rebuilds": self.executor.rank_cache_rebuilds,
            },
            # Cumulative, not ring occupancy (which saturates at the
            # ring bound) — fleet totals must reflect the actual rate.
            "slowQueries": self.profiler.slow_total,
            "slowRing": self.profiler.ring_count(),
            # Workload-shape summary (utils/hotspots.py): cumulative
            # read/write counters + live repeat ratios, so capacity
            # AND access skew read from one health document.
            "workload": workload,
            # Timeline plane (utils/timeline.py): request records
            # taken so far.
            "timeline": {
                "requestsRecorded": _TIMELINE.requests_recorded,
            },
            "watchdog": {
                "running": bool(wd is not None and wd.running),
                "samples": wd.samples_taken if wd is not None else 0,
                "lastSampleAt": (wd.last_sample_at if wd is not None
                                 else None),
            },
            # Adaptive hybrid layout (core/layout.py): how many views
            # serve sparse, what re-layout reclaimed, when it last ran
            # — the capacity axis in the same health document.
            "layout": self.layout.snapshot(),
            # Fault-injection plane (utils/failpoints.py): armed site
            # count + cumulative fires. Nonzero `armed` on a
            # production node is itself a finding.
            "failpoints": {k: v for k, v in FAILPOINTS.snapshot().items()
                           if k in ("armed", "fired")},
            # This node's view of the cluster lifecycle (bounded ring:
            # node-down/up, join/leave, resize begin/complete) — the
            # chaos-visible record GET /cluster/timeline merges
            # fleet-wide.
            "clusterEvents": (self.cluster.recent_events(32)
                              if self.cluster is not None else []),
            "placementGen": (self.cluster.placement_gen
                             if self.cluster is not None else 0),
        }

    @staticmethod
    def _merge_health_totals(nodes: List[Dict[str, Any]]
                             ) -> Dict[str, Any]:
        tot = {"memoryBytes": 0, "paddingBytes": 0, "queueDepth": 0,
               "jitCacheSize": 0, "retraces": 0, "slowQueries": 0,
               "fragmentReads": 0, "fragmentWrites": 0,
               "launchBytes": 0}
        for d in nodes:
            mem = d.get("memory") or {}
            tot["memoryBytes"] += int(mem.get("totalBytes", 0))
            tot["paddingBytes"] += int(mem.get("paddingBytes", 0))
            tot["queueDepth"] += int(
                (d.get("coalescer") or {}).get("queueDepth", 0))
            ex = d.get("executor") or {}
            tot["jitCacheSize"] += int(ex.get("jitCacheSize", 0))
            tot["retraces"] += int(ex.get("retraces", 0))
            tot["slowQueries"] += int(d.get("slowQueries", 0))
            wl = d.get("workload") or {}
            tot["fragmentReads"] += int(wl.get("fragmentReads", 0))
            tot["fragmentWrites"] += int(wl.get("fragmentWrites", 0))
            tot["launchBytes"] += int(ex.get("launchBytes", 0))
        return tot

    def cluster_health(self) -> Dict[str, Any]:
        """The GET /cluster/health document: one node_health() doc per
        member — the local one inline, remote ones fanned out over the
        internal client in parallel — merged with liveness (an
        unreachable node reports healthy=false with the error; a node
        the failure detector marks down reports down=true) and
        staleness (ageS: how old each node's self-report is). Totals
        aggregate memory/queue/jit/slow-query counters fleet-wide, so
        capacity pressure is one document away instead of N scrapes."""
        import threading as _threading
        now = _time.time()
        local = self.node_health()
        if self.cluster is None:
            local["down"] = False
            local["ageS"] = 0.0  # same doc shape as the clustered path
            nodes = [local]
            return {"state": "NORMAL", "totalNodes": 1,
                    "healthyNodes": 1, "nodes": nodes,
                    "totals": self._merge_health_totals(nodes)}
        docs: Dict[str, Dict[str, Any]] = {}
        down = set(getattr(self.cluster, "down_ids", set()))

        def fetch(node):
            if node.id == self.cluster.local.id:
                docs[node.id] = local
                return
            try:
                doc = self._client.node_health(node.uri)
                if not isinstance(doc, dict):
                    raise ValueError(f"bad health body: {doc!r}")
            except Exception as e:
                doc = {"id": node.id, "uri": node.uri, "healthy": False,
                       "error": f"{type(e).__name__}: {e}"}
            # Coordinator-clock receipt stamp: ageS must measure how
            # old the self-report is ON OUR CLOCK, not the cross-host
            # skew a doc["time"] comparison would report.
            doc["_received"] = _time.time()
            docs[node.id] = doc

        members = list(self.cluster.nodes())
        threads = [_threading.Thread(target=fetch, args=(n,))
                   for n in members]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        nodes = []
        end = _time.time()
        for node in members:
            doc = docs.get(node.id,
                           {"id": node.id, "uri": node.uri,
                            "healthy": False, "error": "no response"})
            doc.setdefault("id", node.id)
            doc.setdefault("uri", node.uri)
            doc["down"] = node.id in down
            if doc["down"]:
                doc["healthy"] = False
            received = doc.pop("_received", now)
            doc["ageS"] = max(0.0, end - received)
            nodes.append(doc)
        healthy = [d for d in nodes if d.get("healthy")]
        # Totals aggregate every node that RESPONDED — a down-marked
        # but still-answering node's banks are real fleet HBM and must
        # not vanish from the capacity number just because the failure
        # detector distrusts the node.
        responded = [d for d in nodes if "memory" in d]
        return {
            "state": self.cluster.state,
            "coordinator": next((n.id for n in members
                                 if n.is_coordinator), None),
            "totalNodes": len(nodes),
            "healthyNodes": len(healthy),
            "nodes": nodes,
            "totals": self._merge_health_totals(responded),
        }

    def cluster_timeline_events(self) -> Dict[str, Any]:
        """The GET /cluster/timeline document (no trace id): every
        member's cluster lifecycle event ring — heartbeat down/up
        verdicts, membership changes, resize begin/complete — merged
        chronologically, each event stamped with the node that
        OBSERVED it, plus Chrome trace-event instants (`ph:"i"`) so
        the same document loads in Perfetto beside the per-request
        timelines. A chaos kill and its recovery are visible here and
        in /cluster/health, by design (ROADMAP item 3)."""
        from pilosa_tpu.utils.timeline import TimelineRecorder
        health = self.cluster_health()
        merged: List[Dict[str, Any]] = []
        trace_events: List[Dict[str, Any]] = []
        for pid, nd in enumerate(health["nodes"]):
            evs = nd.get("clusterEvents") or []
            if evs:
                trace_events.extend(TimelineRecorder.process_metadata(
                    pid, str(nd.get("id", pid))))
            for ev in evs:
                merged.append({**ev, "observer": nd.get("id")})
                trace_events.append({
                    "ph": "i", "s": "g", "pid": pid, "tid": 0,
                    "ts": float(ev.get("time", 0.0)) * 1e6,
                    "name": ev.get("type", "event"),
                    "args": {k: v for k, v in ev.items()
                             if k not in ("time", "type")},
                })
        merged.sort(key=lambda e: e.get("time", 0.0))
        return {
            "state": health["state"],
            "totalNodes": health["totalNodes"],
            "respondedNodes": sum(1 for n in health["nodes"]
                                  if "clusterEvents" in n),
            "events": merged,
            "displayTimeUnit": "ms",
            "traceEvents": trace_events,
        }

    # ------------------------------------------------- fault injection

    def failpoints_snapshot(self) -> Dict[str, Any]:
        """GET /internal/failpoints: registered sites, armed specs,
        hit counts. Test-only: 403 unless the plane was enabled at
        boot (any failpoint config present) or by a test harness."""
        self._failpoints_gate()
        return FAILPOINTS.snapshot()

    def failpoints_update(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST /internal/failpoints: body {"arm": {site: spec},
        "disarm": [site, ...], "disarm_all": bool}. Disarms apply
        before arms so one request can atomically retarget the plane."""
        self._failpoints_gate()
        try:
            if body.get("disarm_all"):
                FAILPOINTS.disarm_all()
            for name in body.get("disarm") or []:
                FAILPOINTS.disarm(name)
            for name, spec in (body.get("arm") or {}).items():
                FAILPOINTS.arm(name, str(spec))
        except (KeyError, ValueError) as e:
            raise ApiError(str(e), 400)
        return FAILPOINTS.snapshot()

    @staticmethod
    def _failpoints_gate() -> None:
        if not FAILPOINTS.http_enabled:
            raise ApiError(
                "failpoints surface disabled (enable with "
                "PILOSA_TPU_FAILPOINTS / [failpoints] config at boot)",
                403)

    def cluster_hotspots(self, top_k: Optional[int] = None
                         ) -> Dict[str, Any]:
        """The GET /cluster/hotspots document: one debug_hotspots()
        snapshot per member — local inline, remote fanned out in
        parallel over the internal client (mirroring cluster_health) —
        with fleet totals. An unreachable node is REPORTED with its
        error, never dropped: a missing node's hotspots are exactly
        the blind spot an operator must see."""
        import threading as _threading
        local = self.debug_hotspots(top_k=top_k)
        if self.cluster is None:
            nodes = [{"id": self.holder.node_id, "uri": "",
                      "healthy": True, "hotspots": local}]
            return {"totalNodes": 1, "respondedNodes": 1,
                    "nodes": nodes,
                    "totals": self._merge_hotspot_totals(nodes)}
        docs: Dict[str, Dict[str, Any]] = {}
        down = set(getattr(self.cluster, "down_ids", set()))

        def fetch(node):
            if node.id == self.cluster.local.id:
                docs[node.id] = {"id": node.id, "uri": node.uri,
                                 "healthy": True, "hotspots": local}
                return
            try:
                doc = self._client.node_hotspots(node.uri,
                                                 top_k=top_k)
                if not isinstance(doc, dict):
                    raise ValueError(f"bad hotspots body: {doc!r}")
                docs[node.id] = {"id": node.id, "uri": node.uri,
                                 "healthy": True, "hotspots": doc}
            except Exception as e:
                docs[node.id] = {"id": node.id, "uri": node.uri,
                                 "healthy": False,
                                 "error": f"{type(e).__name__}: {e}"}

        members = list(self.cluster.nodes())
        threads = [_threading.Thread(target=fetch, args=(n,))
                   for n in members]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        nodes = []
        for node in members:
            doc = docs.get(node.id,
                           {"id": node.id, "uri": node.uri,
                            "healthy": False, "error": "no response"})
            doc["down"] = node.id in down
            if doc["down"]:
                doc["healthy"] = False
            nodes.append(doc)
        return {
            "totalNodes": len(nodes),
            "respondedNodes": sum(1 for d in nodes if "hotspots" in d),
            "nodes": nodes,
            "totals": self._merge_hotspot_totals(nodes),
        }

    @staticmethod
    def _merge_hotspot_totals(nodes: List[Dict[str, Any]]
                              ) -> Dict[str, Any]:
        """Fleet-wide workload totals over every node that RESPONDED
        (same rule as the health totals: a down-marked node that still
        answers contributes — its reads are real traffic)."""
        tot = {"fragmentReads": 0, "fragmentWrites": 0, "queries": 0,
               "windowSeen": 0, "windowRepeats": 0}
        for d in nodes:
            hs = d.get("hotspots") or {}
            t = hs.get("totals") or {}
            tot["fragmentReads"] += int(t.get("fragmentReads", 0))
            tot["fragmentWrites"] += int(t.get("fragmentWrites", 0))
            tot["queries"] += int(t.get("queries", 0))
            w = hs.get("queriesWindow") or {}
            tot["windowSeen"] += int(w.get("seen", 0))
            tot["windowRepeats"] += int(w.get("repeats", 0))
        tot["queryRepeatRatio"] = (
            tot["windowRepeats"] / tot["windowSeen"]
            if tot["windowSeen"] else 0.0)
        return tot

    # ---------------------------------------------------------------- status

    def local_shards(self) -> Dict[str, List[int]]:
        """Shards materialized on this node, per index (feeds cluster-wide
        shard discovery; the reference broadcasts availableShards,
        field.go:228)."""
        return {idx.name: idx.available_shards()
                for idx in self.holder.indexes.values()}

    def views_of(self, index: str, field: str) -> List[str]:
        idx = self._index(index)
        return sorted(self._field(idx, field).views.keys())

    def handle_join(self, node_info: dict) -> dict:
        """A node announces itself; topology updates and replicates, and
        this node drives the resize job (reference coordinator nodeJoin →
        generateResizeJob, cluster.go:1017-1230). The cluster enters
        RESIZING with the pre-join placement pinned for reads; every node
        pulls its newly-owned fragments; on completion NORMAL is broadcast
        and the new placement takes over."""
        if self.cluster is None:
            raise ApiError("not clustered", 400)
        from pilosa_tpu.parallel.cluster import Node, STATE_RESIZING
        from pilosa_tpu.parallel.client import ClientError
        node = Node.from_json(node_info)
        # The safe read placement to broadcast is the OLDEST in-flight
        # snapshot (begin_resize pins and returns it atomically), not the
        # current membership: with overlapping joins, a node added by an
        # unfinished earlier resize may not hold its shards yet, so late
        # joiners must route reads all the way back to where the data is
        # guaranteed to live.
        existing = self.cluster.node_by_id(node.id)
        if existing is not None and self.cluster.state != STATE_RESIZING:
            # Idempotent rejoin (a restarted member re-announcing through
            # its seeds, reference cluster.go:1028 nodeJoin "node already
            # in cluster"): no data moved, so no resize — just hand back
            # the current topology. A changed URI (restart on a new
            # address with a stable holder id) must replicate, or every
            # other member keeps dialing the dead one.
            if existing.uri != node.uri:
                existing.uri = node.uri
                self.cluster.save()
                for peer in self.cluster.nodes():
                    if peer.id in (self.cluster.local.id, node.id):
                        continue
                    self.broadcaster.send_now_or_queue(
                        peer.uri, {"type": "topology", "complete": True,
                                   "nodes": [n.to_json() for n in
                                             self.cluster.nodes()]})
            return self.cluster.status()
        prev = [n.to_json() for n in self.cluster.begin_resize()]
        # Pin the translation primary to a PRE-join member: the joiner's
        # empty key store must never become the allocator.
        tp = self.cluster.pin_translate_primary()
        self.cluster.add_node(node)
        for peer in self.cluster.nodes():
            if peer.id in (self.cluster.local.id, node.id):
                continue
            # Sync-first with queued fallback: a reachable peer MUST see
            # the membership change before the resize job's direct
            # resize_pull RPC reaches it, or it pulls against stale
            # placement and the job can finalize with data unmoved.
            self.broadcaster.send_now_or_queue(
                peer.uri, {"type": "node-join", "node": node.to_json(),
                           "prev": prev, "translatePrimary": tp})
        # The joining node adopts the full topology AND the in-flight
        # resize state, so queries it coordinates keep routing reads via
        # the pre-join placement too. (It also gets the same payload in
        # the join RESPONSE — this push covers operator-driven joins
        # where the joiner never called /internal/join itself.)
        try:
            self._client.cluster_message(
                node.uri, {"type": "topology", "complete": True,
                           "nodes": [n.to_json()
                                     for n in self.cluster.nodes()],
                           "prev": prev, "translatePrimary": tp})
        except ClientError:
            pass
        self._start_resize_job()
        return self.cluster.status()

    def join_via_seeds(self, seeds, attempts: int = 1,
                       retry_delay: float = 2.0) -> dict:
        """Announce this node to an existing cluster through any seed —
        the reference's memberlist seed join (gossip/gossip.go:65
        memberlist.Join; join event → coordinator resize,
        cluster.go:1676-1715) without gossip: POST /internal/join to the
        first reachable seed and adopt the returned topology + in-flight
        resize state synchronously (the seed also pushes the same
        payload as a topology message — either arrival order works; the
        handlers are idempotent). The seed drives the resize; this node
        answers its /internal/resize/pull once its server is listening.

        Raises ApiError when every seed is unreachable after
        `attempts` passes over the list (callers that must not fail the
        boot run this in a retry loop — cli cmd_server)."""
        if self.cluster is None:
            raise ApiError("not clustered", 400)
        import json as _json
        import time as _time

        from pilosa_tpu.parallel.client import ClientError
        body = _json.dumps(self.cluster.local.to_json()).encode()
        last: Optional[Exception] = None
        for attempt in range(max(1, attempts)):
            if attempt:
                _time.sleep(retry_delay)
            for seed in seeds:
                if not seed or seed == self.cluster.local.uri:
                    continue
                try:
                    status = self._client._req(
                        "POST", f"{seed}/internal/join", body)
                except ClientError as e:
                    last = e
                    continue
                self.handle_cluster_message({
                    "type": "topology", "complete": True,
                    "nodes": status.get("nodes", []),
                    "prev": status.get("prevNodes"),
                    "translatePrimary": status.get("translatePrimary"),
                })
                return status
        raise ApiError(f"no seed reachable (tried {list(seeds)}): {last}",
                       503)

    def _start_resize_job(self) -> None:
        """Run the data motion for a topology change: every member pulls
        the fragments it now owns (POST /internal/resize/pull — the analog
        of the reference's ResizeInstruction fan-out + ACKs,
        cluster.go:1458-1530), then broadcast resize-complete. On any pull
        failure the cluster STAYS RESIZING — reads keep the safe
        pre-change placement — until a retry succeeds or an operator
        aborts (/cluster/resize/abort)."""
        if self.resize_puller is None:
            return
        import threading

        # Captured AFTER the topology change this job serves: if a newer
        # change bumps the generation while pulls run, this job must NOT
        # finalize — the newer job's completion (whose pulls cover the
        # newest placement) will.
        gen0 = self.cluster.resize_gen

        def pull_one(node, errors):
            try:
                _FP_RESIZE_RPC.fire(uri=node.uri, node=node.id)
                if node.id == self.cluster.local.id:
                    self.resize_puller.pull_owned()
                else:
                    self._client.resize_pull(node.uri)
            except Exception as e:
                errors.append((node.id, e))
                self.logger.printf("resize: pull on %s failed: %r",
                                   node.id, e)

        def run():
            errors: list = []
            threads = [threading.Thread(target=pull_one, args=(n, errors))
                       for n in self.cluster.nodes()]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                self.logger.printf(
                    "resize: %d node(s) failed to pull; cluster stays "
                    "RESIZING (reads keep pre-change placement); retry "
                    "with /internal/join or /cluster/resize/abort",
                    len(errors))
                return
            if self.cluster.resize_gen != gen0:
                self.logger.printf(
                    "resize: superseded by a newer topology change; "
                    "leaving finalization to the newer job")
                return
            self._finish_resize()

        threading.Thread(target=run, daemon=True).start()

    def _moved_shards(self) -> set:
        """Shards whose owner set differs between the pinned pre-change
        placement and the current one — the set placement-change cache
        invalidation must cover. Must run while `prev_nodes` is still
        pinned (before end_resize clears it); pure host placement math,
        no RPCs."""
        moved: set = set()
        if self.cluster is None or self.cluster.prev_nodes is None:
            return moved
        for iname, idx in list(self.holder.indexes.items()):
            for shard in idx.available_shards():
                prev = [n.id for n in self.cluster.shard_nodes(
                    iname, int(shard), previous=True)]
                cur = [n.id for n in self.cluster.shard_nodes(
                    iname, int(shard))]
                if prev != cur:
                    moved.add((iname, int(shard)))
        return moved

    def _note_placement_change(self, moved: set) -> None:
        """The resize just adopted a new placement: drop result/rank
        cache entries covering shards whose ownership moved (the PR 10
        epoch-guard pattern keyed on placement, not fragment,
        generations). The version stamps already make a stale HIT
        impossible — this makes the stale BYTES provably gone at the
        transition, and the counter makes it observable."""
        if not moved:
            return
        from pilosa_tpu.core.cache import RANK_CACHE
        dropped = self.executor.result_cache.invalidate_placement(moved)
        dropped += RANK_CACHE.invalidate_shards(moved)
        self.stats.count("cluster.placement_invalidations", dropped)
        self.logger.printf(
            "resize: placement change moved %d shard(s); dropped %d "
            "result/rank cache entr%s (placement gen %d)",
            len(moved), dropped, "y" if dropped == 1 else "ies",
            self.cluster.placement_gen)

    def _finish_resize(self) -> None:
        """Adopt the new placement everywhere (reference: job DONE → save
        topology, broadcast NORMAL, cluster.go:1048-1060). The broadcast
        carries the membership it completes, so a peer that already saw a
        newer topology change ignores it and stays safely RESIZING; it
        rides the retried async queue so a briefly-down peer converges
        instead of sticking RESIZING forever."""
        members = self.cluster.member_ids()
        moved = self._moved_shards()
        self.cluster.end_resize()
        self._note_placement_change(moved)
        # The pinned translate primary rides along as a second chance for
        # any peer that missed the node-join/leave broadcast carrying it
        # (divergent pins would mint colliding ids indefinitely).
        tp = self.cluster.translate_primary_id
        for peer in self.cluster.nodes():
            if peer.id == self.cluster.local.id:
                continue
            self.broadcaster.send_now_or_queue(
                peer.uri, {"type": "resize-complete", "members": members,
                           **({"translatePrimary": tp} if tp else {})})

    def resize_pull(self) -> dict:
        """One synchronous pull pass (the receiving side of the resize
        job; reference followResizeInstruction, cluster.go:1251-1360)."""
        if self.resize_puller is None:
            return {"fetched": 0}
        return {"fetched": self.resize_puller.pull_owned()}

    def handle_cluster_message(self, msg: dict) -> None:
        """(reference receiveMessage dispatch, server.go:485-580)."""
        if self.cluster is None:
            return
        from pilosa_tpu.parallel.cluster import Node
        typ = msg.get("type")
        if msg.get("translatePrimary"):
            self.cluster.pin_translate_primary(msg["translatePrimary"])
            if msg["translatePrimary"] == self.cluster.local.id:
                self._lift_translate_serving()
        if typ == "node-join":
            prev = [Node.from_json(nd) for nd in msg["prev"]] \
                if msg.get("prev") else None
            self.cluster.begin_resize(prev)
            self.cluster.add_node(Node.from_json(msg["node"]))
        elif typ == "node-leave":
            if msg["nodeID"] == self.cluster.local.id:
                # We were removed: detach to a single-node topology so we
                # stop routing/syncing with stale membership.
                self.cluster.end_resize()
                for n in list(self.cluster.nodes()):
                    if n.id != self.cluster.local.id:
                        self.cluster.remove_node(n.id)
            else:
                prev = [Node.from_json(nd) for nd in msg["prev"]] \
                    if msg.get("prev") else None
                self.cluster.begin_resize(prev)
                self.cluster.remove_node(msg["nodeID"])
        elif typ == "shards-changed":
            # A peer created new shards: drop the cached global shard
            # list so the next read re-discovers (the pull-model
            # counterpart of the reference's CreateShardMessage).
            if self.cluster_executor is not None:
                self.cluster_executor.invalidate_shards_cache(msg["index"])
        elif typ == "resize-complete":
            members = msg.get("members")
            if members is None or \
                    self.cluster.owners_match_membership(members):
                moved = self._moved_shards()
                self.cluster.end_resize()
                self._note_placement_change(moved)
        elif typ == "topology":
            if msg.get("prev"):
                self.cluster.begin_resize(
                    [Node.from_json(nd) for nd in msg["prev"]])
            incoming = [Node.from_json(nd) for nd in msg.get("nodes", [])]
            for node in incoming:
                self.cluster.add_node(node)
            if msg.get("complete"):
                # The sender's view is the FULL membership: drop local
                # members absent from it (a node rejoining with a stale
                # persisted .topology would otherwise resurrect ghosts
                # removed while it was down). Never self-detach here —
                # node-leave owns that transition.
                keep = {n.id for n in incoming} | {self.cluster.local.id}
                for n in list(self.cluster.nodes()):
                    if n.id not in keep:
                        self.cluster.remove_node(n.id)
        elif typ == "set-coordinator":
            for n in self.cluster.nodes():
                n.is_coordinator = (n.id == msg.get("nodeID"))
            self.cluster.save()

    def fragment_nodes(self, index: str, shard: int) -> List[dict]:
        """Nodes owning a shard (reference GetFragmentNodes,
        http/handler.go + api.ShardNodes)."""
        self._index(index)  # 404 on unknown index
        if self.cluster is None:
            return [{"id": "local", "uri": "", "isCoordinator": True}]
        return [n.to_json()
                for n in self.cluster.shard_nodes(index, int(shard))]

    def remove_node(self, node_id: str) -> dict:
        """Remove a node from the cluster and rebalance (reference
        api.RemoveNode, api.go:1084-1141; resize job cluster.go:1150).
        Remaining owners pull newly-owned fragments from replicas."""
        if self.cluster is None:
            raise ApiError("not clustered", 400)
        from pilosa_tpu.parallel.client import ClientError
        if self.cluster.node_by_id(node_id) is None:
            raise ApiError(f"node not found: {node_id}", 404)
        if node_id == self.cluster.local.id:
            raise ApiError("cannot remove the receiving node; send the "
                           "request to another node", 400)
        removed = self.cluster.node_by_id(node_id)
        prev = [n.to_json() for n in self.cluster.begin_resize()]
        was_primary = self.cluster.translate_primary().id == node_id
        tp = None
        if was_primary:
            # Catch our replica up from the departing primary while it is
            # still reachable, then promote OURSELVES: this node's store
            # is the one we just made complete — promoting any other
            # survivor could crown a lagging replica that would mint
            # colliding ids. Known limits without a consensus protocol
            # (accepted, logged): a key allocated on the old primary
            # AFTER this sync and before peers learn of the removal can
            # collide; and if the old primary is already dead the sync
            # fails and our replica may lag — both heal only by operator
            # intervention, exactly like the reference's unreplicated
            # TranslateFile (translate.go:56).
            try:
                self._sync_translate_stores(direct_primary=True)
            except Exception as e:
                self.logger.printf(
                    "remove-node: translate catch-up from departing "
                    "primary failed (%s: %s); promoting %s with its "
                    "current replica — ids allocated on the old primary "
                    "but not yet replicated may be lost",
                    type(e).__name__, e, self.cluster.local.id)
            # Pin BEFORE removing the node: otherwise a concurrent
            # allocation between removal and pin would route to the
            # lexically-first fallback, which may lag.
            tp = self.cluster.pin_translate_primary(self.cluster.local.id)
            # We now SERVE the stream: lift every local store's
            # replica limit — a promoted primary that kept it would
            # withhold its out-of-band adopted entries from successors
            # until the next local allocation (possibly never, on a
            # read-only cluster).
            self._lift_translate_serving()
        self.cluster.remove_node(node_id)
        for peer in self.cluster.nodes():
            if peer.id == self.cluster.local.id:
                continue
            # Sync-first (queued fallback): survivors must apply the
            # removal before this job's direct resize_pull hits them.
            self.broadcaster.send_now_or_queue(
                peer.uri, {"type": "node-leave", "nodeID": node_id,
                           "prev": prev,
                           **({"translatePrimary": tp} if tp else {})})
        # Tell the removed node too (it may still be alive): it detaches
        # to a single-node topology instead of serving with stale 3-node
        # placement and pushing anti-entropy into the survivors. It keeps
        # its data: reads route to it via the pre-change placement until
        # the survivors' pulls complete.
        try:
            self._client.cluster_message(
                removed.uri, {"type": "node-leave", "nodeID": node_id})
        except ClientError:
            pass  # already dead — nothing to detach
        self._start_resize_job()
        return self.cluster.status()

    def set_coordinator(self, node_id: str) -> dict:
        """(reference api.SetCoordinator, api.go:1104)."""
        if self.cluster is None:
            raise ApiError("not clustered", 400)
        from pilosa_tpu.parallel.client import ClientError
        target = self.cluster.node_by_id(node_id)
        if target is None:
            raise ApiError(f"node not found: {node_id}", 404)
        # Apply locally through the same handler peers run, so the two
        # paths cannot diverge.
        self.handle_cluster_message({"type": "set-coordinator",
                                     "nodeID": node_id})
        for peer in self.cluster.nodes():
            if peer.id == self.cluster.local.id:
                continue
            self.broadcaster.send_now_or_queue(
                peer.uri, {"type": "set-coordinator", "nodeID": node_id})
        return self.cluster.status()

    def resize_abort(self) -> dict:
        """(reference api.ResizeAbort, api.go:1141). Divergence, stated in
        the response: resize here is pull-based, so "abort" cannot undo a
        topology change — it accepts the NEW placement immediately
        (cluster-wide), dropping the pre-change read routing. Any data
        motion that had not completed heals via anti-entropy."""
        if self.cluster is None:
            raise ApiError("not clustered", 400)
        from pilosa_tpu.parallel.cluster import STATE_RESIZING
        aborted = self.cluster.state == STATE_RESIZING
        self._finish_resize()
        st = self.cluster.status()
        st["aborted"] = bool(aborted)
        st["note"] = ("pull-based resize: abort adopts the new placement "
                      "now; incomplete data motion heals via anti-entropy")
        return st

    def sync_now(self) -> dict:
        """One synchronous anti-entropy pass (tests + admin)."""
        if self.syncer is None:
            raise ApiError("not clustered", 400)
        # Reconcile translate stores from the primary first, so pushed ids
        # mean the same thing everywhere (chained replication,
        # translate.go:400).
        self._sync_translate_stores()
        return self.syncer.sync_holder()

    def _translate_source(self):
        """Where this replica streams translate logs FROM: its ring
        predecessor (chained replication — each node replicates from
        the node before it in id order, so the primary serves ONE
        stream however large the cluster; reference
        setPrimaryTranslateStore(previousNode), cluster.go:1908-1935).
        Falls back to the pinned primary when the predecessor is DOWN
        (the chain re-forms around failures; allocation always routes
        to the primary regardless)."""
        primary = self._translate_primary()
        prev = self.cluster.previous_node()
        if prev is None or prev.id == primary.id:
            return primary
        if prev.id in getattr(self.cluster, "down_ids", set()):
            return primary
        return prev

    def _lift_translate_serving(self) -> None:
        """This node just became the translate primary: serve the whole
        id-ordered log (see TranslateStore.served_limit)."""
        for idx in self.holder.indexes.values():
            if idx.keys:
                idx.column_translator.served_limit = None
            for f in idx.fields.values():
                if f.options.keys:
                    f.row_translator.served_limit = None

    def _sync_translate_stores(self, direct_primary: bool = False) -> None:
        """`direct_primary=True` bypasses the chain and pulls straight
        from the primary — the pre-promotion catch-up must be complete
        NOW, not one-chain-hop-per-interval eventually (a successful
        pull from a lagging predecessor would otherwise satisfy it and
        the promoted store could mint colliding ids)."""
        from pilosa_tpu.parallel.client import ClientError
        primary = self._translate_primary()
        if primary.id == self.cluster.local.id:
            return
        source = primary if direct_primary else self._translate_source()

        sources = [source] + ([primary] if primary.id != source.id else [])

        def pull(st, idx_name, field_name=None):
            fld = f"&field={field_name}" if field_name else ""
            for node in sources:  # chain first, then the primary
                try:
                    # Incremental: resume from our replica log's byte
                    # offset (reference streams the log tail from an
                    # offset, /internal/translate/data, translate.go:400).
                    st.apply_log(self._client._req(
                        "GET",
                        f"{node.uri}/internal/translate/data"
                        f"?index={idx_name}{fld}"
                        f"&offset={st.replica_offset}", raw=True),
                        resume=True)
                    return
                except ClientError:
                    continue

        for idx in self.holder.indexes.values():
            if idx.keys:
                pull(idx.column_translator, idx.name)
            for f in idx.fields.values():
                if f.options.keys:
                    pull(f.row_translator, idx.name, f.name)

    def resize_now(self) -> dict:
        """Pull newly-owned fragments + drop unowned (tests + admin; the
        reference runs this as coordinator-driven resize jobs,
        cluster.go:1150)."""
        if self.resize_puller is None:
            raise ApiError("not clustered", 400)
        fetched = self.resize_puller.pull_owned()
        removed = self.resize_puller.clean_unowned()
        return {"fetched": fetched, "removed": removed}

    def shards_max(self) -> Dict[str, int]:
        return {idx.name: (max(idx.available_shards()) if
                           idx.available_shards() else 0)
                for idx in self.holder.indexes.values()}

    def status(self) -> Dict[str, Any]:
        # Heartbeat probes hit this: an armed error here is the
        # failpoint way to make THIS node look dead fleet-wide.
        _FP_STATUS.fire()
        if self.cluster is not None:
            return self.cluster.status()
        return {"state": "NORMAL",
                "nodes": [{"id": self.holder.node_id, "isCoordinator": True,
                           "uri": {}}]}

    def info(self) -> Dict[str, Any]:
        import os

        import jax

        from pilosa_tpu import native
        from pilosa_tpu.core.view import BANK_BUDGET
        from pilosa_tpu.executor import executor as executor_mod
        from pilosa_tpu.utils.jaxenv import describe_devices
        native_loaded, native_error = native.status()
        # tailDroppedBytes > 0 means torn op-log tails were sidecarred at
        # open — data the operator should know was dropped (ADVICE r2).
        return {"shardWidth": SHARD_WIDTH, "cpuPhysicalCores": os.cpu_count(),
                "version": __version__,
                "tailDroppedBytes": self.holder.tail_dropped_bytes(),
                # Where this process actually runs: a server that came
                # up on the CPU says so here, per device it addresses,
                # with the allocator's own byte counters. deviceCount
                # is global (other hosts' included under
                # jax.distributed).
                "devices": describe_devices(),
                "deviceCount": jax.device_count(),
                "meshDevices": self.executor.mesh_devices,
                # What a bank is priced against, per device: a bank
                # whose share on one device is within the first is swept
                # resident by TopN, and cached banks are evicted past
                # the second (a bank split over a mesh counts by its
                # share on one device).
                "residentLimits": {
                    "topnBankBytesPerDevice":
                        executor_mod.TOPN_MAX_BANK_BYTES,
                    "bankBudgetBytesPerDevice": BANK_BUDGET.budget},
                # ... and where the budget stands: a device's bytes of
                # cached banks, and how many banks it has evicted since
                # the process started.
                "bankBudget": {"bytesPerDevice": BANK_BUDGET.total,
                               "evictions": BANK_BUDGET.evictions},
                "compileCacheDir": jax.config.jax_compilation_cache_dir,
                "native": {"loaded": native_loaded,
                           "error": native_error}}

    def version(self) -> Dict[str, str]:
        return {"version": __version__}

    # --------------------------------------------------------------- helpers

    def _index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise ApiError(f"index not found: {name}", 404)
        return idx

    def _field(self, idx, name: str):
        f = idx.field(name)
        if f is None:
            raise ApiError(f"field not found: {name}", 404)
        return f

    def _fragment(self, index, field, view, shard):
        idx = self._index(index)
        f = self._field(idx, field)
        v = f.view(view)
        frag = v.fragment(shard) if v else None
        if frag is None:
            raise ApiError("fragment not found", 404)
        return frag
