"""HTTP surface: the reference's REST routes on the stdlib HTTP server.

Reference: /root/reference/http/handler.go:236-280 (route table). Bodies
are JSON (the reference negotiates protobuf or JSON; JSON is the
documented public surface) except import-roaring and fragment data, which
are raw roaring bytes, exactly like the reference.

Routes implemented (public):
  GET  /                      home/info
  POST /index/{i}/query       PQL (body: raw PQL or {"query": ...})
  GET  /schema  /status  /info  /version
  GET  /debug/vars  /debug/queries  /debug/memory  /metrics
  GET  /cluster/health
  GET  /index   /index/{i}
  POST /index/{i}             {"options": {"keys": bool, ...}}
  DEL  /index/{i}
  POST /index/{i}/field/{f}   {"options": {...}}
  DEL  /index/{i}/field/{f}
  POST /index/{i}/field/{f}/import            {"rowIDs": [...], ...}
  POST /index/{i}/field/{f}/import-roaring/{s} raw roaring bytes
  GET  /export?index&field&shard
  POST /recalculate-caches
Internal (node-to-node / sync):
  GET  /internal/fragment/blocks?index&field&view&shard
  GET  /internal/fragment/block/data?...&block
  GET  /internal/fragment/data?...
  GET  /internal/shards/max
  GET  /internal/translate/data?index[&field][&offset]
  GET  /internal/nodes  /internal/health
"""

from __future__ import annotations

import json
import re
import threading
import time
from pilosa_tpu.utils.locks import make_lock
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from pilosa_tpu.server import proto_compat, wire
from pilosa_tpu.server.api import API, ApiError
from pilosa_tpu.utils.timeline import TIMELINE

# Per-endpoint RED latency buckets (seconds): powers of two from
# ~61 µs to 8 s — wide enough that a cold compile, a full-bank sweep
# and a sub-ms cache hit land in different buckets.
REQUEST_BUCKETS = tuple(2.0 ** e for e in range(-14, 4))

# Endpoint label normalization: path parameters collapse to
# placeholders so `pilosa_http_request_seconds{endpoint=...}` stays a
# bounded label set (index/field names must not explode cardinality).
_EP_PATTERNS = [
    (re.compile(r"/index/[^/]+/query"), "/index/{index}/query"),
    (re.compile(r"/index/[^/]+/field/[^/]+/import-roaring/\d+"),
     "/index/{index}/field/{field}/import-roaring/{shard}"),
    (re.compile(r"/index/[^/]+/field/[^/]+/import"),
     "/index/{index}/field/{field}/import"),
    (re.compile(r"/index/[^/]+/field/[^/]+"),
     "/index/{index}/field/{field}"),
    (re.compile(r"/index/[^/]+/field"), "/index/{index}/field"),
    (re.compile(r"/index/[^/]+"), "/index/{index}"),
    (re.compile(r"/cluster/timeline/[^/]+"),
     "/cluster/timeline/{trace}"),
]
_EP_STATIC = frozenset({
    "/", "/schema", "/status", "/info", "/version", "/index",
    "/metrics", "/batch/query", "/export", "/recalculate-caches",
    "/debug/vars", "/debug/queries", "/debug/memory", "/debug/hotspots",
    "/debug/timeline", "/cluster/health", "/cluster/hotspots",
    # Internal/cluster routes are fixed strings: an explicit whitelist,
    # NOT a prefix match — unknown paths under these prefixes must fold
    # into "other" like everything else or a scanner mints series.
    "/cluster/timeline", "/internal/failpoints",
    "/internal/health", "/internal/nodes", "/internal/local-shards",
    "/internal/views", "/internal/join", "/internal/cluster/message",
    "/internal/sync", "/internal/resize/pull", "/internal/shards/max",
    "/internal/fragment/blocks", "/internal/fragment/block/data",
    "/internal/fragment/data", "/internal/fragment/nodes",
    "/internal/attr/blocks", "/internal/attr/block/data",
    "/internal/attr/merge", "/internal/translate/data",
    "/internal/translate/keys", "/internal/translate/ids",
    "/cluster/resize/remove-node", "/cluster/resize/set-coordinator",
    "/cluster/resize/abort", "/cluster/resize/run",
})


def endpoint_label(path: str) -> str:
    """Bounded endpoint label for the RED series. Unknown paths fold
    into "other" — a scanner walking random URLs must not mint series."""
    if path in _EP_STATIC:
        return path
    for rx, label in _EP_PATTERNS:
        if rx.fullmatch(path):
            return label
    return "other"


class Handler(BaseHTTPRequestHandler):
    api: API = None  # injected by serve()
    protocol_version = "HTTP/1.1"
    # Response headers and body go out in separate writes; with Nagle on,
    # a keep-alive internal client pays a ~40 ms delayed-ACK stall per
    # response. (The client side sets TCP_NODELAY on its pooled sockets.)
    disable_nagle_algorithm = True
    # The open request record of the query being handled (None between
    # requests and on every other route), and whether one was opened:
    # the server holds a request the timeline keeps no record of too.
    _rec = None
    _began = False

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):  # route through our logger
        logger = getattr(self.api, "logger", None)
        if logger is not None:
            logger.debugf(fmt % args)

    def _json(self, obj: Any, status: int = 200,
              force_json: bool = False,
              extra_headers: Optional[dict] = None) -> None:
        # The last two stages of a query's request record (self._rec;
        # None on every other route, where these are bare clocks).
        rec = self._rec
        # Content negotiation (reference http/handler.go:447-489 protobuf
        # vs JSON): internal clients ask for the binary wire codec via
        # Accept; JSON is the public surface and the default.
        with TIMELINE.span(rec, "http.serialize"):
            body = None
            if not force_json and wire.CONTENT_TYPE in (
                    self.headers.get("Accept") or ""):
                try:
                    body = wire.dumps(obj)
                    ctype = wire.CONTENT_TYPE
                except TypeError:
                    body = None  # e.g. >64-bit int — JSON handles it
            if body is None:
                body = json.dumps(obj).encode("utf-8")
                ctype = "application/json"
        with TIMELINE.span(rec, "http.write", bytes=len(body)):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (extra_headers or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            self.wfile.write(body)

    def _bytes(self, data: bytes, status: int = 200,
               ctype: str = "application/octet-stream") -> None:
        with TIMELINE.span(self._rec, "http.write", bytes=len(data)):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    def _error(self, msg: str, status: int = 400,
               extra_headers: Optional[dict] = None) -> None:
        self._json({"error": msg}, status, force_json=True,
                   extra_headers=extra_headers)

    def _body(self) -> bytes:
        n = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(n) if n else b""

    def _body_json(self) -> dict:
        raw = self._body()
        if not raw:
            return {}
        if (self.headers.get("Content-Type") or "").startswith(
                wire.CONTENT_TYPE):
            try:
                return wire.loads(raw)
            except wire.WireError as e:
                raise ApiError(f"invalid wire body: {e}")
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid JSON body: {e}")

    @staticmethod
    def _wrap_options(pql, optargs: dict):
        """Wrap every call of a PQL string in Options(...) — the
        request-level ExecOptions shape (reference PostQuery optional
        args, http/handler.go:186)."""
        if not optargs:
            return pql
        from pilosa_tpu.pql import parse_string
        from pilosa_tpu.pql.ast import Call, Query
        parsed = parse_string(pql)
        return Query([Call("Options", dict(optargs), [c])
                      for c in parsed.calls])

    def _exec_optargs(self, q: dict, req: Optional[dict] = None) -> dict:
        """Exec options from URL args, OR'd with protobuf request flags."""
        return {k: True for k in
                ("columnAttrs", "excludeRowAttrs", "excludeColumns")
                if self._qbool(q, k) or (req or {}).get(k)}

    def _query_proto(self, api, index: str, q: dict) -> None:
        """Reference-client protobuf query: decode internal.QueryRequest,
        execute, answer internal.QueryResponse
        (http/handler.go:916-995)."""
        rec = self._rec
        with TIMELINE.span(rec, "http.read", codec="proto") as rd:
            raw = self._body()
            rd.set("bytes", len(raw))
            try:
                req = proto_compat.decode_query_request(raw)
            except proto_compat.ProtoError as e:
                raise ApiError(f"invalid protobuf body: {e}")
            shards = req["shards"] or None
            if q.get("shards"):
                shards = [int(s) for s in q["shards"].split(",")]
        try:
            pql = self._wrap_options(req["query"],
                                     self._exec_optargs(q, req))
            res = api.query(index, pql, shards=shards,
                            remote=req["remote"] or self._qbool(q, "remote"),
                            record=rec)
            with TIMELINE.span(rec, "http.serialize", codec="proto"):
                body = proto_compat.encode_query_response(
                    res["results"],
                    column_attr_sets=res.get("columnAttrs"))
        except ValueError as e:
            body = proto_compat.encode_query_response([], err=str(e))
            self._bytes(body, status=400,
                        ctype=proto_compat.RESPONSE_CONTENT_TYPE)
            return
        self._bytes(body, ctype=proto_compat.RESPONSE_CONTENT_TYPE)

    def _proto_import_body(self, api, index: str, field: str) -> dict:
        """Decode a reference-client import body by field type
        (http/handler.go:1036-1060): int fields carry
        ImportValueRequest, everything else ImportRequest. Timestamps
        are unix nanos (api.go:901) — converted to the seconds floats
        the JSON path accepts."""
        raw = self._body()
        idx = api.holder.index(index)
        f = idx.field(field) if idx is not None else None
        try:
            if f is not None and f.options.type == "int":
                b = proto_compat.decode_import_value_request(raw)
            else:
                b = proto_compat.decode_import_request(raw)
        except proto_compat.ProtoError as e:
            raise ApiError(f"invalid protobuf body: {e}")
        out = {k: v for k, v in b.items()
               if k in ("rowIDs", "columnIDs", "values") and len(v)}
        for k in ("rowKeys", "columnKeys"):
            if b.get(k):
                out[k] = b[k]
        if b.get("timestamps"):
            out["timestamps"] = [t / 1e9 for t in b["timestamps"]]
        if "values" in b and "values" not in out:
            out["values"] = []  # int-field import keeps the values path
        return out

    def _route(self) -> Tuple[str, dict, dict]:
        parsed = urlparse(self.path)
        query = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        return parsed.path.rstrip("/") or "/", query, {}

    @staticmethod
    def _qbool(q: dict, name: str) -> bool:
        """Boolean query-string arg: on for '1'/'true' (case-insensitive),
        off otherwise — so ?clear=false doesn't silently enable."""
        return (q.get(name) or "").lower() in ("1", "true")

    @staticmethod
    def _check_args(q: dict, *allowed: str) -> None:
        """Reject unknown query-string args with 400 (reference
        queryArgValidator middleware, http/handler.go:171-235)."""
        unknown = set(q) - set(allowed)
        if unknown:
            raise ApiError(
                f"invalid query params: {' '.join(sorted(unknown))}")

    # -- dispatch -----------------------------------------------------------

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    def send_response(self, code, message=None):
        # Remember the response status for the per-endpoint RED
        # histogram (each request sets it anew before _observe_request
        # reads it, so connection reuse cannot leak a stale code).
        self._status = code
        super().send_response(code, message)

    def _observe_request(self, method: str, path: str, dur: float
                         ) -> None:
        """One RED observation per request:
        pilosa_http_request_seconds{endpoint,status} with pow2 buckets.
        Slow non-query endpoints cross-link their trace id into the
        slow-query ring (the query routes already record there with a
        full profile) so /debug/queries -> traceId -> /debug/timeline
        works for every surface."""
        api = self.api
        stats = getattr(api, "stats", None)
        if stats is None:
            return
        ep = endpoint_label(path)
        status = getattr(self, "_status", 200)
        stats.with_tags(f"endpoint:{ep}", f"status:{status}").histogram(
            "http_request_seconds", dur, buckets=REQUEST_BUCKETS)
        lqt = getattr(api, "long_query_time", 0.0)
        if lqt > 0 and dur > lqt and ep not in (
                "/index/{index}/query", "/batch/query"):
            tracer = getattr(api, "tracer", None)
            tid = getattr(tracer, "current_trace_id", lambda: None)()
            profiler = getattr(api, "profiler", None)
            if profiler is not None:
                profiler.record_slow("-", f"{method} {ep}", dur,
                                     kind="http", trace_id=tid)

    def _dispatch(self, method: str) -> None:
        path, q, _ = self._route()
        if hasattr(self.api, "tracer"):
            self.api.tracer.extract(self.headers)
        t0 = time.perf_counter()
        err = None
        try:
            handled = self._handle(method, path, q)
            if not handled:
                self._error(f"no route for {method} {path}", 404)
        except ApiError as e:
            err = e
            # e.headers carries response headers (e.g. Retry-After on
            # the coalescer's 429 overload rejection).
            self._error(str(e), e.status,
                        extra_headers=getattr(e, "headers", None))
        except Exception as e:  # mirror the reference's panic recovery
            err = e
            self._error(f"internal error: {type(e).__name__}: {e}", 500)
        finally:
            # The query route's request record closes here, after the
            # reply (or the error reply) is on the socket.
            rec, self._rec = self._rec, None
            began, self._began = self._began, False
            if began:
                self.api.end_request(rec, err)
            try:
                self._observe_request(method, path,
                                      time.perf_counter() - t0)
            except Exception:
                pass  # metrics must never fail a served response

    def _handle(self, method: str, path: str, q: dict) -> bool:
        api = self.api

        if method == "GET":
            if path == "/":
                self._json({"pilosa-tpu": True, **api.info()})
            elif path == "/schema":
                self._json(api.schema())
            elif path == "/status":
                self._json(api.status())
            elif path == "/info":
                self._json(api.info())
            elif path == "/version":
                self._json(api.version())
            elif path == "/debug/vars":
                stats = getattr(api.stats, "snapshot", lambda: {})()
                self._json(stats)
            elif path == "/debug/queries":
                # Structured slow-query ring (utils/profile.py): every
                # query over long_query_time, most recent first, with
                # its profile tree when one was recorded — the
                # structured replacement for grepping SLOW QUERY log
                # lines (reference LongQueryTime, api.go:1048).
                from pilosa_tpu.utils.jaxenv import COMPILES
                self._json({"queries": api.profiler.slow_queries(),
                            "retraces": api.executor.jit_compiles,
                            # Every XLA compile, by JAX's function
                            # name: count, seconds, the request stage
                            # it happened in, and for the executor's
                            # own programs the jit key that missed.
                            "xla": COMPILES.snapshot(),
                            "fusedDispatches":
                                api.executor.fused_dispatches,
                            "fusedQueries": api.executor.fused_queries,
                            "megaLaunches":
                                api.executor.mega_launches,
                            "megaQueries": api.executor.mega_queries,
                            "megaPlanEntries":
                                api.executor.mega_plan_entries,
                            "megaPlanBytes":
                                api.executor.mega_plan_bytes,
                            # Mesh cohort launches (PILOSA_TPU_MESH):
                            # plan buffers run SPMD over the mesh
                            # shard axis, reductions finished by the
                            # collective epilogue (psum/all_gather) —
                            # collectiveBytes is the modeled ICI wire
                            # traffic.
                            "meshLaunches":
                                api.executor.mesh_launches,
                            "meshCollectiveBytes":
                                api.executor.mesh_collective_bytes,
                            "planVerifyPasses":
                                api.executor.plan_verify_passes,
                            "planVerifyRejects":
                                api.executor.plan_verify_rejects,
                            "optPlans": api.executor.opt_plans,
                            "optCseHits": api.executor.opt_cse_hits,
                            "optEntriesEliminated":
                                api.executor.opt_entries_eliminated,
                            "optFoldsReordered":
                                api.executor.opt_folds_reordered,
                            "optBytesSaved":
                                api.executor.opt_bytes_saved,
                            # plan_cost's byte splits + per-opcode
                            # instruction totals over every
                            # megakernel launch: counts from shapes.
                            "launchBytesGather":
                                api.executor.launch_bytes_gather,
                            "launchBytesCompute":
                                api.executor.launch_bytes_compute,
                            "launchBytesExpand":
                                api.executor.launch_bytes_expand,
                            "launchBytesPad":
                                api.executor.launch_bytes_pad,
                            "opcodeTotals":
                                dict(api.executor.opcode_counts),
                            "jitCacheSize":
                                api.executor.jit_cache_size()})
            elif path == "/debug/memory":
                # HBM memory ledger (utils/memledger.py): per-category
                # live vs padded bytes + the top-K largest resident
                # banks — "what is occupying HBM right now".
                self._json(api.debug_memory())
            elif path == "/debug/hotspots":
                # Workload analytics plane (utils/hotspots.py): hot
                # fragments/rows/signatures, write churn, repeat
                # ratios, and the cache-opportunity report.
                self._check_args(q, "topk")
                self._json(api.debug_hotspots(
                    top_k=int(q["topk"]) if q.get("topk") else None))
            elif path == "/cluster/hotspots":
                # Coordinator-merged fleet workload: one hotspots
                # snapshot per node, unreachable nodes reported.
                self._check_args(q, "topk")
                self._json(api.cluster_hotspots(
                    top_k=int(q["topk"]) if q.get("topk") else None))
            elif path == "/debug/timeline":
                # Request-lifecycle timeline plane (utils/timeline.py):
                # Chrome trace-event JSON for the last N requests —
                # open it directly in Perfetto/chrome://tracing;
                # ?slowest=1: the longest records kept beside the ring.
                self._check_args(q, "last", "trace", "slowest")
                self._json(api.debug_timeline(
                    last=int(q["last"]) if q.get("last") else None,
                    trace=q.get("trace"),
                    slowest=q.get("slowest") == "1"))
            elif path == "/cluster/timeline":
                # Cluster lifecycle timeline (no trace id): merged
                # membership/failure/resize events from every member —
                # where a chaos kill and its recovery are visible.
                self._json(api.cluster_timeline_events())
            elif m := re.fullmatch(r"/cluster/timeline/([^/]+)", path):
                # Multi-node timeline for one trace id: legs assembled
                # by the traceparent the cluster already propagates.
                self._json(api.cluster_timeline(m.group(1)))
            elif path == "/internal/failpoints":
                # Test-only fault-injection surface (403 unless the
                # plane was enabled at boot — utils/failpoints.py).
                self._json(api.failpoints_snapshot())
            elif path == "/cluster/health":
                # Coordinator-merged fleet health: per-node memory,
                # queue depth, jit/retrace/slow-query counters,
                # liveness and staleness in one document.
                self._json(api.cluster_health())
            elif path == "/internal/health":
                # One node's self-report (the cluster_health fan-out
                # leg).
                self._json(api.node_health())
            elif path == "/metrics":
                from pilosa_tpu.utils.stats import prometheus_text
                # Memory gauges refresh at scrape time too, so
                # pilosa_memory_bytes is live even between watchdog
                # samples (and on watchdog-less embedded servers).
                api.refresh_memory_gauges()
                self._bytes(prometheus_text(api.stats).encode(),
                            ctype="text/plain; version=0.0.4")
            elif path == "/index":
                self._json(api.schema()["indexes"])
            elif m := re.fullmatch(r"/index/([^/]+)/field", path):
                for idx in api.schema()["indexes"]:
                    if idx["name"] == m.group(1):
                        self._json({"fields": idx.get("fields", [])})
                        return True
                raise ApiError(f"index not found: {m.group(1)}", 404)
            elif m := re.fullmatch(r"/index/([^/]+)", path):
                for idx in api.schema()["indexes"]:
                    if idx["name"] == m.group(1):
                        self._json(idx)
                        return True
                raise ApiError(f"index not found: {m.group(1)}", 404)
            elif path == "/export":
                self._check_args(q, "index", "field", "shard")
                csv = api.export_csv(q["index"], q["field"],
                                     int(q.get("shard", 0)))
                self._bytes(csv.encode(), ctype="text/csv")
            elif path == "/internal/fragment/blocks":
                self._check_args(q, "index", "field", "view", "shard")
                self._json({"blocks": api.fragment_blocks(
                    q["index"], q["field"], q.get("view", "standard"),
                    int(q["shard"]))})
            elif path == "/internal/fragment/block/data":
                self._json(api.fragment_block_data(
                    q["index"], q["field"], q.get("view", "standard"),
                    int(q["shard"]), int(q["block"])))
            elif path == "/internal/fragment/data":
                self._check_args(q, "index", "field", "view", "shard")
                self._bytes(api.fragment_data(
                    q["index"], q["field"], q.get("view", "standard"),
                    int(q["shard"])))
            elif path == "/internal/fragment/nodes":
                self._check_args(q, "index", "shard")
                self._json(api.fragment_nodes(q["index"],
                                              int(q["shard"])))
            elif path == "/internal/attr/blocks":
                self._json({"blocks": api.attr_blocks(
                    q["index"], q.get("field"))})
            elif path == "/internal/attr/block/data":
                self._json(api.attr_block_data(
                    q["index"], q.get("field"), int(q["block"])))
            elif path == "/internal/shards/max":
                self._json({"standard": api.shards_max()})
            elif path == "/internal/translate/data":
                self._bytes(api.translate_data(
                    q["index"], q.get("field"), int(q.get("offset", 0))))
            elif path == "/internal/nodes":
                self._json(api.status().get("nodes", []))
            elif path == "/internal/local-shards":
                self._json(api.local_shards())
            elif path == "/internal/views":
                self._json({"views": api.views_of(q["index"], q["field"])})
            else:
                return False
            return True

        if method == "POST":
            if m := re.fullmatch(r"/index/([^/]+)/query", path):
                self._check_args(q, "shards", "remote", "columnAttrs",
                                 "excludeRowAttrs", "excludeColumns",
                                 "profile")
                # The request record (utils/timeline.py) opens before
                # the body is read and closes in _dispatch after the
                # reply is written, so its stages tile the exchange.
                rec = self._rec = api.begin_request(m.group(1))
                self._began = True
                # Reference-client protobuf surface
                # (http/handler.go:916-995, internal/public.proto).
                if self.headers.get("Content-Type", "").startswith(
                        proto_compat.CONTENT_TYPE):
                    self._query_proto(api, m.group(1), q)
                    return True
                with TIMELINE.span(rec, "http.read") as rd:
                    raw = self._body()
                    rd.set("bytes", len(raw))
                    try:
                        body = json.loads(raw) \
                            if raw.lstrip()[:1] == b"{" else None
                    except json.JSONDecodeError:
                        body = None
                    pql = (body or {}).get("query") if body \
                        else raw.decode()
                    shards = None
                    if q.get("shards"):
                        shards = [int(s) for s in q["shards"].split(",")]
                # URL-arg execution options apply to every call, same as
                # the reference's request-level ExecOptions
                # (http/handler.go:186 PostQuery optional args).
                try:
                    pql = self._wrap_options(pql, self._exec_optargs(q))
                    # Rides the cross-request coalescer when one is
                    # attached (server/coalescer.py); degrades to the
                    # direct api.query path otherwise. ?profile=true
                    # embeds the EXPLAIN ANALYZE-style execution
                    # profile tree in the response (docs/observability
                    # .md); the protobuf surface stays profile-free.
                    resp = api.query_coalesced(
                        m.group(1), pql, shards=shards,
                        remote=self._qbool(q, "remote"),
                        profile=self._qbool(q, "profile"), record=rec)
                    self._json(resp)
                except ApiError:
                    # Already carries its status (429 overload, 408
                    # deadline): must not collapse to a generic 400.
                    raise
                except ValueError as e:
                    raise ApiError(str(e))
            elif path == "/batch/query":
                # Batch endpoint (rebuild extension; no reference route —
                # the reference batches CALLS per query string,
                # executor.go:84; this batches QUERIES per request so N
                # small queries share one HTTP round trip and one
                # pipelined device drain). Body:
                #   {"queries": [{"index", "query", "shards"?}, ...]}
                # Response: {"responses": [{"results": ...}|{"error"}]}.
                body = self._body_json()
                items = body.get("queries")
                if not isinstance(items, list):
                    raise ApiError("body must carry a 'queries' list")
                if len(items) > 1024:
                    # Every item's device programs dispatch before any
                    # result finalizes; an unbounded batch would queue
                    # arbitrarily many pending outputs.
                    raise ApiError("batch too large (max 1024 queries)")
                # Item shape is validated per item by query_batch — a
                # malformed item degrades to {"error"} without failing
                # its batchmates (one contract for HTTP and in-process).
                self._json({"responses": api.query_batch(items)})
            elif m := re.fullmatch(r"/index/([^/]+)/field/([^/]+)/import",
                                   path):
                self._check_args(q, "clear", "remote", "ignoreKeyCheck")
                if self.headers.get("Content-Type", "").startswith(
                        proto_compat.CONTENT_TYPE):
                    # Reference clients: message type follows the field
                    # type (int -> ImportValueRequest, else
                    # ImportRequest; http/handler.go:1036-1060).
                    b = self._proto_import_body(api, m.group(1),
                                                m.group(2))
                else:
                    b = self._body_json()
                remote = self._qbool(q, "remote")
                ignore_keys = self._qbool(q, "ignoreKeyCheck")
                if "values" in b:
                    api.import_values(
                        m.group(1), m.group(2), columns=b.get("columnIDs"),
                        values=b["values"], column_keys=b.get("columnKeys"),
                        clear=self._qbool(q, "clear"), remote=remote,
                        ignore_key_check=ignore_keys)
                else:
                    api.import_bits(
                        m.group(1), m.group(2), rows=b.get("rowIDs"),
                        columns=b.get("columnIDs"),
                        row_keys=b.get("rowKeys"),
                        column_keys=b.get("columnKeys"),
                        timestamps=b.get("timestamps"),
                        clear=self._qbool(q, "clear"), remote=remote,
                        ignore_key_check=ignore_keys)
                self._json({})
            elif m := re.fullmatch(
                    r"/index/([^/]+)/field/([^/]+)/import-roaring/(\d+)",
                    path):
                self._check_args(q, "remote", "clear", "view")
                raw = self._body()
                if self.headers.get("Content-Type", "").startswith(
                        proto_compat.CONTENT_TYPE):
                    # Reference-client ImportRoaringRequest: per-view
                    # roaring payloads + clear flag
                    # (http/handler.go:1554, public.proto).
                    try:
                        b = proto_compat.decode_import_roaring_request(raw)
                    except proto_compat.ProtoError as e:
                        raise ApiError(f"invalid protobuf body: {e}")
                    for view_name, blob in b["views"]:
                        api.import_roaring(
                            m.group(1), m.group(2), int(m.group(3)), blob,
                            clear=b["clear"] or self._qbool(q, "clear"),
                            view=view_name or q.get("view", "standard"),
                            remote=self._qbool(q, "remote"))
                else:
                    api.import_roaring(m.group(1), m.group(2),
                                       int(m.group(3)), raw,
                                       clear=self._qbool(q, "clear"),
                                       view=q.get("view", "standard"),
                                       remote=self._qbool(q, "remote"))
                self._json({})
            elif m := re.fullmatch(r"/index/([^/]+)/field/([^/]+)", path):
                b = self._body_json()
                self._json(api.create_field(m.group(1), m.group(2),
                                            b.get("options"),
                                            remote=self._qbool(q, "remote")))
            elif m := re.fullmatch(r"/index/([^/]+)", path):
                b = self._body_json()
                opts = b.get("options", {})
                self._json(api.create_index(
                    m.group(1), keys=opts.get("keys", False),
                    track_existence=opts.get("trackExistence", True),
                    remote=self._qbool(q, "remote")))
            elif path == "/recalculate-caches":
                api.recalculate_caches()
                self._json({})
            elif path == "/internal/join":
                self._json(api.handle_join(self._body_json()))
            elif path == "/internal/cluster/message":
                api.handle_cluster_message(self._body_json())
                self._json({})
            elif path == "/internal/attr/merge":
                b = self._body_json()
                api.attr_merge(q["index"], q.get("field"),
                               b.get("attrs", {}))
                self._json({})
            elif path == "/cluster/resize/remove-node":
                self._json(api.remove_node(self._body_json().get("id")))
            elif path == "/cluster/resize/set-coordinator":
                self._json(api.set_coordinator(
                    self._body_json().get("id")))
            elif path == "/cluster/resize/abort":
                self._json(api.resize_abort())
            elif path == "/internal/translate/keys":
                if self.headers.get("Content-Type", "").startswith(
                        proto_compat.CONTENT_TYPE):
                    # Reference protobuf leg (http/handler.go:1617).
                    try:
                        b = proto_compat.decode_translate_keys_request(
                            self._body())
                    except proto_compat.ProtoError as e:
                        raise ApiError(f"invalid protobuf body: {e}")
                    ids = api.translate_keys_local(
                        b["index"], b.get("field") or None, b["keys"])
                    self._bytes(
                        proto_compat.encode_translate_keys_response(ids),
                        ctype=proto_compat.RESPONSE_CONTENT_TYPE)
                    return True
                b = self._body_json()
                keys = b.get("keys", [])
                ids = api.translate_keys_local(b["index"], b.get("field"),
                                               keys)
                self._json({"keys": keys, "ids": ids})
            elif path == "/internal/translate/ids":
                b = self._body_json()
                ids = b.get("ids", [])
                keys = api.translate_ids_local(b["index"], b.get("field"),
                                               ids)
                self._json({"ids": ids, "keys": keys})
            elif path == "/internal/failpoints":
                self._json(api.failpoints_update(self._body_json()))
            elif path == "/internal/sync":
                self._json(api.sync_now())
            elif path == "/internal/resize/pull":
                self._json(api.resize_pull())
            elif path == "/cluster/resize/run":
                self._json(api.resize_now())
            else:
                return False
            return True

        if method == "DELETE":
            if m := re.fullmatch(r"/index/([^/]+)/field/([^/]+)", path):
                api.delete_field(m.group(1), m.group(2))
                self._json({})
            elif m := re.fullmatch(r"/index/([^/]+)", path):
                api.delete_index(m.group(1))
                self._json({})
            else:
                return False
            return True

        return False


class PilosaHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that tracks open connection sockets so
    server_close severs lingering keep-alive connections too — without
    this, a 'stopped' node keeps answering pooled internal-client
    connections through its still-alive handler threads."""

    daemon_threads = True
    # The socketserver default listen backlog (5) resets connections
    # under a coalescer-sized concurrent burst; a serving front door
    # needs the accept queue deeper than any one batching window.
    request_queue_size = 128

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._open_conns = set()
        self._conns_lock = make_lock("PilosaHTTPServer._conns_lock")

    def process_request(self, request, client_address):
        with self._conns_lock:
            self._open_conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conns_lock:
            self._open_conns.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self) -> None:
        import socket as _socket
        with self._conns_lock:
            conns = list(self._open_conns)
            self._open_conns.clear()
        for s in conns:
            try:
                s.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def server_close(self):
        super().server_close()
        self.close_all_connections()


def serve(api: API, host: str = "localhost", port: int = 10101,
          background: bool = False, ssl_context=None):
    """Start the HTTP server (reference handler.Serve,
    http/handler.go:150). Returns the server; blocking unless
    background=True. `ssl_context` (config.server_ssl_context) wraps the
    listener for HTTPS — the reference's TLS listener,
    server/server.go:244; one listener carries client AND intra-cluster
    traffic either way."""
    handler = type("BoundHandler", (Handler,), {"api": api})
    server = PilosaHTTPServer((host, port), handler)
    if ssl_context is not None:
        # Handshake deferred to the per-connection handler thread (first
        # read), so a slow TLS client cannot stall the accept loop.
        server.socket = ssl_context.wrap_socket(
            server.socket, server_side=True,
            do_handshake_on_connect=False)
    if background:
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        return server
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
    return server
