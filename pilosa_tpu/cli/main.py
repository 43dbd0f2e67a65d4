"""`python -m pilosa_tpu.cli` — the pilosa-tpu command.

Reference command set (cmd/root.go:40-48): server, import, export, check,
inspect, config, generate-config. Implementations mirror ctl/*.go:
import = bulk CSV loader (ctl/import.go), check = roaring file integrity
(ctl/check.go), inspect = container stats (ctl/inspect.go).
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import signal
import sys

import numpy as np


def drain_telemetry(api, watchdog=None, logger=None) -> None:
    """The telemetry leg of the SIGTERM drain: stop the memory
    watchdog and dump its flight-recorder ring, dump the profiler's
    slow-query ring, and stop the tracer (ExportingTracer.stop joins
    the exporter thread and performs the final flush) — so a graceful
    shutdown never discards buffered telemetry. Factored out of
    cmd_server's finally block so tests can drive it directly with a
    simulated drain."""
    # Re-entrancy guard: the drain runs once per API lifetime. A signal
    # racing the finally block (or a test calling twice) must not dump
    # every ring a second time into the post-mortem log.
    if getattr(api, "_telemetry_drained", False):
        return
    api._telemetry_drained = True
    if watchdog is not None:
        watchdog.stop()
        watchdog.dump(logger)
    profiler = getattr(api, "profiler", None)
    if profiler is not None:
        profiler.dump(logger)
    # Workload recorder: log what was hot (fragments, cacheable
    # signatures, repeat ratio) so post-mortems see the access shape
    # the process served, not just its cost counters.
    from pilosa_tpu.utils.hotspots import WORKLOAD
    if WORKLOAD.enabled:
        WORKLOAD.dump(logger)
    # Timeline plane: the last request records (utils/timeline.py).
    from pilosa_tpu.utils.timeline import TIMELINE
    if TIMELINE.enabled:
        TIMELINE.dump(logger)
    tracer = getattr(api, "tracer", None)
    if tracer is not None:
        # The timeline dump above left the last records in the log;
        # an exporter now ships what it still holds.
        if hasattr(tracer, "stop"):
            tracer.stop()  # final flush of pending spans
        elif hasattr(tracer, "flush"):
            tracer.flush()


def cmd_server(args) -> int:
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.server import API, serve
    from pilosa_tpu.utils.config import load_config
    from pilosa_tpu.utils.logger import Logger
    from pilosa_tpu.utils.stats import MemStatsClient, NopStatsClient
    from pilosa_tpu.utils.tracing import ContextTracer

    cfg = load_config(args.config, {
        "data_dir": args.data_dir, "bind": args.bind,
        "verbose": args.verbose or None,
        "platform": getattr(args, "platform", None),
        "coalescer_enabled": (False if getattr(args, "no_coalescer",
                                               False) else None),
        "coalescer_window_ms": getattr(args, "coalescer_window_ms",
                                       None),
    })
    import jax
    if cfg.platform:
        # Must land before the first device touch. jax.config rather
        # than JAX_PLATFORMS: jax is already imported by now, and the
        # env var is only read at import.
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.jax_coordinator and cfg.jax_num_processes > 1:
        # Multi-host SPMD: after initialize, jax.devices() is global
        # across hosts and the shard mesh spans the whole pod slice
        # (collectives ride ICI within a slice, DCN across; survey §7.6).
        jax.distributed.initialize(
            coordinator_address=cfg.jax_coordinator,
            num_processes=cfg.jax_num_processes,
            process_id=(cfg.jax_process_id
                        if cfg.jax_process_id >= 0 else None))
    from pilosa_tpu import native
    from pilosa_tpu.utils.jaxenv import (
        describe_devices, enable_compile_cache,
    )
    compile_cache_dir = enable_compile_cache()
    # The backend starts HERE, before any data is opened: a platform
    # that cannot initialise (--platform tpu with no chip) kills the
    # server at start instead of at the first query.
    devices = describe_devices()
    native_loaded, native_error = native.status()
    logger = Logger(verbose=cfg.verbose)
    data_dir = os.path.expanduser(cfg.data_dir)
    holder = Holder(data_dir)
    holder.open()

    mesh = None
    if cfg.mesh_devices != 1:
        # Global under jax.distributed: the default mesh spans every
        # host's devices, not only the ones `devices` describes.
        n = cfg.mesh_devices or jax.device_count()
        if n > 1 or cfg.mesh_replicas > 1:
            from pilosa_tpu.parallel import MeshContext
            mesh = MeshContext(jax.devices()[:n],
                               replicas=cfg.mesh_replicas)
    # One line that says where this process runs — a server that came
    # up on the CPU, or without its native library, says so at start.
    logger.printf(
        "devices: platform=%s kind=%s count=%d local=%d mesh=%s "
        "compile_cache=%s native=%s",
        devices[0]["platform"], devices[0]["kind"], jax.device_count(),
        len(devices),
        dict(mesh.mesh.shape) if mesh else "single-device",
        compile_cache_dir,
        "on" if native_loaded else f"off ({native_error})")

    cluster = None
    if cfg.cluster_peers or cfg.cluster_seeds:
        from pilosa_tpu.parallel.cluster import (
            Cluster, Node, STATE_NORMAL,
        )
        local_uri = cfg.advertise or f"{cfg.scheme}://{cfg.bind}"
        # Static peer lists name nodes by URI on every member, so the id
        # must BE the URI there. Seed-joined nodes introduce themselves
        # (the topology replicates their node record), so they use the
        # holder's persisted `.id` — a restart on a new address then
        # rejoins as the SAME member instead of ghosting its old entry.
        local_id = local_uri if cfg.cluster_peers else holder.node_id
        cluster = Cluster(
            Node(local_id, local_uri,
                 is_coordinator=bool(
                     cfg.cluster_peers
                     and local_uri == sorted(cfg.cluster_peers)[0])),
            replica_n=cfg.cluster_replicas,
            topology_path=os.path.join(data_dir, ".topology"))
        for peer in cfg.cluster_peers:
            if peer != local_uri:
                cluster.add_node(Node(peer, peer))
        # Re-adopt dynamically-joined nodes from the persisted topology
        # (reference loads .topology at startup, cluster.go:1611).
        cluster.load()
        cluster.set_state(STATE_NORMAL)

    if cfg.metric_service == "mem":
        stats = MemStatsClient()
    elif cfg.metric_service == "statsd":
        # Mem rides along so /debug/vars keeps working (the reference's
        # multi-client, stats/stats.go:164).
        from pilosa_tpu.utils.stats import (
            MultiStatsClient, StatsdStatsClient,
        )
        stats = MultiStatsClient(
            MemStatsClient(),
            StatsdStatsClient(cfg.metric_host, logger=logger))
    else:
        stats = NopStatsClient()
    if cfg.tracing_endpoint:
        from pilosa_tpu.utils.tracing import ExportingTracer
        tracer = ExportingTracer(cfg.tracing_endpoint,
                                 service_name=cfg.tracing_service_name,
                                 logger=logger,
                                 sampler_type=cfg.tracing_sampler_type,
                                 sampler_param=cfg.tracing_sampler_param)
        tracer.start()
    else:
        tracer = ContextTracer()
    api = API(holder, mesh=mesh, cluster=cluster, stats=stats,
              tracer=tracer, client_ssl_context=cfg.client_ssl_context())
    api.logger = logger
    api.long_query_time = cfg.long_query_time
    api.executor.max_writes_per_request = cfg.max_writes_per_request
    # Fan-out resilience ([cluster] keys): per-request deadline budget,
    # failover backoff, hedged reads, and the three RPC-timeout classes
    # that used to be hard-coded client literals.
    if api.cluster_executor is not None:
        api.cluster_executor.configure(
            fanout_deadline_s=cfg.cluster_fanout_deadline_s,
            backoff_base_s=cfg.cluster_backoff_base_s,
            backoff_cap_s=cfg.cluster_backoff_cap_s,
            hedge_quantile=cfg.cluster_hedge_quantile)
        api._client.configure(
            timeout=cfg.cluster_rpc_timeout_s,
            health_timeout=cfg.cluster_health_timeout_s,
            resize_pull_timeout=cfg.cluster_resize_pull_timeout_s)
    # Fault-injection plane (utils/failpoints.py): arm configured
    # sites and enable the test-only /internal/failpoints surface.
    # Env entries were already merged into cfg.failpoints by
    # load_config (env="" skips a second parse). Production servers
    # with no failpoint config never enable any of this.
    if cfg.failpoints:
        from pilosa_tpu.utils.failpoints import FAILPOINTS
        FAILPOINTS.configure(cfg.failpoints, env="")
        FAILPOINTS.http_enabled = True
        logger.printf("failpoints ARMED (test-only surface enabled): %s",
                      ", ".join(f"{k}={v}"
                                for k, v in sorted(cfg.failpoints.items())))
    elif os.environ.get("PILOSA_TPU_FAILPOINTS_HTTP", "") in ("1", "true"):
        # Chaos harnesses that arm everything over HTTP at runtime
        # (tools/chaos.py) enable the surface without arming anything.
        from pilosa_tpu.utils.failpoints import FAILPOINTS
        FAILPOINTS.http_enabled = True
        logger.printf("failpoints surface enabled (nothing armed)")
    # Bound the /debug/queries slow-query ring (utils/profile.py).
    api.profiler.configure(ring_size=cfg.profile_slow_ring)
    # Workload analytics plane (utils/hotspots.py): the process-wide
    # recorder picks up the [workload] config — decay half-life,
    # rolling repeat window, top-K, LRU bounds, kill switch.
    from pilosa_tpu.utils.hotspots import WORKLOAD
    WORKLOAD.configure(enabled=cfg.workload_enabled,
                       half_life_s=cfg.workload_half_life_s,
                       window_s=cfg.workload_window_s,
                       top_k=cfg.workload_top_k,
                       max_fragments=cfg.workload_max_fragments,
                       max_rows=cfg.workload_max_rows,
                       max_signatures=cfg.workload_max_signatures)
    # Request records (utils/timeline.py): one span tree per request
    # at GET /debug/timeline, its stage seconds in /debug/vars.
    # [timeline] enabled=false is the kill switch. Under a
    # jax.profiler session each span is also a `pilosa:<stage>` event
    # on its thread's line of the trace's host plane; the exporter,
    # when one is configured, ships every finished record.
    import jax.profiler
    from pilosa_tpu.utils.jaxenv import COMPILES
    from pilosa_tpu.utils.timeline import TIMELINE
    TIMELINE.configure(enabled=cfg.timeline_enabled,
                       ring=cfg.timeline_ring,
                       sample_every=cfg.timeline_sample_every)
    TIMELINE.annotation = jax.profiler.TraceAnnotation
    TIMELINE.exporter = tracer if cfg.tracing_endpoint else None
    # Every XLA compile from here on is counted, with its cause
    # (xla.* counters, the table in GET /debug/queries).
    COMPILES.install(stats)
    # Cross-request cache tier ([cache] section): the generation-keyed
    # result cache lives on the executor, the device rank-cache store
    # is process-wide. The PILOSA_TPU_RESULT_CACHE=0 /
    # PILOSA_TPU_RANK_CACHE=0 env kill switches always win inside
    # configure().
    from pilosa_tpu.core.cache import RANK_CACHE
    api.executor.result_cache.configure(
        enabled=cfg.cache_result_enabled,
        max_bytes=cfg.cache_result_max_bytes)
    RANK_CACHE.configure(enabled=cfg.cache_rank_enabled,
                         max_entries=cfg.cache_rank_max_entries)
    # Plan optimizer ([optimizer] section): the env kill switch
    # PILOSA_TPU_PLAN_OPT=0 always wins — config can disable the
    # optimizer, never re-enable it past the blunt switch.
    from pilosa_tpu.executor import megakernel as _megamod
    if not cfg.optimizer_enabled:
        _megamod.PLAN_OPT_ENABLED = False
    # Mesh collective path ([mesh] collectives): same one-way rule —
    # config can disable the mesh cohort launches (per-group fusion
    # under the mesh, the pre-mesh behavior), never re-enable past
    # the PILOSA_TPU_MESH=0 kill switch.
    if not cfg.mesh_collectives:
        _megamod.MESH_ENABLED = False
    coalescer = None
    if cfg.coalescer_enabled:
        # Cross-request query coalescer: concurrent single-query POSTs
        # share one executor batch (server/coalescer.py). On cluster
        # deployments the API routes around it, so attaching is safe
        # either way.
        from pilosa_tpu.server.coalescer import QueryCoalescer
        coalescer = QueryCoalescer(
            api.executor,
            window_s=cfg.coalescer_window_ms / 1e3,
            max_batch=cfg.coalescer_max_batch,
            max_queue=cfg.coalescer_max_queue,
            deadline_s=cfg.coalescer_deadline_ms / 1e3,
            stats=stats, logger=logger,
            pipeline=cfg.coalescer_pipeline)
        coalescer.start()
        api.coalescer = coalescer
    watchdog = None
    if cfg.telemetry_sample_every_s > 0:
        # Always-on memory/health watchdog (utils/memledger.py): ledger
        # + queue gauges sampled into a flight-recorder ring; pressure
        # warnings when device bytes cross the HBM watermark. Host-side
        # only — zero device fences, so it rides under any load.
        from pilosa_tpu.core.view import BANK_BUDGET
        from pilosa_tpu.utils.memledger import LEDGER, MemoryWatchdog

        def _telemetry_gauges():
            coal = api.coalescer
            return {
                "queueDepth": (coal.queue_depth()
                               if coal is not None else 0),
                "jitCacheSize": api.executor.jit_cache_size(),
            }

        watchdog = MemoryWatchdog(
            LEDGER, stats=stats, logger=logger,
            sample_every_s=cfg.telemetry_sample_every_s,
            ring=cfg.telemetry_ring,
            watermark_bytes=int(BANK_BUDGET.budget
                                * cfg.telemetry_hbm_watermark),
            extra_gauges=_telemetry_gauges)
        watchdog.start()
        api.watchdog = watchdog
    # Adaptive hybrid bank layout (core/layout.py): the background
    # re-layout pass demotes sparse/cold views to compact device
    # SparseBanks under the same HBM watermark the watchdog warns on.
    # PILOSA_TPU_HYBRID_LAYOUT=0 kills the whole plane regardless.
    from pilosa_tpu.core.view import BANK_BUDGET as _BANK_BUDGET
    api.layout.configure(
        enabled=cfg.layout_enabled,
        interval_s=cfg.layout_interval_s,
        demote_density=cfg.layout_demote_density,
        min_bytes=cfg.layout_min_bytes,
        promote_rate=cfg.layout_promote_rate,
        watermark_bytes=int(_BANK_BUDGET.budget
                            * cfg.telemetry_hbm_watermark))
    if cfg.layout_enabled and cfg.layout_interval_s > 0:
        api.layout.start()
    from pilosa_tpu.utils.diagnostics import (
        DiagnosticsCollector, RuntimeMonitor,
    )
    diagnostics = DiagnosticsCollector(
        url=cfg.diagnostics_url, interval=cfg.diagnostics_interval,
        holder=holder, logger=logger)
    diagnostics.start()
    runtime_monitor = None
    if cfg.metric_service != "none" and cfg.metric_poll_interval > 0:
        runtime_monitor = RuntimeMonitor(stats, cfg.metric_poll_interval,
                                         holder=holder)
        runtime_monitor.start()
    anti_entropy = None
    if cluster is not None and cfg.anti_entropy_interval > 0:
        from pilosa_tpu.parallel.syncer import AntiEntropyLoop
        anti_entropy = AntiEntropyLoop(api.syncer, cfg.anti_entropy_interval)
        anti_entropy.start()
    heartbeat = translate_repl = None
    if cluster is not None:
        from pilosa_tpu.parallel.heartbeat import (
            Heartbeater, TranslateReplicationLoop,
        )
        if cfg.heartbeat_interval > 0:
            heartbeat = Heartbeater(cluster,
                                    interval=cfg.heartbeat_interval,
                                    suspect_after=cfg.heartbeat_suspect,
                                    probes_per_round=cfg.heartbeat_probes,
                                    logger=logger,
                                    ssl_context=cfg.client_ssl_context())
            heartbeat.start()
        if cfg.translate_replication_interval > 0:
            translate_repl = TranslateReplicationLoop(
                api, cfg.translate_replication_interval)
            translate_repl.start()
    seed_stop = None
    if cfg.cluster_seeds:
        # Seed-based dynamic join (reference: memberlist seed join →
        # coordinator resize, gossip/gossip.go:65, cluster.go:1676).
        # Runs beside the accept loop: the join must wait until this
        # node answers HTTP (the seed's resize job calls back with
        # /internal/resize/pull), and must retry while seeds boot.
        import threading

        seed_stop = threading.Event()

        # Probe the BIND address (loopback only when binding wildcard/
        # loopback): a server bound to a specific interface does not
        # answer on 127.0.0.1, and advertise may be an external address
        # this host cannot reach. A plain TCP connect avoids TLS (certs
        # need not cover the probe name).
        probe_host = cfg.host
        if probe_host in ("", "0.0.0.0", "::", "localhost"):
            probe_host = "127.0.0.1"

        def _seed_join():
            import socket as _socket
            while not seed_stop.is_set():
                try:  # wait for our own LISTENER
                    _socket.create_connection((probe_host, cfg.port),
                                              timeout=1.0).close()
                    break
                except OSError:
                    seed_stop.wait(0.3)
            while not seed_stop.is_set():
                try:
                    api.join_via_seeds(cfg.cluster_seeds)
                    logger.printf("seed join ok: cluster has %d node(s)",
                                  len(cluster.nodes()))
                    return
                except Exception as e:
                    logger.printf("seed join: %s; retrying in 5s", e)
                    seed_stop.wait(5.0)

        threading.Thread(target=_seed_join, daemon=True,
                         name="seed-join").start()
    logger.printf("pilosa-tpu server: data=%s bind=%s tls=%s mesh=%s "
                  "cluster=%s coalescer=%s", data_dir, cfg.bind,
                  "on" if cfg.tls_enabled else "off",
                  mesh.mesh.shape if mesh else "single-device",
                  f"{len(cluster.nodes())} nodes" if cluster else "no",
                  (f"window={cfg.coalescer_window_ms:g}ms "
                   f"batch<={cfg.coalescer_max_batch} "
                   f"queue<={cfg.coalescer_max_queue}")
                  if coalescer is not None else "off")
    # SIGTERM unwinds like Ctrl-C so the finally below runs the full
    # graceful close (flush caches, close holder) — the reference
    # server likewise traps SIGTERM for shutdown (cmd/pilosa/main.go).
    # Python's default TERM action would kill the process mid-buffer.
    def _graceful(signum, frame):
        raise KeyboardInterrupt
    try:
        signal.signal(signal.SIGTERM, _graceful)
    except ValueError:
        pass  # not the main thread (in-process test harness)
    try:
        serve(api, cfg.host, cfg.port,
              ssl_context=cfg.server_ssl_context())
    finally:
        if coalescer is not None:
            # Graceful drain first (SIGTERM lands here via the handler
            # above): admitted requests still execute; new arrivals
            # degrade to the direct path while the listener unwinds.
            coalescer.stop()
        if seed_stop is not None:
            seed_stop.set()
        if api.broadcaster is not None:
            api.broadcaster.stop()
        if heartbeat is not None:
            heartbeat.stop()
        if translate_repl is not None:
            translate_repl.stop()
        if anti_entropy is not None:
            anti_entropy.stop()
        diagnostics.stop()
        api.layout.stop()
        if runtime_monitor is not None:
            runtime_monitor.stop(logger)
        # Telemetry drain: watchdog ring + slow-query ring dump to the
        # log, tracer stop/flush — buffered telemetry survives SIGTERM.
        drain_telemetry(api, watchdog=watchdog, logger=logger)
        holder.close()
        if hasattr(stats, "flush"):
            # Drain buffered statsd datagrams last, after every
            # stats-producing loop above has stopped.
            stats.flush()
    return 0


def _iter_import_csv(args, batch: int = 0):
    """Yield (rows, cols, vals) batches from the CSV files — `row,col`
    lines, or `col,value` with --field-type int. batch=0 yields one
    batch with everything (the local path); a positive batch streams in
    O(batch) memory (the remote path must not materialize a 100M-line
    CSV as Python lists)."""
    rows, cols, vals = [], [], []
    for path in args.files:
        with open(path, newline="") as f:
            for rec in csv.reader(f):
                if not rec:
                    continue
                if args.field_type == "int":
                    cols.append(int(rec[0]))
                    vals.append(int(rec[1]))
                else:
                    rows.append(int(rec[0]))
                    cols.append(int(rec[1]))
                if batch and len(cols) >= batch:
                    yield rows, cols, vals
                    rows, cols, vals = [], [], []
    if cols or not batch:
        yield rows, cols, vals


def _read_import_csv(args):
    """(rows, cols, vals) fully materialized (the local path)."""
    return next(_iter_import_csv(args))


# Pairs per POST on the remote import path: bounds request bodies to a
# few MB while amortizing the round trip (reference ctl/import.go
# buffers 10M bits per request by default).
REMOTE_IMPORT_BATCH = 1_000_000


def _import_remote(args) -> int:
    """POST CSV-derived batches through a running host's import API
    (reference ctl/import.go: the import subcommand posts ImportRequests
    to --host; the receiving node translates/splits/forwards to shard
    owners, api.go:814). Creates the index/field if missing, like the
    local path."""
    from pilosa_tpu.parallel.client import ClientError, InternalClient

    ssl_ctx = None
    if args.tls_skip_verify:
        import ssl
        ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ssl_ctx.check_hostname = False
        ssl_ctx.verify_mode = ssl.CERT_NONE
    client = InternalClient(timeout=300.0, ssl_context=ssl_ctx)
    host = args.host.rstrip("/")

    def ensure(path: str, options: dict) -> None:
        try:
            client._req("POST", f"{host}{path}", obj={"options": options})
        except ClientError as e:
            # Shared predicate: 409 alone also means "wrong cluster
            # state", which must NOT read as success (client.py:292).
            if not InternalClient._is_already_exists(e):
                raise

    ensure(f"/index/{args.index}", {})
    if args.field_type == "int":
        # Streaming min/max prescan so field creation fits the data
        # without materializing the CSV (second pass posts batches).
        lo = hi = None
        for _, _, vals in _iter_import_csv(args, REMOTE_IMPORT_BATCH):
            if vals:
                lo = min(vals) if lo is None else min(lo, min(vals))
                hi = max(vals) if hi is None else max(hi, max(vals))
        ensure(f"/index/{args.index}/field/{args.field}",
               {"type": "int", "min": lo or 0, "max": hi or 0})
    else:
        ensure(f"/index/{args.index}/field/{args.field}", {})
    url = f"{host}/index/{args.index}/field/{args.field}/import"
    total = 0
    for rows, cols, vals in _iter_import_csv(args, REMOTE_IMPORT_BATCH):
        if args.field_type == "int":
            body = {"columnIDs": cols, "values": vals}
        else:
            body = {"rowIDs": rows, "columnIDs": cols}
        client._req("POST", url, obj=body)
        total += len(cols)
    print(f"imported {total} records into "
          f"{args.index}/{args.field} via {host}")
    return 0


def cmd_import(args) -> int:
    """Bulk CSV import: rows of `row,col` (or `col,value` with --field-type
    int). Default: straight into a local holder. With --host: posted
    through a running server's import API (reference ctl/import.go
    supports both shapes)."""
    if args.host:
        return _import_remote(args)
    if not args.data_dir:
        print("import: either --host or --data-dir is required",
              file=sys.stderr)
        return 2
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions

    holder = Holder(os.path.expanduser(args.data_dir))
    holder.open()
    idx = holder.create_index(args.index, error_if_exists=False)
    rows, cols, vals = _read_import_csv(args)
    if args.field_type == "int":
        lo, hi = (min(vals), max(vals)) if vals else (0, 0)
        f = idx.field(args.field) or idx.create_field(
            args.field, FieldOptions(type="int", min=lo, max=hi))
        f.import_values(np.array(cols, np.uint64), np.array(vals, np.int64))
    else:
        f = idx.field(args.field) or idx.create_field(args.field)
        f.import_bits(np.array(rows, np.uint64), np.array(cols, np.uint64))
    idx.add_existence(np.array(cols, np.uint64))
    holder.close()
    print(f"imported {len(cols)} records into {args.index}/{args.field}")
    return 0


def cmd_export(args) -> int:
    from pilosa_tpu.core.holder import Holder

    holder = Holder(os.path.expanduser(args.data_dir))
    holder.open()
    idx = holder.index(args.index)
    if idx is None or idx.field(args.field) is None:
        print(f"not found: {args.index}/{args.field}", file=sys.stderr)
        return 1
    from pilosa_tpu.server.api import export_fragment_lines
    f = idx.field(args.field)
    view = f.view()
    out = sys.stdout if args.output == "-" else open(args.output, "w")
    for shard in (view.available_shards() if view else []):
        for line in export_fragment_lines(idx, args.field, shard):
            out.write(line)
    if out is not sys.stdout:
        out.close()
    holder.close()
    return 0


def cmd_check(args) -> int:
    """Verify roaring fragment file integrity (reference ctl/check.go)."""
    from pilosa_tpu.storage.roaring import Bitmap

    bad = 0
    for path in args.files:
        try:
            with open(path, "rb") as f:
                b = Bitmap.from_bytes(f.read(), tolerate_torn_tail=True)
            if b.tail_dropped:
                bad += 1
                print(f"{path}: TORN TAIL: last op record truncated "
                      f"({b.tail_dropped} bytes; server open would "
                      f"sidecar+truncate)", file=sys.stderr)
            else:
                print(f"{path}: ok ({b.count()} bits, "
                      f"{len(b.containers)} containers, opN={b.op_n})")
        except Exception as e:
            bad += 1
            print(f"{path}: CORRUPT: {e}", file=sys.stderr)
    return 1 if bad else 0


def cmd_inspect(args) -> int:
    """Container stats for fragment files (reference ctl/inspect.go)."""
    from pilosa_tpu.storage.roaring import Bitmap
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    for path in args.files:
        with open(path, "rb") as f:
            b = Bitmap.from_bytes(f.read(), tolerate_torn_tail=True)
        rows = {}
        for key in sorted(b.containers):
            row = (key << 16) // SHARD_WIDTH
            rows.setdefault(row, [0, 0])
            rows[row][0] += 1
            rows[row][1] += b.container_count(key)
        print(f"{path}: {b.count()} bits, {len(b.containers)} containers, "
              f"{len(rows)} rows, opN={b.op_n}")
        if args.verbose:
            for row, (nc, nb) in sorted(rows.items()):
                print(f"  row {row}: {nc} containers, {nb} bits")
    return 0


def cmd_config(args) -> int:
    from pilosa_tpu.utils.config import load_config
    from dataclasses import asdict

    cfg = load_config(args.config, {})
    print(json.dumps(asdict(cfg), indent=2))
    return 0


def cmd_backup(args) -> int:
    """Tar the data directory (snapshots, op-logs, caches, .meta,
    .topology, .id, translate logs) — the offline analog of the
    reference's tar-stream backup of fragment files over HTTP
    (fragment.go:1885-2230, ctl/export.go). Consistent when the server
    is stopped; a live backup may catch a torn op-log tail, which
    restore+open tolerates (sidecar+truncate)."""
    import tarfile

    data_dir = os.path.expanduser(args.data_dir)
    if not os.path.isdir(data_dir):
        print(f"not a directory: {data_dir}", file=sys.stderr)
        return 1
    out_real = os.path.realpath(args.output)
    n = 0
    with tarfile.open(args.output, "w:gz") as tar:
        for root, _dirs, files in os.walk(data_dir):
            for name in files:
                if name.endswith(".torn"):
                    continue
                full = os.path.join(root, name)
                if os.path.realpath(full) == out_real:
                    continue  # -o inside the data dir: skip ourselves
                tar.add(full, arcname=os.path.relpath(full, data_dir))
                n += 1
    print(f"backed up {n} files from {data_dir} to {args.output}")
    return 0


def cmd_restore(args) -> int:
    """Unpack a backup tar into a data directory (must not already hold
    an index tree unless --force)."""
    import tarfile

    import shutil

    data_dir = os.path.expanduser(args.data_dir)
    if os.path.isdir(data_dir) and os.listdir(data_dir):
        if not args.force:
            print(f"refusing to restore into non-empty {data_dir} "
                  f"(use --force)", file=sys.stderr)
            return 1
        # --force REPLACES: leftover post-backup files must not mix
        # with backup-time state.
        shutil.rmtree(data_dir)
    os.makedirs(data_dir, exist_ok=True)
    with tarfile.open(args.input, "r:*") as tar:
        # Refuse traversal and non-file members (symlinks could point
        # outside) up front, instead of trusting the archive and
        # aborting half-extracted.
        for m in tar.getmembers():
            dest = os.path.realpath(os.path.join(data_dir, m.name))
            if not dest.startswith(os.path.realpath(data_dir) + os.sep):
                print(f"unsafe path in archive: {m.name}", file=sys.stderr)
                return 1
            if not (m.isreg() or m.isdir()):
                print(f"unsafe member type in archive: {m.name}",
                      file=sys.stderr)
                return 1
        tar.extractall(data_dir, filter="data")
        n = len(tar.getmembers())
    print(f"restored {n} files into {data_dir}")
    return 0


def cmd_fold(args) -> int:
    """Rewrite fragment files as pure reference-format snapshots.

    This framework's bulk imports append OP_ADD_ROARING extension
    records (storage/roaring.py OP_ADD_ROARING) that the reference
    implementation rejects as an unknown op type — data files are
    one-way compatible until folded (ADVICE r3). Folding replays the
    op-log into the snapshot and rewrites the file with no op tail, so
    a reference node (roaring.go:1037 unmarshalPilosaRoaring) can open
    it: the downgrade/rollback path. Atomic per file (tmp + rename);
    idempotent."""
    from pilosa_tpu.storage.roaring import Bitmap

    bad = 0
    for path in args.files:
        try:
            with open(path, "rb") as f:
                raw = f.read()
            b = Bitmap.from_bytes(raw, tolerate_torn_tail=True)
            if b.tail_dropped and not args.force:
                bad += 1
                print(f"{path}: torn op tail ({b.tail_dropped} bytes); "
                      "re-run with --force to fold anyway",
                      file=sys.stderr)
                continue
            if b.tail_dropped:
                # Same never-destroy-bytes rule as Fragment.open: the
                # dropped tail (a torn append — or, past the torn-append
                # bound, a possibly-salvageable suffix swallowed by a
                # corrupt length field) goes to a .torn sidecar BEFORE
                # the rewrite discards it from the main file.
                side = path + ".torn"
                with open(side, "ab") as f:
                    f.write(raw[len(raw) - b.tail_dropped:])
                    f.flush()
                    os.fsync(f.fileno())
                print(f"{path}: sidecarred {b.tail_dropped} torn tail "
                      f"bytes to {side}", file=sys.stderr)
            out = b.write_bytes()
            tmp = path + ".folding"
            with open(tmp, "wb") as f:
                f.write(out)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            print(f"{path}: folded to pure snapshot "
                  f"({len(out)} bytes, {b.count()} bits)")
        except Exception as e:
            bad += 1
            print(f"{path}: FOLD FAILED: {e}", file=sys.stderr)
    return 1 if bad else 0


def cmd_generate_config(args) -> int:
    from pilosa_tpu.utils.config import Config

    print(Config().to_toml(), end="")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="pilosa-tpu",
        description="A TPU-native distributed bitmap index.")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("server", help="run the server")
    sp.add_argument("-d", "--data-dir", default=None)
    sp.add_argument("-b", "--bind", default=None)
    sp.add_argument("-c", "--config", default=None)
    sp.add_argument("--verbose", action="store_true")
    sp.add_argument("--platform", default=None,
                    help="JAX platform to require (tpu, cpu); a "
                         "platform that cannot start is fatal")
    sp.add_argument("--no-coalescer", action="store_true",
                    help="serve every query on the direct path "
                         "(disable cross-request coalescing)")
    sp.add_argument("--coalescer-window-ms", type=float, default=None,
                    help="coalescer batching window in milliseconds")
    sp.set_defaults(fn=cmd_server)

    ip = sub.add_parser("import", help="bulk import CSV files")
    ip.add_argument("-d", "--data-dir", default=None,
                    help="local holder to import into (omit with --host)")
    ip.add_argument("--host", default=None,
                    help="import through a running server instead of a "
                         "local holder, e.g. http://localhost:10101")
    ip.add_argument("--tls-skip-verify", action="store_true",
                    help="with an https --host: skip certificate "
                         "verification")
    ip.add_argument("-i", "--index", required=True)
    ip.add_argument("-f", "--field", required=True)
    ip.add_argument("--field-type", default="set", choices=["set", "int"])
    ip.add_argument("files", nargs="+")
    ip.set_defaults(fn=cmd_import)

    ep = sub.add_parser("export", help="export a field as CSV")
    ep.add_argument("-d", "--data-dir", required=True)
    ep.add_argument("-i", "--index", required=True)
    ep.add_argument("-f", "--field", required=True)
    ep.add_argument("-o", "--output", default="-")
    ep.set_defaults(fn=cmd_export)

    cp = sub.add_parser("check", help="check fragment file integrity")
    cp.add_argument("files", nargs="+")
    cp.set_defaults(fn=cmd_check)

    np_ = sub.add_parser("inspect", help="inspect fragment containers")
    np_.add_argument("files", nargs="+")
    np_.add_argument("--verbose", action="store_true")
    np_.set_defaults(fn=cmd_inspect)

    bp = sub.add_parser("backup", help="tar a data directory")
    bp.add_argument("-d", "--data-dir", required=True)
    bp.add_argument("-o", "--output", required=True)
    bp.set_defaults(fn=cmd_backup)

    rp = sub.add_parser("restore", help="unpack a backup tar")
    rp.add_argument("-d", "--data-dir", required=True)
    rp.add_argument("-i", "--input", required=True)
    rp.add_argument("--force", action="store_true")
    rp.set_defaults(fn=cmd_restore)

    gp = sub.add_parser("config", help="print resolved configuration")
    gp.add_argument("-c", "--config", default=None)
    gp.set_defaults(fn=cmd_config)

    fp = sub.add_parser(
        "fold", help="rewrite fragment files as pure snapshots "
        "(reference-readable: drops OP_ADD_ROARING extension records)")
    fp.add_argument("files", nargs="+")
    fp.add_argument("--force", action="store_true",
                    help="fold even files with a torn op tail")
    fp.set_defaults(fn=cmd_fold)

    gg = sub.add_parser("generate-config", help="print default TOML config")
    gg.set_defaults(fn=cmd_generate_config)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
