"""Server configuration.

Reference: /root/reference/server/config.go:43 (TOML schema) with cobra/
viper precedence flags > env (PILOSA_*) > TOML file (cmd/root.go:55-75).
Same precedence here: CLI flags > PILOSA_TPU_* env > TOML file > defaults.
"""

from __future__ import annotations

import os

try:
    import tomllib  # Python >= 3.11
except ModuleNotFoundError:  # 3.10 images carry the identical backport
    import tomli as tomllib
from dataclasses import dataclass, field, fields, asdict
from typing import Any, Dict, Optional

ENV_PREFIX = "PILOSA_TPU_"


@dataclass
class Config:
    data_dir: str = "~/.pilosa_tpu"
    bind: str = "localhost:10101"
    verbose: bool = False
    # Query
    max_writes_per_request: int = 5000
    # Queries slower than this (seconds) are logged AND recorded in the
    # structured slow-query ring served at GET /debug/queries; 0
    # disables both.
    long_query_time: float = 0.0
    # Per-query execution profiler (utils/profile.py). ?profile=true on
    # POST /index/{i}/query profiles with a block_until_ready fence
    # after each program; no other query is fenced and no key here can
    # turn a fence on. slow_ring bounds the /debug/queries ring. TOML
    # accepts a [profile] table (slow_ring) or the flat profile_*
    # spelling.
    profile_slow_ring: int = 128
    # Serving-path query coalescer (server/coalescer.py): concurrent
    # single-query POSTs arriving within the batching window share one
    # executor batch. TOML accepts a [coalescer] table (keys without the
    # prefix) or the flat coalescer_* spelling; env/flags use the flat
    # names (PILOSA_TPU_COALESCER_WINDOW_MS, ...).
    coalescer_enabled: bool = True
    coalescer_window_ms: float = 1.5   # max wait for batchmates
    coalescer_max_batch: int = 64      # size cap -> early flush
    coalescer_max_queue: int = 256     # admission bound -> 429 past it
    coalescer_deadline_ms: float = 0.0  # per-request queue deadline; 0 off
    # RTT-hiding pipelined dispatch: batch K+1 plans/launches on the
    # dispatcher while batch K's results drain on a finalizer thread
    # (double-buffered, read-only flushes only — writes barrier).
    # PILOSA_TPU_PIPELINE=0 is the absolute kill switch over this.
    coalescer_pipeline: bool = True
    # TPU
    mesh_devices: int = 0         # 0 = all visible devices
    mesh_replicas: int = 1
    # Mesh cohort path (executor/megakernel.py): megakernel plan
    # buffers run SPMD over the mesh shard axis with in-kernel
    # collective reductions (psum count lanes, all-gather row lanes).
    # TOML accepts a [mesh] table (devices/replicas/collectives) or
    # the flat mesh_* spelling; the env kill switch PILOSA_TPU_MESH=0
    # always wins — config can disable the collective path, never
    # re-enable it past the blunt switch.
    mesh_collectives: bool = True
    # JAX platform to require ("" = whatever JAX picks, which is the
    # CPU with a warning when no accelerator is found). "tpu" makes a
    # chipless start fatal; "cpu" is for tests. The start line and
    # GET /info say which one the process ended up on.
    platform: str = ""
    # Multi-host SPMD (jax.distributed): when coordinator is set, the
    # server calls jax.distributed.initialize before building the mesh,
    # so the mesh spans every host's devices and XLA routes inter-host
    # collectives over DCN (the reference's NCCL/MPI analog is its HTTP
    # scatter-gather, executor.go:2277; see docs/administration.md).
    jax_coordinator: str = ""   # host:port of process 0
    jax_num_processes: int = 0  # 0 = single process
    jax_process_id: int = -1    # -1 = auto/unset
    # Anti-entropy
    anti_entropy_interval: float = 600.0
    # Failure detection (reference: memberlist SWIM probing,
    # gossip/gossip.go:246; here a direct heartbeat prober)
    heartbeat_interval: float = 5.0     # 0 disables
    heartbeat_suspect: int = 3          # consecutive failures -> DOWN
    heartbeat_probes: int = 2           # healthy peers probed per round
    # Standing translate-log replication from the primary (reference
    # monitorReplication, translate.go:359); 0 disables
    translate_replication_interval: float = 10.0
    # Telemetry watchdog (utils/memledger.MemoryWatchdog): always-on
    # sampling of the HBM memory ledger + queue gauges into a bounded
    # flight-recorder ring, dumped to the log on SIGTERM. Near-zero
    # overhead (host-side dict reads; never fences the device). TOML
    # accepts a [telemetry] table (sample_every_s / ring /
    # hbm_watermark) or the flat telemetry_* spelling; env uses
    # PILOSA_TPU_TELEMETRY_SAMPLE_EVERY_S etc. sample_every_s = 0
    # disables the watchdog (the ledger itself is always on).
    telemetry_sample_every_s: float = 10.0
    telemetry_ring: int = 360  # flight-recorder snapshots kept
    # HBM pressure watermark as a fraction of the resident-bank budget
    # (PILOSA_TPU_HBM_BUDGET_BYTES): crossing it logs one warning with
    # the top-K largest banks. 0 disables the warning.
    telemetry_hbm_watermark: float = 0.9
    # Workload analytics plane (utils/hotspots.WorkloadRecorder):
    # access heatmaps, write churn, cache-opportunity estimation.
    # Always host-side dict work on the staging path; `enabled = false`
    # is the kill switch (record calls return before taking any lock).
    # TOML accepts a [workload] table (enabled / half_life_s /
    # window_s / top_k / max_fragments / max_rows / max_signatures) or
    # the flat workload_* spelling; env uses PILOSA_TPU_WORKLOAD_*.
    workload_enabled: bool = True
    # EWMA half-life for "recently hot" rates: a fragment idle for one
    # half-life scores half its previous rate.
    workload_half_life_s: float = 600.0
    # Rolling window for cross-request repeat ratios (queries and
    # coalescer request identities).
    workload_window_s: float = 300.0
    # Entries in /debug/hotspots top-K lists.
    workload_top_k: int = 10
    # LRU bounds on tracked keys (evicted entries fold their counts
    # into the snapshot's `evicted` bucket, keeping totals provable).
    workload_max_fragments: int = 4096
    workload_max_rows: int = 4096
    workload_max_signatures: int = 1024
    # Cross-request cache tier (ROADMAP item 3): the generation-keyed
    # query result cache (executor/result_cache.py — request tier
    # keyed on the coalescer's request identity, eval tier on the
    # staged fingerprint + bank generations) and the device-resident
    # TopN rank cache (core/cache.RANK_CACHE). TOML accepts a [cache]
    # table (result_enabled / result_max_bytes / rank_enabled /
    # rank_max_entries) or the flat cache_* spelling; env uses
    # PILOSA_TPU_CACHE_RESULT_ENABLED etc. The blunt kill switches
    # PILOSA_TPU_RESULT_CACHE=0 / PILOSA_TPU_RANK_CACHE=0 override
    # everything (config can disable, never re-enable past them).
    cache_result_enabled: bool = True
    # LRU byte budget for cached results (host RAM; ledgered under
    # category "result_cache" so /debug/memory totals stay provable).
    cache_result_max_bytes: int = 256 << 20
    cache_rank_enabled: bool = True
    # Live per-view rank vectors kept device-resident (HBM; category
    # "rank_cache"); each is 4 bytes/row.
    cache_rank_max_entries: int = 64
    # Cost-based plan optimizer (ops/plan_opt.py): the pass pipeline
    # that rewrites verified megakernel plans between lowering and
    # launch — cross-request CSE, density-ordered fold reordering,
    # dead-register elimination and lane width narrowing. Every
    # optimized plan still passes verify_plan and stays bit-identical;
    # the knob exists for triage (rule the optimizer out in one move)
    # and A/B measurement. TOML accepts an [optimizer] table
    # (enabled) or the flat optimizer_* spelling; env uses
    # PILOSA_TPU_OPTIMIZER_ENABLED. The blunt kill switch
    # PILOSA_TPU_PLAN_OPT=0 overrides everything (config can disable,
    # never re-enable past it).
    optimizer_enabled: bool = True
    # Adaptive hybrid bank layout (core/layout.py): the background
    # re-layout pass that demotes sparse/cold views to compact device
    # SparseBanks and promotes them back when they heat up, driven by
    # the hotspots demotion ranking under the memledger HBM watermark.
    # TOML accepts a [layout] table (enabled / interval_s /
    # demote_density / min_bytes / promote_rate) or the flat layout_*
    # spelling; env uses PILOSA_TPU_LAYOUT_*. The blunt kill switch
    # PILOSA_TPU_HYBRID_LAYOUT=0 overrides everything (no sparse
    # planning, no re-layout — config can disable, never re-enable
    # past it). interval_s = 0 disables only the background thread
    # (manual relayout and sparse serving still work).
    layout_enabled: bool = True
    layout_interval_s: float = 30.0
    # Banks whose live density (pad share x sampled live bits) falls
    # below this demote even without HBM pressure; above the HBM
    # watermark the ranking demotes top-down regardless.
    layout_demote_density: float = 0.25
    # Banks smaller than this never demote (the win wouldn't cover
    # the bookkeeping).
    layout_min_bytes: int = 1 << 20
    # Sparse views whose decayed read rate climbs above this promote
    # back to dense (and dense banks hotter than it resist demotion
    # below the watermark).
    layout_promote_rate: float = 0.5
    # Request records (utils/timeline.py): a bounded per-process ring
    # of per-request span trees (http.read -> pql.parse ->
    # coalescer.wait -> plan -> h2d -> dispatch -> d2h -> finish ->
    # http.serialize -> http.write) served as Chrome trace-event JSON
    # at GET /debug/timeline, their stage seconds cumulative in
    # /debug/vars. Host-side clock readings only — a `device` span
    # appears only on queries the profiler already fences.
    # `enabled = false` is the kill switch. TOML accepts a [timeline]
    # table (enabled / ring / sample_every) or the flat timeline_*
    # spelling; env uses PILOSA_TPU_TIMELINE_*.
    timeline_enabled: bool = True
    timeline_ring: int = 256        # request records kept
    timeline_sample_every: int = 1  # record 1 in N requests (1 = all)
    # Metrics (reference server/config.go Metric.Service/Host: expvar |
    # statsd | none — "mem" is the expvar equivalent)
    metric_service: str = "mem"   # mem | statsd | none
    metric_host: str = "localhost:8125"  # statsd agent address
    metric_poll_interval: float = 10.0  # runtime gauge sampling; 0 off
    # Diagnostics phone-home (reference server/config.go:105; OFF unless
    # both an interval and an endpoint URL are configured)
    diagnostics_interval: float = 0.0
    diagnostics_url: str = ""
    # Tracing export (reference Jaeger wiring, server/config.go:110-118):
    # OTLP/HTTP JSON endpoint, e.g. http://localhost:4318/v1/traces
    # (Jaeger >=1.35 and the OTel collector both ingest it). "" = record
    # spans in memory only.
    tracing_endpoint: str = ""
    tracing_service_name: str = "pilosa-tpu"
    # Head sampling (reference Tracing.SamplerType/SamplerParam,
    # server/config.go:110-118): const (param 0/1), probabilistic
    # (param = fraction of traces), ratelimiting (param = traces/sec).
    tracing_sampler_type: str = "const"
    tracing_sampler_param: float = 1.0
    # Cluster: static peer URI list (must include this node's own URI) +
    # replication factor (reference cluster.replicas, server/config.go:63)
    cluster_peers: list = field(default_factory=list)
    cluster_replicas: int = 1
    # Dynamic membership: URIs of existing members to join through at
    # boot (reference: memberlist seed join, gossip/gossip.go:65; the
    # join event drives a coordinator resize, cluster.go:1676-1715).
    # Unlike cluster_peers this does NOT list the whole cluster — any
    # one reachable seed suffices, and the node adopts the topology the
    # seed returns. A restarted member re-announcing through its seeds
    # is a no-op (idempotent rejoin).
    cluster_seeds: list = field(default_factory=list)
    # Fan-out resilience knobs (parallel/cluster_executor.py; TOML
    # accepts the [cluster] table — the same table as peers/replicas —
    # or the flat cluster_* spelling; env PILOSA_TPU_CLUSTER_*). These
    # replace the old scattered 5 s / 30 s / 600 s client literals.
    # Per-request scatter-gather deadline: every remote leg gets the
    # REMAINING budget as its RPC timeout, so one wedged peer can
    # never hold a request past it. 0 disables (legs fall back to
    # rpc_timeout_s alone).
    cluster_fanout_deadline_s: float = 30.0
    # Internal-client default RPC timeout (InternalClient.timeout).
    cluster_rpc_timeout_s: float = 30.0
    # Health/hotspots/timeline probe timeout (a wedged node must be
    # REPORTED by the fleet documents, not waited on).
    cluster_health_timeout_s: float = 5.0
    # Synchronous resize pull pass (the node streams every fragment it
    # now owns — minutes on big holders).
    cluster_resize_pull_timeout_s: float = 600.0
    # Exponential backoff between failover rounds: base doubles per
    # round up to cap, with full jitter.
    cluster_backoff_base_s: float = 0.05
    cluster_backoff_cap_s: float = 2.0
    # Hedged reads: a scatter leg slower than this quantile of the
    # recent leg-latency window is re-issued to a spare replica (first
    # success wins, bit-exact by the settle latch). 0 disables.
    cluster_hedge_quantile: float = 0.0
    # Fault-injection plane (utils/failpoints.py): site -> spec table,
    # e.g. [failpoints] "client.connect" = "error". Also settable via
    # PILOSA_TPU_FAILPOINTS="site=spec;site=spec". Any entry enables
    # the test-only POST /internal/failpoints surface.
    failpoints: dict = field(default_factory=dict)
    advertise: str = ""  # URI peers reach us at; default <scheme>://<bind>
    # TLS (reference server/config.go:120-166: TLS.CertificatePath,
    # TLS.CertificateKeyPath, TLS.SkipCertificateVerification; listener
    # wrap at server/server.go:244). When certificate+key are set the
    # listener serves HTTPS — client AND intra-cluster traffic, like the
    # reference — and peers are dialed as https. ca_certificate lets
    # nodes verify a private CA without skip_verify.
    tls_certificate: str = ""       # PEM server certificate (chain)
    tls_key: str = ""               # PEM private key
    tls_ca_certificate: str = ""    # PEM CA bundle for verifying peers
    tls_skip_verify: bool = False   # disable peer cert verification

    @property
    def tls_enabled(self) -> bool:
        return bool(self.tls_certificate or self.tls_key)

    @property
    def scheme(self) -> str:
        return "https" if self.tls_enabled else "http"

    @property
    def host(self) -> str:
        return self.bind.rsplit(":", 1)[0] or "localhost"

    @property
    def port(self) -> int:
        parts = self.bind.rsplit(":", 1)
        return int(parts[1]) if len(parts) == 2 and parts[1] else 10101

    def validate(self) -> None:
        if self.port <= 0 or self.port > 65535:
            raise ValueError(f"invalid port {self.port}")
        if self.mesh_replicas < 1:
            raise ValueError("mesh_replicas must be >= 1")
        if bool(self.tls_certificate) != bool(self.tls_key):
            raise ValueError(
                "tls_certificate and tls_key must be set together")
        if self.coalescer_window_ms < 0 or self.coalescer_deadline_ms < 0:
            raise ValueError("coalescer window/deadline must be >= 0")
        if self.coalescer_max_batch < 1 or self.coalescer_max_queue < 1:
            raise ValueError("coalescer max_batch/max_queue must be >= 1")
        if self.profile_slow_ring < 1:
            raise ValueError("profile slow_ring must be >= 1")
        if self.telemetry_sample_every_s < 0:
            raise ValueError("telemetry sample_every_s must be >= 0")
        if self.workload_half_life_s <= 0 or self.workload_window_s <= 0:
            raise ValueError(
                "workload half_life_s/window_s must be > 0")
        if self.workload_top_k < 1 or self.workload_max_fragments < 1 \
                or self.workload_max_rows < 1 \
                or self.workload_max_signatures < 1:
            raise ValueError(
                "workload top_k/max_* bounds must be >= 1")
        if self.telemetry_ring < 1:
            raise ValueError("telemetry ring must be >= 1")
        if self.cache_result_max_bytes < 0:
            raise ValueError("cache result_max_bytes must be >= 0")
        if self.cache_rank_max_entries < 1:
            raise ValueError("cache rank_max_entries must be >= 1")
        if self.layout_interval_s < 0:
            raise ValueError("layout interval_s must be >= 0")
        if not 0 <= self.layout_demote_density <= 1:
            raise ValueError(
                "layout demote_density must be in [0, 1]")
        if self.layout_min_bytes < 0:
            raise ValueError("layout min_bytes must be >= 0")
        if self.layout_promote_rate < 0:
            raise ValueError("layout promote_rate must be >= 0")
        if self.timeline_ring < 1 or self.timeline_sample_every < 1:
            raise ValueError(
                "timeline ring/sample_every must be >= 1")
        if not 0 <= self.telemetry_hbm_watermark <= 1:
            raise ValueError(
                "telemetry hbm_watermark must be in [0, 1]")
        if self.cluster_fanout_deadline_s < 0:
            raise ValueError("cluster fanout_deadline_s must be >= 0")
        if self.cluster_rpc_timeout_s <= 0 \
                or self.cluster_health_timeout_s <= 0 \
                or self.cluster_resize_pull_timeout_s <= 0:
            raise ValueError(
                "cluster rpc/health/resize_pull timeouts must be > 0")
        if self.cluster_backoff_base_s < 0 \
                or self.cluster_backoff_cap_s < 0:
            raise ValueError("cluster backoff base/cap must be >= 0")
        if not 0 <= self.cluster_hedge_quantile < 1:
            raise ValueError(
                "cluster hedge_quantile must be in [0, 1)")
        if self.failpoints:
            from pilosa_tpu.utils.failpoints import parse_spec
            for site, spec in self.failpoints.items():
                parse_spec(str(spec))  # raises ValueError on bad spec
                if not isinstance(site, str) or not site:
                    raise ValueError(
                        f"failpoint site names must be strings: "
                        f"{site!r}")

    def server_ssl_context(self):
        """ssl.SSLContext for the listener, or None when TLS is off
        (reference getListener, server/server.go:244)."""
        if not self.tls_enabled:
            return None
        import ssl
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(os.path.expanduser(self.tls_certificate),
                            os.path.expanduser(self.tls_key))
        return ctx

    def client_ssl_context(self):
        """ssl.SSLContext for dialing https peers, or None for plain
        http clusters. skip_verify mirrors the reference's
        InsecureSkipVerify (server/server.go:244)."""
        if not (self.tls_enabled or self.tls_ca_certificate
                or self.tls_skip_verify):
            return None
        import ssl
        if self.tls_skip_verify:
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            return ctx
        ctx = ssl.create_default_context()
        if self.tls_ca_certificate:
            ctx.load_verify_locations(
                os.path.expanduser(self.tls_ca_certificate))
        return ctx

    def to_toml(self) -> str:
        lines = []
        tables = []
        for k, v in asdict(self).items():
            if isinstance(v, str):
                lines.append(f'{k} = "{v}"')
            elif isinstance(v, bool):
                lines.append(f"{k} = {str(v).lower()}")
            elif isinstance(v, list):
                items = ", ".join(f'"{x}"' for x in v)
                lines.append(f"{k} = [{items}]")
            elif isinstance(v, dict):
                if v:  # dotted keys need a real table, emitted last
                    tables.append((k, v))
            else:
                lines.append(f"{k} = {v}")
        out = "\n".join(lines) + "\n"
        for name, tbl in tables:
            out += f"\n[{name}]\n"
            for sk, sv in tbl.items():
                out += f'"{sk}" = "{sv}"\n'
        return out


def load_config(path: Optional[str] = None,
                overrides: Optional[Dict[str, Any]] = None) -> Config:
    """flags > env > file > defaults (reference cmd/root.go:55-75)."""
    cfg = Config()
    if path:
        with open(path, "rb") as f:
            data = tomllib.load(f)
        # Validate against the dataclass FIELDS, not hasattr: hasattr
        # also matches read-only properties (tls_enabled, port) and
        # methods (server_ssl_context), which would either crash with
        # a raw AttributeError or silently shadow a method.
        settable = {f.name for f in fields(cfg)}
        for k, v in data.items():
            k = k.replace("-", "_")
            if k == "failpoints":
                # Keys carry dots ("client.connect") — this table
                # stays a dict instead of flattening to field names.
                if not isinstance(v, dict):
                    raise ValueError(
                        f"[{k}] must be a table of "
                        f"key = \"value\" entries")
                setattr(cfg, k, {str(sk): str(sv)
                                 for sk, sv in v.items()})
                continue
            if isinstance(v, dict):
                # TOML table, e.g. [coalescer] window_ms = 2.0 -> the
                # flat coalescer_window_ms field (reference nests its
                # TOML the same way, server/config.go:43).
                for sk, sv in v.items():
                    flat = f"{k}_{sk.replace('-', '_')}"
                    if flat not in settable:
                        raise ValueError(
                            f"unknown config key {k}.{sk!r}")
                    setattr(cfg, flat, sv)
            elif k in settable:
                setattr(cfg, k, v)
            else:
                raise ValueError(f"unknown config key {k!r}")
    for k in list(vars(cfg)):
        env = os.environ.get(ENV_PREFIX + k.upper())
        if env is not None:
            cur = getattr(cfg, k)
            if isinstance(cur, bool):
                setattr(cfg, k, env.lower() in ("1", "true", "yes"))
            elif isinstance(cur, int):
                setattr(cfg, k, int(env))
            elif isinstance(cur, float):
                setattr(cfg, k, float(env))
            elif isinstance(cur, list):
                setattr(cfg, k, [s for s in env.split(",") if s])
            elif isinstance(cur, dict):
                # PILOSA_TPU_FAILPOINTS="site=spec;site=spec" — env
                # entries merge over (and win against) the TOML table.
                merged = dict(cur)
                for part in env.split(";"):
                    part = part.strip()
                    if not part:
                        continue
                    if "=" not in part:
                        raise ValueError(
                            f"bad {ENV_PREFIX}{k.upper()} entry "
                            f"{part!r} (want site=spec)")
                    name, spec = part.split("=", 1)
                    merged[name.strip()] = spec.strip()
                setattr(cfg, k, merged)
            else:
                setattr(cfg, k, env)
    for k, v in (overrides or {}).items():
        if v is not None:
            setattr(cfg, k, v)
    cfg.validate()
    return cfg
