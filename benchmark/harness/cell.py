"""One run of one cell: start the server, load or re-open, warm up,
measure, stop, compare, reduce, report.

The parent stays off jax. Set-up is everything from process start to
the first measured request. After the window the server is stopped
first (SIGTERM, exit code 0 required), then the answers are compared
with the plain reference off the clock, then the readers named by the
metric files turn snapshots, requests and the trace into numbers.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import loadgen
from harness.manifest import Manifest
from harness.server import BenchFailure, Server, server_env
from harness.trace_reduce import top

HERE = os.path.dirname(os.path.abspath(__file__))
VERIFY_THREADS = max(4, (os.cpu_count() or 8) - 1)


class Failures:
    """What went wrong, counted in full and quoted in part."""

    def __init__(self):
        self.count = 0
        self.messages = []

    def add(self, msg: str, n: int = 1) -> None:
        self.count += n
        if len(self.messages) < 20:
            self.messages.append(msg)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def state_dir(root: str, cfg: dict, platform: str) -> str:
    """A fixed directory per configuration as it is run (the path is
    part of the compile cache's key): the name, and a digest of the
    sizes and the platform so that a rehearsal at another size never
    re-opens this one's data."""
    key = json.dumps([cfg["name"], cfg["shards"], cfg["grid_rows"],
                      cfg["n_days"], cfg["data_seed"], platform])
    tag = hashlib.sha1(key.encode()).hexdigest()[:8]
    return os.path.join(root, ".state", f"{cfg['name']}-{tag}")


def write_toml(path: str, server_config: dict) -> None:
    with open(path, "w") as f:
        for k, v in server_config.items():
            f.write(f"{k} = {json.dumps(v)}\n")


def prune_cache(cache_dir: str, since: float) -> list:
    """Delete the compile-cache files written since `since`, in place
    (the path is part of the cache's key), so that every run of a cell
    starts from the same cache state: what set-up compiles in every
    run, and nothing that an earlier run's window (or, where the
    traffic file says so, its concurrent warm-up) happened to meet.
    Returns their names (JAX names a file after the jitted function)."""
    names = []
    for base, _, files in os.walk(cache_dir):
        for name in files:
            path = os.path.join(base, name)
            try:
                if not name.startswith(".") and \
                        os.path.getmtime(path) >= since:
                    os.remove(path)
                    names.append(name)
            except FileNotFoundError:
                pass
    return names


class Tracer:
    """Switches the launcher's profiler on for a stretch of the window
    by dropping files into its control directory."""

    def __init__(self, ctl: str):
        self.ctl = ctl
        shutil.rmtree(ctl, ignore_errors=True)
        os.makedirs(ctl)
        self._timers = []

    def schedule(self, at_s: float, for_s: float) -> None:
        for name, delay in (("start", at_s), ("stop", at_s + for_s)):
            t = threading.Timer(delay, self._touch, args=(name,))
            t.daemon = True
            t.start()
            self._timers.append(t)

    def _touch(self, name: str) -> None:
        with open(os.path.join(self.ctl, name), "w"):
            pass

    def finish(self) -> None:
        """Make sure `stop` is there and wait until the profiler has
        written its trace."""
        for t in self._timers:
            t.join()
        if not os.path.exists(os.path.join(self.ctl, "started")):
            raise BenchFailure("the profiler never started")
        self._touch("stop")
        deadline = time.monotonic() + 180
        while not os.path.exists(os.path.join(self.ctl, "stopped")):
            if time.monotonic() > deadline:
                raise BenchFailure("the profiler did not stop in 180 s")
            time.sleep(0.05)

    def reduce(self, checkout: str) -> dict:
        """In a child that may import jax: this parent never does."""
        out = os.path.join(self.ctl, "summary.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        subprocess.run([sys.executable,
                        os.path.join(HERE, "trace_reduce.py"),
                        self.ctl, out], cwd=checkout, env=env, check=True)
        with open(out) as f:
            return json.load(f)


def warm_up(srv, port, dataset, data, traffic, path, failures) -> dict:
    """Family queries once each (checked), the pinned shapes, then the
    cell's own traffic from the traffic file's fixed warm-up seed until
    the program stops compiling (or the file's limit)."""
    t0 = time.monotonic()
    for pql, want in dataset.family_queries(data):
        got = srv.query(dataset.INDEX, pql)
        if not dataset.equal(got, want):
            failures.add(f"family query {pql}: server "
                         f"{str(got)[:200]} reference {str(want)[:200]}")
    log(f"warm-up: family queries took {time.monotonic() - t0:.1f} s")
    w = traffic["warmup"]
    pinned = list(loadgen.pinned_stream(dataset, data, traffic, w["seed"]))
    reqs, _, _ = loadgen.run_clients(port, path, [iter(pinned)], 3600)
    check(reqs, reqs, dataset, failures, "warm-up pinned")
    log(f"warm-up: {len(reqs)} pinned shapes by {time.monotonic() - t0:.1f} s")

    def compiles() -> int:
        return srv.get("/internal/health")["executor"]["retraces"]

    slices, flat, last = 0, 0, compiles()
    t_slices = time.monotonic()
    t_slices_wall = time.time()
    while flat < w["flat_slices"] and \
            time.monotonic() - t_slices < w["max_seconds"]:
        streams = [loadgen.client_stream(dataset, data, traffic,
                                         w["seed"] + 1 + slices, c)
                   for c in range(traffic["clients"])]
        reqs, _, _ = loadgen.run_clients(port, path, streams,
                                         w["slice_seconds"])
        bad = [r for r in reqs if r.status != 200]
        if bad:
            failures.add(f"warm-up: {len(bad)} requests failed, first "
                         f"{bad[0].status} {bad[0].body[:200]!r}", len(bad))
        slices += 1
        now = compiles()
        log(f"warm-up: slice {slices}, {len(reqs)} requests, "
            f"{now} compiles so far")
        flat = flat + 1 if now == last else 0
        last = now
    return {"warmup_s": time.monotonic() - t0, "warmup_slices": slices,
            "slices_from": t_slices_wall,
            "warmup_compiles": last, "warmup_settled":
                flat >= w["flat_slices"]}


def check(reqs: list, sample: list, dataset, failures: Failures,
          what: str) -> int:
    """Every request: HTTP 200. The sample: a JSON `results` list of
    one whose decoded answer equals the numpy recomputation (limit: 0
    differ). Returns how many answers were compared."""
    for r in reqs:
        if r.status != 200:
            failures.add(f"{what}: {r.pql} -> {r.status} "
                         f"{r.body[:200]!r}")

    def one(r):
        if r.status != 200:
            return None
        try:
            (got,) = json.loads(r.body)["results"]
        except (ValueError, KeyError, TypeError):
            return f"{what}: {r.pql} -> unreadable {r.body[:200]!r}"
        want = r.ref()
        if dataset.equal(got, want):
            return None
        return (f"{what}: {r.pql}\n  server:    {str(got)[:300]}\n"
                f"  reference: {str(want)[:300]}")

    with ThreadPoolExecutor(VERIFY_THREADS) as pool:
        for msg in pool.map(one, sample):
            if msg is not None:
                failures.add(msg)
    return sum(1 for r in sample if r.status == 200)


def per_second(done: list, t_start: float, seconds: float) -> list:
    """Replies read in each second of the window: a ramp here says
    that something still warmed up inside it."""
    counts = [0] * max(1, int(np.ceil(seconds)))
    for r in done:
        counts[min(len(counts) - 1, int(r.t_recv - t_start))] += 1
    return counts


def family_times(reqs: list) -> dict:
    """family -> [requests, p50 ms, p95 ms]: which shapes make the tail."""
    by_family = {}
    for r in reqs:
        by_family.setdefault(r.family, []).append(
            1e3 * (r.t_recv - r.t_send))
    return {fam: [len(ts), loadgen.percentile(ts, 50),
                  loadgen.percentile(ts, 95)]
            for fam, ts in sorted(by_family.items())}


def draw_sample(reqs: list, n: int, seed: int, run_length: int = 0) -> list:
    """A seeded sample of the window's requests. Half of it (where the
    traffic file gives `verify_run_length`) is runs of that many
    replies next to each other in the order they were read: requests
    that shared a coalesced flush are answered together, so a fault
    that lives in certain batches is met batch by batch. The rest
    covers every family: the families take turns until `n` are drawn."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 77])
    out = []
    if run_length and len(reqs) > n:
        by_recv = sorted(reqs, key=lambda r: r.t_recv)
        starts = rng.choice(len(by_recv) // run_length,
                            min(n // 2 // run_length,
                                len(by_recv) // run_length), replace=False)
        for k in sorted(starts.tolist()):
            out.extend(by_recv[k * run_length:(k + 1) * run_length])
    taken = {id(r) for r in out}
    by_family = {}
    for r in reqs:
        if id(r) not in taken:
            by_family.setdefault(r.family, []).append(r)
    pools = []
    for fam in sorted(by_family):
        rs = by_family[fam]
        pools.append([rs[i] for i in rng.permutation(len(rs))])
    while len(out) < n and any(pools):
        for p in pools:
            if p and len(out) < n:
                out.append(p.pop())
    return out


def run_cell(checkout: str, workload: str, seed: int, seconds: float,
             trace: bool, t_proc: float, platform: str = "tpu",
             sizes: dict = None) -> dict:
    """Run the cell once and return the result object (the last line).
    `sizes` overrides the configuration's scale for a rehearsal off the
    chip."""
    if checkout not in sys.path:
        sys.path.append(checkout)   # the loader's client-side roaring
    man = Manifest(checkout)
    wl = man.workload(workload)
    cfg = dict(man.config(wl["config"]), **(sizes or {}))
    traffic = man.load_json("traffic", wl["traffic"])
    dataset = man.load_module("datasets", cfg["dataset"])
    root = man.roots[0]
    state = state_dir(root, cfg, platform)
    os.makedirs(state, exist_ok=True)
    data_dir = os.path.join(state, "data")
    marker = os.path.join(state, "loaded.json")
    want_marker = dataset.fingerprint(cfg, cfg["shard_width"])
    loaded = False
    if os.path.exists(marker):
        with open(marker) as f:
            loaded = json.load(f) == want_marker
    if not loaded:
        shutil.rmtree(data_dir, ignore_errors=True)
        if os.path.exists(marker):
            os.remove(marker)
    toml = os.path.join(state, "server.toml")
    write_toml(toml, cfg["server_config"])
    env = server_env(os.path.join(state, "jax_cache"))
    tracer = launcher = None
    if trace:
        tracer = Tracer(os.path.join(state, "trace_ctl"))
        env["BENCH_TRACE_CTL"] = tracer.ctl
        launcher = [os.path.join(HERE, "launcher.py")]

    def start() -> Server:
        return Server(checkout, data_dir, platform, toml,
                      os.path.join(state, "server.log"), env, launcher)

    srv = start()
    failures = Failures()
    try:
        data = dataset.make(cfg, cfg["shard_width"])   # while it starts
        info = srv.wait_ready()
        setup = {"server_start_s": time.monotonic() - srv.t_spawn}
        dev0 = info["devices"][0]
        if dev0["platform"] != platform:
            raise BenchFailure(f"server runs on {dev0['platform']}, "
                               f"wanted {platform}")
        if info["deviceCount"] < wl["chips"] and platform != "cpu":
            raise BenchFailure(f"{info['deviceCount']} devices, the cell "
                               f"asks for {wl['chips']}")
        if info["shardWidth"] != cfg["shard_width"]:
            raise BenchFailure(f"shard width {info['shardWidth']}")
        if platform != "cpu" and \
                info["meshDevices"] != cfg["server_config"]["mesh_devices"]:
            raise BenchFailure(f"mesh of {info['meshDevices']} devices")
        t0 = time.monotonic()
        if not loaded:
            # Load, then restart: every window, the first run's too,
            # is served from a re-opened directory.
            dataset.load(srv, data, log)
            if srv.stop() != 0:
                raise BenchFailure(f"server exited {srv.proc.returncode} "
                                   f"after the load:\n{srv.log_tail()}")
            with open(marker, "w") as f:
                json.dump(want_marker, f)
            srv = start()
            info = srv.wait_ready()
            setup["server_start_s"] = time.monotonic() - srv.t_spawn
        setup["load_s"] = time.monotonic() - t0
        path = f"/index/{dataset.INDEX}/query"
        port = srv.port     # a control's server hands out its proxy's
        setup.update(warm_up(srv, port, dataset, data, traffic, path,
                             failures))
        before = srv.snapshot()
        streams = [loadgen.client_stream(dataset, data, traffic, seed, c)
                   for c in range(traffic["clients"])]
        if tracer:
            length = min(traffic["trace"]["seconds"], seconds / 2)
            tracer.schedule(min(traffic["trace"]["at_s"], seconds / 4),
                            length)
        # The window runs on the monotonic clock; the wall clock is
        # read once beside it, for file times and the profiler's marks.
        wall_ahead = time.time() - time.monotonic()
        setup["setup_s"] = time.monotonic() - t_proc
        # ------------------------------------------------ the window
        reqs, t_start, t_end = loadgen.run_clients(port, path, streams,
                                                   seconds)
        # -----------------------------------------------------------
        after = srv.snapshot()
        if tracer:
            tracer.finish()
        cache_dir = after["info"]["compileCacheDir"]
        rc = srv.stop()
    except BaseException:
        srv.kill()
        raise
    if rc != 0:
        raise BenchFailure(f"server exited {rc} on SIGTERM; log tail:\n"
                           f"{srv.log_tail()}")
    # What the run compiled from the traffic file's `prune_cache_from`
    # on goes, so that the next run starts where this one did.
    since = {"window": t_start + wall_ahead,
             "warmup_slices": setup.pop("slices_from")}[
                 traffic["prune_cache_from"]]
    pruned = prune_cache(cache_dir, since)
    summary = tracer.reduce(checkout) if tracer else None
    if summary and summary["busy_s"] <= 0:
        raise BenchFailure("no operation ran on a device while the "
                           "window was traced")

    # ---------------------------------------- compare, off the clock
    t0 = time.monotonic()
    sample = draw_sample(reqs, traffic["verify_sample"], seed,
                         traffic.get("verify_run_length", 0))
    compared = check(reqs, sample, dataset, failures, "window")
    failed = failures.count
    ok_in_window = [r for r in reqs
                    if r.status == 200 and r.t_recv <= t_end]
    late = sorted(r.late_s for r in reqs)
    print(json.dumps({
        "check": {"http_not_200": sum(r.status != 200 for r in reqs),
                  "http_not_200_limit": 0,
                  "answers_compared": compared,
                  "answers_in_window": len(reqs),
                  "answers_differing": failed, "answers_differing_limit": 0,
                  "families_compared": sorted({r.family for r in sample}),
                  "reference_s": time.monotonic() - t0},
        "generator": {"clients": traffic["clients"], "sent": len(reqs),
                      "late_p50_ms": 1e3 * late[len(late) // 2],
                      "late_p99_ms": 1e3 * loadgen.percentile(late, 99),
                      "late_max_ms": 1e3 * late[-1]},
        "window": {"completed": len(ok_in_window),
                   "per_second": per_second(ok_in_window, t_start, seconds),
                   "compiles": after["health"]["executor"]["retraces"]
                   - before["health"]["executor"]["retraces"],
                   "cache_files_pruned": len(pruned),
                   "pruned_programs": sorted(
                       {n.split("-")[0] for n in pruned})[:12]},
        "families": family_times(reqs),
        "devices": [[d["id"], d["bytesInUse"], d["peakBytesInUse"]]
                    for d in after["info"]["devices"]],
        "setup": setup,
        "failures": failures.messages[:5]}), flush=True)

    ctx = {"before": before, "after": after, "requests": reqs,
           "seconds": seconds, "t_start": t_start, "t_end": t_end,
           "completed": len(ok_in_window), "setup": setup,
           "trace": summary, "config": cfg, "traffic": traffic,
           "dataset": dataset, "device_kind": dev0["kind"],
           "ops_in_trace": 0}
    if summary:
        ctx["ops_in_trace"] = sum(
            1 for r in reqs if r.status == 200
            and summary["started"] <= r.t_recv + wall_ahead
            <= summary["stopped"])
    metrics = {}
    section = "per_layer" if trace else "end_to_end"
    for m in man.metrics_for(section, workload):
        spec = man.metric_spec(m["name"])
        reader = man.load_module("readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    peaks = [d["peakBytesInUse"] for d in after["info"]["devices"]
             if d["peakBytesInUse"] is not None]
    device = {"platform": dev0["platform"], "kind": dev0["kind"],
              "count": info["deviceCount"],
              "memory_peak_bytes": max(peaks) if peaks else None}
    result = {"correct": failed == 0, "attempted": len(reqs),
              "failed": failed, "metrics": metrics, "device": device}
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": top(summary["ops"]),
                               "idle_gaps": top(summary["gaps"])}
    return result
