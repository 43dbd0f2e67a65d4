#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and answer right,
on the chip?

Drives the system the way a user does: one `pilosa-tpu server` child
(the only process that touches the device), data loaded through the
public import routes over HTTP, PQL posted to /index/taxi/query, every
answer compared with a numpy recomputation on the same arrays. Then a
concurrent mixed burst (the coalescer -> fusion -> megakernel path), a
SIGTERM + restart on the same data and compile-cache directories
(acknowledged writes read back from disk; the cache hits), and — when
the host shows four devices — the same data and queries against one
server driving a 4-device mesh.

Data is one chip's share of the north-star deployment (BASELINE.json
config 5: 1B rides x 1024 shards on v5e-64 = 16 shards per chip):
16 shards x 2^20 rides of the NYC-taxi schema, with a 1023-row one-hot
pickup_grid field whose dense bank is [1024, 16, 32768] u32 = 2 GiB.

This parent never imports jax: a parent that has touched JAX holds the
chip, and the server child then fails or hangs. It exits non-zero, and
prints no result line, when the server does not come up on --platform
(default tpu), when any answer differs, or when any phase fails.

    python chip_smoke.py                      # on the chip, full size
    python chip_smoke.py --platform cpu --shards 1 --grid-rows 15

The last stdout line is exactly {"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}} — the device as the server's JAX
reports it. The full record (sizes, times, counters, `reduced`) is the
stdout line before it and --out/record.json (default
chiprun_out/chip_smoke/), next to the server logs.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from datetime import date, timedelta

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

INDEX = "taxi"
FULL_SHARDS = 16      # one chip's share of 1024 shards on 64 chips
FULL_GRID_ROWS = 1023  # +1 zero slot = a 1024-slot bank
N_DAYS = 28           # pickup dates: 2019-01-01 .. 2019-01-28
DAY0 = date(2019, 1, 1)
BURST_THREADS = 64
BURST_PER_THREAD = 3
HTTP_TIMEOUT_S = 900  # a cold query waits for its compile


class SmokeFailure(Exception):
    """A check failed; the run exits non-zero with this message."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ server


class Server:
    """One `python -m pilosa_tpu.cli server` child."""

    started: list = []   # every child ever started, for the deadline

    def __init__(self, data_dir: str, platform: str, mesh_devices: int,
                 log_path: str):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        # The caller's environment passes through untouched, so a
        # JAX_COMPILATION_CACHE_DIR set from outside places the cache.
        env = dict(os.environ, PILOSA_TPU_MESH_DEVICES=str(mesh_devices))
        self._log = open(log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "-d", data_dir, "-b", f"127.0.0.1:{self.port}",
             "--platform", platform],
            cwd=HERE, env=env, stdout=self._log, stderr=self._log)
        Server.started.append(self)

    def wait_ready(self, timeout_s: float = 300.0) -> dict:
        """Poll GET /info until the listener answers; a child that
        exits first (no chip under --platform tpu) fails the run."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} before "
                    f"answering; log tail:\n{self.log_tail()}")
            try:
                return self.get("/info")
            except (urllib.error.URLError, ConnectionError):
                time.sleep(0.1)
        raise SmokeFailure(f"server not ready after {timeout_s:.0f}s")

    def request(self, method: str, path: str, body: bytes = None,
                ctype: str = "application/json") -> dict:
        req = urllib.request.Request(self.base + path, data=body,
                                     method=method)
        if body is not None:
            req.add_header("Content-Type", ctype)
        try:
            with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} -> {e.code}: {e.read()[:500]!r}")

    def get(self, path: str) -> dict:
        return self.request("GET", path)

    def post_json(self, path: str, obj: dict) -> dict:
        return self.request("POST", path, json.dumps(obj).encode())

    def query(self, pql: str):
        (res,) = self.request("POST", f"/index/{INDEX}/query",
                              pql.encode(), "text/plain")["results"]
        return res

    def executor_health(self) -> dict:
        return self.get("/internal/health")["executor"]

    def log_tail(self, n: int = 30) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(
                "utf-8", "replace")

    def stop(self) -> int:
        """SIGTERM, wait for the graceful drain, return the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise SmokeFailure("server ignored SIGTERM for 120s")
        self._log.close()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def check_server_log(path: str) -> None:
    with open(path, "rb") as f:
        text = f.read().decode("utf-8", "replace")
    for needle in ("Traceback (most recent call last)", "LockOrderError"):
        if needle in text:
            at = text.index(needle)
            raise SmokeFailure(f"{needle!r} in {path}:\n"
                               f"{text[max(0, at - 300):at + 1500]}")


# -------------------------------------------------------------------- data


class Rides:
    """The rides, as the numpy arrays every reference answer is
    recomputed from. Set fields are kept one boolean row per row id so
    a Set() applied to the server is one assignment here."""

    def __init__(self, seed: int, n_shards: int, grid_rows: int,
                 shard_width: int):
        rng = np.random.default_rng(seed)
        self.shard_width = shard_width
        self.n_shards = n_shards
        self.grid_rows = grid_rows
        n = self.n = n_shards * shard_width
        cab = rng.integers(0, 3, n, dtype=np.uint8)      # yellow/green/fhv
        pax = rng.integers(1, 7, n, dtype=np.uint8)
        self.dist = rng.integers(0, 300, n).astype(np.int64)  # 0.1 miles
        self.amount = self.dist * 25 // 10 + rng.integers(3, 20, n)
        self.day = rng.integers(0, N_DAYS, n, dtype=np.uint8)
        # Pickup zones are skewed in the real data (midtown dwarfs the
        # outer boroughs): Zipf(0.8) over the grid rows.
        p = 1.0 / np.arange(1, grid_rows + 1) ** 0.8
        cdf = np.cumsum(p / p.sum())
        self.grid = np.minimum(np.searchsorted(cdf, rng.random(n)),
                               grid_rows - 1).astype(np.uint16)
        self.cab = {r: cab == r for r in range(3)}
        self.pax = {r: pax == r for r in range(1, 7)}
        # pickup is a time field whose row is the cab type.
        self.pickup = {r: self.cab[r].copy() for r in range(3)}

    def shard(self, s: int) -> slice:
        return slice(s * self.shard_width, (s + 1) * self.shard_width)

    def in_days(self, d0: int, d1: int) -> np.ndarray:
        return (self.day >= d0) & (self.day < d1)


def roaring_bytes(rows: np.ndarray, cols: np.ndarray,
                  shard_width: int) -> bytes:
    """Serialized roaring bitmap of (row, column-in-shard) bits for one
    shard — what POST .../import-roaring/{shard} takes."""
    from pilosa_tpu.storage import Bitmap

    b = Bitmap()
    b.direct_add_n(rows.astype(np.uint64) * np.uint64(shard_width)
                   + cols.astype(np.uint64))
    b.optimize()
    return b.write_bytes()


def load(srv: Server, rides: Rides) -> None:
    """Schema + data through the public routes: import-roaring for the
    set and time fields (per-view payloads, computed client-side as
    upstream's batch importers do), JSON /import for the BSI values."""
    srv.post_json(f"/index/{INDEX}", {})
    for name, opts in (
            ("cab_type", {}), ("passenger_count", {}), ("pickup_grid", {}),
            ("dist", {"type": "int", "min": 0, "max": 300}),
            ("amount", {"type": "int", "min": 0, "max": 1000}),
            ("pickup", {"type": "time", "timeQuantum": "YMD"})):
        srv.post_json(f"/index/{INDEX}/field/{name}", {"options": opts})
    sw = rides.shard_width
    cab = sum(r * m.astype(np.uint8) for r, m in rides.cab.items())
    pax = sum(r * m.astype(np.uint8) for r, m in rides.pax.items())
    every = np.arange(sw)

    def put(field: str, shard: int, rows: np.ndarray,
            cols: np.ndarray = every, view: str = "standard") -> None:
        srv.request("POST", f"/index/{INDEX}/field/{field}"
                    f"/import-roaring/{shard}?view={view}",
                    roaring_bytes(rows, cols, sw),
                    "application/octet-stream")

    for s in range(rides.n_shards):
        sl = rides.shard(s)
        put("cab_type", s, cab[sl])
        put("passenger_count", s, pax[sl])
        put("pickup_grid", s, rides.grid[sl])
        ids = list(range(sl.start, sl.stop))
        for field, vals in (("dist", rides.dist), ("amount", rides.amount)):
            srv.post_json(f"/index/{INDEX}/field/{field}/import",
                          {"columnIDs": ids, "values": vals[sl].tolist()})
        # A YMD time field keeps each bit in its standard, year, month
        # and day views.
        for view in ("standard", "standard_2019", "standard_201901"):
            put("pickup", s, cab[sl], view=view)
        day = rides.day[sl]
        for d in range(N_DAYS):
            on = np.flatnonzero(day == d)
            put("pickup", s, cab[sl][on], on,
                view=f"standard_201901{d + 1:02d}")
        log(f"loaded shard {s + 1}/{rides.n_shards}")


# ----------------------------------------------------------------- queries


def iso(day_index: int) -> str:
    return (DAY0 + timedelta(days=day_index)).isoformat()


def topn(counts: np.ndarray, n: int) -> list:
    order = np.lexsort((np.arange(len(counts)), -counts))
    return [{"id": int(r), "count": int(counts[r])}
            for r in order[:n] if counts[r] > 0]


def family_queries(r: Rides) -> list:
    """One query per family the deployment serves: (pql, expected)."""
    grid_counts = np.bincount(r.grid, minlength=r.grid_rows)
    grid_cab0 = np.bincount(r.grid[r.cab[0]], minlength=r.grid_rows)
    groups = [{"group": [{"field": "cab_type", "rowID": c},
                         {"field": "passenger_count", "rowID": p}],
               "count": int((r.cab[c] & r.pax[p]).sum())}
              for c in range(3) for p in range(1, 7)]
    return [
        ("Count(Intersect(Row(cab_type=0), Row(passenger_count=2)))",
         int((r.cab[0] & r.pax[2]).sum())),
        ("Count(Row(cab_type=2))", int(r.cab[2].sum())),
        ("Count(Row(dist < 50))", int((r.dist < 50).sum())),
        ("Sum(Row(cab_type=1), field=amount)",
         {"value": int(r.amount[r.cab[1]].sum()),
          "count": int(r.cab[1].sum())}),
        ("TopN(pickup_grid, n=10)", topn(grid_counts, 10)),
        ("TopN(pickup_grid, Row(cab_type=0), n=10)", topn(grid_cab0, 10)),
        ("GroupBy(Rows(cab_type), Rows(passenger_count))",
         [g for g in groups if g["count"]]),
        (f"Count(Row(pickup=0, from='{iso(4)}', to='{iso(11)}'))",
         int((r.pickup[0] & r.in_days(4, 11)).sum())),
        (f"Count(Row(pickup=1, from='{iso(0)}', to='{iso(N_DAYS)}'))",
         int(r.pickup[1].sum())),
    ]


BURST_SHAPES = 10


def burst_query(r: Rides, shape: int, rng) -> tuple:
    """(pql, thunk computing the reference answer) of one shape with
    freshly drawn row ids and thresholds."""
    g1, g2 = (int(x) for x in rng.integers(0, r.grid_rows, 2))
    a = int(rng.integers(0, 3))
    b = int(rng.integers(1, 7))
    t = int(rng.integers(1, 300))
    in_g1 = r.grid == g1
    if shape == 0:
        return (f"Count(Intersect(Row(pickup_grid={g1}), "
                f"Row(cab_type={a})))",
                lambda: int((in_g1 & r.cab[a]).sum()))
    if shape == 1:
        return (f"Count(Row(dist < {t}))",
                lambda: int((r.dist < t).sum()))
    if shape == 2:
        return (f"Count(Row(amount > {2 * t}))",
                lambda: int((r.amount > 2 * t).sum()))
    if shape == 3:
        return (f"Count(Union(Row(pickup_grid={g1}), "
                f"Row(passenger_count={b})))",
                lambda: int((in_g1 | r.pax[b]).sum()))
    if shape == 4:
        return (f"Count(Difference(Row(passenger_count={b}), "
                f"Row(pickup_grid={g1})))",
                lambda: int((r.pax[b] & ~in_g1).sum()))
    if shape == 5:
        return (f"Count(Intersect(Row(pickup_grid={g1}), "
                f"Row(dist < {t})))",
                lambda: int((in_g1 & (r.dist < t)).sum()))
    if shape == 6:
        return (f"Intersect(Row(pickup_grid={g1}), Row(cab_type={a}), "
                f"Row(passenger_count={b}))",
                lambda: {"columns": np.flatnonzero(
                    in_g1 & r.cab[a] & r.pax[b]).tolist()})
    if shape == 7:
        return (f"Sum(Row(pickup_grid={g1}), field=amount)",
                lambda: {"value": int(r.amount[in_g1].sum()),
                         "count": int(in_g1.sum())})
    if shape == 8:
        d0, d1 = sorted(rng.choice(N_DAYS + 1, 2, replace=False).tolist())
        return (f"Count(Row(pickup={a}, from='{iso(d0)}', "
                f"to='{iso(d1)}'))",
                lambda: int((r.pickup[a] & r.in_days(d0, d1)).sum()))
    return (f"Count(Xor(Row(pickup_grid={g1}), Row(pickup_grid={g2})))",
            lambda: int((in_g1 ^ (r.grid == g2)).sum()))


def burst_queries(r: Rides, seed: int, n: int) -> list:
    """n distinct queries, mixed in shape, none of them a repeat of a
    family query — neither cache can answer, and a coalesced flush
    holds several signatures at once."""
    rng = np.random.default_rng(seed + 1)
    seen = {q for q, _ in family_queries(r)}
    out = []
    i = 0
    while len(out) < n:
        pql, want = burst_query(r, i % BURST_SHAPES, rng)
        i += 1
        if pql not in seen:
            seen.add(pql)
            out.append((pql, want()))
    return out


def run_queries(srv: Server, queries: list, what: str) -> float:
    """Post each query, compare; returns the first answer's seconds."""
    first_s = None
    for pql, want in queries:
        t0 = time.monotonic()
        got = srv.query(pql)
        if first_s is None:
            first_s = time.monotonic() - t0
        if got != want:
            raise SmokeFailure(f"{what}: {pql}\n  server:    "
                               f"{str(got)[:400]}\n  reference: "
                               f"{str(want)[:400]}")
    return first_s


def run_burst(srv: Server, queries: list) -> None:
    """All threads post at once, each its own distinct queries."""
    per = [queries[i::BURST_THREADS] for i in range(BURST_THREADS)]
    gate = threading.Barrier(BURST_THREADS)

    def worker(mine):
        gate.wait()
        return [(pql, want, srv.query(pql)) for pql, want in mine]

    with ThreadPoolExecutor(BURST_THREADS) as pool:
        for fut in [pool.submit(worker, mine) for mine in per]:
            for pql, want, got in fut.result():
                if got != want:
                    raise SmokeFailure(
                        f"burst: {pql}\n  server:    {str(got)[:400]}\n"
                        f"  reference: {str(want)[:400]}")


def apply_writes(srv: Server, r: Rides) -> list:
    """Two acknowledged Sets; the next Counts must reflect them, and
    so must the Counts after a restart. Returns the follow-up checks."""
    col = int(np.flatnonzero(r.cab[0])[0])       # a cab_type=0 ride
    col_t = int(np.flatnonzero(~r.pickup[1])[7])  # not yet in pickup row 1
    d = int(r.day[col_t])
    for pql in (f"Set({col}, cab_type=2)",
                f"Set({col_t}, pickup=1, {iso(d)}T00:00)"):
        if srv.query(pql) is not True:
            raise SmokeFailure(f"{pql} was not acknowledged as a change")
    r.cab[2][col] = True
    r.pickup[1][col_t] = True
    return [
        ("Count(Row(cab_type=2))", int(r.cab[2].sum())),
        ("Count(Intersect(Row(cab_type=0), Row(cab_type=2)))", 1),
        (f"Count(Row(pickup=1, from='{iso(d)}', to='{iso(d + 1)}'))",
         int((r.pickup[1] & r.in_days(d, d + 1)).sum())),
    ]


# ------------------------------------------------------------------ checks


def cache_files(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def bank_bytes(r: Rides) -> int:
    """Bytes of pickup_grid's dense device bank: slots pad to the next
    power of two above rows+1 (one zero slot), 128 KiB per shard-row."""
    slots = 1 << int(r.grid_rows).bit_length()
    return slots * r.n_shards * (r.shard_width // 8)


def check_devices(info: dict, platform: str, n_mesh: int,
                  r: Rides) -> list:
    devs = info["devices"]
    if any(d["platform"] != platform for d in devs):
        raise SmokeFailure(f"server runs on {devs}, wanted {platform}")
    if not info["native"]["loaded"]:
        raise SmokeFailure(f"native library not loaded: "
                           f"{info['native']['error']}")
    used = devs[:n_mesh]
    if platform == "cpu":
        return used  # the CPU backend keeps no allocator counters
    share = bank_bytes(r) // n_mesh
    for d in used:
        if d["bytesInUse"] is None or d["bytesInUse"] < share:
            raise SmokeFailure(
                f"device {d['id']} holds {d['bytesInUse']} bytes; the "
                f"pickup_grid bank alone is {share} per device")
    mean = sum(d["bytesInUse"] for d in used) / n_mesh
    if max(d["bytesInUse"] for d in used) > 1.5 * mean:
        raise SmokeFailure(f"banks are not spread over the mesh: {used}")
    return used


def versions() -> dict:
    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


# -------------------------------------------------------------------- legs


def serve_leg(name: str, data_dir: str, args, mesh_devices: int,
              out_dir: str, body) -> dict:
    """Start a server, run body(srv, info), SIGTERM it; every failure
    path stops the child."""
    log_path = os.path.join(out_dir, f"server_{name}.log")
    srv = Server(data_dir, args.platform, mesh_devices, log_path)
    try:
        info = srv.wait_ready()
        start_s = time.monotonic() - srv.t_spawn
        with open(log_path, "rb") as f:
            start_line = next((ln.decode() for ln in f.read().splitlines()
                               if b" devices: platform=" in ln), None)
        if start_line is None or \
                f"platform={args.platform} " not in start_line:
            raise SmokeFailure(f"start line does not name platform "
                               f"{args.platform}: {start_line!r}")
        rec = body(srv, info)
        rec["start_s"] = start_s
        rec["start_line"] = start_line.split(" INFO ", 1)[-1]
        rc = srv.stop()
    except BaseException:
        srv.kill()
        raise
    if rc != 0:
        raise SmokeFailure(f"{name} server exited {rc} on SIGTERM; log "
                           f"tail:\n{srv.log_tail()}")
    check_server_log(log_path)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="platform the server must run on; cpu is for "
                         "the tier-1 test, and is named in the record")
    ap.add_argument("--shards", type=int, default=FULL_SHARDS)
    ap.add_argument("--grid-rows", type=int, default=FULL_GRID_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=0,
                    help="devices for the mesh leg; 0 = 4 when the "
                         "server shows at least 4, else no mesh leg")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--deadline", type=int, default=1150,
                    help="give up (non-zero) after this many seconds")
    args = ap.parse_args()

    def on_deadline(signum, frame):
        # Kill first: client threads blocked on a live server would
        # otherwise hold the unwind for their whole HTTP timeout.
        for srv in Server.started:
            srv.kill()
        raise SmokeFailure(f"deadline of {args.deadline}s passed")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(args.deadline)
    os.makedirs(args.out, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_data_")
    try:
        record = run(args, data_dir)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    with open(os.path.join(args.out, "record.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))
    # The result line: these two keys and nothing else.
    print(json.dumps({"ok": record["ok"], "device": record["device"]}),
          flush=True)
    return 0


def run(args, data_dir: str) -> dict:
    t_run = time.monotonic()
    record = {"ok": False, "device": None, "versions": versions(),
              "seed": args.seed}
    state = {}

    def cold(srv: Server, info: dict) -> dict:
        devs = info["devices"]
        # As jax.devices() reports it: /info's deviceCount is global.
        record["device"] = {"platform": devs[0]["platform"],
                            "kind": devs[0]["kind"],
                            "count": info["deviceCount"]}
        if devs[0]["platform"] != args.platform:
            raise SmokeFailure(f"server runs on {devs[0]['platform']}, "
                               f"wanted {args.platform}")
        t0 = time.monotonic()
        rides = state["rides"] = Rides(args.seed, args.shards,
                                       args.grid_rows, info["shardWidth"])
        gen_s = time.monotonic() - t0
        t0 = time.monotonic()
        load(srv, rides)
        load_s = time.monotonic() - t0
        log(f"generated in {gen_s:.1f}s, loaded in {load_s:.1f}s")
        fam = family_queries(rides)
        first_s = run_queries(srv, fam, "cold")
        log(f"first answer {first_s:.1f}s after the first query")
        state["writes"] = apply_writes(srv, rides)
        run_queries(srv, state["writes"], "after Set")
        burst = burst_queries(rides, args.seed,
                              BURST_THREADS * BURST_PER_THREAD)
        before = srv.executor_health()
        t0 = time.monotonic()
        run_burst(srv, burst)
        burst_s = time.monotonic() - t0
        health = srv.executor_health()
        launches = health["megaLaunches"] - before["megaLaunches"]
        if health["megakernelEnabled"] and launches < 1:
            raise SmokeFailure("megakernel is on and the burst "
                               "launched it 0 times")
        info2 = srv.get("/info")
        used = check_devices(info2, args.platform, 1, rides)
        state["cache_dir"] = info2["compileCacheDir"]
        return {"load_s": load_s, "generate_s": gen_s,
                "first_answer_s": first_s, "burst_wall_s": burst_s,
                "queries_run": len(fam) + len(state["writes"]) + 2
                + len(burst),
                "megakernel_enabled": health["megakernelEnabled"],
                "mega_launches": launches,
                "mega_queries": health["megaQueries"]
                - before["megaQueries"],
                "jit_compiles": health["retraces"],
                "devices": used,
                "bank_bytes_expected": bank_bytes(rides)}

    record["cold"] = serve_leg("cold", data_dir, args, 1, args.out, cold)
    cache_dir = state["cache_dir"]
    files_cold = cache_files(cache_dir)
    rides = state["rides"]
    # Family answers now include both Sets (the model was updated).
    fam = family_queries(rides) + state["writes"]

    def warm(srv: Server, info: dict) -> dict:
        first_s = run_queries(srv, fam, "after restart")
        health = srv.executor_health()
        return {"first_answer_s": first_s,
                "queries_run": len(fam),
                "jit_compiles": health["retraces"],
                "devices": check_devices(srv.get("/info"), args.platform,
                                         1, rides)}

    record["warm"] = serve_leg("warm", data_dir, args, 1, args.out, warm)
    files_warm = cache_files(cache_dir)
    # Spawn -> listener up, plus the first query's own time (bank
    # upload and compile included); the cold leg's load sits between
    # the two and is not counted.
    record["start_to_first_answer_s"] = {
        leg: record[leg]["start_s"] + record[leg]["first_answer_s"]
        for leg in ("cold", "warm")}
    record["compile_cache"] = {"dir": cache_dir,
                               "files_after_cold": files_cold,
                               "files_after_warm": files_warm}
    if files_cold == 0 or files_warm != files_cold:
        raise SmokeFailure(f"compile cache did not carry the restart: "
                           f"{record['compile_cache']}")

    n_mesh = args.chips or (4 if record["device"]["count"] >= 4 else 0)
    if n_mesh > 1:
        if record["device"]["count"] < n_mesh:
            raise SmokeFailure(f"--chips {n_mesh} but the server shows "
                               f"{record['device']['count']} devices")
        burst = burst_queries(rides, args.seed + 100,
                              BURST_THREADS * BURST_PER_THREAD)

        def mesh(srv: Server, info: dict) -> dict:
            if info["meshDevices"] != n_mesh:
                raise SmokeFailure(f"mesh of {info['meshDevices']} "
                                   f"devices, wanted {n_mesh}")
            run_queries(srv, fam, f"mesh of {n_mesh}")
            before = srv.executor_health()
            run_burst(srv, burst)
            health = srv.executor_health()
            launches = health["meshLaunches"] - before["meshLaunches"]
            if health["megakernelEnabled"] and launches < 1:
                raise SmokeFailure("megakernel is on and the mesh burst "
                                   "launched 0 mesh cohorts")
            return {"mesh_devices": n_mesh,
                    "queries_run": len(fam) + len(burst),
                    "mesh_launches": launches,
                    "mesh_collective_bytes":
                        health["meshCollectiveBytes"],
                    "jit_compiles": health["retraces"],
                    "devices": check_devices(srv.get("/info"),
                                             args.platform, n_mesh, rides)}

        record["mesh"] = serve_leg("mesh", data_dir, args, n_mesh,
                                   args.out, mesh)

    record.update({
        "rides": rides.n, "shards": rides.n_shards,
        "fields": {"cab_type": 3, "passenger_count": 6,
                   "pickup_grid": rides.grid_rows, "dist": "int 0..300",
                   "amount": "int 0..1000",
                   "pickup": f"time YMD, {N_DAYS} days x 3 rows"},
        "chips_used": max(1, n_mesh),
        "reduced": [f"{k}: {full} -> {got}" for k, full, got in (
            ("shards", FULL_SHARDS, args.shards),
            ("pickup_grid rows", FULL_GRID_ROWS, args.grid_rows))
            if got != full],
        "wall_s": time.monotonic() - t_run,
        "ok": True,
    })
    return record


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        sys.exit(1)
