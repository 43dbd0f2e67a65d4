"""OS-process-level cluster fault injection — the analog of the
reference's docker+pumba cluster tests
(/root/reference/internal/clustertests/cluster_test.go:54-70, which
pauses a node 10 s mid-import and asserts anti-entropy heals it, and
Dockerfile-clustertests:17-19): three REAL `pilosa-tpu server`
processes on localhost, faults injected with real signals.

- SIGSTOP one node mid-import (the pumba pause): imports keep landing
  (fan-out to the frozen peer is swallowed and healed later), then the
  node resumes and anti-entropy converges every replica.
- SIGKILL the same node mid-import: its oplog may tear mid-record;
  restart on the same data dir must recover the torn tail, rejoin the
  static topology, and resync via anti-entropy.

Convergence is asserted the way the fragment syncer itself reasons:
identical per-block checksums on every owning replica, plus identical
query results through every node."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

N_NODES = 3
REPLICAS = 2
N_SHARDS = 4
ROWS = 3


def _free_ports(n):
    import socket
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _req(port, method, path, body=None, timeout=30):
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                               data=data, method=method)
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return json.loads(resp.read() or b"{}")


class ProcCluster:
    def __init__(self, tmp_path):
        self.tmp = tmp_path
        self.ports = _free_ports(N_NODES)
        self.uris = [f"http://127.0.0.1:{p}" for p in self.ports]
        self.procs = [None] * N_NODES
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        self.env = dict(os.environ)
        # Correctness tests: the children run on the CPU wherever the
        # suite runs, and leave any chip to the process that needs it.
        self.env["JAX_PLATFORMS"] = "cpu"
        self.env["PYTHONPATH"] = repo
        for i in range(N_NODES):
            d = tmp_path / f"node{i}"
            d.mkdir(exist_ok=True)
            peers = ", ".join(f'"{u}"' for u in self.uris)
            (d / "config.toml").write_text(
                f'bind = "127.0.0.1:{self.ports[i]}"\n'
                f"cluster_peers = [{peers}]\n"
                f"cluster_replicas = {REPLICAS}\n"
                "anti_entropy_interval = 2.0\n"
                "heartbeat_interval = 1.0\n"
                "translate_replication_interval = 1.0\n"
                'metric_service = "none"\n'
                "metric_poll_interval = 0\n")

    def start(self, i):
        d = self.tmp / f"node{i}"
        log = open(d / "server.log", "ab")
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "-d", str(d), "-c", str(d / "config.toml"),
             "--platform", "cpu"],
            stdout=log, stderr=log, env=self.env)

    def start_all(self):
        for i in range(N_NODES):
            self.start(i)
        deadline = time.time() + 120
        for i, port in enumerate(self.ports):
            while True:
                try:
                    _req(port, "GET", "/status", timeout=5)
                    break
                except (urllib.error.URLError, OSError):
                    if time.time() > deadline:
                        raise RuntimeError(
                            f"node {i} never became ready; log:\n" +
                            (self.tmp / f"node{i}" / "server.log")
                            .read_text()[-2000:])
                    if self.procs[i].poll() is not None:
                        raise RuntimeError(
                            f"node {i} exited rc={self.procs[i].returncode}"
                            ":\n" + (self.tmp / f"node{i}" / "server.log")
                            .read_text()[-2000:])
                    time.sleep(0.5)

    def stop_all(self):
        for p in self.procs:
            if p is not None and p.poll() is None:
                p.terminate()
        for p in self.procs:
            if p is not None:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)


@pytest.fixture
def cluster(tmp_path):
    c = ProcCluster(tmp_path)
    c.start_all()
    yield c
    c.stop_all()


class Importer(threading.Thread):
    """Continuously imports bits through node0 until stopped, retrying
    on transient failures (the reference's import client retries
    through the pause the same way). Tracks exactly which bits landed
    (an import batch either succeeds as a whole or is retried)."""

    def __init__(self, port):
        super().__init__(daemon=True)
        self.port = port
        self.stop_evt = threading.Event()
        self.landed = set()  # (row, col)
        self.batches = 0
        self.next_col = 0

    def run(self):
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        while not self.stop_evt.is_set():
            batch = []
            for _ in range(40):
                shard = self.next_col % N_SHARDS
                col = shard * SHARD_WIDTH + (self.next_col // N_SHARDS)
                batch.append((self.next_col % ROWS, col))
                self.next_col += 1
            body = {"rowIDs": [r for r, _ in batch],
                    "columnIDs": [c for _, c in batch]}
            while not self.stop_evt.is_set():
                try:
                    _req(self.port, "POST",
                         "/index/ci/field/cf/import", body, timeout=60)
                    self.landed.update(batch)
                    self.batches += 1
                    break
                except (urllib.error.URLError, OSError):
                    time.sleep(0.5)
            time.sleep(0.05)

    def stop(self):
        self.stop_evt.set()
        self.join(timeout=90)


def wait_converged(c, up_ports, want_counts, deadline_s=90):
    """Until deadline: every row Count agrees with `want_counts`
    through every live node, and every owning replica reports
    identical fragment block checksums for every shard."""
    q = " ".join(f"Count(Row(cf={r}))" for r in range(ROWS))
    deadline = time.time() + deadline_s
    last = None
    while time.time() < deadline:
        try:
            ok = True
            for port in up_ports:
                res = _req(port, "POST", "/index/ci/query",
                           q.encode())["results"]
                if res != want_counts:
                    ok = False
                    last = (port, res, want_counts)
                    break
            if ok:
                checked = 0
                for shard in range(N_SHARDS):
                    sums = set()
                    nodes = _req(up_ports[0], "GET",
                                 f"/internal/fragment/nodes?index=ci"
                                 f"&shard={shard}")
                    owner_ports = [c.ports[c.uris.index(n["uri"])]
                                   for n in nodes
                                   if c.ports[c.uris.index(n["uri"])]
                                   in up_ports]
                    assert owner_ports, (shard, nodes, up_ports)
                    for port in owner_ports:
                        blocks = _req(
                            port, "GET",
                            f"/internal/fragment/blocks?index=ci&field=cf"
                            f"&view=standard&shard={shard}")["blocks"]
                        if not blocks:
                            # e.g. a restarted node pre-resync: retry,
                            # don't abort — this is the state the loop
                            # exists to wait out.
                            sums.add(f"empty:{port}")
                            continue
                        sums.add(json.dumps(blocks, sort_keys=True))
                    checked += len(owner_ports)
                    if len(sums) > 1:
                        ok = False
                        last = ("blocks", shard)
                        break
                # Replica pairs must actually have been compared: with
                # all nodes up every shard has REPLICAS owners.
                if ok and len(up_ports) == N_NODES:
                    assert checked == N_SHARDS * REPLICAS, checked
            if ok:
                return
        except (urllib.error.URLError, OSError) as e:
            last = repr(e)
        time.sleep(1.0)
    raise AssertionError(f"cluster did not converge: {last}")


@pytest.mark.slow
@pytest.mark.timeout(540)
def test_pause_and_kill_mid_import(cluster):
    c = cluster
    _req(c.ports[0], "POST", "/index/ci", {})
    _req(c.ports[0], "POST", "/index/ci/field/cf", {})
    # Schema must reach every node before imports fan out.
    for port in c.ports:
        deadline = time.time() + 30
        while time.time() < deadline:
            idxs = {i["name"] for i in _req(port, "GET",
                                            "/schema")["indexes"]}
            if "ci" in idxs:
                break
            time.sleep(0.5)

    imp = Importer(c.ports[0])
    imp.start()
    try:
        # Let some data land everywhere first.
        deadline = time.time() + 60
        while imp.batches < 3 and time.time() < deadline:
            time.sleep(0.5)
        assert imp.batches >= 3

        # --- Fault 1: SIGSTOP node2 for 10 s mid-import (pumba pause,
        # cluster_test.go:54-70). Its sockets stay open; fan-out legs
        # stall on the frozen peer and are swallowed, healed later.
        victim = c.procs[2]
        victim.send_signal(signal.SIGSTOP)
        time.sleep(10)
        victim.send_signal(signal.SIGCONT)
        # Imports kept flowing during the pause.
        b0 = imp.batches
        deadline = time.time() + 60
        while imp.batches < b0 + 2 and time.time() < deadline:
            time.sleep(0.5)
        assert imp.batches >= b0 + 2

        # --- Fault 2: SIGKILL node2 mid-import — torn oplog tail risk.
        victim.kill()
        victim.wait(timeout=30)
        b0 = imp.batches
        deadline = time.time() + 90
        while imp.batches < b0 + 2 and time.time() < deadline:
            time.sleep(0.5)
        assert imp.batches >= b0 + 2, "imports stalled after node kill"
    finally:
        imp.stop()

    from collections import Counter
    by_row = Counter(r for r, _ in imp.landed)
    want = [by_row.get(r, 0) for r in range(ROWS)]

    # Survivors converge while node2 is dead (its replicas have a live
    # second owner at REPLICAS=2).
    wait_converged(c, [c.ports[0], c.ports[1]], want)

    # Restart node2 on its kill-torn data dir: torn-tail recovery +
    # rejoin + anti-entropy resync to full convergence.
    c.start(2)
    deadline = time.time() + 120
    while True:
        try:
            _req(c.ports[2], "GET", "/status", timeout=5)
            break
        except (urllib.error.URLError, OSError):
            if time.time() > deadline:
                log = (c.tmp / "node2" / "server.log").read_text()[-2000:]
                raise RuntimeError("node2 failed to restart:\n" + log)
            time.sleep(0.5)
    wait_converged(c, c.ports, want, deadline_s=120)

    # --- Keyed translation across real processes: writes through
    # DIFFERENT nodes (non-primaries adopt allocations out-of-band),
    # then the chained replication loops converge every node's served
    # log to a byte-prefix of the primary's (the chain invariant,
    # cluster.go:1908-1935).
    _req(c.ports[0], "POST", "/index/tk", {"options": {"keys": True}})
    _req(c.ports[0], "POST", "/index/tk/field/kf", {})
    time.sleep(1)  # schema broadcast
    for i, key in enumerate(("alpha", "beta", "gamma")):
        _req(c.ports[i], "POST", "/index/tk/query",
             f"Set('{key}', kf=1)".encode())
    for port in c.ports:
        res = _req(port, "POST", "/index/tk/query", b"Count(Row(kf=1))")
        assert res["results"] == [3], (port, res)
    import urllib.request as _ur
    deadline = time.time() + 60
    while True:
        logs = []
        for port in c.ports:
            with _ur.urlopen(f"http://127.0.0.1:{port}/internal/"
                             "translate/data?index=tk&offset=0",
                             timeout=10) as r:
                logs.append(r.read())
        full = max(logs, key=len)
        if all(len(lg) > 0 and full.startswith(lg) for lg in logs) \
                and sum(len(lg) == len(full) for lg in logs) == len(logs):
            break
        if time.time() > deadline:
            raise AssertionError(
                f"translate logs did not converge: {[len(x) for x in logs]}")
        time.sleep(1)
