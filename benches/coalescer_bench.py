"""CPU microbench: serving-path throughput, coalescer off vs on.

64 client threads issue single-`Count` PQL queries over a shared view
bank through a live PilosaHTTPServer — the ISSUE's acceptance shape for
the cross-request coalescer. Phase 1 serves every request on the direct
path (no coalescer); phase 2 attaches a QueryCoalescer and repeats the
identical load. Responses are checked byte-identical across phases per
query string; aggregate qps and the coalescer's occupancy stats go to
stdout as ONE JSON line (progress chatter on stderr).

Two workloads:
- identical: every thread issues the same Count — the ISSUE's
  acceptance shape (64 concurrent single-Count requests over a shared
  bank) and the headline `value`; one window's worth of requests
  executes as ONE device sweep.
- mixed: threads spread over 8 distinct rows (dedup collapses repeats
  of the same row inside one window; the executor batch pipelines the
  distinct remainder) — the harder secondary number.

Clients hold ONE keep-alive connection each (http.client), the shape a
pooled production client presents — a fresh TCP connect + handler
thread per request costs ~4 ms on this box and would swamp what the
bench measures in both modes equally.

Env knobs: COALESCER_BENCH_THREADS (64), COALESCER_BENCH_QUERIES (25
per thread per phase), COALESCER_BENCH_ROWS (8 distinct rows),
COALESCER_BENCH_SHARDS (96).
"""

import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_THREADS = int(os.environ.get("COALESCER_BENCH_THREADS", 64))
N_QUERIES = int(os.environ.get("COALESCER_BENCH_QUERIES", 25))
N_ROWS = int(os.environ.get("COALESCER_BENCH_ROWS", 8))
N_SHARDS = int(os.environ.get("COALESCER_BENCH_SHARDS", 96))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build(tmp):
    """Dense shared bank (~30% density), written straight into
    container storage like bench.py's builder: Count(Row) then sweeps a
    [shards, words] row slice wide enough that per-query device+plan
    work, not connection churn, is what the phases compare."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    h = Holder(tmp)
    h.open()
    idx = h.create_index("b")
    f = idx.create_field("f")
    rng = np.random.default_rng(3)
    view = f.create_view_if_not_exists("standard")
    words_per_row = SHARD_WIDTH // 64
    for shard in range(N_SHARDS):
        frag = view.create_fragment_if_not_exists(shard)
        dense = rng.integers(0, 2**63, N_ROWS * words_per_row,
                             dtype=np.uint64)
        dense &= rng.integers(0, 2**63, N_ROWS * words_per_row,
                              dtype=np.uint64)
        frag.storage.set_dense_range(0, dense)
        for row in range(N_ROWS):
            frag._touch_row(row)
    return h


class Client:
    """One keep-alive connection, re-dialed on server-side close."""

    def __init__(self, host, port):
        self.host, self.port = host, port
        self.conn = http.client.HTTPConnection(host, port, timeout=60)

    def post(self, q):
        for attempt in (0, 1):
            try:
                self.conn.request("POST", "/index/b/query", body=q)
                return self.conn.getresponse().read()
            except (http.client.HTTPException, OSError):
                if attempt:
                    raise
                self.conn.close()
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=60)

    def close(self):
        self.conn.close()


def run_phase(host, port, queries):
    """N_THREADS keep-alive clients x N_QUERIES requests; returns
    (qps, responses) where responses maps query -> observed bodies."""
    observed = {q: set() for q in queries}
    obs_lock = threading.Lock()
    errors = []
    barrier = threading.Barrier(N_THREADS + 1)

    def worker(tid):
        local = {}
        client = Client(host, port)
        try:
            barrier.wait()
            for i in range(N_QUERIES):
                q = queries[(tid + i) % len(queries)]
                local.setdefault(q, set()).add(client.post(q))
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            client.close()
        with obs_lock:
            for q, bodies in local.items():
                observed[q].update(bodies)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return N_THREADS * N_QUERIES / dt, observed


def main():
    import jax

    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import tempfile

    from pilosa_tpu.server import API, serve
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    out = {"metric": "coalescer_serving_speedup", "unit": "x",
           "threads": N_THREADS, "queries_per_thread": N_QUERIES,
           "distinct_rows": N_ROWS, "shards": N_SHARDS,
           "platform": jax.devices()[0].platform}
    with tempfile.TemporaryDirectory() as tmp:
        log("bench: building holder")
        h = build(tmp)
        api = API(h, stats=MemStatsClient())
        srv = serve(api, "localhost", 0, background=True)
        host, port = "localhost", srv.server_address[1]
        mixed = [f"Count(Row(f={r}))".encode() for r in range(N_ROWS)]
        identical = [b"Count(Row(f=1))"]
        log("bench: warmup (bank upload + compile)")
        warm = Client(host, port)
        for q in mixed:
            warm.post(q)
        warm.close()

        results = {}
        for workload, queries in (("identical", identical),
                                  ("mixed", mixed)):
            log(f"bench: {workload}/direct")
            direct_qps, direct_obs = run_phase(host, port, queries)
            coal = QueryCoalescer(api.executor, window_s=0.002,
                                  max_batch=N_THREADS, max_queue=1024,
                                  stats=api.stats)
            coal.start()
            api.coalescer = coal
            log(f"bench: {workload}/coalesced")
            coal_qps, coal_obs = run_phase(host, port, queries)
            api.coalescer = None
            coal.stop()
            for q in queries:
                bodies = direct_obs[q] | coal_obs[q]
                assert len(bodies) == 1, \
                    f"responses diverged for {q!r}: {bodies}"
            results[workload] = {
                "direct_qps": round(direct_qps, 1),
                "coalesced_qps": round(coal_qps, 1),
                "speedup": round(coal_qps / direct_qps, 2),
            }
            log(f"bench: {workload}: direct {direct_qps:.0f} qps, "
                f"coalesced {coal_qps:.0f} qps "
                f"({coal_qps / direct_qps:.2f}x)")

        snap = api.stats.snapshot()
        # batch_size is a real cumulative histogram now (pow2 buckets);
        # report the mean + the bucket distribution.
        bs = snap["histograms"].get("coalescer.batch_size", {})
        out.update(results)
        out["value"] = results["identical"]["speedup"]
        out["batch_size_mean"] = (round(bs["sum"] / bs["count"], 2)
                                  if bs.get("count") else None)
        out["batch_size_buckets"] = bs.get("buckets")
        out["deduped"] = snap["counters"].get("coalescer.deduped", 0)
        out["flush_reasons"] = {
            k.split(".", 2)[2]: v for k, v in snap["counters"].items()
            if k.startswith("coalescer.flush.")}
        srv.shutdown()
        srv.server_close()
        h.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
