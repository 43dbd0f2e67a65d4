"""CPU microbench: repeated-traffic serving throughput, result cache
off vs on (ISSUE 10 acceptance: >=3x on a >=80%-repeat workload).

64 client threads issue single-`Count` PQL queries drawn from a
Zipfian mix over N_ROWS distinct rows through a live PilosaHTTPServer
— the heavy-repetition shape PR 6's workload plane measures
(`coalescer.window_repeat`, cache-opportunity `estSavedS`) and the
generation-keyed result cache (executor/result_cache.py) now acts on.
Phase 1 serves every request with the cache disabled (the
PILOSA_TPU_RESULT_CACHE=0 regime); phase 2 enables it and repeats the
IDENTICAL schedule. Responses are checked byte-identical across
phases per query string; aggregate qps, the observed hit ratio, and
the speedup go to stdout as ONE JSON line (progress chatter on
stderr).

The Zipfian mix (pmf ~ 1/rank^ZIPF_S over N_ROWS rows) concentrates
~half the traffic on a handful of hot queries while keeping a long
tail of colder ones — the cache must win on the hot set while the
tail churns through it, a harsher shape than all-identical. The
schedule is precomputed per thread so both phases replay exactly the
same request sequence; its repeat fraction (1 - distinct/total) is
recorded and asserted >= 0.8.

Clients hold ONE keep-alive connection each (http.client), the shape
a pooled production client presents (see coalescer_bench.py).

Env knobs: RESULT_CACHE_BENCH_THREADS (64),
RESULT_CACHE_BENCH_QUERIES (25 per thread per phase),
RESULT_CACHE_BENCH_ROWS (64 distinct rows),
RESULT_CACHE_BENCH_SHARDS (192), RESULT_CACHE_BENCH_ZIPF_S (1.1).
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

N_THREADS = int(os.environ.get("RESULT_CACHE_BENCH_THREADS", 64))
N_QUERIES = int(os.environ.get("RESULT_CACHE_BENCH_QUERIES", 25))
N_ROWS = int(os.environ.get("RESULT_CACHE_BENCH_ROWS", 64))
N_SHARDS = int(os.environ.get("RESULT_CACHE_BENCH_SHARDS", 192))
ZIPF_S = float(os.environ.get("RESULT_CACHE_BENCH_ZIPF_S", 1.1))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build(tmp):
    """Dense shared bank (~30% density), written straight into
    container storage (the coalescer_bench builder): each Count(Row)
    miss sweeps a [shards, words] row slice wide enough that per-query
    plan+dispatch+device work, not connection churn, is what the cache
    elides."""
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


def zipf_schedule():
    """One fixed Zipfian request schedule per thread (replayed by both
    phases): pmf ~ 1/rank^ZIPF_S over N_ROWS rows."""
    rng = np.random.default_rng(7)
    p = 1.0 / np.arange(1, N_ROWS + 1) ** ZIPF_S
    p /= p.sum()
    sched = [
        [f"Count(Row(f={r}))".encode()
         for r in rng.choice(N_ROWS, size=N_QUERIES, p=p)]
        for _ in range(N_THREADS)
    ]
    total = N_THREADS * N_QUERIES
    distinct = len({q for ts in sched for q in ts})
    return sched, 1.0 - distinct / total


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


def run_phase(host, port, schedule):
    """N_THREADS keep-alive clients replaying the fixed schedule;
    returns (qps, observed) where observed maps query -> bodies."""
    observed = {}
    obs_lock = threading.Lock()
    errors = []
    barrier = threading.Barrier(N_THREADS + 1)

    def worker(tid):
        local = {}
        client = Client(host, port)
        try:
            barrier.wait()
            for q in schedule[tid]:
                local.setdefault(q, set()).add(client.post(q))
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            client.close()
        with obs_lock:
            for q, bodies in local.items():
                observed.setdefault(q, set()).update(bodies)

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
    from pilosa_tpu.utils.stats import MemStatsClient

    schedule, repeat_fraction = zipf_schedule()
    assert repeat_fraction >= 0.8, \
        f"workload must be >=80% repeats, got {repeat_fraction:.3f}"
    out = {"metric": "result_cache_serving_speedup", "unit": "x",
           "threads": N_THREADS, "queries_per_thread": N_QUERIES,
           "distinct_rows": N_ROWS, "shards": N_SHARDS,
           "zipf_s": ZIPF_S,
           "repeat_fraction": round(repeat_fraction, 4),
           "platform": jax.devices()[0].platform}
    with tempfile.TemporaryDirectory() as tmp:
        log("bench: building holder")
        h = build(tmp)
        api = API(h, stats=MemStatsClient())
        srv = serve(api, "localhost", 0, background=True)
        host, port = "localhost", srv.server_address[1]
        rc = api.executor.result_cache
        log("bench: warmup (bank upload + compile)")
        rc.enabled = False
        warm = Client(host, port)
        for r in range(N_ROWS):
            warm.post(f"Count(Row(f={r}))".encode())
        warm.close()

        log("bench: phase 1 (cache OFF — the "
            "PILOSA_TPU_RESULT_CACHE=0 regime)")
        off_qps, off_obs = run_phase(host, port, schedule)
        log(f"bench: cache-off {off_qps:.0f} qps")

        rc.enabled = True
        rc.clear()
        log("bench: phase 2 (cache ON)")
        on_qps, on_obs = run_phase(host, port, schedule)
        log(f"bench: cache-on {on_qps:.0f} qps "
            f"({on_qps / off_qps:.2f}x)")

        for q, bodies in on_obs.items():
            merged = bodies | off_obs.get(q, set())
            assert len(merged) == 1, \
                f"responses diverged for {q!r}: {merged}"

        snap = rc.snapshot()
        out.update({
            "value": round(on_qps / off_qps, 2),
            "cache_off_qps": round(off_qps, 1),
            "cache_on_qps": round(on_qps, 1),
            "hit_ratio": round(snap["hitRatio"], 4),
            "hits": snap["hits"],
            "misses": snap["misses"],
            "cache_bytes": snap["bytes"],
            "cache_entries": snap["entries"],
        })
        srv.shutdown()
        srv.server_close()
        h.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
