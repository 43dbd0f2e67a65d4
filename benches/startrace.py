"""Star-trace benchmark — BASELINE.md config 1: the getting-started
index (users star repositories), measured END-TO-END through the HTTP
server: POST /index/{i}/query with Row / Intersect / Count / TopN,
p50 latency per query. Baseline is the same computation on host numpy
sets (the serving overhead the reference's "sub-second" claim includes,
docs/faq.md:11).

Prints one JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_USERS = 2000
N_REPOS = 1_000_000
STARS_PER_USER = 2000
ITERS = 20
BATCH = int(os.environ.get("PILOSA_BENCH_BATCH", 16))
PORT = 10941


def post(path, body):
    req = urllib.request.Request(f"http://127.0.0.1:{PORT}{path}",
                                 data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.server import API, serve

    rng = np.random.default_rng(3)
    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        api = API(holder)
        srv = serve(api, "127.0.0.1", PORT, background=True)
        try:
            post("/index/repository", "{}")
            post("/index/repository/field/stargazer", "{}")
            users = np.repeat(np.arange(N_USERS, dtype=np.uint64),
                              STARS_PER_USER)
            repos = rng.integers(0, N_REPOS, N_USERS * STARS_PER_USER,
                                 dtype=np.uint64)
            holder.index("repository").field("stargazer").import_bits(
                users, repos)


            q = ("Count(Intersect(Row(stargazer=14), Row(stargazer=19))) "
                 "TopN(stargazer, n=5)")
            want = post("/index/repository/query", q)  # warm
            import jax
            ctx = {"platform": jax.devices()[0].platform}
            times = []
            for _ in range(ITERS):
                t0 = time.perf_counter()
                got = post("/index/repository/query", q)
                times.append(time.perf_counter() - t0)
                assert got == want
            tpu_t = float(np.median(times)) / 2  # per call

            # Batched serving shape: BATCH queries per /batch/query
            # request — one HTTP round trip, one pipelined device
            # drain.
            batch_body = json.dumps({"queries": [
                {"index": "repository", "query": q}] * BATCH})
            got_b = post("/batch/query", batch_body)  # warm
            assert all(r == want for r in got_b["responses"])
            btimes = []
            for _ in range(max(3, ITERS // 4)):
                t0 = time.perf_counter()
                got_b = post("/batch/query", batch_body)
                btimes.append((time.perf_counter() - t0) / BATCH)
            batch_t = float(np.median(btimes)) / 2  # per call

            # numpy baseline: same answers (distinct (user,repo) pairs —
            # duplicates collapse in a bitmap) from the raw pair arrays.
            set14 = np.unique(repos[users == 14])
            set19 = np.unique(repos[users == 19])
            pairs = np.unique(np.stack([users, repos], axis=1), axis=0)
            t0 = time.perf_counter()
            cnt = len(np.intersect1d(set14, set19, assume_unique=True))
            counts = np.bincount(pairs[:, 0].astype(np.int64))
            order = np.argsort(-counts, kind="stable")[:5]
            top = [{"id": int(u), "count": int(counts[u])} for u in order]
            cpu_t = (time.perf_counter() - t0) / 2
            assert cnt == want["results"][0]
            got_top = want["results"][1]
            assert [p["count"] for p in top] == \
                [p["count"] for p in got_top], (top, got_top)
            print(json.dumps({
                "metric": "startrace_http_p50_latency",
                "value": tpu_t,
                "unit": "seconds",
                "vs_baseline": cpu_t / tpu_t,
                "batch_calls": BATCH,
                "batch_p50_per_call": batch_t,
                "batch_vs_baseline": cpu_t / batch_t,
                **ctx,
            }))
        finally:
            srv.shutdown()
            holder.close()


if __name__ == "__main__":
    main()
