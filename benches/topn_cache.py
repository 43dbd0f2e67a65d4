"""BASELINE config 2: TopN over a 1M-column set field, single shard,
warm ranked cache vs numpy exact recount (reference rankCache,
cache.go:136 + fragment.top, fragment.go:1067)."""
import json, os, sys, tempfile, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from pilosa_tpu.utils.jaxenv import enable_compile_cache
enable_compile_cache()
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor

rng = np.random.default_rng(2)
with tempfile.TemporaryDirectory() as tmp:
    h = Holder(tmp); h.open()
    idx = h.create_index("c2")
    f = idx.create_field("f")  # default ranked cache, 50k
    rows = rng.integers(0, 5000, 4_000_000).astype(np.uint64)
    cols = rng.integers(0, 1 << 20, 4_000_000).astype(np.uint64)
    t0 = time.perf_counter()
    f.import_bits(rows, cols)
    load_s = time.perf_counter() - t0
    ex = Executor(h)
    (want,) = ex.execute("c2", "TopN(f, n=10)")  # warm
    import jax
    ctx = {"platform": jax.devices()[0].platform}
    times = []
    for _ in range(200):
        t0 = time.perf_counter()
        (got,) = ex.execute("c2", "TopN(f, n=10)")
        times.append(time.perf_counter() - t0)
    assert got.pairs == want.pairs
    p50 = float(np.median(times))
    assert ex.topn_cache_hits > 0  # really the warm ranked-cache path
    # numpy baseline: exact recount + top-k over the same bits
    per_row = {}
    t0 = time.perf_counter()
    u, c = np.unique((rows << np.uint64(20)) + cols, return_counts=False), None
    counts = np.bincount((u >> np.uint64(20)).astype(np.int64), minlength=5000)
    order = np.argsort(-counts, kind="stable")[:10]
    base_s = time.perf_counter() - t0
    base_pairs = [(int(r), int(counts[r])) for r in order]
    assert base_pairs == want.pairs, (base_pairs[:3], want.pairs[:3])
    h.close()
print(json.dumps({"metric": "topn_ranked_cache_p50_latency", "value": p50,
                  "unit": "seconds", "vs_baseline": base_s / p50,
                  "columns": 1 << 20, "distinct_rows": 5000,
                  "cache_hits": True, "load_seconds": round(load_s, 2),
                  **ctx}))
