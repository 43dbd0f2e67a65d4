"""BSI benchmark — BASELINE.md config 3: int field over 10M columns,
16 shards; Range/Sum/Min/Max through the production executor vs an exact
numpy host baseline on the same planes.

Prints one JSON line per op: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_COLS = 10_000_000
N_SHARDS = 16
VMIN, VMAX = 0, 100_000
ITERS = 5
BATCH = int(os.environ.get("PILOSA_BENCH_BATCH", 16))


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    rng = np.random.default_rng(7)
    cols = np.arange(N_COLS, dtype=np.uint64)
    vals = rng.integers(VMIN, VMAX, N_COLS, dtype=np.int64)

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        from pilosa_tpu.core.field import FieldOptions
        idx = holder.create_index("bsi")
        f = idx.create_field("v", FieldOptions(type="int", min=VMIN,
                                               max=VMAX))
        t0 = time.perf_counter()
        f.import_values(cols, vals)
        load_s = time.perf_counter() - t0

        ex = Executor(holder)

        queries = {
            "range_gt": (f"Count(Range(v > {VMAX // 2}))",
                         lambda: int((vals > VMAX // 2).sum())),
            "sum": ('Sum(field="v")', lambda: {"value": int(vals.sum()),
                                       "count": len(vals)}),
            "min": ('Min(field="v")', lambda: {"value": int(vals.min()),
                                       "count": int((vals == vals.min())
                                                    .sum())}),
            "max": ('Max(field="v")', lambda: {"value": int(vals.max()),
                                       "count": int((vals == vals.max())
                                                    .sum())}),
        }
        out = {"metric": "bsi_ops_per_sec", "unit": "ops/sec",
               "loaded_cols": N_COLS, "load_seconds": round(load_s, 2)}
        batched = " ".join(q for q, _ in queries.values())
        ex.execute("bsi", batched)  # warm compile
        import jax
        out["platform"] = jax.devices()[0].platform
        # correctness
        results = ex.execute("bsi", batched)
        for (name, (_, ref)), got in zip(queries.items(), results):
            want = ref()
            if isinstance(want, dict):
                assert got.value == want["value"] and \
                    got.count == want["count"], (name, got, want)
            else:
                assert got == want, (name, got, want)
        # TPU timing (batched — dispatches pipeline before fetch)
        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            ex.execute("bsi", batched)
            times.append((time.perf_counter() - t0) / len(queries))
        tpu_t = float(np.median(times))
        # Cross-request batch (execute_batch): BATCH requests of the
        # 4-op query share ONE overlapped device->host drain — the
        # serving amortization for high-RTT links.
        reqs = [("bsi", batched, None)] * BATCH
        ex.execute_batch(reqs)  # warm
        btimes = []
        for _ in range(max(2, ITERS // 2)):
            t0 = time.perf_counter()
            got = ex.execute_batch(reqs)
            btimes.append((time.perf_counter() - t0)
                          / (len(queries) * BATCH))
            assert not any(isinstance(r, Exception) for r in got)
        batch_t = float(np.median(btimes))
        # host baseline: same predicates on the raw values
        t0 = time.perf_counter()
        for _, ref in queries.values():
            ref()
        cpu_t = (time.perf_counter() - t0) / len(queries)
        out["value"] = 1.0 / tpu_t
        out["vs_baseline"] = cpu_t / tpu_t
        out["batch_requests"] = BATCH
        out["batch_p50_per_call"] = batch_t
        out["batch_vs_baseline"] = cpu_t / batch_t
        print(json.dumps(out))
        holder.close()


if __name__ == "__main__":
    main()
