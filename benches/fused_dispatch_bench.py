"""CPU/TPU microbench: same-signature batch fusion, unfused vs fused.

The ISSUE's acceptance shape: B structurally identical Count queries
(different row ids over one shared view bank) served three ways —

- serial:    one `Executor.execute` per query (the un-batched serving
             baseline: one plan + one program dispatch + one drain
             each);
- pipelined: `Executor.execute_batch` with fusion disabled
             (PILOSA_TPU_FUSION semantics forced off) — the PR 1/PR 3
             state: one overlapped drain, but still one program
             dispatch per query;
- fused:     `Executor.execute_batch` with fusion on — one vmapped
             program dispatch for the whole signature group.

Results are checked identical across all three modes per B before any
number is reported. Aggregate queries/sec per (mode, B) goes to stdout
as ONE JSON line naming the platform (progress chatter on stderr).

Columns confine to FUSED_BENCH_COL_SPAN (default 65536) low columns of
each shard so view banks width-trim to ~2k words: that makes each
query's device compute genuinely 1-ms-class, which is the north-star
shape — per-program HOST overhead (plan + dispatch + drain), the thing
fusion amortizes, then shows instead of drowning under a popcount that
is itself CPU-bound at full shard width on the XLA CPU backend.

Env knobs: FUSED_BENCH_B ("1,8,64,256"), FUSED_BENCH_REPS (30),
FUSED_BENCH_SHARDS (4), FUSED_BENCH_ROWS (256),
FUSED_BENCH_COL_SPAN (65536), FUSED_BENCH_SECONDS (1.0 max per timed
mode).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BATCHES = [int(b) for b in
           os.environ.get("FUSED_BENCH_B", "1,8,64,256").split(",")]
REPS = int(os.environ.get("FUSED_BENCH_REPS", 30))
N_SHARDS = int(os.environ.get("FUSED_BENCH_SHARDS", 4))
N_ROWS = int(os.environ.get("FUSED_BENCH_ROWS", 256))
COL_SPAN = int(os.environ.get("FUSED_BENCH_COL_SPAN", 65536))
MAX_SECONDS = float(os.environ.get("FUSED_BENCH_SECONDS", 1.0))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build(tmp):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    h = Holder(tmp)
    h.open()
    idx = h.create_index("b")
    f = idx.create_field("f")
    rng = np.random.default_rng(42)
    n = 200_000
    rows = rng.integers(0, N_ROWS, n).astype(np.uint64)
    cols = (rng.integers(0, N_SHARDS, n).astype(np.uint64)
            * np.uint64(SHARD_WIDTH)
            + rng.integers(0, COL_SPAN, n).astype(np.uint64))
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    return h


def timed_interleaved(mode_fns, reps):
    """Per-mode BEST single-batch time over `reps` interleaved rounds
    (mode A, mode B, ... per round). Interleaving + min is the noise
    shield for a shared box: a background burst taxes every mode's
    worst reps equally and the best rep approaches the true cost."""
    best = {fn.__name__: float("inf") for fn in mode_fns}
    done = {fn.__name__: 0 for fn in mode_fns}
    t_start = time.perf_counter()
    for _ in range(reps):
        for fn in mode_fns:
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            if dt < best[fn.__name__]:
                best[fn.__name__] = dt
            done[fn.__name__] += 1
        if time.perf_counter() - t_start > MAX_SECONDS * len(mode_fns):
            break
    return best, done


def main():
    import tempfile

    import jax

    from pilosa_tpu.executor import Executor, executor as executor_mod
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()

    platform = jax.devices()[0].platform
    log(f"platform={platform} shards={N_SHARDS} rows={N_ROWS}")
    out = {"bench": "fused_dispatch", "platform": platform,
           "shards": N_SHARDS, "reps": REPS, "modes": {}}
    with tempfile.TemporaryDirectory() as tmp:
        h = build(tmp)
        ex = Executor(h)
        for B in BATCHES:
            queries = [f"Count(Row(f={r % N_ROWS}))" for r in range(B)]
            reqs = [("b", q, None) for q in queries]

            def serial():
                return [ex.execute("b", q)[0] for q in queries]

            def pipelined():
                prev = executor_mod.FUSION_ENABLED
                executor_mod.FUSION_ENABLED = False
                try:
                    return [r[0][0] for r in ex.execute_batch(reqs)]
                finally:
                    executor_mod.FUSION_ENABLED = prev

            def fused():
                return [r[0][0] for r in ex.execute_batch(reqs)]

            want = serial()  # also warms the single-program compile
            for mode_fn in (pipelined, fused):  # warm + verify
                got = mode_fn()
                assert got == want, (mode_fn.__name__, got[:4], want[:4])
            fd0 = ex.fused_dispatches
            fused()
            if B > 1:
                assert ex.fused_dispatches == fd0 + 1, \
                    "fused mode must be exactly one dispatch"
            row = {}
            best, done = timed_interleaved((serial, pipelined, fused),
                                           REPS)
            for name, dt in best.items():
                qps = B / dt
                row[name] = {"qps": round(qps, 1),
                             "s_per_batch": round(dt, 6)}
                log(f"B={B:4d} {name:9s} {qps:10.0f} q/s "
                    f"(best of {done[name]})")
            row["speedup_vs_serial"] = round(
                row["fused"]["qps"] / row["serial"]["qps"], 2)
            row["speedup_vs_pipelined"] = round(
                row["fused"]["qps"] / row["pipelined"]["qps"], 2)
            out["modes"][str(B)] = row
        h.close()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
