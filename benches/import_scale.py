"""Native import-path thread scaling.

Measures pn_import_build throughput (the fragment bulk-import hot path,
reference fragment.go:1494-1604 + errgroup-parallel forwarding
api.go:878-888) at PILOSA_NATIVE_THREADS = 1, 2, 4, 8 — each in a fresh
subprocess because the worker count latches on first native call.
Prints one JSON line per thread count plus a summary line.

On the 1-vCPU bench box the counts all share one core, so throughput is
flat (slightly lower at >1 from atomic-OR overhead) — the measurement
that matters runs on a multi-core host; this harness is how to take it.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, time, sys
import numpy as np
sys.path.insert(0, %(repo)r)
from pilosa_tpu import native
assert native.available()
rng = np.random.default_rng(11)
n = 8_000_000
rows = rng.integers(0, 4, n, dtype=np.uint64)
cols = rng.integers(0, 1 << 20, n, dtype=np.uint64)
native.import_build(rows[:1000], cols[:1000], 20)  # warm lib load
best = None
for _ in range(3):
    t0 = time.perf_counter()
    keys, words, counts, payload, nbits = native.import_build(
        rows, cols, 20)
    dt = time.perf_counter() - t0
    best = dt if best is None else min(best, dt)
print(json.dumps({"pairs": n, "seconds": best,
                  "pairs_per_sec": n / best, "nbits": int(nbits)}))
"""


def main():
    results = {}
    for threads in (1, 2, 4, 8):
        env = {**os.environ, "PILOSA_NATIVE_THREADS": str(threads)}
        p = subprocess.run(
            [sys.executable, "-c", CHILD % {"repo": REPO}], env=env,
            capture_output=True, text=True, timeout=600)
        if p.returncode != 0:
            print(json.dumps({"metric": "import_build_pairs_per_sec",
                              "threads": threads, "value": 0.0,
                              "unit": "pairs/sec", "vs_baseline": 0.0,
                              "error": p.stderr[-300:]}), flush=True)
            continue
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        results[threads] = rec["pairs_per_sec"]
        print(json.dumps({"metric": "import_build_pairs_per_sec",
                          "threads": threads,
                          "value": rec["pairs_per_sec"],
                          "unit": "pairs/sec",
                          "vs_baseline": (rec["pairs_per_sec"]
                                          / results.get(1, 1.0)
                                          if 1 in results else 1.0)}),
              flush=True)
    if 1 in results:
        best_t = max(results, key=results.get)
        print(json.dumps({
            "metric": "import_build_thread_scaling",
            "value": results[best_t] / results[1],
            "unit": "x_vs_1_thread",
            "vs_baseline": results[best_t] / results[1],
            "best_threads": best_t,
            "host_cpus": os.cpu_count(),
        }))


if __name__ == "__main__":
    main()
