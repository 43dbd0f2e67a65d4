"""Tanimoto similarity benchmark — BASELINE.md config 4 (scaled): TopN
with tanimotoThreshold over molecule fingerprints (reference
docs/examples.md chemical-similarity workload; pruning
fragment.go:1087-1093).

Schema matches the reference's chem-usecase: ROWS are molecules
(chembl ids), COLUMNS are Morgan fingerprint bit positions, so
TopN(fingerprint, Row(fingerprint=<query mol>), tanimotoThreshold=T)
ranks molecules by similarity to the query molecule. The executor's
width-trimmed banks matter here: rows span only 4096 of the 2^20 shard
columns, so the sweep bank is 16x smaller than an untrimmed one.

Measures p50 similarity-search latency through the production executor
and validates against an exact bit-packed numpy Tanimoto on the same
data. Prints one JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_MOLECULES = 200_000
FP_BITS = 4096
BITS_PER_MOL = 48       # typical Morgan density
THRESHOLD = 60          # tanimoto percent
QUERY_MOL = 12345
ITERS = 5


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    rng = np.random.default_rng(11)
    # fingerprint bit positions per molecule (with possible repeats —
    # repeats collapse, as in real fingerprints)
    fp = rng.integers(0, FP_BITS, (N_MOLECULES, BITS_PER_MOL))
    rows = np.repeat(np.arange(N_MOLECULES, dtype=np.uint64), BITS_PER_MOL)
    cols = fp.reshape(-1).astype(np.uint64)

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        idx = holder.create_index("mole")
        f = idx.create_field("fingerprint")
        t0 = time.perf_counter()
        f.import_bits(rows, cols)
        load_s = time.perf_counter() - t0

        ex = Executor(holder)
        q = (f"TopN(fingerprint, Row(fingerprint={QUERY_MOL}), "
             f"n=50, tanimotoThreshold={THRESHOLD})")
        (want,) = ex.execute("mole", q)  # warm: bank + compile

        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            (got,) = ex.execute("mole", q)
            times.append(time.perf_counter() - t0)
            assert got.pairs == want.pairs
        tpu_t = float(np.median(times))

        # Exact numpy baseline on bit-packed fingerprints [mol, 512 bytes]
        # (pack build excluded, matching the TPU side's cached bank).
        mat = np.zeros((N_MOLECULES, FP_BITS), dtype=bool)
        mat[rows.astype(np.int64), cols.astype(np.int64)] = True
        packed = np.packbits(mat, axis=1)
        t0 = time.perf_counter()
        filt = packed[QUERY_MOL]
        inter = np.bitwise_count(packed & filt).sum(axis=1)
        raw = np.bitwise_count(packed).sum(axis=1)
        src = int(np.bitwise_count(filt).sum())
        denom = raw + src - inter
        keep = (denom > 0) & ((inter * 100) // np.maximum(denom, 1)
                              >= THRESHOLD) & (inter > 0)
        pairs = sorted(((int(m), int(inter[m]))
                        for m in np.nonzero(keep)[0]),
                       key=lambda rc: (-rc[1], rc[0]))[:50]
        cpu_t = time.perf_counter() - t0
        assert pairs == want.pairs, (pairs[:3], want.pairs[:3])

        import jax
        print(json.dumps({
            "metric": "tanimoto_molecule_topn_p50_latency",
            "platform": jax.devices()[0].platform,
            "value": tpu_t,
            "unit": "seconds",
            "vs_baseline": cpu_t / tpu_t,
            "molecules": N_MOLECULES,
            "load_seconds": round(load_s, 2),
        }))
        holder.close()


if __name__ == "__main__":
    main()
