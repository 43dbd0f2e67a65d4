"""Threshold (N-of-M) bench: the OP_THRESH thermometer lowering vs
the classical union-of-k-subsets expansion a client would otherwise
send (PR 16 acceptance lane).

``Threshold(r1..rn, k=K)`` lowers to ~K*N plan rows (K thermometer
accumulators swept once per operand); the equivalent
``Union(Intersect(...k-subset...) for every subset)`` lowers to
C(N,K) intersect chains plus the final union — combinatorial in the
plan buffer, identical in the answer. Both forms run as megakernel
batches on the same index; the record carries measured plan entries,
plan bytes, and wall time for each, plus the bit-identity check. The
expansion leg runs with the optimizer ON too, so the comparison is
"best possible expansion" vs the opcode — CSE already dedupes the
shared subsets, and the gap that remains is the point of the opcode.

One JSON line per (n, k) shape on stdout, naming the backend it ran
on. Env knobs: THRESH_BENCH_BITS (400000),
THRESH_BENCH_ROWS (16), THRESH_BENCH_QUERIES (8 per leg),
THRESH_BENCH_REPEATS (3).
"""

import itertools
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_BITS = int(os.environ.get("THRESH_BENCH_BITS", 400_000))
N_ROWS = int(os.environ.get("THRESH_BENCH_ROWS", 16))
N_QUERIES = int(os.environ.get("THRESH_BENCH_QUERIES", 8))
REPEATS = int(os.environ.get("THRESH_BENCH_REPEATS", 3))
SHAPES = ((4, 2), (6, 3), (8, 4))  # (n operands, k threshold)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)


def operand_rows(q, n):
    """n distinct Row() atoms per query index q, overlapping across
    queries so the cross-request CSE has real work on both legs."""
    return [f"Row({'f' if (q + i) % 2 else 'g'}={(q + i) % N_ROWS})"
            for i in range(n)]


def thresh_pql(rows, k):
    return f"Count(Threshold({', '.join(rows)}, k={k}))"


def expansion_pql(rows, k):
    subsets = [f"Intersect({', '.join(s)})"
               for s in itertools.combinations(rows, k)]
    return f"Count(Union({', '.join(subsets)}))"


def run_leg(ex, reqs):
    from pilosa_tpu.executor import megakernel as megamod
    assert megamod.MEGAKERNEL_ENABLED
    entries0 = ex.mega_plan_entries
    pbytes0 = ex.mega_plan_bytes
    launches0 = ex.mega_launches
    walls, out = [], None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = ex.execute_batch_shaped(reqs)
        walls.append(time.perf_counter() - t0)
    reps = ex.mega_launches - launches0
    return out, {
        "wall_ms": round(1e3 * statistics.median(walls), 3),
        "mega_launches": reps,
        "plan_entries": (ex.mega_plan_entries - entries0)
        // max(1, reps),
        "plan_bytes": (ex.mega_plan_bytes - pbytes0) // max(1, reps),
    }


def main():
    import jax

    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import megakernel as megamod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    log(f"thresh-bench: building holder ({N_BITS} bits, {N_ROWS} rows)")
    with tempfile.TemporaryDirectory() as tmp:
        h = Holder(tmp)
        h.open()
        idx = h.create_index("bench")
        f = idx.create_field("f")
        g = idx.create_field("g")
        rng = np.random.default_rng(42)
        rows = rng.integers(0, N_ROWS, N_BITS).astype(np.uint64)
        cols = rng.integers(0, 2 * SHARD_WIDTH, N_BITS).astype(np.uint64)
        f.import_bits(rows, cols)
        g.import_bits(rows[::2], cols[::2])
        idx.add_existence(cols)
        ex = Executor(h)
        ex.result_cache.enabled = False
        prev = megamod.MEGAKERNEL_ENABLED
        megamod.MEGAKERNEL_ENABLED = True
        try:
            for n, k in SHAPES:
                ops = [operand_rows(q, n) for q in range(N_QUERIES)]
                treqs = [("bench", thresh_pql(r, k), None) for r in ops]
                ereqs = [("bench", expansion_pql(r, k), None)
                         for r in ops]
                for rq in (treqs, ereqs):  # warm compiled variants
                    ex.execute_batch_shaped(rq)
                t_out, t_stats = run_leg(ex, treqs)
                e_out, e_stats = run_leg(ex, ereqs)
                assert t_out == e_out, \
                    f"Threshold != expansion at n={n} k={k}"
                emit({
                    "bench": "thresh_vs_expansion",
                    "n": n, "k": k, "subsets": len(
                        list(itertools.combinations(range(n), k))),
                    "queries": N_QUERIES,
                    "repeats": REPEATS,
                    "threshold": t_stats,
                    "expansion": e_stats,
                    "plan_entry_ratio": round(
                        e_stats["plan_entries"]
                        / max(1, t_stats["plan_entries"]), 2),
                    "bit_identical": True,
                    "backend": jax.devices()[0].platform,
                })
        finally:
            megamod.MEGAKERNEL_ENABLED = prev
        h.close()


if __name__ == "__main__":
    main()
