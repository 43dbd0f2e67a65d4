"""Capacity bench for the adaptive hybrid bank layout (ISSUE 13).

Measures the capacity axis the hybrid layout exists for — resident
shards per byte of HBM — plus the guardrail the hot path must hold:

- **Corpus**: per shard, one "hot" field (a few well-filled rows; the
  serving hot set, must stay dense) and one "cold" field with a
  Zipfian density profile (row r carries ~``base / (r+1)^alpha`` set
  bits), the million-user shape where most rows are nearly empty.
- **Capacity lane**: ledgered device bytes per shard with the dense
  layout vs after the re-layout pass demotes the cold views —
  ``shardsPerGiB`` each way and their ratio (target: >= 2x).
- **Hot q/s lane**: a repeated Count burst over the HOT rows with the
  hybrid layout enabled (hot stays dense) vs the
  ``PILOSA_TPU_HYBRID_LAYOUT=0`` regime — the <5% regression gate.
- **Sparse rows/s lane**: Count throughput over the demoted sparse
  rows (the path OP_EXPAND serves).

Emits one JSON record per run on stdout (the repo's jsonl bench
convention); committed artifacts live beside this file as
``layout_bench_rNN_<backend>.jsonl``.

Usage::

    JAX_PLATFORMS=cpu python -m benches.layout_bench
    python -m benches.layout_bench --shards 4 --rows 4000 --iters 200
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from typing import Any, Dict, Optional, Sequence

import numpy as np


def build_corpus(holder, shards: int, rows: int, alpha: float,
                 base: int, seed: int = 7):
    """One index: `shards` shards, a hot field (8 dense rows) and a
    Zipfian cold field (`rows` rows, density ~ base/(r+1)^alpha)."""
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    rng = np.random.default_rng(seed)
    idx = holder.create_index("cap")
    hot = idx.create_field("hot")
    cold = idx.create_field("cold")
    all_cols = []
    for s in range(shards):
        col0 = s * SHARD_WIDTH
        # Hot: 8 rows x ~2500 bits inside a 4096-col window.
        hr = rng.integers(0, 8, 20000).astype(np.uint64)
        hc = (col0 + rng.integers(0, 4096, 20000)).astype(np.uint64)
        hot.import_bits(hr, hc)
        # Cold: Zipfian density, most rows nearly empty.
        counts = np.maximum(
            1, (base / np.power(np.arange(rows) + 1, alpha))
        ).astype(np.int64)
        cr = np.repeat(np.arange(rows, dtype=np.uint64), counts)
        cc = (col0 + rng.integers(0, 4096, int(counts.sum()))
              ).astype(np.uint64)
        cold.import_bits(cr, cc)
        all_cols.append(hc)
        all_cols.append(cc)
    idx.add_existence(np.unique(np.concatenate(all_cols)))
    return idx


def _qps(ex, queries, iters: int) -> float:
    """Median-of-3 queries/s over `iters` executions of the list."""
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(iters):
            ex.execute("cap", queries[i % len(queries)])
        samples.append(iters / (time.perf_counter() - t0))
    return statistics.median(samples)


def run(shards: int = 2, rows: int = 4000, alpha: float = 1.1,
        base: int = 64, iters: int = 200,
        seed: int = 7) -> Dict[str, Any]:
    from pilosa_tpu.core import layout as layout_mod
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.layout import LayoutManager
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils.hotspots import WORKLOAD
    from pilosa_tpu.utils.memledger import LEDGER

    WORKLOAD.reset()
    rec: Dict[str, Any] = {
        "bench": "layout_capacity", "shards": shards, "rows": rows,
        "alpha": alpha, "base": base, "iters": iters,
    }
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        build_corpus(holder, shards, rows, alpha, base, seed)
        ex = Executor(holder)
        ex.result_cache.enabled = False  # measure the real path

        hot_qs = [f"Count(Row(hot={r}))" for r in range(8)]
        cold_qs = [f"Count(Row(cold={r}))" for r in range(64)]
        # Warm + materialize the dense banks (and keep hot HOT so the
        # re-layout pass leaves it dense).
        for q in hot_qs + cold_qs[:8]:
            ex.execute("cap", q)
        dense_bytes = LEDGER.total_bytes(device_only=True)
        hot_dense_qps = _qps(ex, hot_qs, iters)
        cold_dense_qps = _qps(ex, cold_qs, iters)

        # Re-layout under a fresh heat map where only HOT is hot (the
        # steady state a real deployment reaches once the cold field's
        # EWMA decays): cold demotes, hot must stay dense.
        WORKLOAD.reset()
        for q in hot_qs * 4:
            ex.execute("cap", q)
        mgr = LayoutManager(holder, min_bytes=1024)
        summary = mgr.relayout_once()
        rec["relayout"] = summary
        hybrid_bytes = LEDGER.total_bytes(device_only=True)
        # Touch the sparse path once so its (small) banks are resident
        # before the byte snapshot comparison is judged.
        for q in cold_qs[:8]:
            ex.execute("cap", q)
        hybrid_bytes = max(hybrid_bytes,
                           LEDGER.total_bytes(device_only=True))
        hot_hybrid_qps = _qps(ex, hot_qs, iters)
        cold_hybrid_qps = _qps(ex, cold_qs, iters)

        # Kill-switch q/s baseline (dense planning, same process).
        layout_mod.HYBRID_LAYOUT_ENABLED = False
        try:
            for q in hot_qs:
                ex.execute("cap", q)
            hot_kill_qps = _qps(ex, hot_qs, iters)
        finally:
            layout_mod.HYBRID_LAYOUT_ENABLED = True

        gib = 1 << 30
        rec.update({
            "denseDeviceBytes": dense_bytes,
            "hybridDeviceBytes": hybrid_bytes,
            "bytesPerShardDense": dense_bytes / shards,
            "bytesPerShardHybrid": hybrid_bytes / shards,
            "shardsPerGiBDense": gib / max(1, dense_bytes / shards),
            "shardsPerGiBHybrid": gib / max(1, hybrid_bytes / shards),
            "shardsPerByteRatio": dense_bytes / max(1, hybrid_bytes),
            "hotQpsDense": hot_dense_qps,
            "hotQpsHybrid": hot_hybrid_qps,
            "hotQpsKillSwitch": hot_kill_qps,
            "hotRegressionPct": 100.0 * (1.0 - hot_hybrid_qps
                                         / hot_dense_qps),
            "coldQpsDense": cold_dense_qps,
            "coldQpsHybrid": cold_hybrid_qps,
            "sparseRowsPerS": cold_hybrid_qps,  # 1 row counted/query
        })
        holder.close()
    return rec


def quick_capacity(shards: int = 2, rows: int = 2000,
                   iters: int = 50) -> Optional[Dict[str, Any]]:
    """Small-shape capacity stanza for bench.py's record (never
    raises: the main bench must not die on a capacity probe)."""
    try:
        rec = run(shards=shards, rows=rows, iters=iters)
        return {k: rec[k] for k in
                ("shardsPerByteRatio", "bytesPerShardDense",
                 "bytesPerShardHybrid", "hotQpsDense", "hotQpsHybrid",
                 "hotRegressionPct", "sparseRowsPerS", "relayout")}
    except Exception as e:  # pragma: no cover - probe guard
        return {"error": f"{type(e).__name__}: {e}"}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="layout_bench")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--alpha", type=float, default=1.1)
    ap.add_argument("--base", type=int, default=64)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    rec = run(shards=args.shards, rows=args.rows, alpha=args.alpha,
              base=args.base, iters=args.iters, seed=args.seed)
    import jax
    rec["backend"] = jax.devices()[0].platform
    rec["t"] = time.time()
    print(json.dumps(rec))
    ok = rec["shardsPerByteRatio"] >= 2.0 \
        and rec["hotRegressionPct"] < 5.0
    print(f"layout_bench: shards-per-byte x{rec['shardsPerByteRatio']:.1f}, "
          f"hot regression {rec['hotRegressionPct']:+.2f}% -> "
          f"{'PASS' if ok else 'FAIL'}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
