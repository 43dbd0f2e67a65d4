"""Mixed-signature burst bench: heterogeneous megakernel +
RTT-hiding pipelined dispatch vs the PR 10 serving path (ISSUE 11
acceptance: device idle ratio under a 64-thread mixed burst measurably
drops — target <= half — with responses bit-identical under both kill
switches).

Four lanes, each one JSON line on stdout naming the backend it ran on
(progress chatter on stderr):

* ``mixed``: 64 client threads fire single-query PQL drawn from four
  signature families (Count(Row), Row, Count(Intersect),
  Count(Union)) through an in-process QueryCoalescer — the realistic
  mixed flood PR 4's same-signature fusion cannot collapse (one XLA
  launch per distinct shape). The identical schedule replays under
  four configs {megakernel, pipeline} x {off, on}; responses must be
  BYTE-IDENTICAL across all four, and the dispatch-gap analyzer's
  ``pilosa_device_idle_ratio`` is recorded per config (median over
  REPEATS bursts — the enqueue-interval analyzer is scheduler-noisy).

* ``tanimoto``: the BASELINE.json chemical-similarity scenario as a
  *serving-path* top-K: 64 threads issue the Count(Row(fp=c)) /
  Count(Intersect(Row(fp=Q), Row(fp=c))) probes of a Tanimoto top-K
  over molecule fingerprints — a fused AND+popcount flood of exactly
  two signatures that the megakernel runs as single plan-buffer
  launches. The client-side top-K is validated bit-exactly against a
  packed-numpy Tanimoto on the same data.

* ``opt``: the PR 16 cost-based plan optimizer lane — a 64-thread
  shared-subtree burst (every query reuses Intersect/Threshold
  subtrees across requests) replayed with the megakernel forced ON
  under ``PILOSA_TPU_PLAN_OPT`` on vs off. Responses must be
  BYTE-IDENTICAL; the record carries the measured plan-entry and
  plan+slab byte reduction plus the optimizer counters (cse hits,
  folds reordered) that /metrics exports as
  ``pilosa_executor_opt_*_total``.

* ``multichip``: the serving-path lane over an N-device mesh, in this
  same process (a child started from a parent that has touched jax
  cannot have the chips). It runs when at least two devices are
  visible — a multi-chip host, or XLA_FLAGS=
  --xla_force_host_platform_device_count=8 with JAX_PLATFORMS=cpu set
  before the start. The mixed burst runs against a mesh-sharded
  executor: one SPMD cohort launch per flush, Count lanes psum'd
  in-kernel, rows all-gathered. The record carries mesh q/s, the
  collective-reduce bytes and the profiler-asserted d2h accounting
  (4 bytes per Count — the final answer, ZERO host bytes of per-shard
  partials), with responses byte-identical to PILOSA_TPU_MESH=0.

Env knobs: MEGA_BENCH_THREADS (64), MEGA_BENCH_QUERIES (256 total),
MEGA_BENCH_ROWS (16), MEGA_BENCH_BITS (400000), MEGA_BENCH_REPEATS
(5), MEGA_BENCH_BATCH (16), MEGA_BENCH_MOLECULES (20000),
MEGA_BENCH_CANDIDATES (192), MEGA_BENCH_TOPK (50),
MEGA_BENCH_MESH_DEVICES (8).
"""

import json
import os
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_THREADS = int(os.environ.get("MEGA_BENCH_THREADS", 64))
N_QUERIES = int(os.environ.get("MEGA_BENCH_QUERIES", 256))
N_ROWS = int(os.environ.get("MEGA_BENCH_ROWS", 16))
N_BITS = int(os.environ.get("MEGA_BENCH_BITS", 400_000))
REPEATS = int(os.environ.get("MEGA_BENCH_REPEATS", 5))
MAX_BATCH = int(os.environ.get("MEGA_BENCH_BATCH", 16))
N_MOLECULES = int(os.environ.get("MEGA_BENCH_MOLECULES", 20_000))
N_CANDIDATES = int(os.environ.get("MEGA_BENCH_CANDIDATES", 192))
TOPK = int(os.environ.get("MEGA_BENCH_TOPK", 50))
MESH_DEVICES = int(os.environ.get("MEGA_BENCH_MESH_DEVICES", 8))
FP_BITS = 4096
BITS_PER_MOL = 48


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def backend():
    import jax
    return jax.devices()[0].platform


def emit(rec):
    print(json.dumps(rec), flush=True)


def burst(co, queries):
    """Fire the queries from N_THREADS client threads (each worker
    submits its slice sequentially — the pooled-client shape); returns
    (responses dict, wall seconds)."""
    n_workers = min(N_THREADS, len(queries))
    results, errors = {}, []
    barrier = threading.Barrier(n_workers + 1)

    def worker(w):
        try:
            barrier.wait()
            for i in range(w, len(queries), n_workers):
                results[i] = co.submit("bench", queries[i])
        except Exception as e:  # noqa: BLE001
            errors.append((w, e))

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join(timeout=120)
    wall = time.perf_counter() - t0
    assert not errors, errors[:3]
    assert len(results) == len(queries)
    return results, wall


def run_config(ex, queries, mega, pipeline):
    """One measured burst under a (megakernel, pipeline) setting;
    median idle ratio over REPEATS replays."""
    from pilosa_tpu.executor import megakernel as megamod
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient
    from pilosa_tpu.utils.timeline import TIMELINE

    prev = megamod.MEGAKERNEL_ENABLED
    megamod.MEGAKERNEL_ENABLED = mega
    try:
        ratios, walls = [], []
        launches0 = ex.mega_launches
        fused0 = ex.fused_dispatches
        results = None
        for _ in range(REPEATS):
            TIMELINE.reset()
            co = QueryCoalescer(ex, window_s=0.002, max_batch=MAX_BATCH,
                                max_queue=4 * len(queries),
                                stats=MemStatsClient(),
                                pipeline=pipeline)
            co.start()
            try:
                results, wall = burst(co, queries)
            finally:
                co.stop()
            ratios.append(TIMELINE.gap_summary()["idleRatio"])
            walls.append(wall)
        return {
            "idle_ratio": statistics.median(ratios),
            "idle_ratios": [round(r, 4) for r in ratios],
            "qps": len(queries) / statistics.median(walls),
            "mega_launches": ex.mega_launches - launches0,
            "fused_dispatches": ex.fused_dispatches - fused0,
        }, results
    finally:
        megamod.MEGAKERNEL_ENABLED = prev


def lane_mixed():
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    log(f"mega-bench: building mixed-burst holder ({N_BITS} bits, "
        f"{N_ROWS} rows)")
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
        # Distinct queries throughout: the result cache and read-dedup
        # would otherwise absorb the very launches under measurement.
        ex.result_cache.enabled = False
        queries = []
        for k in range(N_QUERIES):
            r = k % N_ROWS
            form = (k // N_ROWS) % 4
            queries.append([
                f"Count(Row(f={r}))",
                f"Row(g={r})",
                f"Count(Intersect(Row(f={r}), Row(g={r})))",
                f"Count(Union(Row(f={r}), Row(g={r})))"][form])
        queries = queries[:N_QUERIES]
        # Shuffle the submission order (fixed seed): pooled workers
        # that resolve in one flush submit their next queries together,
        # so any structured order phase-locks flushes onto a single
        # signature family and the megakernel never sees a mixed batch.
        perm = np.random.default_rng(3).permutation(len(queries))
        queries = [queries[int(p)] for p in perm]
        for q in queries:  # warm every compiled variant
            ex.execute_full("bench", q)

        configs = [("baseline", False, False), ("mega", True, False),
                   ("pipeline", False, True), ("mega+pipeline", True, True)]
        stats, shapes = {}, {}
        for name, mega, pipe in configs:
            log(f"mega-bench: config {name}")
            stats[name], shapes[name] = run_config(ex, queries, mega,
                                                   pipe)
        base = shapes["baseline"]
        for name in ("mega", "pipeline", "mega+pipeline"):
            assert shapes[name] == base, \
                f"config {name} responses differ from baseline"
        rec = {
            "bench": "mega_burst_mixed",
            "threads": min(N_THREADS, N_QUERIES),
            "queries": len(queries),
            "signatures": 4,
            "max_batch": MAX_BATCH,
            "repeats": REPEATS,
            "configs": stats,
            "idle_ratio_baseline": stats["baseline"]["idle_ratio"],
            "idle_ratio_mega_pipeline":
                stats["mega+pipeline"]["idle_ratio"],
            "idle_drop_factor": round(
                stats["baseline"]["idle_ratio"]
                / max(1e-9, stats["mega+pipeline"]["idle_ratio"]), 3),
            "bit_identical_all_configs": True,
            "backend": backend(),
        }
        emit(rec)
        h.close()


def lane_tanimoto():
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import megakernel as megamod
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    log(f"mega-bench: building tanimoto holder ({N_MOLECULES} molecules)")
    rng = np.random.default_rng(11)
    fp = rng.integers(0, FP_BITS, (N_MOLECULES, BITS_PER_MOL))
    rows = np.repeat(np.arange(N_MOLECULES, dtype=np.uint64),
                     BITS_PER_MOL)
    cols = fp.reshape(-1).astype(np.uint64)
    with tempfile.TemporaryDirectory() as tmp:
        h = Holder(tmp)
        h.open()
        idx = h.create_index("bench")
        f = idx.create_field("fp")
        f.import_bits(rows, cols)
        ex = Executor(h)
        ex.result_cache.enabled = False

        q_mol = 12345
        cands = rng.choice(N_MOLECULES, N_CANDIDATES, replace=False)
        cands = [int(c) for c in cands if c != q_mol]
        # The serving-path Tanimoto probe mix: numerator |Q ∧ c| per
        # candidate (fused AND+popcount) + cardinalities |c|, |Q| —
        # exactly two heterogeneous signatures, INTERLEAVED so every
        # coalescer flush carries both (the mixed shape the megakernel
        # collapses; a family-sorted list phase-aligns the worker pool
        # into same-signature flushes the vmap path already handles).
        queries = []
        for c in cands:
            queries.append(
                f"Count(Intersect(Row(fp={q_mol}), Row(fp={c})))")
            queries.append(f"Count(Row(fp={c}))")
        queries.append(f"Count(Row(fp={q_mol}))")
        # Shuffled submission order, un-shuffled on read-back (see
        # lane_mixed: structured orders phase-lock the worker pool
        # into same-signature flushes).
        perm = np.random.default_rng(3).permutation(len(queries))
        shuffled = [queries[int(p)] for p in perm]
        launches0 = ex.mega_launches
        # Force the megakernel ON for this lane (default `auto` is
        # TPU-only): the lane's point is the fused AND+popcount flood
        # running as plan-buffer launches.
        prev_mega = megamod.MEGAKERNEL_ENABLED
        megamod.MEGAKERNEL_ENABLED = True
        co = QueryCoalescer(ex, window_s=0.002, max_batch=MAX_BATCH,
                            max_queue=4 * len(queries),
                            stats=MemStatsClient(), pipeline=True)
        co.start()
        try:
            shuffled_res, wall = burst(co, shuffled)
        finally:
            co.stop()
            megamod.MEGAKERNEL_ENABLED = prev_mega
        results = {int(perm[i]): r for i, r in shuffled_res.items()}
        n = len(cands)
        inter = [results[2 * i]["results"][0] for i in range(n)]
        card = [results[2 * i + 1]["results"][0] for i in range(n)]
        q_card = results[2 * n]["results"][0]
        sims = [(i_qc / (q_card + c - i_qc) if (q_card + c - i_qc) else 0.0)
                for i_qc, c in zip(inter, card)]
        order = sorted(range(n), key=lambda i: (-sims[i], cands[i]))
        got = [(cands[i], round(sims[i], 6)) for i in order[:TOPK]]

        # Exact packed-numpy Tanimoto over the same candidate set.
        packed = np.zeros((N_MOLECULES, FP_BITS // 8), np.uint8)
        mol_idx = np.repeat(np.arange(N_MOLECULES), BITS_PER_MOL)
        flat = fp.reshape(-1)
        np.bitwise_or.at(packed, (mol_idx, flat // 8),
                         (1 << (flat % 8)).astype(np.uint8))
        pop = np.unpackbits(packed, axis=1).sum(axis=1)
        qv = packed[q_mol]
        want = []
        for c in cands:
            i_qc = int(np.unpackbits(packed[c] & qv).sum())
            denom = int(pop[q_mol]) + int(pop[c]) - i_qc
            want.append((c, round(i_qc / denom if denom else 0.0, 6)))
        want = sorted(want, key=lambda t: (-t[1], t[0]))[:TOPK]
        assert got == want, "serving-path Tanimoto top-K != exact numpy"

        emit({
            "bench": "mega_burst_tanimoto_topk",
            "molecules": N_MOLECULES,
            "fp_bits": FP_BITS,
            "candidates": n,
            "topk": TOPK,
            "probe_queries": len(queries),
            "wall_s": round(wall, 4),
            "probes_per_sec": round(len(queries) / wall, 1),
            "mega_launches": ex.mega_launches - launches0,
            "topk_exact_match": True,
            "backend": backend(),
        })
        h.close()


def lane_opt():
    """Plan-optimizer on/off over a shared-subtree burst: same
    schedule, megakernel forced ON both times, PLAN_OPT toggled.
    Responses byte-identical; plan entries / plan+slab bytes drop."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import megakernel as megamod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.stats import MemStatsClient

    log(f"mega-bench: building opt-lane holder ({N_BITS} bits)")
    with tempfile.TemporaryDirectory() as tmp:
        h = Holder(tmp)
        h.open()
        idx = h.create_index("bench")
        f = idx.create_field("f")
        g = idx.create_field("g")
        rng = np.random.default_rng(42)
        rows = rng.integers(0, N_ROWS, N_BITS).astype(np.uint64)
        cols = rng.integers(0, 2 * SHARD_WIDTH,
                            N_BITS).astype(np.uint64)
        f.import_bits(rows, cols)
        g.import_bits(rows[::2], cols[::2])
        idx.add_existence(cols)
        ex = Executor(h)
        ex.result_cache.enabled = False
        # Shared-subtree families: every query around row r reuses the
        # Intersect(Row(f=r), Row(g=r)) subtree (once commuted — the
        # canonicalized fingerprint must still hit), plus a Threshold
        # whose top rung is that same AND. This is the cross-request
        # shape the CSE pass exists for.
        queries = []
        for k in range(N_QUERIES):
            r = k % N_ROWS
            r2 = (r + 1) % N_ROWS
            queries.append([
                f"Count(Intersect(Row(f={r}), Row(g={r})))",
                f"Intersect(Row(g={r}), Row(f={r}))",
                f"Count(Union(Intersect(Row(f={r}), Row(g={r})), "
                f"Row(f={r2})))",
                f"Count(Threshold(Row(f={r}), Row(g={r}), "
                f"Row(f={r2}), k=2))"][(k // N_ROWS) % 4])
        perm = np.random.default_rng(3).permutation(len(queries))
        queries = [queries[int(p)] for p in perm]
        for q in queries:  # warm every compiled variant
            ex.execute_full("bench", q)

        prev_mega = megamod.MEGAKERNEL_ENABLED
        prev_opt = megamod.PLAN_OPT_ENABLED
        megamod.MEGAKERNEL_ENABLED = True
        stats, shapes = {}, {}
        try:
            for name, opt_on in (("opt-off", False), ("opt-on", True)):
                log(f"mega-bench: config {name}")
                megamod.PLAN_OPT_ENABLED = opt_on
                entries0 = ex.mega_plan_entries
                pbytes0 = ex.mega_plan_bytes
                launches0 = ex.mega_launches
                c0 = (ex.opt_cse_hits, ex.opt_entries_eliminated,
                      ex.opt_folds_reordered, ex.opt_bytes_saved)
                walls, results = [], None
                for _ in range(REPEATS):
                    co = QueryCoalescer(
                        ex, window_s=0.002, max_batch=MAX_BATCH,
                        max_queue=4 * len(queries),
                        stats=MemStatsClient(), pipeline=True)
                    co.start()
                    try:
                        results, wall = burst(co, queries)
                    finally:
                        co.stop()
                    walls.append(wall)
                stats[name] = {
                    "qps": len(queries) / statistics.median(walls),
                    "mega_launches": ex.mega_launches - launches0,
                    "plan_entries": ex.mega_plan_entries - entries0,
                    "plan_bytes": ex.mega_plan_bytes - pbytes0,
                    "cse_hits": ex.opt_cse_hits - c0[0],
                    "entries_eliminated":
                        ex.opt_entries_eliminated - c0[1],
                    "folds_reordered": ex.opt_folds_reordered - c0[2],
                    "bytes_saved": ex.opt_bytes_saved - c0[3],
                }
                shapes[name] = results
        finally:
            megamod.MEGAKERNEL_ENABLED = prev_mega
            megamod.PLAN_OPT_ENABLED = prev_opt
        assert shapes["opt-on"] == shapes["opt-off"], \
            "optimizer responses differ from kill-switch path"
        off, on = stats["opt-off"], stats["opt-on"]
        assert on["cse_hits"] > 0, "shared-subtree burst must CSE"
        assert off["cse_hits"] == 0 and off["bytes_saved"] == 0, \
            "kill switch must keep the optimizer fully out"
        emit({
            "bench": "mega_burst_opt",
            "threads": min(N_THREADS, N_QUERIES),
            "queries": len(queries),
            "repeats": REPEATS,
            "configs": stats,
            "plan_entry_reduction": round(
                1 - on["plan_entries"] / max(1, off["plan_entries"]),
                4),
            "plan_byte_reduction": round(
                1 - on["plan_bytes"] / max(1, off["plan_bytes"]), 4),
            "slab_bytes_saved": on["bytes_saved"],
            "bit_identical_opt_on_off": True,
            "backend": backend(),
        })
        h.close()


def lane_multichip():
    """Serving-path lane over an N-device mesh: one SPMD cohort launch
    per flush, Count/Sum reduced in-kernel (psum), rows all-gathered.
    Prints ONE JSON record on stdout."""
    import jax

    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import megakernel as megamod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH
    from pilosa_tpu.parallel import MeshContext
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.profile import QueryProfile
    from pilosa_tpu.utils.stats import MemStatsClient

    devs = jax.devices()
    n_mesh = min(MESH_DEVICES, len(devs))
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

        queries = []
        for k in range(N_QUERIES):
            r = k % N_ROWS
            queries.append([
                f"Count(Row(f={r}))",
                f"Row(g={r})",
                f"Count(Intersect(Row(f={r}), Row(g={r})))",
                f"Count(Union(Row(f={r}), Row(g={r})))"][
                    (k // N_ROWS) % 4])
        perm = np.random.default_rng(3).permutation(len(queries))
        queries = [queries[int(p)] for p in perm]

        megamod.MEGAKERNEL_ENABLED = True

        def serving_qps(executor):
            executor.result_cache.enabled = False
            for q in queries[:8]:  # warm the cohort programs
                executor.execute_full("bench", q)
            walls, results = [], None
            for _ in range(REPEATS):
                co = QueryCoalescer(executor, window_s=0.002,
                                    max_batch=MAX_BATCH,
                                    max_queue=4 * len(queries),
                                    stats=MemStatsClient(),
                                    pipeline=True)
                co.start()
                try:
                    results, wall = burst(co, queries)
                finally:
                    co.stop()
                walls.append(wall)
            return len(queries) / statistics.median(walls), results

        mesh_ex = Executor(h, mesh=MeshContext(devs[:n_mesh]))
        mesh_qps, mesh_res = serving_qps(mesh_ex)
        collective = mesh_ex.mesh_collective_bytes
        launches = mesh_ex.mesh_launches
        assert launches > 0, "burst never took the mesh cohort path"

        # Kill-switch twin on the same sharded banks: PILOSA_TPU_MESH=0
        # semantics, byte-identical responses required.
        megamod.MESH_ENABLED = False
        off_qps, off_res = serving_qps(Executor(h, mesh=MeshContext(
            devs[:n_mesh])))
        megamod.MESH_ENABLED = True
        assert mesh_res == off_res, \
            "mesh responses differ from PILOSA_TPU_MESH=0 path"

        # The zero-host-bytes claim on the Count/Sum reduce path: the
        # profiler's d2h accounting must see ONE uint32 (the psum'd
        # final answer) per count lane, never the [S] partial vector.
        count_qs = [("bench", q, None) for q in queries
                    if q.startswith("Count")][:16]
        profs = [QueryProfile("bench", q) for _, q, _ in count_qs]
        out = mesh_ex.execute_batch(count_qs, profiles=profs)
        assert not any(isinstance(r, Exception) for r in out), out[:3]
        d2h = [p.d2h_bytes for p in profs]
        assert all(b == 4 for b in d2h), f"host partials on reduce: {d2h}"

        print(json.dumps({
            "bench": "mega_burst_multichip",
            "mesh_devices": n_mesh,
            "threads": min(N_THREADS, N_QUERIES),
            "queries": len(queries),
            "repeats": REPEATS,
            "mesh_qps": mesh_qps,
            "qps_mesh_off": off_qps,
            "mesh_launches": launches,
            "collective_bytes": collective,
            "d2h_bytes_per_count": 4,
            "bit_identical_mesh_on_off": True,
            "backend": jax.devices()[0].platform,
        }, sort_keys=True), flush=True)
        h.close()


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    lanes = sys.argv[1:] or ["mixed", "tanimoto", "opt"] + (
        ["multichip"] if len(jax.devices()) >= 2 else [])
    if "mixed" in lanes:
        lane_mixed()
    if "tanimoto" in lanes:
        lane_tanimoto()
    if "opt" in lanes:
        lane_opt()
    if "multichip" in lanes:
        lane_multichip()


if __name__ == "__main__":
    main()
