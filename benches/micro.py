"""Micro-benchmarks mirroring the reference's benchmark suites
(roaring/roaring_test.go:1392-1620 kernel ops;
fragment_internal_test.go:663-2280 import/snapshot/blocks).

Each line: {"metric", "value", "unit", ...}. Device lines carry the
backend they ran on; host numbers exercise the native C++ codec and
the numpy storage paths."""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, iters=5):
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit(metric, value, unit, **extra):
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      **extra}))


def bench_roaring_kernels():
    """IntersectionCount / Union / serialization on the host paths
    (reference BenchmarkIntersectionCount*, BenchmarkUnion*)."""
    from pilosa_tpu.storage.roaring import Bitmap
    from pilosa_tpu import native

    rng = np.random.default_rng(0)
    n = 1 << 22  # 4M-bit universe
    a = Bitmap(np.unique(rng.integers(0, n, 500_000, dtype=np.uint64)))
    b = Bitmap(np.unique(rng.integers(0, n, 500_000, dtype=np.uint64)))

    t = timeit(lambda: a.intersection_count(b))
    emit("host_intersection_count", 1 / t, "ops/sec")
    t = timeit(lambda: a.union(b))
    emit("host_union", 1 / t, "ops/sec")
    data = a.write_bytes()
    t = timeit(lambda: a.write_bytes())
    emit("host_roaring_serialize", len(data) / t / 1e6, "MB/sec",
         native=native.available())
    t = timeit(lambda: Bitmap.from_bytes(data))
    emit("host_roaring_parse", len(data) / t / 1e6, "MB/sec",
         native=native.available())


def bench_fragment_paths():
    """Import / snapshot / block checksums (reference BenchmarkFragment_*).

    Two data shapes: 100 rows (dense containers, ~625 bits each — the
    round-2-comparable shape, dense-scatter import path) and 1000 rows
    (10 hash blocks, ~62 bits/container — array-encoded containers,
    sorted-group import path; also what makes the dirty-one-block
    checksum meaningfully incremental)."""
    from pilosa_tpu.core.fragment import Fragment

    rng = np.random.default_rng(1)
    n_bits = 1_000_000
    rows = rng.integers(0, 100, n_bits, dtype=np.uint64)
    wide_rows = rng.integers(0, 1000, n_bits, dtype=np.uint64)
    cols = rng.integers(0, 1 << 20, n_bits, dtype=np.uint64)

    with tempfile.TemporaryDirectory() as tmp:
        frag = Fragment(os.path.join(tmp, "f"), "i", "f", "standard", 0)
        frag.open()
        t0 = time.perf_counter()
        frag.bulk_import(rows, cols)
        emit("fragment_bulk_import", n_bits / (time.perf_counter() - t0),
             "bits/sec")
        t = timeit(lambda: frag._snapshot(), iters=3)
        emit("fragment_snapshot", 1 / t, "ops/sec")
        # Same shape as rounds 1-2 under the same key (cold full pass).
        t = timeit(lambda: (frag._invalidate_block_checksums(),
                            frag.checksum_blocks()), iters=3)
        emit("fragment_blocks_checksum", 1 / t, "ops/sec")
        frag.close()

        # reopen replays snapshot via the native codec
        frag2 = Fragment(os.path.join(tmp, "f"), "i", "f", "standard", 0)
        t = timeit(lambda: (frag2.open(), frag2.close()), iters=3)
        emit("fragment_open", 1 / t, "ops/sec")

        wide = Fragment(os.path.join(tmp, "w"), "i", "w", "standard", 0)
        wide.open()
        t0 = time.perf_counter()
        wide.bulk_import(wide_rows, cols)
        emit("fragment_bulk_import_wide",
             n_bits / (time.perf_counter() - t0), "bits/sec")
        t = timeit(lambda: wide._snapshot(), iters=3)
        emit("fragment_snapshot_sparse", 1 / t, "ops/sec")
        # Cold pass (cache invalidated each run: the reference's
        # every-sync cost, fragment.go:1259-1355) vs the incremental
        # path: idle (nothing dirty) and one dirty block of ten.
        t = timeit(lambda: (wide._invalidate_block_checksums(),
                            wide.checksum_blocks()), iters=3)
        emit("fragment_blocks_checksum_wide", 1 / t, "ops/sec")
        t = timeit(lambda: wide.checksum_blocks(), iters=3)
        emit("fragment_blocks_checksum_idle", 1 / t, "ops/sec")
        t = timeit(lambda: (wide.set_bit(1, 1), wide.clear_bit(1, 1),
                            wide.checksum_blocks()), iters=3)
        emit("fragment_blocks_checksum_dirty1", 1 / t, "ops/sec")
        wide._snapshot()
        wide.close()

        # Sparse-shape open: ~16k array-encoded containers through the
        # encoding-split native load.
        wide2 = Fragment(os.path.join(tmp, "w"), "i", "w", "standard", 0)
        t = timeit(lambda: (wide2.open(), wide2.close()), iters=3)
        emit("fragment_open_sparse", 1 / t, "ops/sec")


def bench_query_qps():
    """Warm end-to-end PQL dispatch rate (parse -> compiled-tree cache
    hit -> device exec -> fetch) for a small Count(Intersect) — the
    per-query host overhead floor (reference executor.Execute,
    executor.go:84)."""
    import tempfile
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    rng = np.random.default_rng(5)
    with tempfile.TemporaryDirectory() as tmp:
        h = Holder(tmp)
        h.open()
        idx = h.create_index("q")
        for name in ("f", "g"):
            fld = idx.create_field(name)
            cols = rng.integers(0, 4 << 20, 200_000, dtype=np.uint64)
            fld.import_bits(rng.integers(0, 50, len(cols), dtype=np.uint64),
                            cols)
        ex = Executor(h)
        q = "Count(Intersect(Row(f=3), Row(g=7)))"
        ex.execute("q", q)  # compile + bank upload
        t = timeit(lambda: ex.execute("q", q), iters=100)
        emit("pql_count_qps", 1 / t, "queries/sec")
        h.close()


def bench_device_kernels():
    """Fused device sweeps (the reference's per-container kernels land
    here as one XLA op)."""
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.ops.bitset import popcount, WORDS_PER_SHARD

    rng = np.random.default_rng(2)
    shape = (64, 4, WORDS_PER_SHARD)  # 64 rows x 4 shards
    a = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    jax.block_until_ready((a, b))
    nbytes = a.nbytes + b.nbytes

    f = jax.jit(lambda x, y: popcount(jnp.bitwise_and(x, y),
                                      axis=(-2, -1)))
    np.asarray(f(a, b))
    t = timeit(lambda: np.asarray(f(a, b)))
    emit("device_and_popcount", nbytes / t / 1e9, "GB/sec",
         backend=jax.devices()[0].platform)


def bench_device_time_table():
    """Pure device-side sweep rates via the chained-iteration slope
    method: per-sweep time = slope between fori_loop chain lengths,
    cancelling host<->device RTT — the number `device_and_popcount`
    above cannot give. Emits one GB/s line per kernel
    family, the roofline evidence table. Kernels match the reference's
    hot container loops: AND+popcount (roaring.go:2438), OR (:2654),
    XOR (:3400), ANDNOT (:3031).

    Validity: every iteration ADDS to EVERY operand bank a
    salt threaded from the previous iteration's popcount, so XLA cannot
    elide, hoist, or share any sweep's memory traffic (a
    one-operand salt let the AND sweep report an impossible 3.5x the
    roofline; additive salting is used because XOR salts reassociate
    out of an XOR kernel). Per-iteration time is the Theil-Sen median
    over all chain-length pairs (min/median/max reported) and any
    median above roofline*1.05 is re-measured, then marked invalid=true
    rather than published as a number."""
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.ops.bitset import popcount, WORDS_PER_SHARD
    from benches.benchenv import (make_salted_chain, timed_fetch,
                                           validated_chain_slope)

    rows = int(os.environ.get("PILOSA_MICRO_ROWS", 255))
    shards = int(os.environ.get("PILOSA_MICRO_SHARDS", 8))
    shape = (rows, shards, WORDS_PER_SHARD)
    # Operands are generated ON DEVICE: this is a pure kernel bench
    # (contents are random words either way).
    ka, kb = jax.random.split(jax.random.key(3))
    a = jax.block_until_ready(jax.random.bits(ka, shape, jnp.uint32))
    b = jax.block_until_ready(jax.random.bits(kb, shape, jnp.uint32))

    kernels = {
        # bytes_read_factor: how many operand banks each sweep streams.
        "sweep_popcount": (1, lambda x, y, sx, sy: popcount(
            (x + sx), axis=(-2, -1))),
        "sweep_and_popcount": (2, lambda x, y, sx, sy: popcount(
            jnp.bitwise_and((x + sx), (y + sy)),
            axis=(-2, -1))),
        "sweep_or_popcount": (2, lambda x, y, sx, sy: popcount(
            jnp.bitwise_or((x + sx), (y + sy)),
            axis=(-2, -1))),
        "sweep_xor_popcount": (2, lambda x, y, sx, sy: popcount(
            jnp.bitwise_xor((x + sx), (y + sy)),
            axis=(-2, -1))),
        "sweep_andnot_popcount": (2, lambda x, y, sx, sy: popcount(
            jnp.bitwise_and((x + sx),
                            jnp.bitwise_not((y + sy))),
            axis=(-2, -1))),
        # The filtered TopN's shape: ONE bank plus a broadcast [S, W]
        # filter row (nbanks=1 — crediting two banks would inflate its
        # GB/s ~2x vs what it actually moves).
        "sweep_filter_popcount": (1, lambda x, y, sx, sy: popcount(
            jnp.bitwise_and((x + sx), (y[0] + sy)),
            axis=(-2, -1))),
    }

    dev = jax.devices()[0]
    for name, (nbanks, kern) in kernels.items():
        chain = make_salted_chain(kern)
        try:
            r = validated_chain_slope(
                lambda k: timed_fetch(lambda: chain(a, b, k)),
                a.nbytes * nbanks, dev)
        except RuntimeError as e:
            emit(name, 0.0, "GB/sec", error=str(e))
            continue
        emit(name, r["gbps_median"], "GB/sec",
             backend=dev.platform, bank_mb=a.nbytes >> 20,
             method="salted-chain-slope", **{
                 k: r[k] for k in
                 ("gbps_min", "gbps_max", "slope_pairs", "roofline_frac",
                  "roofline_gbps_assumed", "device_kind")},
             **({"invalid": True, "error": r["error"]}
                if r.get("invalid") else {}))


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    bench_device_time_table()
    bench_device_kernels()
    bench_query_qps()
    bench_roaring_kernels()
    bench_fragment_paths()


if __name__ == "__main__":
    main()
