"""Tanimoto similarity at scale — BASELINE.md config 4 (100M-fingerprint
class) via the CHUNKED TopN streaming path.

The reference workload (docs/examples.md:211-333): rows are molecules,
columns are 4096-bit Morgan fingerprint positions, and
TopN(fingerprint, Row(fingerprint=q), tanimotoThreshold=T) ranks
molecules by Tanimoto similarity to q. At this scale the full view bank
exceeds the TopN HBM budget, so the executor streams rows through
transient chunk banks with one-chunk lookahead
(executor/executor.py:_execute_topn) — the path whose throughput this
benchmark measures. Reported `mols_per_sec` is linear in N (each chunk
is independent), so `projected_100m_s` = 1e8 / mols_per_sec is the
honest extrapolation to the full BASELINE config.

Scale knob: PILOSA_TANIMOTO_N (default 1_000_000). Host memory per
molecule: one sorted-u16 array container (~100 B data+overhead; the
array encoding of SURVEY component #3, reference roaring.go:55-63) plus
~200 B of dict/row bookkeeping — 100M molecules ≈ 15-30 GB host RAM,
versus ~800 GB if containers were dense. The generation-side positions
array is uint16 (~9.6 GB at 100M), and the numpy baseline streams in
1M-row packed chunks, so no stage materializes O(N) dense data. The
device side is narrow too: banks trim to 128 u32 words/row
(max_columns=4096), and the chunked sweep touches only real
fingerprint bytes.

Prints one JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_MOLECULES = int(os.environ.get("PILOSA_TANIMOTO_N", 1_000_000))
FP_BITS = 4096
BITS_PER_MOL = 48
THRESHOLD = 60
QUERY_MOL = 12345
ITERS = int(os.environ.get("PILOSA_TANIMOTO_ITERS", 3))
CHUNK_ROWS = 65536


def build_positions(rng, n):
    """Sorted fingerprint bit positions [n, BITS_PER_MOL] (may repeat).
    uint16: at 100M molecules this array is ~9.6 GB, not the ~38 GB an
    int64 default would cost."""
    return np.sort(rng.integers(0, FP_BITS, (n, BITS_PER_MOL),
                                dtype=np.uint16), axis=1)


def pack_chunk(pos_chunk):
    """Packed u64 words [rows, FP_BITS//64] for a positions chunk."""
    n = len(pos_chunk)
    words = np.zeros((n, FP_BITS // 64), dtype=np.uint64)
    flat = words.reshape(-1)
    np.bitwise_or.at(
        flat,
        np.arange(n).repeat(BITS_PER_MOL) * (FP_BITS // 64)
        + (pos_chunk >> 6).reshape(-1),
        np.uint64(1) << (pos_chunk & 63).astype(np.uint64).reshape(-1))
    return words


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()

    # Chunked path knobs must be set before the executor module loads.
    os.environ.setdefault("PILOSA_TPU_TOPN_CHUNK_ROWS", str(CHUNK_ROWS))
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import executor as executor_mod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    executor_mod.TOPN_CHUNK_ROWS = CHUNK_ROWS
    # Force the streaming path regardless of N so the measured number is
    # the chunked throughput (at 100M it engages on its own).
    executor_mod.TOPN_MAX_BANK_BYTES = 64 << 20

    rng = np.random.default_rng(11)
    t0 = time.perf_counter()
    positions = build_positions(rng, N_MOLECULES)
    gen_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        from pilosa_tpu.core.field import FieldOptions
        idx = holder.create_index("mole")
        # Declared column bound: fingerprint banks trim to exactly
        # 4096 bits (512 B/row) instead of the 8 KiB container floor.
        f = idx.create_field("fingerprint",
                             FieldOptions(max_columns=FP_BITS))
        view = f.create_view_if_not_exists("standard")
        frag = view.create_fragment_if_not_exists(0)
        # Direct array-encoded container writes (the ImportRoaring-class
        # fast path at bulk scale): molecule i's sorted fingerprint
        # positions become the u16 array container at the head of its
        # row span; ~100 B per molecule host-side, the memory story that
        # makes 100M molecules ~15 GB instead of ~800 GB dense.
        t0 = time.perf_counter()
        store = frag.storage
        containers = store.containers
        cpr = SHARD_WIDTH // 65536
        # Vectorized per-row dedup: rows are pre-sorted, so the unique
        # values are exactly the elements that differ from their left
        # neighbor. One boolean mask for the whole matrix replaces 100M
        # np.unique calls (~11 us each, which dominated the 100M
        # leg's build).
        keep = np.empty(positions.shape, dtype=bool)
        keep[:, 0] = True
        np.not_equal(positions[:, 1:], positions[:, :-1], out=keep[:, 1:])
        for i in range(N_MOLECULES):
            containers[i * cpr] = positions[i][keep[i]]
        del keep  # ~4.8 GB at 100M; must not survive into the query phase
        for i in range(N_MOLECULES):
            frag._touch_row(i)
        converted = N_MOLECULES
        load_s = time.perf_counter() - t0

        ex = Executor(holder)
        q = (f"TopN(fingerprint, Row(fingerprint={QUERY_MOL}), "
             f"n=50, tanimotoThreshold={THRESHOLD})")
        t0 = time.perf_counter()
        (want,) = ex.execute("mole", q)  # cold: includes compiles
        cold_s = time.perf_counter() - t0

        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            (got,) = ex.execute("mole", q)
            times.append(time.perf_counter() - t0)
            assert got.pairs == want.pairs
        tpu_t = float(np.median(times))

        # Exact numpy baseline over the same data (one core), streamed in
        # packed chunks so baseline memory stays bounded at any N.
        t0 = time.perf_counter()
        filt = pack_chunk(positions[QUERY_MOL:QUERY_MOL + 1])[0]
        src = int(np.bitwise_count(filt).sum())
        inter_parts, raw_parts = [], []
        for c0 in range(0, N_MOLECULES, 1_000_000):
            pw = pack_chunk(positions[c0:c0 + 1_000_000])
            inter_parts.append(np.bitwise_count(pw & filt).sum(axis=1))
            raw_parts.append(np.bitwise_count(pw).sum(axis=1))
        inter = np.concatenate(inter_parts)
        raw = np.concatenate(raw_parts)
        denom = raw + src - inter
        passing = (denom > 0) & ((inter * 100) // np.maximum(denom, 1)
                                 >= THRESHOLD) & (inter > 0)
        pairs = sorted(((int(m), int(inter[m]))
                        for m in np.nonzero(passing)[0]),
                       key=lambda rc: (-rc[1], rc[0]))[:50]
        cpu_t = time.perf_counter() - t0
        assert pairs == want.pairs, (pairs[:3], want.pairs[:3])

        mols_per_sec = N_MOLECULES / tpu_t
        import jax
        print(json.dumps({
            "metric": "tanimoto_chunked_mols_per_sec",
            "platform": jax.devices()[0].platform,
            # (k, filtered, fixed layout, membership form) of every
            # positions-bank kernel the queries compiled.
            "pbank_kernels": [list(k) for k in Executor._PBANK_KERNELS],
            "value": mols_per_sec,
            "unit": "molecules/sec",
            "vs_baseline": (N_MOLECULES / cpu_t) and
                           mols_per_sec / (N_MOLECULES / cpu_t),
            "molecules": N_MOLECULES,
            "p50_query_s": tpu_t,
            "cold_query_s": round(cold_s, 2),
            "projected_100m_s": round(1e8 / mols_per_sec, 2),
            "chunk_rows": CHUNK_ROWS,
            "array_containers": converted,
            "gen_seconds": round(gen_s, 2),
            "load_seconds": round(load_s, 2),
        }))
        holder.close()


if __name__ == "__main__":
    main()
