"""KEPT FOR ONE REASON ONLY: `benchmark/configs/taxi-chip.json` and
`taxi-host4.json` cite this file under `assumed.extras`, and only a
`benchmark` PR may edit them. It is not an instrument, nothing runs it,
and its numbers are not the repo's: the taxi deployment is
`benchmark/datasets/taxi.py`, measured by `benchmark/run.py`. The next
`benchmark` issue re-cites that file and deletes this one (ROADMAP C12).

NYC-taxi-shaped multi-field workload — BASELINE.md config 5 (scaled).

The reference's flagship example (docs/examples.md:15-209): one index of
rides with low-cardinality set fields (cab_type, passenger_count), BSI
int fields (dist_miles, total_amount_dollars), and a time field
(pickup). Queries mix Count/Intersect, BSI range + Sum, TopN, GroupBy,
and a time-range Row — the cross-section a taxi dashboard issues.

Scaled: PILOSA_TAXI_N rides (default 10M, = 10 shards of 2^20 columns;
the 1B x 1024-shard BASELINE config is this times 100 — every query
here is a per-shard map + associative reduce, so shards scale linearly
onto chips; HBM per shard is what the budget manager bounds).

For each query family: p50 latency through the production executor vs
an exact numpy recomputation on the same arrays, printed as one JSON
line each, plus a closing summary line.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_RIDES = int(os.environ.get("PILOSA_TAXI_N", 10_000_000))
N_TIMED = min(N_RIDES, 200_000)  # rides that also get pickup timestamps
ITERS = int(os.environ.get("PILOSA_TAXI_ITERS", 3))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(metric, tpu_t, cpu_t, **extra):
    print(json.dumps({"metric": metric, "value": tpu_t, "unit": "seconds",
                      "vs_baseline": cpu_t / tpu_t if tpu_t else 0.0,
                      **extra}), flush=True)


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    rng = np.random.default_rng(5)
    cols = np.arange(N_RIDES, dtype=np.uint64)
    cab = rng.integers(0, 3, N_RIDES).astype(np.uint64)       # yellow/green/fhv
    pax = rng.integers(1, 7, N_RIDES).astype(np.uint64)
    dist = rng.integers(0, 300, N_RIDES).astype(np.int64)     # tenths of miles
    amount = (dist * 25 // 10 + rng.integers(3, 20, N_RIDES)).astype(np.int64)

    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        idx = holder.create_index("taxi")
        t0 = time.perf_counter()
        idx.create_field("cab_type").import_bits(cab, cols)
        log(f"taxi: cab_type loaded {time.perf_counter()-t0:.1f}s")
        idx.create_field("passenger_count").import_bits(pax, cols)
        log(f"taxi: passenger_count loaded {time.perf_counter()-t0:.1f}s")
        idx.create_field("dist", FieldOptions(type="int", min=0, max=300)) \
            .import_values(cols, dist)
        log(f"taxi: dist loaded {time.perf_counter()-t0:.1f}s")
        idx.create_field("amount", FieldOptions(type="int", min=0,
                                                max=1000)) \
            .import_values(cols, amount)
        log(f"taxi: amount loaded {time.perf_counter()-t0:.1f}s")
        pickup = idx.create_field("pickup",
                                  FieldOptions(type="time",
                                               time_quantum="YMD"))
        from datetime import datetime
        days = rng.integers(0, 28, N_TIMED)  # kept for the numpy baseline
        pickup.import_bits(
            np.zeros(N_TIMED, np.uint64), cols[:N_TIMED],
            timestamps=[datetime(2019, 1, 1 + int(d)) for d in days])
        idx.add_existence(cols)
        load_s = time.perf_counter() - t0
        log(f"taxi: loaded in {load_s:.1f}s")

        import jax
        ctx = {"platform": jax.devices()[0].platform}

        ex = Executor(holder)

        def p50(q):
            t0 = time.perf_counter()
            (want,) = ex.execute("taxi", q)  # warm
            log(f"taxi: warm {q[:40]!r} {time.perf_counter()-t0:.1f}s")
            times = []
            for _ in range(ITERS):
                t0 = time.perf_counter()
                (got,) = ex.execute("taxi", q)
                times.append(time.perf_counter() - t0)
            return float(np.median(times)), want

        # 1. fused Count(Intersect) over two set fields
        t, got = p50("Count(Intersect(Row(cab_type=0), "
                     "Row(passenger_count=2)))")
        t0 = time.perf_counter()
        want = int(((cab == 0) & (pax == 2)).sum())
        c1 = time.perf_counter() - t0
        assert got == want
        emit("taxi_count_intersect_p50", t, c1, count=got, **ctx)

        # 2. BSI range count
        t, got = p50("Count(Row(dist < 50))")
        t0 = time.perf_counter()
        want = int((dist < 50).sum())
        c2 = time.perf_counter() - t0
        assert got == want
        emit("taxi_bsi_range_count_p50", t, c2, count=got, **ctx)

        # 3. Sum over a filtered row
        t, got = p50("Sum(Row(cab_type=1), field=amount)")
        t0 = time.perf_counter()
        want_v = int(amount[cab == 1].sum())
        want_c = int((cab == 1).sum())
        c3 = time.perf_counter() - t0
        assert (got.value, got.count) == (want_v, want_c)
        emit("taxi_sum_filtered_p50", t, c3, sum=got.value, **ctx)

        # 4. TopN over passenger_count
        t, got = p50("TopN(passenger_count, n=3)")
        t0 = time.perf_counter()
        counts = [(int(p), int((pax == p).sum())) for p in range(1, 7)]
        want_pairs = sorted(counts, key=lambda rc: (-rc[1], rc[0]))[:3]
        c4 = time.perf_counter() - t0
        assert got.pairs == want_pairs
        emit("taxi_topn_p50", t, c4, **ctx)

        # 5. GroupBy cab_type x passenger_count (batched expansion)
        t, got = p50("GroupBy(Rows(cab_type), Rows(passenger_count))")
        t0 = time.perf_counter()
        want_n = sum(1 for c in range(3) for p in range(1, 7)
                     if ((cab == c) & (pax == p)).any())
        c5 = time.perf_counter() - t0
        assert len(got) == want_n
        for gc in got:
            c, p = gc.group[0].row_id, gc.group[1].row_id
            assert gc.count == int(((cab == c) & (pax == p)).sum())
        emit("taxi_groupby_p50", t, c5, groups=len(got), **ctx)

        # 6. time-range row count. Baseline: the same [from, to) date
        # filter vectorized over the drawn days.
        t, got = p50("Count(Row(pickup=0, from='2019-01-05', "
                     "to='2019-01-12'))")
        t0 = time.perf_counter()
        want = int(((days >= 4) & (days < 11)).sum())  # days 5..11 Jan
        c6 = time.perf_counter() - t0
        assert got == want, (got, want)
        emit("taxi_time_range_count_p50", t, c6, count=got, **ctx)

        print(json.dumps({
            "metric": "taxi_workload_total",
            "value": N_RIDES, "unit": "rides",
            "vs_baseline": 1.0,
            "shards": (N_RIDES + (1 << 20) - 1) >> 20,
            "load_seconds": round(load_s, 1),
            **ctx,
        }))
        holder.close()


if __name__ == "__main__":
    main()
