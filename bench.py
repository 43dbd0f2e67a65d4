"""Benchmark: exact-TopN bank sweep throughput on the TPU.

One process. Prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "platform", "device_kind", "device_count", ...} and
exits 0 — or exits non-zero, printing no record, when JAX's first
device is not a TPU. There is no CPU record: a CPU time is never
written under this metric's name.

Workload (BASELINE.md: "PQL ops/sec/chip ...; bits-scanned/sec; p50 TopN
latency"): a set field with 1023 rows x 8 shards (~1 GiB of packed
bitmap data) at ~30% density. The query is exact TopN(f, n=10) through
the in-process executor path: PQL parse -> executor -> one fused
popcount sweep over the HBM-resident view bank (or the HBM rank cache
once it is warm) -> host top-k. Queries are issued BATCH_CALLS to a
request (multi-call PQL, reference executor.go:84) so the executor's
dispatch-then-fetch pipeline overlaps device sweeps with the per-call
host round trip.

Baseline: the identical exact computation on host numpy over the same
packed words (vectorized popcount+reduce), which is also the
correctness reference for the device answer.

Two timings are reported:
- end-to-end (`value`): median per-call latency of the batched TopN query
  through the executor — includes the host<->device round trip.
- device-time (`device_bits_per_sec` / `device_gbps` / `roofline_frac`):
  K sweeps chained inside ONE jit (lax.fori_loop), timed by the slope
  between chain lengths so the per-fetch round trip cancels.

Metric: bits scanned per second = rows x shards x 2^20 / median latency.

This is the pre-round single cell, kept running until the served-path
benchmark (ROADMAP A1) replaces it.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# 1023 rows (not 1024): bank capacity pads to the next power of two ABOVE
# rows+1, so 1024 rows would double the upload for one slot of zeros.
N_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", 8))
N_ROWS = int(os.environ.get("PILOSA_BENCH_ROWS", 1023))
TPU_ITERS = 6
CPU_ITERS = 3
BATCH_CALLS = 8  # TopN calls per query; dispatches pipeline before fetch
TIMING_BUDGET_S = 90.0  # stop the timing loop early past this (>=2 samples)


def build_holder(tmp):
    log("bench: building holder data")
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    holder = Holder(tmp)
    holder.open()
    idx = holder.create_index("bench")
    f = idx.create_field("f")
    rng = np.random.default_rng(42)
    view = f.create_view_if_not_exists("standard")
    words_per_row = SHARD_WIDTH // 64
    for shard in range(N_SHARDS):
        frag = view.create_fragment_if_not_exists(shard)
        # One bulk region per shard: rows 0..N_ROWS-1 at ~30% density
        # (AND of two uniform randoms), written straight into container
        # storage (the import fast path measured separately).
        dense = rng.integers(0, 2**63, N_ROWS * words_per_row,
                             dtype=np.uint64)
        dense &= rng.integers(0, 2**63, N_ROWS * words_per_row,
                              dtype=np.uint64)
        frag.storage.set_dense_range(0, dense)
        for row in range(N_ROWS):
            frag._touch_row(row)
    return holder


def bench_tpu(holder, out):
    from pilosa_tpu.executor import Executor

    ex = Executor(holder)
    log("bench: warming TPU path (bank upload + compile)")
    t0 = time.perf_counter()
    (want,) = ex.execute("bench", "TopN(f, n=10)")  # warm: upload+compile
    log(f"bench: warm done in {time.perf_counter() - t0:.1f}s, timing")
    # Measure a BATCH_CALLS-call query: the executor dispatches every
    # call's device program before fetching any result, so per-call cost
    # amortizes the host<->device round trip (the reference likewise
    # evaluates every call of a query, executor.go:84, and clients batch
    # calls per request).
    q = " ".join("TopN(f, n=10)" for _ in range(BATCH_CALLS))
    ex.execute("bench", q)  # warm the batched path
    times = []
    loop_t0 = time.perf_counter()
    for i in range(TPU_ITERS):
        t0 = time.perf_counter()
        got = ex.execute("bench", q)
        times.append((time.perf_counter() - t0) / BATCH_CALLS)
        assert all(g.pairs == want.pairs for g in got)
        if time.perf_counter() - loop_t0 > TIMING_BUDGET_S and \
                len(times) >= 2:
            log(f"bench: timing budget hit after {len(times)} iters")
            break
    out["tpu_s_per_call"] = float(np.median(times))
    out["tpu_timing"] = f"median-of-{len(times)}"
    stage_timeline_breakdown(ex, q, out)
    cache_stats_stanza(ex, out)
    roofline_stanza(ex, out)
    slo_stanza(out, times)
    return out["tpu_s_per_call"], want.pairs


def cache_stats_stanza(ex, out):
    """Cross-request cache engagement during the timed loop: how much
    of the repeated-TopN workload the device rank cache and the result
    cache served, so the record shows WHICH regime the headline number
    measured (cold sweeps vs warm cache)."""
    rc = ex.result_cache.snapshot()
    out["result_cache"] = {
        "hits": rc["hits"], "misses": rc["misses"],
        "hitRatio": round(rc["hitRatio"], 4),
        "bytes": rc["bytes"], "enabled": rc["enabled"],
    }
    out["rank_cache"] = {
        "hits": ex.rank_cache_hits,
        "patches": ex.rank_cache_patches,
        "rebuilds": ex.rank_cache_rebuilds,
        "warm_topn_hits": ex.topn_cache_hits,
    }
    log(f"bench: cache stats result={out['result_cache']} "
        f"rank={out['rank_cache']}")


def roofline_stanza(ex, out):
    """The roofline recorder's launch counters and the executor's
    cumulative plan_cost byte splits. A TopN-only bench takes the fused
    (non-megakernel) path, so zero launches is a legitimate stanza."""
    from pilosa_tpu.utils.roofline import ROOFLINE
    snap = ROOFLINE.snapshot()
    out["roofline"] = {
        k: snap[k] for k in (
            "enabled", "rooflineGbps", "rooflineSource", "estimateOnly",
            "launches", "fencedLaunches", "achievedGbps",
            "rooflineFraction", "bytesByKind", "opcodeTotals",
            "driftFlags")}
    out["roofline"]["launchBytes"] = (
        ex.launch_bytes_gather + ex.launch_bytes_compute
        + ex.launch_bytes_expand + ex.launch_bytes_pad)


def slo_stanza(out, times):
    """Would the measured latency distribution hold a serving SLO?
    Replays the timed loop's per-call latencies through a private
    SentinelRecorder (utils/sentinel.py) against the objective in
    PILOSA_BENCH_SLO (default "99% < 25ms") on a synthetic clock — the
    record then carries budget consumed and any burn-rate alerts the
    run would have fired."""
    from pilosa_tpu.server.http import SLO_BUCKETS
    from pilosa_tpu.utils.sentinel import SentinelRecorder
    from pilosa_tpu.utils.stats import MemStatsClient

    spec = os.environ.get("PILOSA_BENCH_SLO", "99% < 25ms")
    sent = SentinelRecorder()
    sent.configure(enabled=True, ring=64, decimate=10,
                   alert_ring=32, objectives={"query": spec})
    stats = MemStatsClient()
    red = stats.with_tags("endpoint:/index/{index}/query", "status:200")
    # Replay in ~8 sentinel ticks; the synthetic clock advances by the
    # real wall time each chunk of calls took, so q/s and the burn
    # windows see the measured rate, not an arbitrary one.
    clock = 0.0
    sent.sample({}, stats.snapshot()["histograms"], now=clock)
    chunk = max(1, len(times) // 8)
    for i, s in enumerate(times):
        red.histogram("http_request_seconds", s, buckets=SLO_BUCKETS)
        clock += max(s, 1e-9)
        if (i + 1) % chunk == 0 or i == len(times) - 1:
            sent.sample({}, stats.snapshot()["histograms"], now=clock)
    snap = sent.slo_snapshot()
    ep = next(e for e in snap["endpoints"] if "target" in e)
    out["slo"] = {
        "objective": spec,
        "target": ep["target"],
        "thresholdS": ep["thresholdS"],
        "thresholdBucket": ep["thresholdBucket"],
        "budgetConsumed": round(ep["budgetConsumed"], 6),
        "budgetRemaining": round(ep["budgetRemaining"], 6),
        "rates": {k: round(v, 6) if v == v else v
                  for k, v in ep["rates"].items()},
        "alertsFired": snap["alerts"]["fired"],
        "alerts": [e["key"] for e in snap["alerts"]["ring"]
                   if e["event"] == "fire"],
    }


def stage_timeline_breakdown(ex, q, out, iters: int = 3):
    """Where the per-call time goes, not just its total: a few profiled
    (device-fenced) runs AFTER the timed loop record plan/dispatch/
    device/fetch medians on the host clock."""
    from pilosa_tpu.utils.profile import QueryProfile

    stages = {"planS": [], "dispatchS": [], "deviceS": [], "fetchS": []}
    for _ in range(max(1, iters)):
        prof = QueryProfile("bench", q, sample_device=True)
        ex.execute("bench", q, profile=prof)
        stages["planS"].append(prof.totals["plan"])
        stages["dispatchS"].append(prof.totals["dispatch"])
        stages["deviceS"].append(prof.totals["device"])
        stages["fetchS"].append(prof.totals["materialize"])
    out["stage_breakdown"] = {
        k: float(np.median(v)) for k, v in stages.items()}


def bench_device_time(holder):
    """Pure device sweep rate: K popcount sweeps chained in one jit.

    Each timing fetches ONE scalar that depends on a chain of K
    full-bank sweeps; the slope between chain lengths cancels both the
    fetch round trip and the dispatch overhead. Each iteration perturbs
    the bank with a salt threaded from the previous iteration's popcount
    total, so XLA cannot CSE/hoist any sweep — every iteration must
    re-read the full bank from HBM. Slopes come from >=3 chain-length
    pairs and the median is marked invalid if it exceeds the chip's HBM
    roofline by >5%. Replaces: the reference's container popcount loop
    (/root/reference/roaring/roaring.go:2438) as driven by the TopN scan.
    """
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.ops.bitset import popcount
    from pilosa_tpu.utils.benchenv import (make_salted_chain, timed_fetch,
                                           validated_chain_slope)

    field = holder.index("bench").field("f")
    view = field.view()
    bank = view.device_bank(tuple(range(N_SHARDS)), trim=True)
    arr = bank.array  # [slots, shards, words] u32, device-resident
    bank_bytes = int(arr.size) * 4

    chain = make_salted_chain(
        lambda x, y, sx, sy: popcount(x + sx, axis=-1))
    r = validated_chain_slope(
        lambda k: timed_fetch(lambda: chain(arr, arr, k)),
        bank_bytes, jax.devices()[0])

    # AND+popcount, i.e. Count(Intersect(...)) (reference
    # intersectionCountBitmapBitmap, roaring.go:2438) — as a two-operand
    # salted chain: both operands perturbed independently, 2x bank
    # traffic credited.
    and_chain = make_salted_chain(
        lambda x, y, sx, sy: popcount(
            jnp.bitwise_and(x + sx, y + sy), axis=-1))
    r_and = validated_chain_slope(
        lambda k: timed_fetch(lambda: and_chain(arr, arr, k)),
        2 * bank_bytes, jax.devices()[0])
    out = {
        "device_sweep_s": r["per_iter_s"],
        "device_bits_per_sec": bank_bytes * 8 / r["per_iter_s"],
        "device_gbps": r["gbps_median"],
        "device_gbps_min": r["gbps_min"],
        "device_gbps_max": r["gbps_max"],
        "roofline_gbps_assumed": r["roofline_gbps_assumed"],
        "roofline_frac": r["roofline_frac"],
        "bank_bytes": bank_bytes,
        "device_and_gbps": r_and["gbps_median"],
        "device_and_gbps_min": r_and["gbps_min"],
        "device_and_gbps_max": r_and["gbps_max"],
        "device_and_roofline_frac": r_and["roofline_frac"],
    }
    if r.get("invalid"):
        out["device_time_invalid"] = True
        out["device_time_error"] = r["error"]
    if r_and.get("invalid"):
        out["device_and_invalid"] = True
    return out


def bench_cpu(holder):
    """Host baseline: exact popcounts over the same packed rows + top-k."""
    log("bench: running CPU baseline")
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    f = holder.index("bench").field("f")
    view = f.view()
    per_shard = [view.fragment(s).storage.dense_range(0,
                                                      N_ROWS * SHARD_WIDTH)
                 .reshape(N_ROWS, -1) for s in range(N_SHARDS)]
    data = np.stack(per_shard, axis=1)  # [R, S, words]

    def run():
        if hasattr(np, "bitwise_count"):
            counts = np.bitwise_count(data).sum(axis=(1, 2))
        else:
            counts = np.array([np.unpackbits(r.view(np.uint8)).sum()
                               for r in data])
        order = np.argsort(-counts, kind="stable")[:10]
        return [(int(r), int(counts[r])) for r in order]

    pairs = run()
    times = []
    for _ in range(CPU_ITERS):
        t0 = time.perf_counter()
        pairs = run()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), pairs


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"bench: first device is {dev.platform} ({dev.device_kind}), "
            "not a tpu; this benchmark has no CPU record")
        return 1
    from pilosa_tpu.ops.bitset import SHARD_WIDTH
    bits = N_ROWS * N_SHARDS * SHARD_WIDTH
    out = {"metric": "exact_topn_bits_scanned_per_sec", "unit": "bits/sec",
           "platform": dev.platform, "device_kind": dev.device_kind,
           "device_count": len(jax.devices())}
    with tempfile.TemporaryDirectory() as tmp:
        holder = build_holder(tmp)
        cpu_t, cpu_pairs = bench_cpu(holder)
        tpu_t, tpu_pairs = bench_tpu(holder, out)
        if [c for _, c in tpu_pairs] != [c for _, c in cpu_pairs]:
            raise AssertionError(f"device TopN {tpu_pairs} != host numpy "
                                 f"{cpu_pairs}")
        out.update(bench_device_time(holder))
        holder.close()
    out["value"] = bits / tpu_t
    out["cpu_value"] = bits / cpu_t
    out["vs_baseline"] = cpu_t / tpu_t
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
