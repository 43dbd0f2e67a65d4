"""BSI device-time bench: the DEVICE time of the four fused BSI query
programs (Range >, Sum, Min, Max — reference fragment.go:767,794,827,
857-1035) with the salted-chain slope method (benches/benchenv.py), which
cancels all host<->device round trips. The device time is the serving
ceiling; end to end (a dispatch and a blocking fetch on top, per op) is
`benchmark/run.py`'s `point-serial` cell, whose shapes carry Sum and
BSI ranges.

Bank shape matches config 3: depth+1 planes x 10 shards x 32768 words
(10M columns of a 0..100k int field). Operands are generated on device
— a pure kernel bench, contents are random either way.
bytes_per_iter credits ONE full bank
read per sweep; Sum/Min/Max stream some planes more than once, so
their GB/s under-reports (conservative, same convention as micro.py).

Prints one JSON line per op plus a combined bsi_device_ops_per_sec.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEPTH = 17          # bit depth of a 0..100k int field (config 3)
# 10M columns / 2^20 shard width; overridable because the 23 MB bank
# the config-3 shape implies can leave the longest chain's device time
# (~3 ms) inside the host clock's fetch jitter — a wider bank (e.g. 96
# shards = 226 MB) lifts the slope signal clear of the noise without
# changing the per-byte rate being measured.
N_SHARDS = int(os.environ.get("PILOSA_BSI_DEVICE_SHARDS", "10"))
VALUE = 50_000


def emit(rec):
    print(json.dumps(rec), flush=True)


def make_plane_chain(kern):
    """One-bank variant of benchenv.make_salted_chain: kern(planes)
    -> array/scalar of counts. Every iteration ADDS a carry-derived
    salt to the whole bank (addition does not distribute over the
    bitwise ops being measured), so no iteration's memory traffic can
    be elided or hoisted — the validity rules of benchenv apply."""
    import jax
    import jax.numpy as jnp

    def chain_impl(x, k):
        def body(_, carry):
            acc, salt = carry
            sx = salt ^ jnp.uint32(0x9E3779B9)
            tot = jnp.sum(kern(x + sx)).astype(jnp.uint32)
            return acc + tot, tot ^ salt
        acc, _ = jax.lax.fori_loop(0, k, body,
                                   (jnp.uint32(0), jnp.uint32(0)))
        return acc

    jitted = jax.jit(chain_impl)
    return lambda x, k: jitted(x, np.int32(k))


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from pilosa_tpu.executor import bsi as B
    from pilosa_tpu.ops.bitset import WORDS_PER_SHARD, popcount
    from benches.benchenv import timed_fetch, validated_chain_slope

    shape = (DEPTH + 1, N_SHARDS, WORDS_PER_SHARD)
    planes = jax.block_until_ready(
        jax.random.bits(jax.random.key(5), shape, jnp.uint32))

    axes = (-2, -1)
    kernels = {
        "bsi_device_range_gt": lambda p: popcount(
            B.gt(p, VALUE), axis=axes),
        "bsi_device_sum": lambda p: B.sum_count(p)[0].sum()
        + B.sum_count(p)[1],
        "bsi_device_min": lambda p: popcount(
            B.min_mask(p)[1], axis=axes) + B.min_mask(p)[0].sum(),
        "bsi_device_max": lambda p: popcount(
            B.max_mask(p)[1], axis=axes) + B.max_mask(p)[0].sum(),
    }

    dev = jax.devices()[0]
    op_seconds = {}
    for name, kern in kernels.items():
        chain = make_plane_chain(kern)
        try:
            r = validated_chain_slope(
                lambda k: timed_fetch(lambda: chain(planes, k)),
                planes.nbytes, dev)
        except RuntimeError as e:
            emit({"metric": name, "value": 0.0, "unit": "GB/sec",
                  "error": str(e)})
            continue
        op_seconds[name] = planes.nbytes / (r["gbps_median"] * 1e9)
        emit({"metric": name, "value": r["gbps_median"],
              "unit": "GB/sec", "backend": dev.platform,
              "bank_mb": planes.nbytes >> 20,
              "device_op_seconds": op_seconds[name],
              "method": "salted-chain-slope",
              **{k: r[k] for k in
                 ("gbps_min", "gbps_max", "slope_pairs", "roofline_frac",
                  "roofline_gbps_assumed", "device_kind")},
              **({"invalid": True, "error": r["error"]}
                 if r.get("invalid") else {})})

    if op_seconds:
        mean_s = sum(op_seconds.values()) / len(op_seconds)
        emit({"metric": "bsi_device_ops_per_sec", "value": 1.0 / mean_s,
              "unit": "ops/sec", "backend": dev.platform,
              "note": "device time only (chain slope)", "ops_measured":
              sorted(op_seconds)})


if __name__ == "__main__":
    main()
