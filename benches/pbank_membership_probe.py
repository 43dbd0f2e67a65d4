"""Membership-kernel probe: measure ns/position on the device for each
membership form over a fixed-layout positions bank shape (R x L u16,
~48-bit sparse filter):

- compare: the [P] x [QCAP] equality fan-out (the device default)
- search:  binary search in the sorted query positions (log2 QCAP)
- gather:  the filter-bit-table dynamic gather (the dense fallback)

Timing: salted chains (timing an identical repeat lets XLA reuse the
previous result); each iteration XORs a salt derived from the previous
result into the query positions so no sweep can be CSE'd. Prints one
JSON line per variant, each naming the platform it ran on."""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

R = int(os.environ.get("PILOSA_PROBE_ROWS", 4_194_304))  # 4M rows
L = 48
QK = int(os.environ.get("PILOSA_PROBE_QBITS", 48))  # query on-bits
ONLY = [v for v in os.environ.get("PILOSA_PROBE_ONLY", "").split(",") if v]
ITERS = [4, 12]  # chain lengths for the slope


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform

    rng = np.random.default_rng(3)
    pos = np.sort(rng.integers(0, 4096, (R, L), dtype=np.uint16), axis=1)
    q = np.unique(rng.integers(0, 4096, QK * 2, dtype=np.uint16))[:QK]
    q32 = q.astype(np.int32)
    positions = R * L

    pos_dev = jnp.asarray(pos)
    qtop_dev = jnp.asarray(q32)
    # Filter bit table for the gather form: 4096 bits = 128 u32 words.
    fw = np.zeros(128, np.uint32)
    for p in q:
        fw[p >> 5] |= np.uint32(1) << (p & 31)
    fw_dev = jnp.asarray(fw)

    def counts_compare(p, qt):
        return (p[..., None].astype(jnp.int32) == qt).any(-1) \
            .sum(axis=1, dtype=jnp.int32)

    def counts_search(p, qt):
        idx = jnp.clip(jnp.searchsorted(qt, p.astype(jnp.int32)),
                       0, QK - 1)
        return (jnp.take(qt, idx) == p.astype(jnp.int32)) \
            .sum(axis=1, dtype=jnp.int32)

    def counts_gather(p, _qt):
        bits = (jnp.take(fw_dev, (p >> 5).astype(jnp.int32),
                         mode="fill", fill_value=0)
                >> (p & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return bits.sum(axis=1, dtype=jnp.int32)

    def run_variant(name, fn, qarg):
        """Chain K sweeps, salt threaded through the query positions
        (XOR of a tiny salt keeps them valid i32s; counts feed the next
        salt so iterations serialize)."""
        @jax.jit
        def chain(qt, k):
            def body(_, carry):
                qt_c, acc = carry
                c = fn(pos_dev, qt_c)
                s = (c[0] & 1).astype(qt_c.dtype)
                return (qt_c ^ s, acc + c[-1])
            (_, acc) = jax.lax.fori_loop(
                0, k, body, (qt, jnp.int32(0)))
            return acc

        for k in ITERS:  # warm both shapes
            np.asarray(chain(qarg, k))
        times = {}
        for k in ITERS:
            reps = []
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(chain(qarg, k))
                reps.append(time.perf_counter() - t0)
            times[k] = min(reps)
        per_iter = (times[ITERS[1]] - times[ITERS[0]]) \
            / (ITERS[1] - ITERS[0])
        print(json.dumps({
            "metric": "pbank_membership_ns_per_position",
            "platform": platform,
            "variant": name,
            "value": per_iter / positions * 1e9,
            "unit": "ns/position",
            "rows": R, "slots": L, "qk": QK,
            "per_sweep_s": per_iter,
        }), flush=True)
        return per_iter

    results = {}
    for name, fn in (("compare", counts_compare), ("search", counts_search),
                     ("gather", counts_gather)):
        if not ONLY or name in ONLY:
            results[name] = run_variant(name, fn, qtop_dev)

    best = min(results, key=results.get)
    print(json.dumps({"metric": "pbank_membership_best",
                      "platform": platform,
                      "best": best,
                      "value": results[best] / positions * 1e9,
                      "unit": "ns/position",
                      "speedup_vs_compare":
                      results.get("compare", results[best])
                      / results[best]}), flush=True)


if __name__ == "__main__":
    main()
