"""Salted-chain timing of the PositionsBank TopN kernel stages at one-
segment scale. Timing an identical repeated call is invalid (identical
executions were observed to return in 0.0 ms for a lax.top_k over 8M
rows), so every stage is
measured the way benchenv measures sweeps: K iterations chained in one
fori_loop, every iteration's input perturbed by a salt carried from the
previous iteration's output, per-iteration time = Theil-Sen slope
across chain lengths (RTT and dispatch cancel).
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P = int(os.environ.get("PILOSA_PROBE_POSITIONS", 384 << 20))
R = int(os.environ.get("PILOSA_PROBE_ROWS", 8 << 20))
K = 50
BLOCK = int(os.environ.get("PILOSA_PROBE_BLOCK", 8192))
Q = 64


def main():
    from benches.benchenv import (timed_fetch,
                                           validated_chain_slope)
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.integers(0, 4096, P, dtype=np.uint16))
    starts = jnp.asarray(np.linspace(0, P, R + 1).astype(np.int32))
    fw = jnp.asarray(rng.integers(0, 2**32, 128, dtype=np.uint32))
    qpad = jnp.asarray(np.concatenate(
        [np.sort(rng.choice(4096, 48, replace=False)),
         np.full(Q - 48, 0xFFFF)]).astype(np.uint16))
    score0 = jnp.asarray(rng.integers(-1, 60, R, dtype=np.int32))
    dev = jax.devices()[0]

    def chain(stage):
        """stage(salt) -> u32 scalar; chained k times."""
        def impl(k):
            def body(_, carry):
                acc, salt = carry
                out = stage(salt)
                return acc + out, out ^ salt
            acc, _ = jax.lax.fori_loop(
                0, k, body, (jnp.uint32(0), jnp.uint32(1)))
            return acc
        jit = jax.jit(impl, static_argnums=())
        return lambda k: jit(np.int32(k))

    def report(name, stage, nbytes):
        c = chain(stage)
        try:
            r = validated_chain_slope(
                lambda k: timed_fetch(lambda: c(k)), nbytes, dev,
                ks=(2, 6, 12, 20), reps=3)
            per_iter = nbytes / (r["gbps_median"] * 1e9)
            print(f"{name}: {per_iter*1000:.1f} ms/iter "
                  f"(spread {nbytes/(r['gbps_max']*1e9)*1000:.1f}-"
                  f"{nbytes/(r['gbps_min']*1e9)*1000:.1f} ms)", flush=True)
        except RuntimeError as e:
            print(f"{name}: REFUSED ({e})", flush=True)

    # Stage definitions; each consumes the salt so no iteration can be
    # shared, and returns a u32 scalar the next iteration depends on.
    def s_gather(salt):
        p2 = pos + salt.astype(jnp.uint16)  # shifts every position
        posi = (p2 & jnp.uint16(4095)).astype(jnp.int32)
        bits = (jnp.take(fw, posi >> 5, mode="fill", fill_value=0)
                >> (posi & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return bits.sum().astype(jnp.uint32)

    def s_cumsum_rowdiff(salt):
        bits = ((pos + salt.astype(jnp.uint16)) & jnp.uint16(1))\
            .astype(jnp.uint32)
        s = jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])
        c = s[starts[1:]] - s[starts[:-1]]
        return c.sum().astype(jnp.uint32)

    def s_compare(salt):
        p2 = (pos + salt.astype(jnp.uint16)) & jnp.uint16(4095)
        m = (p2[:, None] == qpad[None, :]).any(axis=1)
        return m.astype(jnp.uint32).sum()

    def s_flat_topk(salt):
        s2 = score0 + salt.astype(jnp.int32)
        v, i = jax.lax.top_k(s2, K)
        return (v.sum() + i.sum()).astype(jnp.uint32)

    def s_two_stage_topk(salt):
        s2 = score0 + salt.astype(jnp.int32)
        nb = R // BLOCK
        sb = s2.reshape(nb, BLOCK)
        v, i = jax.lax.top_k(sb, K)
        base = (jnp.arange(nb, dtype=jnp.int32) * BLOCK)[:, None]
        cand_v = v.reshape(-1)
        cand_i = (i.astype(jnp.int32) + base).reshape(-1)
        gv, gi = jax.lax.top_k(cand_v, K)
        return (gv.sum() + jnp.take(cand_i, gi).sum()).astype(jnp.uint32)

    def s_full_kernel(salt):
        # the production kernel shape: gather bits, cumsum rowdiff,
        # threshold/tanimoto filter, flat top_k
        p2 = (pos + salt.astype(jnp.uint16)) & jnp.uint16(4095)
        posi = p2.astype(jnp.int32)
        bits = (jnp.take(fw, posi >> 5, mode="fill", fill_value=0)
                >> (posi & 31).astype(jnp.uint32)) & jnp.uint32(1)
        s = jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])
        raw = (starts[1:] - starts[:-1]).astype(jnp.int32)
        c = (s[starts[1:]] - s[starts[:-1]]).astype(jnp.int32)
        keep = c >= 1
        denom = raw + 48 - c
        keep &= (denom > 0) & (c * 100 >= 60 * denom)
        sc = jnp.where(keep, c, -1)
        v, i = jax.lax.top_k(sc, K)
        return (v.sum() + i.sum()).astype(jnp.uint32)

    report("gather_only", s_gather, P * 2)
    report("cumsum_rowdiff", s_cumsum_rowdiff, P * 2)
    report("compare_only", s_compare, P * 2)
    report("flat_topk_8M", s_flat_topk, R * 4)
    report("two_stage_topk_8M", s_two_stage_topk, R * 4)
    report("full_kernel", s_full_kernel, P * 2)


if __name__ == "__main__":
    main()
