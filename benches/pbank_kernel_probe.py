"""On-chip cost split of the PositionsBank TopN kernel at one-segment
scale (384M positions): gather-into-filter-table vs cumsum vs the
sparse-filter broadcast-compare alternative (no gather: the tanimoto
query fingerprint has ~48 set positions, so membership is a dense
[P] x [Q] compare-reduce, which is VPU-shaped instead of
gather-shaped). Times via the salted chain-slope harness so RTT
cancels.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P = int(os.environ.get("PILOSA_PROBE_POSITIONS", 384 << 20))
R = int(os.environ.get("PILOSA_PROBE_ROWS", 8 << 20))
# Padded sparse-filter slots: the compare's fan-out is this wide
# whatever the query's own on-bits (the served kernel pads to
# PILOSA_TPU_PBANK_SPARSE_BITS).
Q = int(os.environ.get("PILOSA_PROBE_QSLOTS", 64))
ONLY = [v for v in os.environ.get("PILOSA_PROBE_ONLY", "").split(",") if v]


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.integers(0, 4096, P, dtype=np.uint16))
    starts = jnp.asarray(
        np.linspace(0, P, R + 1).astype(np.int32))
    fw = jnp.asarray(rng.integers(0, 2**32, 128, dtype=np.uint32))
    qpos = jnp.asarray(
        np.sort(rng.choice(4096, 48, replace=False))
        .astype(np.uint16))
    qpad = jnp.concatenate(
        [qpos, jnp.full((Q - 48,), 0xFFFF, jnp.uint16)])

    def timed(f, *args):
        f_j = jax.jit(f)
        out = jax.block_until_ready(f_j(*args))  # compile
        reps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f_j(*args))
            reps.append(time.perf_counter() - t0)
        return float(np.median(reps)), out

    def k_gather(pos, fw):
        posi = pos.astype(jnp.int32)
        bits = (jnp.take(fw, posi >> 5, mode="fill", fill_value=0)
                >> (posi & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return bits.astype(jnp.uint32).sum()

    def k_cumsum(pos):
        bits = (pos & jnp.uint16(1)).astype(jnp.uint32)
        s = jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])
        return s[-1]

    def k_rowdiff(pos, starts):
        bits = (pos & jnp.uint16(1)).astype(jnp.uint32)
        s = jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])
        c = s[starts[1:]] - s[starts[:-1]]
        return c.sum()

    def k_compare(pos, qpad):
        # membership against <=Q sparse filter positions, no gather:
        # [P] x [Q] broadcast compare, reduced over Q.
        m = (pos[:, None] == qpad[None, :]).any(axis=1)
        return m.astype(jnp.uint32).sum()

    def k_compare_rowsum(pos, qpad, starts):
        m = (pos[:, None] == qpad[None, :]).any(axis=1)
        bits = m.astype(jnp.uint32)
        s = jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])
        c = s[starts[1:]] - s[starts[:-1]]
        return c.sum()

    for name, f, args in [
        ("gather_only", k_gather, (pos, fw)),
        ("cumsum_only", k_cumsum, (pos,)),
        ("cumsum_rowdiff", k_rowdiff, (pos, starts)),
        ("compare_only", k_compare, (pos, qpad)),
        ("compare_rowsum_full", k_compare_rowsum, (pos, qpad, starts)),
    ]:
        if ONLY and name not in ONLY:
            continue
        t, out = timed(f, *args)
        print(f"{name}: {t*1000:.1f} ms  ({P/t/1e9:.2f} Gpos/s) "
              f"positions={P} qslots={Q} out={out}",
              flush=True)


if __name__ == "__main__":
    main()
