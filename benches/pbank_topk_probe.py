"""Cost of the top-k stage of the PositionsBank kernel at 8M rows:
flat lax.top_k vs two-stage blocked exact top-k vs approx_max_k.
Exactness note: the two-stage form is exact for k<=block top-k — every
global top-k element is in its block's top-k candidates.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

R = int(os.environ.get("PILOSA_PROBE_ROWS", 8 << 20))
K = 50
BLOCK = int(os.environ.get("PILOSA_PROBE_BLOCK", 8192))


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    score = jnp.asarray(rng.integers(-1, 60, R, dtype=np.int32))

    def timed(f, *args):
        f_j = jax.jit(f)
        jax.block_until_ready(f_j(*args))
        reps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f_j(*args))
            reps.append(time.perf_counter() - t0)
        return float(np.median(reps))

    def flat_topk(s):
        return jax.lax.top_k(s, K)

    def two_stage(s):
        nb = R // BLOCK
        sb = s.reshape(nb, BLOCK)
        v, i = jax.lax.top_k(sb, K)              # [nb, K] per block
        base = (jnp.arange(nb, dtype=jnp.int32) * BLOCK)[:, None]
        cand_v = v.reshape(-1)
        cand_i = (i.astype(jnp.int32) + base).reshape(-1)
        gv, gi = jax.lax.top_k(cand_v, K)        # over nb*K candidates
        return gv, jnp.take(cand_i, gi)

    def approx(s):
        return jax.lax.approx_max_k(s.astype(jnp.float32), K)

    t = timed(flat_topk, score)
    print(f"flat_topk: {t*1000:.1f} ms", flush=True)
    t = timed(two_stage, score)
    print(f"two_stage(block={BLOCK}): {t*1000:.1f} ms", flush=True)
    t = timed(approx, score)
    print(f"approx_max_k: {t*1000:.1f} ms", flush=True)

    # equivalence check (values must match exactly; ties may reorder)
    fv, fi = jax.jit(flat_topk)(score)
    tv, ti = jax.jit(two_stage)(score)
    assert np.array_equal(np.asarray(fv), np.asarray(tv)), "top-k values differ"
    print("two_stage values == flat values", flush=True)


if __name__ == "__main__":
    main()
