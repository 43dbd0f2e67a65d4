"""On-chip cost split of the PositionsBank TopN kernel at library scale
(402.7 M positions in 8 M rows): what a segment program pays for the
membership of every position in a sparse filter and for turning those
bits into per-row counts, in each form the served kernel has or could
have.

Membership: the table gather, the `[P] x [Q]` compare fan-out (in i32,
as `Executor._pbank_kernel` compared until PR 41), the same compare in
chunks OR-ed together, a compare unrolled one query slot at a time (no
`[P, Q]` axis for XLA to lay across the lanes), and the chunked compare
in u16, as the bank stores its positions and the kernel compares now.
Row sums: the `[P]` cumsum differenced at the row starts by two gathers
or by one, the fixed layout `[R, L]` (one axis-1 reduce, lanes padded
to 128 in HBM) and the slot-major layout `[L, R]` (rows on the lanes:
an add over L planes). Every timed program returns its per-row counts,
so no reduce folds into the one after it.

    PILOSA_PROBE_QSLOTS   query slots, one or several: "104,128"
    PILOSA_PROBE_SLOTS    L of the two padded layouts: "104,128"
    PILOSA_PROBE_ONLY     variants by name (a name covers its widths)
    PILOSA_PROBE_POSITIONS / PILOSA_PROBE_ROWS

`gather_only` (3.8 s a pass on the v5e) runs only when named. The
probe times nothing off a TPU (a reading from any other platform would
pass for a chip's), and every line names the device it ran on.
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P = int(os.environ.get("PILOSA_PROBE_POSITIONS", 384 << 20))
R = int(os.environ.get("PILOSA_PROBE_ROWS", 8 << 20))


def _ints(name: str, default: str) -> list:
    return [int(v) for v in os.environ.get(name, default).split(",") if v]


# The compare's fan-out is as wide as the bank's widest row, whatever
# the query's own on-bits (the served kernel pads to that width).
QS = _ints("PILOSA_PROBE_QSLOTS", "104,128")
LS = _ints("PILOSA_PROBE_SLOTS", "104,128")
ONLY = [v for v in os.environ.get("PILOSA_PROBE_ONLY", "").split(",") if v]
CHUNK = 112     # the widest fan-out under the cliff (PERF.md §7 row 27)


def main():
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"pbank_kernel_probe: the device is {device.platform} "
                 f"({device.device_kind}), not a TPU: nothing timed")
    rng = np.random.default_rng(3)
    # Real positions a row: what the padded layouts hold beside pads.
    real = P // R

    def qslots(q):
        qpos = np.sort(np.random.default_rng(q).choice(
            4096, min(48, q), replace=False))
        return jnp.asarray(np.concatenate(
            [qpos, np.full(q - len(qpos), 1 << 30)]).astype(np.int32))

    def timed(f, *args):
        f_j = jax.jit(f)
        try:
            out = jax.block_until_ready(f_j(*args))  # compile
        except Exception as e:  # a form the compiler refuses: say so
            return None, f"{e!r:.300}"
        reps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f_j(*args))
            reps.append(time.perf_counter() - t0)
        return float(np.median(reps)), out

    def report(name, t, out, slots, **kw):
        extra = " ".join(f"{k}={v}" for k, v in kw.items())
        if t is None:
            print(f"{name}: failed {extra} "
                  f"device={device.device_kind.replace(' ', '_')}: {out}",
                  flush=True)
            return
        print(f"{name}: {t*1000:.1f} ms  ({slots/t/1e9:.2f} Gslots/s) "
              f"positions={P} slots={slots} rows={R} {extra} "
              f"device={device.device_kind.replace(' ', '_')} "
              f"out={int(np.asarray(out.ravel()[:1024]).sum())}",
              flush=True)

    def wanted(name):
        return not ONLY or name in ONLY

    # -- membership forms (layout-agnostic: pos is [P], [R, L] or [L, R])

    def m_fanout(pos, q):
        return (pos[..., None].astype(jnp.int32) == q).any(-1)

    def chunks(q):
        n = -(-q.shape[0] // CHUNK)
        step = -(-q.shape[0] // n)
        return [q[c0:c0 + step] for c0 in range(0, q.shape[0], step)]

    def m_chunked(pos, q):
        return functools.reduce(
            jnp.logical_or, (m_fanout(pos, part) for part in chunks(q)))

    def m_chunked_u16(pos, q):
        # As m_chunked, compared as the u16 the bank stores (a query
        # pad becomes 0xFFFE: no row pad, no real position).
        q16 = jnp.where(q < (1 << 16), q, 0xFFFE).astype(jnp.uint16)
        return functools.reduce(
            jnp.logical_or, ((pos[..., None] == part).any(-1)
                             for part in chunks(q16)))

    def m_unrolled(pos, q):
        p32 = pos.astype(jnp.int32)
        m = p32 == q[0]
        for i in range(1, q.shape[0]):
            m |= p32 == q[i]
        return m

    def bits_of(pos):
        return (pos & jnp.uint16(1)).astype(jnp.uint32)

    def csum(bits):
        return jnp.concatenate(
            [jnp.zeros(1, jnp.uint32), jnp.cumsum(bits, dtype=jnp.uint32)])

    def two_gathers(s, starts):
        return (s[starts[1:]] - s[starts[:-1]]).astype(jnp.int32)

    def one_gather(s, starts):
        g = s[starts]
        return (g[1:] - g[:-1]).astype(jnp.int32)

    # -- flat layout ---------------------------------------------------
    flat_names = ("gather_only", "cumsum_only", "cumsum_rowdiff",
                  "rowdiff_one_gather", "compare_only", "compare_chunked",
                  "compare_unrolled", "compare_rowsum_full",
                  "compare_rowsum_one_gather")
    if any(wanted(n) for n in flat_names if n != "gather_only") \
            or "gather_only" in ONLY:
        pos = jnp.asarray(rng.integers(0, 4096, P, dtype=np.uint16))
        starts = jnp.asarray(np.linspace(0, P, R + 1).astype(np.int32))
        fw = jnp.asarray(rng.integers(0, 2**32, 128, dtype=np.uint32))

        def k_gather(pos, fw):
            posi = pos.astype(jnp.int32)
            bits = (jnp.take(fw, posi >> 5, mode="fill", fill_value=0)
                    >> (posi & 31).astype(jnp.uint32)) & jnp.uint32(1)
            return bits.astype(jnp.uint32).sum()

        for name, f, args in [
            ("gather_only", k_gather, (pos, fw)),
            ("cumsum_only", lambda pos: csum(bits_of(pos))[-1:], (pos,)),
            ("cumsum_rowdiff",
             lambda pos, st: two_gathers(csum(bits_of(pos)), st),
             (pos, starts)),
            ("rowdiff_one_gather",
             lambda pos, st: one_gather(csum(bits_of(pos)), st),
             (pos, starts)),
        ]:
            if name == "gather_only" and name not in ONLY:
                continue
            if wanted(name):
                t, out = timed(f, *args)
                report(name, t, out, P)
        for q in QS:
            qv = qslots(q)
            for name, member in [("compare_only", m_fanout),
                                 ("compare_chunked", m_chunked),
                                 ("compare_unrolled", m_unrolled)]:
                if wanted(name):
                    # The bits leave as u8: what the compare alone costs.
                    t, out = timed(
                        lambda pos, qv, member=member:
                        member(pos, qv).astype(jnp.uint8), pos, qv)
                    report(name, t, out, P, qslots=q)
            for name, rowsum in [("compare_rowsum_full", two_gathers),
                                 ("compare_rowsum_one_gather", one_gather)]:
                if wanted(name):
                    t, out = timed(
                        lambda pos, qv, st, rowsum=rowsum: rowsum(
                            csum(m_chunked(pos, qv).astype(jnp.uint32)), st),
                        pos, qv, starts)
                    report(name, t, out, P, qslots=q)
        del pos, starts, fw

    # -- padded layouts: real positions first, 0xFFFF pads after --------
    padded = {"fixed_compare_rowsum": (m_chunked, False),
              "fixed_unrolled_rowsum": (m_unrolled, False),
              "slot_major_compare_rowsum": (m_chunked, True),
              "slot_major_u16_rowsum": (m_chunked_u16, True),
              "slot_major_unrolled_rowsum": (m_unrolled, True)}
    for slots in LS:
        if not any(wanted(n) for n in padded):
            break
        mat = rng.integers(0, 4096, (R, slots), dtype=np.uint16)
        mat[:, real:] = 0xFFFF
        for name, (member, slot_major) in padded.items():
            # Every form at the first L; the served form at the others.
            if not wanted(name) or (slots != LS[0] and (
                    slot_major or member is not m_chunked)):
                continue
            arr = jnp.asarray(np.ascontiguousarray(mat.T) if slot_major
                              else mat)
            axis = 0 if slot_major else 1
            for q in QS[:1]:
                qv = qslots(q)
                t, out = timed(
                    lambda a, qv, member=member, axis=axis:
                    member(a, qv).sum(axis=axis, dtype=jnp.int32), arr, qv)
                report(name, t, out, R * slots, qslots=q,
                       layout=("[L,R]" if slot_major else "[R,L]"),
                       L=slots, real_positions=R * real)
            del arr
        del mat


if __name__ == "__main__":
    main()
