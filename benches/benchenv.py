"""Device-time measurement helpers shared by the benches/ scripts: the
salted chain-slope method and its physical-validity guard."""

import time

from benchmark.harness.peaks import PEAK_HBM_GBPS

# Tolerance above the roofline before a slope measurement is rejected:
# covers catalog rounding, not measurement error.
ROOFLINE_SLACK = 1.05


class UnknownDeviceKind(LookupError):
    """The device's kind has no row in the benchmark's peak table."""


def resolve_roofline(device):
    """(peak HBM GB/s, device kind) of a jax device, from the one peak
    table the repo keeps (benchmark/harness/peaks.py). A kind that is
    not there is an error, never a default."""
    kind = getattr(device, "device_kind", "") or ""
    try:
        return PEAK_HBM_GBPS[kind][0], kind
    except KeyError:
        raise UnknownDeviceKind(
            f"no HBM peak on record for device kind {kind!r}") from None


def chain_slope_gbps(timed, bytes_per_iter, ks=(8, 32, 72, 128), reps=3,
                     warm_all=False):
    """Per-iteration sweep rate from the chained-iteration slope method,
    measured across MULTIPLE chain-length pairs so one noisy sample
    cannot fabricate a slope.

    Chain lengths are deliberately long (the traced-k chain makes extra
    iterations compile-free): the per-iteration signal between the
    shortest and longest chain is (128-8) x sweep-time, which must
    stand clear of the jitter of one blocking fetch on the host clock.

    `timed(k)` must run a k-iteration chain whose every iteration has a
    true data dependency on the previous one (see make_salted_chain)
    and return wall seconds for one blocking fetch. The per-iteration
    time is the Theil-Sen estimate — the median over ALL pairwise
    slopes, negatives included, so noise cannot be laundered by
    discarding the slow-looking pairs. Raises RuntimeError when the
    median slope is non-positive or more than half the pairs are
    (host clock too noisy to measure)."""
    import numpy as np

    # One untimed warm call covers compile + first-touch: the traced-k
    # chain (make_salted_chain's default) compiles a single program for
    # every length. A static_k chain must pass warm_all=True so each
    # length's compile stays out of the timed reps.
    for k in (ks if warm_all else ks[:1]):
        timed(k)
    med = {k: float(np.median([timed(k) for _ in range(reps)])) for k in ks}
    slopes = []
    for i, ka in enumerate(ks):
        for kb in ks[i + 1:]:
            slopes.append((med[kb] - med[ka]) / (kb - ka))
    n_nonpos = sum(1 for s in slopes if s <= 0)
    ts = float(np.median(slopes))
    if ts <= 0 or n_nonpos > len(slopes) // 2:
        raise RuntimeError(
            f"chain-slope: median slope {ts:.3e}s with {n_nonpos}/"
            f"{len(slopes)} non-positive pairs from times {med}; "
            "host clock too noisy for a device-time measurement")
    pos = sorted(s for s in slopes if s > 0)
    return {
        "gbps_min": bytes_per_iter / pos[-1] / 1e9,
        "gbps_median": bytes_per_iter / ts / 1e9,
        "gbps_max": bytes_per_iter / pos[0] / 1e9,
        "per_iter_s": ts,
        "slope_pairs": len(slopes),
        "slope_pairs_nonpositive": n_nonpos,
        "chain_times_s": {str(k): med[k] for k in ks},
    }


def validated_chain_slope(timed, bytes_per_iter, device,
                          ks=(8, 32, 72, 128), reps=3, retries=1):
    """chain_slope_gbps + the physical-validity guard: a median above
    roofline*ROOFLINE_SLACK is re-measured up to `retries` times; if it
    stays impossible the result is returned with "invalid": True so no
    record ever presents an above-roofline number as a measurement. A
    device whose kind has no peak on record raises (resolve_roofline):
    a fraction of a guessed peak is not a result."""
    roofline, kind = resolve_roofline(device)
    last = None
    for _ in range(retries + 1):
        last = chain_slope_gbps(timed, bytes_per_iter, ks=ks, reps=reps)
        if last["gbps_median"] <= roofline * ROOFLINE_SLACK:
            break
    last["roofline_gbps_assumed"] = roofline
    last["device_kind"] = kind
    last["roofline_frac"] = last["gbps_median"] / roofline
    if last["gbps_median"] > roofline * ROOFLINE_SLACK:
        last["invalid"] = True
        last["error"] = (
            f"measured {last['gbps_median']:.0f} GB/s exceeds the "
            f"{roofline:.0f} GB/s roofline for {kind}; the chain failed "
            "to defeat compiler elision or the slope is noise")
    return last


def make_salted_chain(kern, static_k=False):
    """Build the standard data-dependent chain for chain_slope_gbps.

    `kern(x, y, salt_x, salt_y)` computes one full sweep over its
    operand banks, with EVERY operand perturbed by its uint32 salt, and
    returns an array/scalar of counts. The chain threads each
    iteration's total back in as the next salt, so no iteration's
    memory traffic can be elided, hoisted, or CSE'd by XLA (an
    un-salted chain once measured 3.5x the roofline, which is
    physically impossible). Kernels must perturb with ADDITION
    (x + salt_x), never XOR: XOR salts reassociate — (x^sx)^(y^sy) =
    (x^y)^(sx^sy) lets LICM hoist the loop-invariant x^y and stream
    one bank instead of two — while addition does not distribute over
    any of the bitwise ops being measured. The two salts are distinct
    functions of the carry as defense in depth.

    The chain length k is a TRACED argument by default, so each kernel
    family compiles exactly ONE device program no matter how many chain
    lengths the slope method times (static-k chains pay one compile
    per length, 4 per kernel). A traced bound lowers fori_loop to a
    while loop whose per-iteration bookkeeping lands IN the slope — a
    bias that
    UNDER-reports GB/s (µs of scalar work vs a ~ms full-bank sweep),
    i.e. conservative for a roofline-bounded measurement. static_k=True
    restores the unrolled-loop behavior for comparison."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def chain_impl(x, y, k):
        def body(_, carry):
            acc, salt = carry
            sx = salt ^ jnp.uint32(0x9E3779B9)
            sy = salt * jnp.uint32(0x85EBCA6B) + jnp.uint32(0xC2B2AE35)
            tot = jnp.sum(kern(x, y, sx, sy)).astype(jnp.uint32)
            return acc + tot, tot ^ salt
        acc, _ = jax.lax.fori_loop(
            0, k, body, (jnp.uint32(0), jnp.uint32(0)))
        return acc

    if static_k:
        # graftlint: disable=GL006 — bench-harness probe: compiles are
        # the measurement, not serving traffic; no executor exists here.
        return jax.jit(chain_impl, static_argnums=2)
    # graftlint: disable=GL006 — bench-harness probe, as above.
    jitted = jax.jit(chain_impl)
    # np.int32 keeps the scalar's dtype (and thus the trace signature)
    # stable across every chain length: one compile total.
    return lambda x, y, k: jitted(x, y, np.int32(k))


def timed_fetch(fn):
    """Wall seconds for one blocking to-host fetch of fn()'s result."""
    import numpy as np

    t0 = time.perf_counter()
    np.asarray(fn())
    return time.perf_counter() - t0
