#!/usr/bin/env python3
"""Compile and run every Pallas kernel in ops/pallas_kernels.py
NON-interpret on the attached chip, at the shapes the served taxi
deployment produces, and compare each with its jnp reference.

Tier-1 only ever runs these kernels in interpret mode; this is the one
place Mosaic sees them. A kernel that stops compiling here is deleted,
not kept as an opt-in nobody can test (`chip_smoke.py --pallas` runs
this first, then serves with PILOSA_TPU_PALLAS=1).

Prints one JSON line {kernel: {"ok", "shape", "error"}}; exits
non-zero if any kernel failed. --platform cpu runs the same calls in
interpret mode at a small size, to check this script itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--grid-rows", type=int, default=1023)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from pilosa_tpu.ops import pallas_kernels as pk
    from pilosa_tpu.ops.bitset import WORDS_PER_SHARD, popcount
    from pilosa_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    S, W = args.shards, WORDS_PER_SHARD
    slots = 1 << int(args.grid_rows).bit_length()
    k_bank, k_filt = jax.random.split(jax.random.key(0))
    # Generated on the device: the full bank is 2 GiB.
    bank = jax.random.bits(k_bank, (slots, S, W), jnp.uint32)
    filt = jax.random.bits(k_filt, (S, W), jnp.uint32)

    def row_counts():
        got = pk.bank_row_counts(bank, interpret=interpret)
        return got, popcount(bank, axis=(-2, -1))

    def row_counts_masked():
        inter, raw = pk.bank_row_counts_masked(bank, filt,
                                               interpret=interpret)
        return (jnp.stack([inter, raw]),
                jnp.stack([popcount(bank & filt, axis=(-2, -1)),
                           popcount(bank, axis=(-2, -1))]))

    def plane_counts():
        # amount is int 0..1000: 10 value planes + the exists plane.
        planes = bank[:11]
        return (pk.bsi_plane_counts(planes, filt, interpret=interpret),
                popcount(planes & filt, axis=(-2, -1)))

    checks = {
        "bank_row_counts": ((slots, S, W), row_counts),
        "bank_row_counts_masked": ((slots, S, W), row_counts_masked),
        "bsi_plane_counts": ((11, S, W), plane_counts),
    }
    out = {}
    for name, (shape, fn) in checks.items():
        try:
            got, want = fn()
            ok = bool(jnp.array_equal(got, want))
            out[name] = {"ok": ok, "shape": str(shape),
                         "error": None if ok else "differs from jnp"}
        except Exception as e:  # the verdict on this kernel, recorded
            traceback.print_exc()
            out[name] = {"ok": False, "shape": str(shape),
                         "error": f"{type(e).__name__}: {str(e)[:600]}"}
        print(f"pallas_chip_check: {name}: {out[name]}", file=sys.stderr,
              flush=True)
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "interpret": interpret}
    print(json.dumps(out), flush=True)
    return 0 if all(v["ok"] for k, v in out.items()
                    if k != "device") else 1


if __name__ == "__main__":
    sys.exit(main())
