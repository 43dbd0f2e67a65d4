"""How the megakernel's slab reads its rows from a bank that may be 2 GiB.

A `Row` leaf reads the view's full bank whenever it fits (`Executor.
_get_bank_for`), so `ops/megakernel.build_program` fills its register
slab from banks up to `[1024, 16, 32768]`. Three bodies of that read, at
`--slots` rows of one bank:

- `pick_rows`: the shipped one (`ops/bitset.pick_rows`: a loop, a
  dynamic slice a slot);
- `index`: `bank[slots]`, what the slab did before PR 39;
- `vmap`: `jax.vmap` of a dynamic slice (StableHLO: the same gather);

and `mega_plan`, the whole interpreter over one such bank. For each:
the compiled program's temporaries (MiB) and its ops as large as the
bank; on a chip also `ms`, the wall time a launch over `--launches`
back-to-back launches with one wait at the end, and whether the three
reads agree.

    python benches/mega_slab_probe.py [--rows 1024] [--slots 8]
    python benches/mega_slab_probe.py --describe v5e:2x2

`--describe` compiles for a TPU that is described, not attached (the TPU
compiler comes with jax): nothing runs, no `ms`. TPU only otherwise,
unless --allow-cpu (which checks that the reads agree, at a small
`--rows`, and prints no time).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--words", type=int, default=32768)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--launches", type=int, default=20)
    ap.add_argument("--describe", default="", metavar="TOPOLOGY")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from pilosa_tpu.ops import megakernel as mk
    from pilosa_tpu.ops.bitset import pick_rows
    from pilosa_tpu.utils.jaxenv import enable_compile_cache

    R, S, W, n = args.rows, args.shards, args.words, args.slots
    if args.describe:
        from jax.experimental import topologies
        device = topologies.get_topology_desc(
            platform="tpu", topology_name=args.describe).devices[0]
    else:
        enable_compile_cache()
        device = jax.devices()[0]
        if device.platform != "tpu" and not args.allow_cpu:
            print("no TPU attached: use --describe", file=sys.stderr)
            return 2
    at = SingleDeviceSharding(device)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=at)

    t_pad = 2 * n
    reads = {
        "pick_rows": lambda b, sl: pick_rows(W, (b, sl)),
        "index": lambda b, sl: b[sl],
        "vmap": lambda b, sl: jax.vmap(
            lambda s: jax.lax.dynamic_index_in_dim(b, s, 0, False))(sl),
    }
    bank_t, slots_t = spec((R, S, W), jnp.uint32), spec((n,), jnp.int32)
    programs = {k: (jax.jit(f), (bank_t, slots_t)) for k, f in reads.items()}
    programs["mega_plan"] = (
        jax.jit(mk.build_program(S, W, t_pad)),
        ((bank_t,), (slots_t,), spec((t_pad,), jnp.int32),
         spec((4, 4), jnp.int32), spec((2,), jnp.int32),
         spec((2,), jnp.int32)))
    compiled, recs = {}, []
    for name, (fn, avals) in programs.items():
        c = compiled[name] = fn.lower(*avals).compile()
        mem = c.memory_analysis()
        big = sorted({m.group(1) for m in re.finditer(
            rf"= u32\[{R},{S},\d+\]\S* (\w[\w-]*)\(", c.as_text())}
            - {"parameter", "get-tuple-element"})
        recs.append({"program": name, "bank": [R, S, W], "slots": n,
                     "temp_mib": mem.temp_size_in_bytes / 2 ** 20,
                     "bank_sized_ops": big})
    ok = True
    if not args.describe:
        rng = np.random.default_rng(39)
        # Rows of their own number, so that a wrong row shows.
        bank = jax.jit(lambda: jnp.broadcast_to(
            jnp.arange(R, dtype=jnp.uint32)[:, None, None], (R, S, W))
            + jnp.uint32(1))()
        slots = jnp.asarray(rng.integers(0, R, n), jnp.int32)
        operands = {
            "mega_plan": ((bank,), (slots,), jnp.full(t_pad, W, jnp.int32),
                          jnp.zeros((4, 4), jnp.int32),
                          jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))}
        want = None
        for rec in recs:
            c = compiled[rec["program"]]
            ops = operands.get(rec["program"], (bank, slots))
            out = jax.block_until_ready(c(*ops))
            t0 = time.perf_counter()
            outs = [c(*ops) for _ in range(args.launches)]
            jax.block_until_ready(outs)
            if device.platform == "tpu":
                rec["ms"] = (time.perf_counter() - t0) / args.launches * 1e3
            del outs
            if rec["program"] in reads:
                got = np.asarray(out[:, 0, 0])
                want = got if want is None else want
                rec["agrees"] = bool((got == want).all()
                                     and (got == np.asarray(slots) + 1).all())
                ok = ok and rec["agrees"]
            del out
    for rec in recs:
        print(json.dumps(rec))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
