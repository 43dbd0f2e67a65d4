"""Candidate bodies of a filter group's program, timed on the chip.

Inside a batch the filter trees of one signature launch as ONE program
(`Executor._filter_group_fn`, the program `tree_row_multi`;
executor/fusion.py `_FilterGroup`). This script stages the six filter
families of the taxi deployment's `topn-sweep` traffic on their real
operand shapes — `cab_type` `[4, 16, 32768]`, the `dist` / `amount` BSI
plane banks, the `pickup` day views (1, 8 and 32 folded views),
`dist_miles` x `total_amount_dollars`, `pickup_elapsed_time_of_day`; a
holder of 16 full-width shards whose banks are mostly zeros: a bitwise
program's time does not depend on the bits — and times three bodies of
the group's program at every lane count, in ONE process:

- `lanes`: the shipped body — the tree traced once a lane, lane b over
  its own banks and row b of the stacked operands, slots handed to the
  leaves one by one (every bank read a dynamic slice);
- `lanes_gather`: the same, slots handed over as a vector, as
  `tree_row` takes them (a BSI leaf then gathers its planes; the other
  leaves are unchanged);
- `vmap`: `jax.vmap` of the tree over the stacked operands, the body
  `_FuseGroup` runs for terminal evals (every leaf's read by a batched
  slot becomes a gather over its bank).

Per body and lane count: `device_ms`, the module's duration in a
profiler trace (median of the launches), `enqueue_ms`, the host's
wall time a launch over back-to-back launches (one `block_until_ready`
at the end: it holds the argument handling of a lane's banks), and the
device ops' names. Lane count 1 is `tree_row`, the program a member
launches alone. Every body's lanes are compared with `tree_row`'s
output; a mismatch fails the run.

    python benches/filter_group_variants.py [--shards 16] [--launches 20]
        [--out chiprun_out/filter_group_variants]

TPU only unless --allow-cpu (a CPU run checks exactness at `--shards 2`
and prints no time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from datetime import datetime, timedelta

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DAY0 = datetime(2019, 1, 1)


def _iso(day: int) -> str:
    return f"{DAY0 + timedelta(days=day):%Y-%m-%dT%H:%M}"


# name -> the filter tree's text (its constants do not matter).
FILTERS = {
    "dist_lt": "Row(dist < 120)",
    "amount_gt": "Row(amount > 240)",
    "cab_dist": "Intersect(Row(cab_type=1), Row(dist < 120))",
    "miles_dollars": "Intersect(Row(dist_miles=5), "
                     "Row(total_amount_dollars=13))",
    "tod": "Row(pickup_elapsed_time_of_day=17)",
    "range_1": f"Row(pickup=1, from='{_iso(3)}', to='{_iso(4)}')",
    "range_8": f"Row(pickup=1, from='{_iso(3)}', to='{_iso(10)}')",
    "range_32": f"Row(pickup=1, from='{_iso(1)}', to='{_iso(27)}')",
}


def _fill(holder, n_shards: int, rides_a_shard: int = 512) -> None:
    """The taxi schema's filter fields, a few rides a shard and one in
    each shard's last column, so that every view is full width."""
    import numpy as np
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.ops.bitset import SHARD_WIDTH
    idx = holder.create_index("taxi")
    rng = np.random.default_rng(36)
    cols = np.unique(np.concatenate([
        s * SHARD_WIDTH + np.append(
            rng.integers(0, SHARD_WIDTH - 1, rides_a_shard), SHARD_WIDTH - 1)
        for s in range(n_shards)])).astype(np.uint64)
    n = len(cols)
    dist = rng.integers(0, 300, n)
    amount = dist * 25 // 10 + rng.integers(3, 20, n)
    cab = rng.integers(0, 3, n).astype(np.uint64)
    for name, rows in (("cab_type", cab), ("dist_miles", dist // 10),
                       ("total_amount_dollars", amount // 10),
                       ("pickup_elapsed_time_of_day",
                        rng.integers(0, 48, n))):
        idx.create_field(name).import_bits(rows.astype(np.uint64), cols)
    for name, vals, hi in (("dist", dist, 300), ("amount", amount, 1000)):
        idx.create_field(name, FieldOptions(type="int", min=0, max=hi)) \
            .import_values(cols, vals)
    # Every day holds every shard's last column.
    last = (cols % SHARD_WIDTH) == SHARD_WIDTH - 1
    pickup = idx.create_field("pickup", FieldOptions(
        type="time", time_quantum="YMD"))
    for d in range(28):
        on = last | (rng.integers(0, 28, n) == d)
        pickup.import_bits(cab[on], cols[on],
                           [DAY0 + timedelta(days=d)] * int(on.sum()))
    idx.add_existence(cols)


def _bodies(ex, rep, lanes: int, width: int, tag: str) -> dict:
    """body name -> (jitted fn, its arguments for `lanes` copies of
    `rep`'s operands, lane -> [S, W] of its output)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from pilosa_tpu.executor.executor import _align_words, named
    run, n_idx = rep.runner(), len(rep.idxs)
    row = np.asarray([*rep.idxs, *rep.params], np.uint32)
    ops = jnp.asarray(np.tile(row, (lanes, 1)))
    banks = (rep.bank_arrays,) * lanes
    if lanes == 1:
        idxs, params, _ = ex._staged_args(rep)
        fn = jax.jit(named(run, f"fgv_{tag}_solo"))
        return {"tree_row": (fn, (rep.bank_arrays, idxs, params, None),
                             lambda out, b: out)}

    def lanes_gather(banks, ops):
        return tuple(_align_words(
            run(bk, ops[b, :n_idx].astype(np.int32), ops[b, n_idx:], None),
            width) for b, bk in enumerate(banks))

    vmapped = jax.vmap(run, in_axes=(None, 0, 0, None))

    def vmap(bank_arrays, ops):
        return _align_words(vmapped(
            bank_arrays, ops[:, :n_idx].astype(np.int32), ops[:, n_idx:],
            None), width)

    shipped, _ = ex._filter_group_fn(rep, lanes, width)
    # The shipped program under a name of its own in the trace.
    shipped = jax.jit(named(shipped.__wrapped__, f"fgv_{tag}_lanes{lanes}"))
    return {
        "lanes": (shipped, (rep.shared_banks,
                            (rep.owned_banks,) * lanes, ops),
                  lambda out, b: out[b]),
        "lanes_gather": (
            jax.jit(named(lanes_gather, f"fgv_{tag}_lanesgather{lanes}")),
            (banks, ops), lambda out, b: out[b]),
        "vmap": (jax.jit(named(vmap, f"fgv_{tag}_vmap{lanes}")),
                 (rep.bank_arrays, ops), lambda out, b: out[b]),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--launches", type=int, default=20)
    ap.add_argument("--only", default="", help="comma list of FILTERS names")
    ap.add_argument("--out", default="chiprun_out/filter_group_variants")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    from benches.sweep_variants import _device_times
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor.fusion import FILTER_LANES
    from pilosa_tpu.pql.parser import parse_string_cached
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.allow_cpu:
        print(f"first device is {dev.platform}, not a tpu", file=sys.stderr)
        return 1
    os.makedirs(args.out, exist_ok=True)
    only = [n for n in args.only.split(",") if n] or list(FILTERS)

    rows, timed = [], []
    with tempfile.TemporaryDirectory() as tmp:
        holder = Holder(tmp)
        holder.open()
        _fill(holder, args.shards)
        ex = Executor(holder)
        idx = holder.index("taxi")
        shards = list(range(args.shards))
        for name in only:
            call = parse_string_cached(FILTERS[name]).calls[0]
            rep, _, _ = ex._stage_eval(idx, call, shards, "row")
            width = rep.width
            want = None
            for lanes in (1, *FILTER_LANES):
                for body, (fn, fargs, lane) in _bodies(
                        ex, rep, lanes, width, name).items():
                    rec = {"filter": name, "body": body, "lanes": lanes,
                           "banks": [list(a.shape) for a in rep.bank_arrays
                                     ][:3], "n_banks": len(rep.bank_arrays)}
                    rows.append(rec)
                    try:
                        out = jax.block_until_ready(fn(*fargs))
                        if want is None:
                            want = np.asarray(out)
                        for b in range(lanes):
                            if not (np.asarray(lane(out, b)) == want).all():
                                rec["error"] = f"lane {b} differs"
                        timed.append((rec, fn, fargs))
                    except Exception as e:
                        rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        if on_tpu:
            def launches(fn, fargs, n):
                out = None
                t0 = time.perf_counter()
                for _ in range(n):
                    out = fn(*fargs)
                jax.block_until_ready(out)
                return (time.perf_counter() - t0) / n * 1e3

            for rec, fn, fargs in timed:
                launches(fn, fargs, 4)
                rec["enqueue_ms"] = sorted(
                    launches(fn, fargs, args.launches) for _ in range(3))[1]
            trace_dir = os.path.join(args.out, "trace")
            with jax.profiler.trace(trace_dir):
                for rec, fn, fargs in timed:
                    launches(fn, fargs, 8)
            mods = _device_times(trace_dir)
            for rec, fn, _ in timed:
                durs, names = mods.get(f"jit_{fn.__name__}", ([], {}))
                if durs:
                    rec["device_ms"] = float(np.median(durs))
                    rec["device_ops"] = names
        holder.close()

    with open(os.path.join(args.out, "record.json"), "w") as f:
        json.dump({"platform": dev.platform, "device_kind": dev.device_kind,
                   "shards": args.shards, "rows": rows}, f, indent=1)
    print(f"{'filter':14} {'lanes':>5} {'body':13} {'device_ms':>10} "
          f"{'ms/lane':>8} {'enqueue_ms':>10}  ops")
    for r in rows:
        d = r.get("device_ms")
        print(f"{r['filter']:14} {r['lanes']:5d} {r['body']:13} "
              f"{d if d is None else round(d, 4)!s:>10} "
              f"{'' if d is None else round(d / r['lanes'], 4)!s:>8} "
              f"{round(r.get('enqueue_ms', 0), 4)!s:>10}  "
              f"{r.get('error') or r.get('device_ops', '')}")
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
