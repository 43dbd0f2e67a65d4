"""Candidate bodies of `groupby_sum`, timed on the chip (ISSUE 46).

`groupby_sum` takes the last level's prefixes `pre [p, S, W]`, the last
child's bank `[R, S, W]`, the summed field's plane bank and three int32
index vectors, and returns u32 `[planes, lanes]`: |prefix ∧ row ∧
not-null ∧ plane_j| for every group and plane, the last row |mask|. This
script times each candidate body at the SSB cells' own shapes (bank
`[1024, 16, 32768]` or `[256, …]`, 24 planes + not-null, `pre` of 8, 40
or 104 prefixes, 8 to 512 lanes; `--shards 15` is a mesh device's share)
two ways, in ONE process:

- `chain_ms`: the chain-slope method of `benches/benchenv.py`, the salt
  threaded into the INDEX vectors (`(si + salt) % R`, `(pi + salt) % p`:
  a launch's rows depend on the launch before, nothing can be hoisted,
  and no operand bank is rewritten — salting a 2 GiB bank additively
  would cost more than the body it times);
- `launch_ms`: N back-to-back launches, one `block_until_ready`.

Forms: `pr45` is the body the served path had until ISSUE 46 (the group
masks `[lanes, S, W]` written by `pick_rows`, then
`masked_row_counts_multi` four planes a pass: the yardstick); `lax_*`
plain `jax.lax` with vector partials, operand rows read by dynamic
slices of a TILE, the masks of a block of groups formed per tile and
`population_count(mask[:, None] & planes[None])` summed over the major
axis only; `pallas_*` the kernel of `pilosa_tpu/ops/groupsum.py` at other tiles
than the served one (`pallas` alone is the served parameters; group
blocks of 32 and 128 and two or four groups a grid step read as the
same number of grid steps does, PERF.md §6 PR 46, and were taken out). Every
form's counts are compared with `pr45`'s, and `pr45`'s with numpy on
three groups; a mismatch fails the run.

    python benches/groupsum_variants.py [--cases 8:8,128:40,512:104]
        [--rows 1024 --shards 16 --words 32768 --depth 24] [--only ...]
        [--distinct-rows 40]
    python benches/groupsum_variants.py --mesh 4 --shards 60
    python benches/groupsum_variants.py --describe v5e:2x2 [--mesh 4 ...]

TPU only: off a TPU it exits 1 (a CPU run of the kernel is interpreted
and is no number; `tests/test_groupsum_kernel.py` checks its results).
`--describe` compiles every form for a described chip, prints each
program's ops and temporaries, and runs nothing; it exits 1 when the
served kernel, at a device's shards a multiple of eight, is anything but
one custom call over the resident arrays (no row loop, no temporaries). With `--mesh N` the
banks are split along S over N chips as a server with `mesh_devices = N`
places them: the kernel under `shard_map` with one `psum`, `pr45` under
GSPMD as it was served. It loads the TPU library in this process: run it
alone, never from the tests.
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _variants(mesh=None):
    """name -> body(pre, pi, bank, si, plane_bank, sel, w) -> u32
    [planes, lanes] (`mesh`: a MeshContext, or None for one chip). The
    kernel is always the compiled one: this script runs on a TPU or
    compiles for a described one."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from pilosa_tpu.ops import groupsum
    from pilosa_tpu.ops.bitset import (masked_row_counts_multi, pick_rows,
                                       popcount)

    def pr45(pre, pi, bank, si, plane_bank, sel, w, k=4):
        depth = sel.shape[0] - 1
        planes = pick_rows(w, (plane_bank, sel))
        picks = [(bank, si)] + ([] if pre is None else [(pre, pi)])
        mask = pick_rows(w, *picks, fixed=(planes[-1],))
        rows = [masked_row_counts_multi(
            mask, *(planes[j] for j in range(i, min(i + k, depth))))
            for i in range(0, depth, k)]
        rows.append(popcount(mask, axis=(-2, -1))[None])
        return jnp.concatenate(rows)

    def lax_tiles(tile, gb):
        """Vector partials in plain lax: per tile of `tile` words the
        planes sliced once, per block of `gb` groups the masks formed
        from tile-sized dynamic slices, counts summed over the major
        axis into [gb, planes, 1024], lanes reduced once at the end."""
        def run(pre, pi, bank, si, plane_bank, sel, w):
            lanes, n_planes = si.shape[0], sel.shape[0]
            s = bank.shape[-2]
            tw, blk = min(tile, w), min(gb, lanes)
            v = min(1024, tw)

            def cut(arr, i, w0):
                return lax.dynamic_slice(arr, (i, 0, w0), (1, s, tw))[0]

            def tile_step(t, acc):
                w0 = t * tw
                pl_t = jnp.stack([cut(plane_bank, sel[j], w0)
                                  for j in range(n_planes)])
                pl_v = pl_t.reshape(n_planes, -1, v)

                def block_step(b, acc):
                    rows = []
                    for i in range(blk):
                        g = b * blk + i
                        r = cut(bank, si[g], w0) & pl_t[-1]
                        if pre is not None:
                            r = r & cut(pre, pi[g], w0)
                        rows.append(r)
                    mask = jnp.stack(rows).reshape(blk, -1, v)
                    cnt = jnp.sum(lax.population_count(
                        mask[:, None] & pl_v[None]).astype(jnp.uint32),
                        axis=2, dtype=jnp.uint32)
                    # the last "plane" is not-null itself: mask ∧ it = mask
                    at = (b * blk, 0, 0)
                    old = lax.dynamic_slice(acc, at, cnt.shape)
                    return lax.dynamic_update_slice(acc, old + cnt, at)
                return lax.fori_loop(0, lanes // blk, block_step, acc)
            acc = lax.fori_loop(
                0, w // tw, tile_step,
                jnp.zeros((lanes, n_planes, v), jnp.uint32))
            return jnp.sum(acc, axis=-1, dtype=jnp.uint32).T
        return run

    def pallas(**params):
        def run(pre, pi, bank, si, plane_bank, sel, w):
            if not params:      # as served
                return groupsum.group_plane_counts(
                    pre, pi, bank, si, plane_bank, sel, w,
                    mesh=mesh and mesh.mesh)
            if mesh is not None:
                raise ValueError("a tile or a rule of this script's own "
                                 "is timed on one chip")
            return groupsum._tile_counts(pre, pi, bank, si, plane_bank,
                                         sel, w=w, interpret=False,
                                         **params)
        return run

    out = {
        "pr45": pr45,
        "lax_t16384_g8": lax_tiles(16384, 8),
        "lax_t4096_g16": lax_tiles(4096, 16),
        "pallas": pallas(),
    }
    # Other tiles than the served 4,096 words (of 16 shards, 25 planes).
    for tile in (1024, 2048, 8192):
        out[f"pallas_t{tile}"] = pallas(tile=tile)
    # Where a device's shards are no multiple of eight the served rule
    # cuts a large bank to the launch's distinct rows; both sides of it.
    out["pallas_whole"] = pallas(compact=False)
    out["pallas_distinct"] = pallas(compact=True)

    def halves(counts):
        n = counts.shape[-1] // groupsum.LANES
        while n % 2 == 0:
            n //= 2
            counts = counts[:, :n * groupsum.LANES] \
                + counts[:, n * groupsum.LANES:]
        return functools.reduce(jnp.add, [
            counts[:, k:k + groupsum.LANES]
            for k in range(0, n * groupsum.LANES, groupsum.LANES)])

    def pallas_halves(*operands, **w):
        """The served kernel with a tile's register columns added by
        halves, not one after another: the same adds, a fifth of the
        operations to trace and lower at every start."""
        served, groupsum._fold_words = groupsum._fold_words, halves
        try:
            return pallas()(*operands, **w)
        finally:
            groupsum._fold_words = served
    out["pallas_halves"] = pallas_halves
    return out


def _selected(args, mesh):
    """The forms `--only` names, all without it."""
    variants = _variants(mesh)
    only = args.only.split(",") if args.only else variants
    return {k: v for k, v in variants.items() if k in only}


def _groups(rng, lanes, p, rows, distinct):
    """(pi, si) of `lanes` distinct groups of a p x rows grid in the
    level loop's order: prefix by prefix, a prefix's rows ascending.
    The rows are drawn from `distinct` of the bank's (0: from all): a
    served launch names few — the last child's rows that survived the
    pruning, 40 at most on the SSB cells."""
    import numpy as np
    pool = np.arange(rows) if not distinct else np.sort(
        rng.choice(rows, size=min(distinct, rows), replace=False))
    pairs = np.sort(rng.choice(p * len(pool), size=lanes, replace=False))
    return (pairs // len(pool)).astype(np.int32), \
        pool[pairs % len(pool)].astype(np.int32)


def _ops(hlo_text):
    """{op kind: count} of a compiled program's ENTRY computation."""
    entry = hlo_text[hlo_text.index("ENTRY"):]
    kinds = collections.Counter()
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (?:\(.*?\)|\S+) ([a-z\-]+)\(",
                     line)
        if m and m.group(1) not in ("parameter", "constant", "bitcast",
                                    "get-tuple-element", "tuple"):
            kinds[m.group(1)] += 1
        if line.startswith("}"):
            break
    return dict(kinds)


def _cases(args):
    return [tuple(int(x) for x in c.split(":"))
            for c in args.cases.split(",")]


def describe(args):
    """Compile every form at every case for a described topology; print
    each program's ops and temporaries. Nothing runs."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.describe)
    mesh = None
    if args.mesh > 1:
        from pilosa_tpu.parallel import MeshContext
        mesh = MeshContext(topo.devices[:args.mesh])
        bank_at, vec_at = mesh.bank_sharding(), mesh.replicated()
    else:
        bank_at = vec_at = SingleDeviceSharding(topo.devices[0])
    S, W, planes = args.shards, args.words, args.depth + 1

    def sds(shape, dtype, at):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=at)
    variants = _selected(args, mesh)
    ok = True
    for lanes, p in _cases(args):
        ops = (sds((p, S, W), jnp.uint32, bank_at),
               sds((lanes,), jnp.int32, vec_at),
               sds((args.rows, S, W), jnp.uint32, bank_at),
               sds((lanes,), jnp.int32, vec_at),
               sds((planes + 7, S, W), jnp.uint32, bank_at),
               sds((planes,), jnp.int32, vec_at))
        for name, body in variants.items():
            rec = {"variant": name, "lanes": lanes, "prefixes": p,
                   "shards": S, "mesh": args.mesh}
            try:
                t0 = time.perf_counter()
                c = jax.jit(functools.partial(body, w=W)).lower(
                    *ops).compile()
                rec["compile_s"] = round(time.perf_counter() - t0, 2)
                rec["ops"] = _ops(c.as_text())
                rec["temp_mb"] = round(
                    c.memory_analysis().temp_size_in_bytes / 2**20, 1)
                if name == "pallas" and S // args.mesh % 8 == 0 and (
                        rec["ops"].get("custom-call") != 1
                        or "while" in rec["ops"] or rec["temp_mb"] > 1):
                    # What the served body rests on: the kernel reads
                    # the resident arrays where they lie.
                    raise AssertionError(
                        f"the served kernel copies an operand or loops "
                        f"over rows: {rec['ops']}, {rec['temp_mb']} MiB")
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                ok = False
            print(json.dumps(rec), flush=True)
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024,
                    help="rows of the last child's bank")
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--words", type=int, default=32768)
    ap.add_argument("--depth", type=int, default=24)
    ap.add_argument("--cases", default="8:8,128:40,256:104,512:104",
                    help="lanes:prefixes, comma separated")
    ap.add_argument("--distinct-rows", type=int, default=0, metavar="N",
                    help="rows of the bank a launch's groups name "
                         "(0: any of them)")
    ap.add_argument("--only", default="")
    ap.add_argument("--launches", type=int, default=8)
    ap.add_argument("--mesh", type=int, default=1, metavar="N")
    ap.add_argument("--describe", default="", metavar="TOPOLOGY")
    ap.add_argument("--out", default="chiprun_out/groupsum_variants")
    args = ap.parse_args()
    if args.describe:
        return describe(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benches.benchenv import chain_slope_gbps, timed_fetch
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "devices": len(jax.devices())}), flush=True)
    if dev.platform != "tpu":
        print(f"first device is {dev.platform}, not a tpu", file=sys.stderr)
        return 1
    S, W, planes = args.shards, args.words, args.depth + 1
    R = args.rows
    mesh = None
    if args.mesh > 1:
        from pilosa_tpu.parallel import MeshContext
        mesh = MeshContext(jax.devices()[:args.mesh])
    bits = functools.partial(jax.random.bits, dtype=jnp.uint32)

    def draw(seed, n, dense):
        # ~50 % (planes), ~25 % (rows and prefixes); made on the device
        # (under a mesh: split along S as the server's banks are).
        def make():
            k = jax.random.PRNGKey(seed)
            a = bits(k, (n, S, W))
            return a if dense else a & bits(jax.random.fold_in(k, 1),
                                            (n, S, W))
        if mesh is None:
            return jax.jit(make)()
        return jax.jit(make, out_shardings=mesh.bank_sharding())()

    p_max = max(p for _, p in _cases(args))
    bank, plane_bank = draw(1, R, False), draw(2, planes + 7, True)
    pre_all = draw(3, p_max, False)
    # The planes' slots: not in bank order, not contiguous.
    sel_np = np.random.default_rng(46).permutation(planes + 7)[:planes] \
        .astype(np.int32)
    sel = jnp.asarray(sel_np)
    jax.block_until_ready((bank, plane_bank, pre_all))

    variants = _selected(args, mesh)
    os.makedirs(args.out, exist_ok=True)
    rows, ok = [], True
    for lanes, p in _cases(args):
        pre = pre_all[:p] if p < p_max else pre_all
        pi_np, si_np = _groups(np.random.default_rng(lanes + p), lanes, p, R,
                               args.distinct_rows)
        pi, si = jnp.asarray(pi_np), jnp.asarray(si_np)
        ref = None
        for name, body in variants.items():
            rec = {"variant": name, "lanes": lanes, "prefixes": p,
                   "shards": S, "rows": R, "mesh": args.mesh,
                   "distinct_rows": len(np.unique(si_np))}
            rows.append(rec)
            try:
                fn = jax.jit(functools.partial(body, w=W))
                t0 = time.perf_counter()
                got = np.asarray(fn(pre, pi, bank, si, plane_bank, sel))
                rec["first_call_s"] = round(time.perf_counter() - t0, 2)
                if ref is None:
                    ref = got
                    if name == "pr45":
                        for g in (0, lanes // 2, lanes - 1):
                            m = np.asarray(bank[si_np[g]]) \
                                & np.asarray(pre[pi_np[g]]) \
                                & np.asarray(plane_bank[sel_np[-1]])
                            want = [int(np.bitwise_count(
                                m & np.asarray(plane_bank[j])).sum())
                                for j in sel_np[:-1]]
                            want.append(int(np.bitwise_count(m).sum()))
                            if got[:, g].tolist() != want:
                                raise AssertionError(
                                    f"pr45 differs from numpy at group {g}")
                elif not np.array_equal(ref, got):
                    raise AssertionError(f"{name} differs from "
                                         f"{next(iter(variants))}")
                rec["exact"] = True

                def chain_impl(pre, pi, bank, si, plane_bank, sel, k):
                    def step(_, carry):
                        acc, salt = carry
                        out = body(pre, (pi + salt % p) % p, bank,
                                   (si + salt % R) % R, plane_bank, sel, W)
                        tot = jnp.sum(out, dtype=jnp.uint32)
                        return acc + tot, (tot ^ salt).astype(jnp.int32) \
                            & 0xFFFF
                    return jax.lax.fori_loop(
                        0, k, step, (jnp.uint32(0), jnp.int32(0)))[0]
                chain = jax.jit(chain_impl)
                r = chain_slope_gbps(
                    lambda k: timed_fetch(lambda: chain(
                        pre, pi, bank, si, plane_bank, sel, np.int32(k))),
                    1, ks=(2, 4, 8, 12), reps=3)
                rec["chain_ms"] = r["per_iter_s"] * 1e3

                def launches(n):
                    out = None
                    t0 = time.perf_counter()
                    for _ in range(n):
                        out = fn(pre, pi, bank, si, plane_bank, sel)
                    jax.block_until_ready(out)
                    return (time.perf_counter() - t0) / n * 1e3
                launches(2)
                rec["launch_ms"] = sorted(
                    launches(args.launches) for _ in range(3))[1]
            except Exception as e:  # a form the compiler refuses is a row
                rec["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                ok = False
            print(json.dumps(rec), flush=True)
    with open(os.path.join(args.out, "record.json"), "w") as f:
        json.dump({"platform": dev.platform, "device_kind": dev.device_kind,
                   "shape": [R, S, W], "planes": planes, "rows": rows},
                  f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
