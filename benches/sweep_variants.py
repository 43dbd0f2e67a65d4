"""Candidate bodies of the filtered TopN bank sweep, timed on the chip.

The served sweep (`Executor._counts_fn`) is one XLA fusion over a
`[R, S, W]` uint32 bank and an `[S, W]` filter. This script times each
candidate body at the benchmark cell's shape (`[1024, 16, 32768]`, 2 GiB)
three ways, in ONE process:

- `launch_ms`: N back-to-back launches of the jitted body (filters
  rotating, one `block_until_ready` at the end) on the host clock — what
  the server's queue of sweeps pays per sweep;
- `device_ms`: the body's XLA module duration in a profiler trace of those
  launches, with the names of the device ops inside it (the op name is
  what `topn_sweep_roofline` matches);
- `chain_ms`: the salted chain-slope method of `benches/benchenv.py` (the
  filter is salted, the bank is loop-invariant as in serving; the
  unfiltered body salts the bank, one more VPU add per word).

`bytes_accessed` is the compiled executable's `cost_analysis()`. Every
candidate's counts are compared with the first variant's on the device
and with numpy on a few rows; a mismatch fails the run.

A group's body (`ops/bitset.masked_row_counts_multi`, the program
`topn_sweep_multi`) takes K filters and is timed the same way, filters
rotating through its K operands; its rows also carry `device_ms_per_filter`,
and every lane is compared with the one-filter body's counts.

The run also fails when the SHIPPED bodies (`ops/bitset.masked_row_counts`
= `topn_sweep`, `popcount` over a row's shards and words =
`topn_sweep_unfiltered`, and `masked_row_counts_multi` at 2 and 4 filters
= `topn_sweep_multi`; every row carries its `program`) stop compiling to
what their rate rests on: one fusion reading the bank, named
`popcnt_reduce_fusion` (the op `topn_sweep_roofline` and
`tanimoto_sweep_roofline` match), whose windows — wherever the filter is
cut — are wide enough that the row reductions hide behind the bank's bytes
(`bank_fusions`, read from the compiled HLO; `_check_shipped`), and that
op once per launch in the device trace (twice where an odd lane is left
over). On a bank of narrow rows (one shard a row, one lane: `--rows
2097152 --shards 1 --words 128`, the chem cell's) the reductions bind
whatever the windows are; the uncut bodies are one fusion there all the
same, and `--only shipped,unfiltered` — a tanimoto answer's two programs —
exits 0 (a group's body is a fusion a filter there, and is printed
unchecked).

    python benches/sweep_variants.py [--rows 1024 --shards 16 --words 32768]
        [--launches 40] [--out chiprun_out/sweep_variants]
    python benches/sweep_variants.py --describe v5e:2x2 [--rows ...]
    python benches/sweep_variants.py --describe v5e:2x2 --mesh 4 --shards 64

TPU only unless --allow-cpu (a CPU run checks exactness at a small shape
and prints no rate). `--describe` compiles every body for a TPU that is
described, not attached (the TPU compiler comes with jax; nothing runs, no
chip time) and checks the shipped bodies' fusion: for a width the cells do
not hold, or after a jax / libtpu upgrade. With `--mesh N` the bank is split
over N of the described chips along its shard axis, as a server with
`mesh_devices = N` places it: each device's program must then be the
one-chip program at a device's share of the bank, plus ONE all-reduce of
the counts and no other collective. It loads the TPU library in
this process, which takes a machine-wide lock: run it alone, never from
the tests.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# The shipped body of a group of K filters (`topn_sweep_multi`), by K.
SHIPPED_MULTI = {f"shipped_multi{k}": k for k in (2, 4)}
# The bodies _check_shipped holds, by the program the executor jits them
# as.
CHECKED = {"shipped": "topn_sweep", "unfiltered": "topn_sweep_unfiltered",
           **dict.fromkeys(SHIPPED_MULTI, "topn_sweep_multi")}
N_FILTERS = 8


def _checked(name, n_words):
    """Whether _check_shipped holds body `name` at this width: the
    one-filter bodies always, a group's where its filters are cut. On
    rows under two lanes XLA gives a group one fusion a FILTER — K bank
    reads, which the row still prints and times (PERF.md §7 row 12)."""
    from pilosa_tpu.ops.bitset import sweep_filter_pieces
    return name in CHECKED and (name not in SHIPPED_MULTI
                                or sweep_filter_pieces(n_words) > 1)


def _n_filters(body):
    """How many filter operands a body takes: one, or the K of a
    multi-filter body (its `filters` attribute)."""
    return getattr(body, "filters", 1)


def _variants(R, S, W):
    """name -> body(c, f) or, with a `filters` attribute K, body(c, f0 ..
    f{K-1}) -> [K, R]; every body names the bank `c` (_bank_fusions finds
    the fusions that read it by that name)."""
    import jax.numpy as jnp
    from jax import lax
    from pilosa_tpu.ops.bitset import (masked_row_counts,
                                       masked_row_counts_multi, popcount,
                                       sweep_filter_pieces)

    def multi(k, body):
        def run(c, *fs):
            return body(c, *fs)
        run.filters = k
        return run

    def multi_pieces(p):           # K filters x p word pieces
        cuts = [slice(w0, w0 + W // p) for w0 in range(0, W, W // p)]

        def run(c, *fs):
            return jnp.stack([sum(popcount(c[..., w] & f[..., w],
                                           axis=(-2, -1)) for w in cuts)
                              for f in fs])
        return run

    def one_output(c, f):
        return popcount(c & f, axis=(-2, -1))

    def unfiltered(c, f):
        return popcount(c, axis=(-2, -1))

    def words_then_shards(c, f):   # barrier keeps XLA from merging the two
        per_shard = lax.optimization_barrier(popcount(c & f, axis=-1))
        return jnp.sum(per_shard, axis=-1, dtype=jnp.uint32)

    def shard_slabs(g):            # filter pieces along the SHARD axis
        def run(c, f):
            return sum(popcount(c[:, s0:s0 + g] & f[s0:s0 + g],
                                axis=(-2, -1)) for s0 in range(0, S, g))
        return run

    def word_pieces(k):            # ... along the word axis
        cuts = [slice(w0, w0 + W // k) for w0 in range(0, W, W // k)]

        def run(c, f):
            return sum(popcount(c[..., w] & f[..., w], axis=(-2, -1))
                       for w in cuts)
        return run

    out = {
        "one_output": one_output,
        "unfiltered": unfiltered,
        "shipped": lambda c, f: masked_row_counts(c, f),
        "words_then_shards": words_then_shards,
    }
    served = sweep_filter_pieces(W)     # "shipped" is this many pieces
    for k in (2, 4, 8):
        if W % (128 * k) == 0 and k != served:
            out[f"word_pieces{k}"] = word_pieces(k)
    if S % 8 == 0 and S > 8:
        out["shard_slabs8"] = shard_slabs(8)
    for name, k in SHIPPED_MULTI.items():
        out[name] = multi(k, masked_row_counts_multi)
    # Other piece counts than the rule's, and the eight lanes not built.
    for k, p in ((2, 4), (4, 1), (8, 1), (8, 2)):
        shipped = k in SHIPPED_MULTI.values() \
            and p == sweep_filter_pieces(W, k)
        if W % (128 * p) == 0 and not shipped:
            out[f"multi{k}_pieces{p}"] = multi(k, multi_pieces(p))
    return out


def _bank_fusions(hlo_text):
    """[[name, window bounds, iteration bounds]] of the fusions that read
    the bank (parameter `c`; the SPMD partitioner renames it and keeps
    the name in its metadata) in a compiled body's HLO."""
    out = []
    renamed = re.search(r'%(\S+) = \S+ parameter\(0\), sharding=[^\n]*'
                        r'op_name="c"', hlo_text)
    bank = re.escape(renamed.group(1)) if renamed else r"c\.\d+"
    for line in hlo_text.splitlines():
        m = re.search(rf"%(\S+) = .* fusion\(%{bank}[,)]", line)
        if m:
            out.append([m.group(1)] + [
                [int(x) for x in re.findall(r"\d+", b.group(1))] if b else []
                for b in (re.search(rf'"{key}":\[([^\]]*)\]', line)
                          for key in ("output_window_bounds",
                                      "iteration_bounds"))])
    return out


# Measured on a v5e (PERF.md §6, PR 25): every body costs ~30 ns per row,
# window step and output (a row's slice of the window reduced across lanes
# to one scalar), and an unfiltered sweep streams the bank at 755 GB/s.
ROW_STEP_NS = 30.0
SWEEP_GBPS = 755.0


def _check_shipped(name, fusions, shape):
    """What the shipped bodies' rate rests on: ONE pass over the bank
    (one fusion for the equal pieces, one more for an odd last lane),
    under the op name the benchmark's `topn_sweep_roofline` matches
    (`^popcnt_reduce_fusion$` after the trace reducer strips `.N`), and —
    where the filter is cut — windows wide enough that the row reductions
    (rows x steps over shards x steps over words x outputs x ROW_STEP_NS)
    take no longer than the bank's bytes at 0.7 of SWEEP_GBPS, ISSUE 25's
    bar. The uncut body's windows are the parent's, whatever they are.
    Returns the reductions' estimated ms."""
    from pilosa_tpu.ops.bitset import sweep_filter_pieces
    R, S, W = shape
    filters = SHIPPED_MULTI.get(name, 1)
    # The unfiltered body has no filter to cut: one piece, the whole row.
    pieces = 1 if name == "unfiltered" else sweep_filter_pieces(W, filters)
    expect = 2 if W // 128 % pieces else 1
    if len(fusions) != expect:
        raise AssertionError(f"{name}: {len(fusions)} fusions read the "
                             f"bank, not {expect}: {fusions}")
    for op, _window, _iters in fusions:
        if not re.fullmatch(r"popcnt_reduce_fusion(\.\d+)*", op):
            raise AssertionError(f"{name}: the bank's fusion is `{op}`")
    # The widest fusion is the equal pieces'; the other has one piece.
    widest = max(fusions, key=lambda f: f[1][2] * f[2][2])
    reduce_ms = sum(
        R * iters[1] * iters[2] * (pieces if f is widest else 1)
        * filters * ROW_STEP_NS / 1e6
        for f in fusions for iters in (f[2],))
    stream_ms = R * S * W * 4 / SWEEP_GBPS / 1e6
    if pieces > 1 and reduce_ms > stream_ms / 0.7:
        raise AssertionError(
            f"{name}: windows {[f[1:] for f in fusions]}: the row "
            f"reductions would take ~{reduce_ms:.2f} ms against "
            f"{stream_ms:.2f} ms of streaming")
    return reduce_ms


COLLECTIVE = re.compile(r"^\s*(?:ROOT )?%\S+ = .+? (all-reduce|all-gather|"
                        r"reduce-scatter|collective-permute|all-to-all)"
                        r"(?:-start)?\(")


def _collectives(hlo_text):
    """Names of the collective ops of a compiled SPMD body, in order (an
    async pair counts once, by its `-start`)."""
    return [m.group(1) for m in map(COLLECTIVE.search,
                                    hlo_text.splitlines()) if m]


def _device_times(trace_dir):
    """{module name: ([durations ms], {op names})} from the newest xplane."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}
    data = ProfileData.from_file(paths[-1])
    mods = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        ops = [(e.start_ns, e.name) for e in lines.get("XLA Ops", [])]
        for ev in lines.get("XLA Modules", []):
            name = ev.name.split("(", 1)[0]
            d, names = mods.setdefault(name, ([], {}))
            d.append(ev.duration_ns / 1e6)
            end = ev.start_ns + ev.duration_ns
            for s, n in ops:
                if ev.start_ns <= s < end:
                    n = n.strip().lstrip("%").split(" ", 1)[0]
                    names[n] = names.get(n, 0) + 1
        break
    return mods


def describe(args):
    """Compile every body for the first device of a described topology
    and print each one's bank fusions; nothing runs. rc 1 when a shipped
    body fails _check_shipped or the compiler refuses a body."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.describe)
    R, S, W = args.rows, args.shards, args.words
    if args.mesh > 1:
        from pilosa_tpu.parallel import MeshContext
        mesh = MeshContext(topo.devices[:args.mesh])
        bank_at, filt_at = mesh.bank_sharding(), mesh.row_sharding()
    else:
        bank_at = filt_at = SingleDeviceSharding(topo.devices[0])
    bank = jax.ShapeDtypeStruct((R, S, W), jnp.uint32, sharding=bank_at)
    filt = jax.ShapeDtypeStruct((S, W), jnp.uint32, sharding=filt_at)
    variants = _variants(R, S, W)
    if args.only:
        variants = {k: v for k, v in variants.items()
                    if k in args.only.split(",")}
    ok = True
    for name, body in variants.items():
        rec = {"variant": name, "shape": [R, S, W], "mesh": args.mesh}
        try:
            hlo = jax.jit(body).lower(
                bank, *[filt] * _n_filters(body)).compile().as_text()
            rec["bank_fusions"] = _bank_fusions(hlo)
            rec["collectives"] = _collectives(hlo)
            if name in CHECKED:
                rec["program"] = CHECKED[name]
            if _checked(name, W):
                rec["reduce_ms_estimate"] = _check_shipped(
                    name, rec["bank_fusions"], (R, S // args.mesh, W))
                # One all-reduce of the counts.
                got = rec["collectives"]
                if got != (["all-reduce"] if args.mesh > 1 else []):
                    raise AssertionError(
                        f"{name}: collectives {got}, not one all-reduce "
                        "and nothing else")
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {str(e)[:400]}"
            ok = False
        print(json.dumps(rec))
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--words", type=int, default=32768)
    ap.add_argument("--launches", type=int, default=40)
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="chiprun_out/sweep_variants")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--mesh", type=int, default=1, metavar="N",
                    help="with --describe: split the bank's shard axis over "
                         "N of the described chips")
    ap.add_argument("--describe", default="", metavar="TOPOLOGY",
                    help="compile for this described TPU (v5e:2x2), run "
                         "nothing")
    args = ap.parse_args()
    if args.describe:
        return describe(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benches.benchenv import (make_salted_chain, timed_fetch,
                                           validated_chain_slope)
    from pilosa_tpu.utils.jaxenv import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.allow_cpu:
        print(f"first device is {dev.platform}, not a tpu", file=sys.stderr)
        return 1
    R, S, W = args.rows, args.shards, args.words
    bank_bytes = R * S * W * 4
    os.makedirs(args.out, exist_ok=True)

    k_bank, k_filt = jax.random.split(jax.random.PRNGKey(25))
    bits = functools.partial(jax.random.bits, dtype=jnp.uint32)
    # ~25 % density, as an AND of two draws; the rate does not depend on it.
    bank = bits(k_bank, (R, S, W)) & bits(jax.random.fold_in(k_bank, 1),
                                          (R, S, W))
    filts = [bits(jax.random.fold_in(k_filt, i), (S, W))
             for i in range(N_FILTERS)]
    jax.block_until_ready((bank, filts))

    def operands(body, i=0):
        """The filter operands of launch `i` of `body`: K of them,
        rotating, so lane 0 of launch 0 is filts[0]'s."""
        return [filts[(i + j) % N_FILTERS] for j in range(_n_filters(body))]

    sample = [0, 1, R // 2, R - 1]
    host_rows = np.asarray(bank[np.asarray(sample)])
    host_f = np.asarray(filts[0])
    want_f = np.bitwise_count(host_rows & host_f).sum(axis=(1, 2))
    want_u = np.bitwise_count(host_rows).sum(axis=(1, 2))

    variants = _variants(R, S, W)
    jitted_ref = jax.jit(variants["one_output"])
    if args.only:
        variants = {k: v for k, v in variants.items()
                    if k in args.only.split(",")}
    rows, jitted, ref = [], {}, None
    for name, body in variants.items():
        rec = {"variant": name}
        rows.append(rec)
        try:
            body.__name__ = body.__qualname__ = f"sv_{name}"
            fn = jax.jit(body)
            t0 = time.perf_counter()
            compiled = fn.lower(bank, *operands(body)).compile()
            rec["compile_s"] = time.perf_counter() - t0
            cost = compiled.cost_analysis() or {}
            rec["bytes_accessed"] = cost.get("bytes accessed")
            if on_tpu:
                rec["bank_fusions"] = _bank_fusions(compiled.as_text())
                if name in CHECKED:
                    rec["program"] = CHECKED[name]
                if _checked(name, W):
                    # A shipped body that fails its check is timed all
                    # the same: on a bank of narrow rows the reading IS
                    # the finding (the exit code still says it failed).
                    try:
                        rec["reduce_ms_estimate"] = _check_shipped(
                            name, rec["bank_fusions"], (R, S, W))
                    except AssertionError as e:
                        rec["error"] = f"AssertionError: {str(e)[:400]}"
                        print(f"{name}: {rec['error']}", file=sys.stderr)
            out = fn(bank, *operands(body))
            if _n_filters(body) > 1:
                # Lane k is filter k's counts, bit for bit the one-filter
                # body's.
                lanes = np.asarray(out)
                for k, f in enumerate(operands(body)):
                    one = np.asarray(jitted_ref(bank, f))
                    if not np.array_equal(lanes[k], one):
                        raise AssertionError(f"{name}: lane {k} differs "
                                             "from one_output")
                got = lanes[0]
            else:
                got = np.asarray(out)
            want = want_u if name == "unfiltered" else want_f
            if got[sample].tolist() != want.tolist():
                raise AssertionError(f"{name}: {got[sample]} != {want}")
            if name != "unfiltered":
                if ref is None:
                    ref = got
                elif not np.array_equal(ref, got):
                    raise AssertionError(f"{name}: differs from "
                                         f"{rows[0]['variant']}")
            rec["exact"] = True
            jitted[name] = fn
        except Exception as e:  # a candidate the compiler refuses is a row
            rec["error"] = f"{type(e).__name__}: {str(e)[:400]}"
            print(f"{name}: {rec['error']}", file=sys.stderr)
    if not on_tpu:
        print(json.dumps({"platform": dev.platform, "rows": rows}))
        return 0 if all("error" not in r for r in rows) else 1

    def launches(name, n):
        fn, out = jitted[name], None
        # The operand lists are made before the clock starts.
        ops = [operands(variants[name], i) for i in range(N_FILTERS)]
        t0 = time.perf_counter()
        for i in range(n):
            out = fn(bank, *ops[i % N_FILTERS])
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    by_name = {r["variant"]: r for r in rows}
    for name in jitted:
        launches(name, 4)
        ts = sorted(launches(name, args.launches) for _ in range(3))
        by_name[name]["launch_ms"] = ts[1]
        by_name[name]["launch_ms_min"] = ts[0]

    trace_dir = os.path.join(args.out, "trace")
    with jax.profiler.trace(trace_dir):
        for name in jitted:
            launches(name, 8)
    for mod, (durs, names) in _device_times(trace_dir).items():
        rec = by_name.get(mod.replace("jit_sv_", "", 1))
        if rec is not None:
            rec["device_ms"] = float(np.median(durs))
            rec["device_ops"] = names
            sweeps = sum(n for op, n in names.items() if re.fullmatch(
                r"popcnt_reduce_fusion(\.\d+)*", op))
            if _checked(rec["variant"], W) and "error" not in rec and \
                    sweeps != len(durs) * len(rec["bank_fusions"]):
                rec["error"] = (f"{sweeps} popcnt_reduce_fusion ops in "
                                f"{len(durs)} launches: {names}")

    for name, fn in jitted.items():
        if _n_filters(variants[name]) > 1:
            continue    # the chain salts one filter: launches and the trace
        if name == "unfiltered":
            chain = make_salted_chain(
                lambda x, y, sx, sy: fn(x + sx, y))
        else:
            chain = make_salted_chain(
                lambda x, y, sx, sy: fn(x, y + sy))
        try:
            r = validated_chain_slope(
                lambda k: timed_fetch(lambda: chain(bank, filts[0], k)),
                bank_bytes, dev, ks=(4, 16, 36, 64))
            by_name[name]["chain_ms"] = r["per_iter_s"] * 1e3
            if r.get("invalid"):
                by_name[name]["chain_invalid"] = r["error"]
        except Exception as e:
            by_name[name]["chain_error"] = f"{type(e).__name__}: {e}"[:300]

    for rec in rows:
        for k in ("launch_ms", "device_ms", "chain_ms"):
            if k in rec:
                rec[k.replace("_ms", "_gbps")] = bank_bytes / rec[k] / 1e6
        filters = _n_filters(variants[rec["variant"]])
        if filters > 1 and "device_ms" in rec:
            rec["filters"] = filters
            rec["device_ms_per_filter"] = rec["device_ms"] / filters
    record = {"platform": dev.platform, "device_kind": dev.device_kind,
              "shape": [R, S, W], "bank_bytes": bank_bytes,
              "launches": args.launches, "rows": rows}
    with open(os.path.join(args.out, "record.json"), "w") as f:
        json.dump(record, f, indent=1)
    for rec in rows:
        print(json.dumps(rec))
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
