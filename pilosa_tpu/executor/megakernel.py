"""Executor leg of the heterogeneous megakernel (ops/megakernel.py).

FusionCollector.flush hands its signature groups here first: groups
whose staged evals lowered to megakernel IR are packed — across
DIFFERENT signatures — into one plan buffer and ONE compiled-program
launch per shard-count cohort; everything else (literal operands,
Shift, solo cohorts where the vmapped per-group program is already
optimal) flows back to the per-group fusion path untouched.

The launch stands UNDER the existing _FuseGroup plumbing: each taken
group's ``out`` becomes a _MegaView selecting its member lanes from
the launch's shared (counts, rows) outputs, so every FusedEval handle
already returned to result code resolves unchanged — one host fetch
per launch output, per-entry slices bit-identical to the unfused path
(tests/test_megakernel.py pins this op-by-op).

Mesh cohorts: when the executor carries a MeshContext the SAME plan
buffer dispatches once and runs SPMD over the mesh shard axis — banks
are already mesh-sharded (put_bank), plan buffers replicate, and the
collective epilogue (ops/megakernel.mesh_epilogue) finishes the
reduction in-kernel: count lanes psum to final ``[Nc]`` answers, row
lanes all-gather via replicated out_shardings. The jit-cache key gains
the mesh cache_key (device set + axis split change the partitioned
program), verify_plan runs with the MeshSpec (shard-axis agreement,
replica-axis no-op proof, collective lane typing), and d2h accounting
shrinks to the final answers — zero per-shard partials on the
Count/Sum reduce path.

Kill switch: PILOSA_TPU_MEGAKERNEL=0 restores per-group fusion
exactly. PILOSA_TPU_MESH=0 kills the mesh cohort path (per-group
fusion under the mesh, exactly the pre-mesh behavior).
PILOSA_TPU_MEGA_BYTES caps the launch's register-slab HBM footprint;
an over-budget cohort falls back rather than OOM.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pilosa_tpu.ops import megakernel as mk
from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.memledger import LEDGER
from pilosa_tpu.utils.profile import transfer
from pilosa_tpu.utils.timeline import TIMELINE

def _default_enabled() -> bool:
    """PILOSA_TPU_MEGAKERNEL: 1 forces on, 0 kills, default `auto` =
    on exactly when the backend is a TPU. The launch collapse pays
    where the per-launch floor is the bottleneck; on CPU an XLA launch
    costs ~20 µs while the interpreter's per-launch slab gather is
    real memcpy, so the per-group vmap path measured faster there
    (PR 11's CPU bench, since removed). `auto` asks the backend: a device
    that cannot initialise is an error here, not "feature off"."""
    flag = os.environ.get("PILOSA_TPU_MEGAKERNEL", "auto").strip().lower()
    if flag in ("1", "true", "yes", "on"):
        return True
    if flag in ("0", "false", "no", "off"):
        return False
    import jax
    return jax.devices()[0].platform == "tpu"


# Evaluated once at first flush-time import (banks exist by then, so
# the backend is initialized); tests and benches toggle the module
# attribute directly, exactly like executor.FUSION_ENABLED.
MEGAKERNEL_ENABLED = _default_enabled()

# Register-slab HBM budget per launch: the interpreter materializes
# [T_pad, S, W] uint32 registers (gathered operand rows + scratch); a
# cohort whose slab would exceed this runs per-group instead.
MEGA_MAX_BYTES = int(os.environ.get("PILOSA_TPU_MEGA_BYTES", 1 << 30))


def _default_mesh_enabled() -> bool:
    """PILOSA_TPU_MESH: the mesh cohort path runs by default whenever
    the executor carries a MeshContext; 0 is the blunt kill switch
    that restores the pre-mesh behavior (per-group fusion under the
    mesh) — the bit-exactness lever the check.sh mesh smoke and the
    64-thread burst test flip."""
    flag = os.environ.get("PILOSA_TPU_MESH", "on").strip().lower()
    return flag not in ("0", "false", "no", "off")


# Module attribute like MEGAKERNEL_ENABLED: tests/benches toggle it
# directly; the env var sets the process default.
MESH_ENABLED = _default_mesh_enabled()


def _default_verify_mode() -> str:
    """PILOSA_TPU_PLAN_VERIFY: `on` checks every plan before launch,
    `off` disables the gate, default `auto` checks the first launch of
    each jit-cache key (every fresh capacity bucket / bank composition
    is verified once; steady-state repeats of a proven shape skip the
    host pass). tests/conftest.py and tools/check.sh pin `on`."""
    flag = os.environ.get("PILOSA_TPU_PLAN_VERIFY", "auto").strip().lower()
    if flag in ("1", "true", "yes", "on"):
        return "on"
    if flag in ("0", "false", "no", "off"):
        return "off"
    return "auto"


# Module attribute like MEGAKERNEL_ENABLED: tests and tools toggle it
# directly; the env var sets the process default.
PLAN_VERIFY_MODE = _default_verify_mode()


def _default_opt_enabled() -> bool:
    """PILOSA_TPU_PLAN_OPT: the cost-based plan optimizer
    (ops/plan_opt.py — cross-request CSE, density-ordered folds, DCE +
    register compaction, width narrowing) runs over every finished
    plan by default; 0 is the blunt kill switch that launches the raw
    Lowering output instead. The `[optimizer]` config section
    (utils/config.py, wired in cli/main.py) can also disable it, but
    never re-enables past this env var."""
    flag = os.environ.get("PILOSA_TPU_PLAN_OPT", "on").strip().lower()
    return flag not in ("0", "false", "no", "off")


# Module attribute, toggled directly by tests/benches like
# MEGAKERNEL_ENABLED; the env var sets the process default.
PLAN_OPT_ENABLED = _default_opt_enabled()


class _MegaView:
    """One group's window onto a launch's shared outputs. Satisfies
    exactly the slice of the device-array surface _FuseGroup/FusedEval
    resolution touches: ``[b]`` for device_words, ``np.asarray`` for
    the one shared host fetch, ``copy_to_host_async`` for prefetch."""

    __slots__ = ("launch", "mode", "lanes", "width")

    def __init__(self, launch: "_MegaLaunch", mode: str,
                 lanes: List[int], width: int) -> None:
        self.launch = launch
        self.mode = mode
        self.lanes = lanes
        self.width = width

    def _dev(self) -> Any:
        out = self.launch.out
        return out[0] if self.mode == "count" else out[1]

    def __getitem__(self, b: int) -> Any:
        lane = self.lanes[b]
        if self.mode == "count":
            return self._dev()[lane]
        return self._dev()[lane, :, :self.width]

    def lane_nbytes(self, b: int) -> int:
        """Host bytes ONE member's finalize moves — the d2h accounting
        seam FusedEval.nbytes delegates to. Shape metadata only, never
        a device sync. Under a mesh epilogue a count lane is a single
        reduced uint32 (counts are [Nc], not [Nc, S]) — the
        zero-host-bytes-on-the-reduce-path number the profiler's d2h
        assertion reads."""
        arr = self._dev()
        if self.mode == "count":
            return int(arr.nbytes) // max(1, int(arr.shape[0]))
        return int(arr.shape[-2]) * int(self.width) * 4

    # graftlint: materialize — the FusedEval.host convention: the
    # launch output fetches ONCE (cached on the launch) and every
    # group view slices the shared host copy.
    def __array__(self, dtype: Any = None, copy: Any = None) -> np.ndarray:
        host = self.launch.host(self.mode)
        out = host[self.lanes]
        if self.mode != "count":
            out = out[:, :, :self.width]
        return np.asarray(out, dtype=dtype) if dtype is not None else out

    def copy_to_host_async(self) -> None:
        fn = getattr(self._dev(), "copy_to_host_async", None)
        if fn is not None:
            fn()


class _MegaLaunch:
    """One dispatched plan-buffer program and its shared outputs."""

    __slots__ = ("out", "_host_counts", "_host_rows", "__weakref__")

    def __init__(self, out: Tuple[Any, Any]) -> None:
        self.out = out
        self._host_counts: Optional[np.ndarray] = None
        self._host_rows: Optional[np.ndarray] = None

    # graftlint: materialize — shared device->host boundary for the
    # whole launch (see _MegaView.__array__).
    def host(self, mode: str) -> np.ndarray:
        if mode == "count":
            if self._host_counts is None:
                self._host_counts = np.asarray(self.out[0])
            return self._host_counts
        if self._host_rows is None:
            self._host_rows = np.asarray(self.out[1])
        return self._host_rows


def _eligible(group: Any) -> bool:
    rep = group.entries[0]
    return rep.ir is not None and rep.mode in ("count", "row")


def run_megakernel(executor: Any, groups: Dict[tuple, Any]
                   ) -> Dict[tuple, Any]:
    """Take what lowers, launch one program per shard-count cohort,
    return the groups the caller must still run per-group. Build
    failures fall back silently (results must never depend on the
    megakernel); failures after dispatch surface per member exactly
    like _FuseGroup errors."""
    if not MEGAKERNEL_ENABLED or (executor.mesh is not None
                                  and not MESH_ENABLED):
        return groups
    cohorts: Dict[int, List[Any]] = {}
    remaining: Dict[tuple, Any] = {}
    for key, group in groups.items():
        if group.entries and _eligible(group):
            cohorts.setdefault(group.entries[0].n_shards, []).append(group)
        else:
            remaining[key] = group
    for n_shards, cohort in cohorts.items():
        # A single-signature cohort already runs as one (vmapped)
        # launch — the interpreter buys nothing and loses the lane
        # parallelism, so only heterogeneous cohorts take this path.
        if len(cohort) < 2:
            for g in cohort:
                remaining[("solo", id(g))] = g
            continue
        try:
            plan, w_mega, lanes = _build(cohort)
        except Exception:
            # Lowering is best-effort by contract: any surprise means
            # the per-group path answers instead.
            for g in cohort:
                remaining[("fallback", id(g))] = g
            continue
        if mk.slab_nbytes(plan.n_regs, n_shards, w_mega) > MEGA_MAX_BYTES:
            for g in cohort:
                remaining[("budget", id(g))] = g
            continue
        _launch(executor, cohort, plan, n_shards, w_mega, lanes)
    return remaining


def _build(cohort: List[Any]) -> Tuple[mk.Plan, int, List[List[int]]]:
    """Lower every entry of every group into one plan; returns the
    plan, the launch word width, and per-group member lanes. The plan
    optimizer runs HERE — inside the build, before the verify gate —
    so every downstream consumer (the _launch verifier, the plan_fuzz
    capture hook, the telemetry) sees exactly the plan that will
    dispatch."""
    w_mega = max(e.width for g in cohort for e in g.entries)
    with TIMELINE.stage("plan.lower") as sp:
        low = mk.Lowering()
        lanes: List[List[int]] = []
        for g in cohort:
            g_lanes = []
            for e in g.entries:
                g_lanes.append(low.add_entry(e.ir, e.bank_arrays, e.idxs,
                                             e.params, e.width, e.mode))
            lanes.append(g_lanes)
        plan = low.finish()
        sp.set("entries", plan.n_instrs)
    if PLAN_OPT_ENABLED:
        with TIMELINE.stage("plan.optimise") as sp:
            try:
                from pilosa_tpu.ops import plan_opt
                plan, _stats = plan_opt.optimize_plan(
                    plan, cohort[0].entries[0].n_shards, w_mega)
                sp.set("entries", plan.n_instrs)
            except Exception:
                # Best-effort by contract: a surprised optimizer means
                # the raw Lowering plan launches, never a failed
                # request.
                pass
    return plan, w_mega, lanes


def _launch(executor: Any, cohort: List[Any], plan: mk.Plan,
            n_shards: int, w_mega: int,
            lanes: List[List[int]]) -> None:
    import jax
    import jax.numpy as jnp

    ex = executor
    n_entries = sum(len(g.entries) for g in cohort)
    mesh = getattr(ex, "mesh", None)
    epi = spec = None
    try:
        key = plan.sig(n_shards, w_mega)
        if mesh is not None:
            # Mesh cohort: one plan buffer, every device slice. The
            # epilogue types one collective per real output lane and
            # the jit-cache key gains the mesh identity (device set /
            # axis split change the partitioned program) plus an
            # epilogue marker (the mesh program returns [Nc] counts,
            # not [Nc, S]).
            epi = mk.mesh_epilogue(plan, mesh.SHARD_AXIS)
            spec = mk.MeshSpec(mesh.SHARD_AXIS, mesh.REPLICA_AXIS,
                               mesh.n_shard_devices, mesh.replicas,
                               epi)
            key = f"{key}|{mesh.cache_key()}|epi"
        fn = ex._jit_get(key)
        jit_hit = fn is not None
        # Plan-IR verification gate: the checked-IR contract
        # (ops/megakernel.verify_plan) runs BEFORE anything is
        # uploaded or dispatched. `on` = every launch, `auto` = the
        # first launch per jit-cache key (a fresh compiled shape's
        # first plan is always checked). A reject raises here — it is
        # caught below and lands on the cohort's groups per member, so
        # a lowering bug surfaces as request errors, never as wrong
        # bits on device.
        if PLAN_VERIFY_MODE == "on" or (PLAN_VERIFY_MODE == "auto"
                                        and not jit_hit):
            with TIMELINE.stage("plan.verify"):
                try:
                    mk.verify_plan(plan, n_shards, w_mega, mesh=spec)
                except mk.PlanVerifyError:
                    ex._note_plan_verify(False)
                    raise
                ex._note_plan_verify(True)
        if fn is None:
            ex._note_jit_compile("mega_plan", key)
            if mesh is not None:
                # GSPMD partitions the interpreter over the mesh-
                # sharded banks; the epilogue's count-lane sum over
                # the shard axis lowers to the psum, and replicated
                # out_shardings inserts the row lanes' all_gather.
                fn = jax.jit(
                    mk.build_program(n_shards, w_mega, plan.n_regs,
                                     epilogue=epi),
                    out_shardings=(mesh.replicated(),
                                   mesh.replicated()))
            else:
                fn = jax.jit(mk.build_program(n_shards, w_mega,
                                              plan.n_regs))
            ex._jit_put(key, fn)
        # Plan buffers are per-launch data (the whole point: new mixed
        # composition, same compiled program) — upload them now and
        # charge the bytes as this launch's plan-buffer H2D. Sparse
        # banks (plan.xbanks) are already device-resident pairs; only
        # their slot lists upload. Under a mesh they land REPLICATED
        # (every device reads the same instruction stream) — a bare
        # asarray would commit them to one device and fight the
        # sharded banks inside the partitioned program.
        if mesh is None:
            _put = jnp.asarray
        else:
            def _put(a: Any) -> Any:
                return jax.device_put(np.asarray(a), mesh.replicated())
        plan_bytes = plan.plan_nbytes
        with transfer("h2d", plan_bytes,
                      4 + len(plan.slots) + len(plan.xslots)):
            slots_dev = tuple(_put(s) for s in plan.slots)
            widths_dev = _put(plan.widths)
            instrs_dev = _put(plan.instrs)
            out_count_dev = _put(plan.out_count)
            out_row_dev = _put(plan.out_row)
            xslots_dev = tuple(_put(s) for s in plan.xslots)
        with ex._dispatch_span("mega_plan") as ds:
            ds.set("entries", n_entries)
            out = ex._call_program(fn, plan.banks, slots_dev, widths_dev,
                                   instrs_dev, out_count_dev, out_row_dev,
                                   plan.xbanks, xslots_dev)
        dispatch_s = ds.duration()
    except Exception as e:
        for g in cohort:
            g.error = e
            g.entries, g.profs, g.nodes = [], [], []
        return
    launch = _MegaLaunch(out)
    # Launch cost: price the verified IR's HBM traffic in host numpy —
    # microseconds, no fences, and best-effort by contract: a surprised
    # cost model must never fail a request that already has its results
    # in flight.
    try:
        cost = mk.plan_cost(plan, n_shards, w_mega, mesh=spec)
    except Exception:
        cost = None
    try:
        for g, g_lanes in zip(cohort, lanes):
            rep = g.entries[0]
            g.out = _MegaView(launch, rep.mode, g_lanes, rep.width)
            g.batched = True
        # Ledger the launch's device residents: live bytes are the real
        # lanes' outputs; padding is the pow2 capacity slack in the slab,
        # instruction buffer and output lanes. Keyed on the launch object,
        # unregistered when the last member's response drops it.
        # Under a mesh epilogue a count lane's output is ONE reduced
        # uint32, not an [S] partial vector — the ledger's live bytes
        # track what the launch actually keeps resident.
        lane_bytes = sum(
            int(np.prod((1,) if mesh is not None
                        else (e.n_shards,)) if e.mode == "count"
                else np.prod((e.n_shards, e.width))) * 4
            for g in cohort for e in g.entries)
        slab = mk.slab_nbytes(plan.n_regs, n_shards, w_mega)
        live_slab = mk.slab_nbytes(plan.n_slots + plan.n_xslots,
                                   n_shards, w_mega)
        LEDGER.track(launch, "fusion_pad", lane_bytes,
                     padded_bytes=(slab - live_slab) + plan_bytes,
                     batch=n_entries, groups=len(cohort),
                     planEntries=plan.n_instrs)
        ex._note_mega(n_entries, plan.n_instrs, plan_bytes)
        if spec is not None:
            ex._note_mesh(spec.n_devices,
                          cost.get("collectiveBytes", 0)
                          if cost is not None else 0)
        if cost is not None:
            ex._note_launch_cost(cost)
        if plan.opt_stats is not None:
            ex._note_opt(plan.opt_stats)
        _attribute(ex, cohort, launch, jit_hit, dispatch_s, plan,
                   plan_bytes, n_entries, cost)
    except Exception as e:
        # Per-member error isolation, the _FuseGroup.run contract: an
        # async device failure surfacing here (e.g. the ?profile=true
        # _fence_device inside _attribute) lands on THIS cohort's
        # groups — FusedEval._out checks `error` before `out`, so the
        # already-assigned views never serve — and batchmates in other
        # cohorts/groups are unharmed.
        for g in cohort:
            g.error = e
    finally:
        for g in cohort:
            g.entries, g.profs, g.nodes = [], [], []


def _attribute(ex: Any, cohort: List[Any], launch: _MegaLaunch,
               jit_hit: bool, dispatch_s: float, plan: mk.Plan,
               plan_bytes: int, n_entries: int,
               cost: Optional[Dict[str, Any]] = None) -> None:
    """Profile attribution, the _FuseGroup._attribute
    convention: the program ran once for the whole launch, so every
    member sees the shared dispatch (and, under ?profile=true, the
    fenced wait) labeled with its launch coordinates."""
    fence_profs: List[Tuple[Any, Any]] = []
    opt = plan.opt_stats
    mega_index = 0
    for g in cohort:
        for prof, node in zip(g.profs, g.nodes):
            b = mega_index
            mega_index += 1
            if prof is None or node is None:
                continue
            prof.tree_jit(node, jit_hit)
            prof.tree_h2d(node, plan_bytes // max(1, n_entries))
            prof.tree_dispatch(node, dispatch_s)
            node.attrs["megaBatch"] = n_entries
            node.attrs["megaIndex"] = b
            node.attrs["planEntries"] = plan.n_instrs
            node.attrs["planBytes"] = plan_bytes
            if cost is not None:
                # The cost vector rides the slow-query ring: a
                # post-mortem profile shows what the launch MOVED, not
                # just how long it took.
                node.attrs["launchBytes"] = cost["totalBytes"]
                node.attrs["opcodeHist"] = dict(cost["opcodeHist"])
                if "collectiveBytes" in cost:
                    # Mesh launch: which mesh carried it and what the
                    # collectives moved over ICI — the per-chip HBM
                    # share is deviceBytes in the same vector.
                    node.attrs["meshDevices"] = cost["meshDevices"]
                    node.attrs["collectiveBytes"] = \
                        cost["collectiveBytes"]
            if opt is not None:
                # The optimizer's before/after so a profile reader can
                # attribute the reduction without the /metrics deltas.
                node.attrs["planEntriesBefore"] = opt.entries_before
                node.attrs["planEntriesAfter"] = opt.entries_after
            prof.set_fused(n_entries)
            if prof.sample_device:
                fence_profs.append((prof, node))
    device_s = 0.0
    if fence_profs:
        from pilosa_tpu.executor.executor import _fence_device
        with TIMELINE.stage("device", megaBatch=n_entries):
            device_s = _fence_device(launch.out)
        for prof, node in fence_profs:
            prof.tree_device(node, device_s)
    # Cache-opportunity attribution AFTER the (?profile=true) fence — the
    # per-entry share of one launch, same cost basis as the fused and
    # unfused paths.
    per_eval = (dispatch_s + device_s) / max(1, n_entries)
    for g in cohort:
        for e in g.entries:
            if e.fp is not None:
                WORKLOAD.note_eval_seconds(e.fp, per_eval)
