"""Same-signature query fusion: one XLA dispatch for N batched queries.

The dominant serving shape is a flood of structurally identical
1-ms-class queries — ``Count(Row(user=X))`` for a million different X.
The coalescer (server/coalescer.py) already lands them in one
``Executor.execute_batch``, and read-dedup collapses *equal* queries,
but each remaining *similar* query still paid its own host dispatch:
plan + ``fn(...)`` enqueue, which the PR 3 profiler shows dwarfing the
fenced device time for small trees. The roaring line of work (Chambi
et al., arXiv:1402.6407) wins by amortizing per-op overhead across
batched bitmap operations; this module is the dispatch-level analog.

A compiled tree program is fully parameterized by its traced operand
vectors (``idxs``, ``params``) under a shape signature ``sig``
(Executor._stage_tree); an eval that also carries literal operands
(``lits``, a time range past MAX_STATIC_RANGE_VIEWS) never enters a
group. So N staged evals with the same
``(sig, bank identity)`` — same tree shape over the same device banks,
different row ids / BSI predicates — can stack their
operand vectors along a new leading batch axis and run through ONE
jitted ``vmap`` of the representative's program, returning ``[B, S]``
counts or ``[B, S, W]`` row words that finalize slices per query.
Bitwise ops and popcounts are deterministic elementwise/reduce
kernels, so per-query results are bit-identical to the unfused path.

Batch sizes pad up to a power of two (repeating the first entry's
operands) so the compile cache holds O(log B) fused variants per
signature instead of one per batch size; the pad lanes are sliced off
before any result is read.

Write fencing is the collector's caller's job: ``execute_batch``
flushes the collector before dispatching any write-containing request
and dispatches that request uncollected, so no read fuses across a
write that orders between them (tests/test_fusion.py pins this).

The same collector holds the batch's filtered TopN calls, in two kinds
of group that launch in turn.

A TopN's filter tree is a staged eval like any other, and its only
reader is the sweep the collector already holds back. So it waits too
(``add_filter``): the filters of one begin half that share a signature
and the width their sweeps want form a ``_FilterGroup``, and the group
launches ONE ``tree_row_multi`` program for up to ``FILTER_GROUP_MAX``
of them, from ONE operand upload (the members' ``idxs`` and ``params``,
a row a lane). The program is the representative's tree as one lane
body, called a lane: lane ``b`` reads row ``b`` of the operands, the
view banks the group's members share (ONE operand each) and its own
member's arrays where two members may differ (a time range's day views,
a row-subset bank), so a leaf's read by a slot is a dynamic slice (a
``vmap`` would make it a gather over the bank) and members over
different days still share a launch. Its lanes
come out as separate ``[S, W]`` arrays at the sweep's width. A group of
one runs ``tree_row`` as a call outside a batch does. These groups
never enter ``run_megakernel``: they are not in ``self.groups``.

The sweeps (``add_sweep``) are not a ``_FuseGroup`` at all: a sweep
group's members each bring a filter of their own, a lane of whatever
filter group made it, and share only the bank. The group's one program
takes the bank and K filter operands and reads the bank once
(``ops/bitset.masked_row_counts_multi``). A sweep lane holds a handle
to its filter's lane and no device op touches one in between: a sweep
group launches after the filter groups its lanes come from, at
``flush()`` or, when it is full and they are all in flight, at once
(tests/test_sweep_groups.py, tests/test_filter_groups.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.memledger import LEDGER
from pilosa_tpu.utils.timeline import TIMELINE


class FusedEval:
    """One query's slice of a fusion group's output. Stands in for the
    device array ``_eval_tree`` would have returned: ``np.asarray``
    resolves it (sharing ONE device->host fetch across the whole
    group), ``copy_to_host_async``/``nbytes`` make it a valid
    ``_Pending.arrays`` entry, and ``device_words()`` hands consumers
    that want to stay on device the sliced jax array."""

    __slots__ = ("group", "b", "shape", "slice_nbytes")

    def __init__(self, group: "_FuseGroup", b: int,
                 shape: Tuple[int, ...]) -> None:
        self.group = group
        self.b = b
        self.shape = shape  # per-query output shape ([S] or [S, W])
        self.slice_nbytes = int(np.prod(shape)) * 4

    @property
    def nbytes(self) -> int:
        # A megakernel _MegaView knows the REAL per-lane host bytes —
        # under a mesh epilogue a count lane is one reduced uint32,
        # not the [S] partial vector the stage-time shape assumed.
        # Asking the resolved output keeps the profiler's d2h
        # accounting honest without this handle knowing launch kinds.
        out = self.group.out
        fn = getattr(out, "lane_nbytes", None)
        if fn is not None:
            return int(fn(self.b))
        return self.slice_nbytes

    def _out(self) -> Any:
        g = self.group
        if g.error is not None:
            raise g.error
        if g.out is None:
            # Resolution before the batch's flush point means a staged
            # eval leaked outside execute_batch's dispatch/flush
            # bracket — run the group now rather than deadlock.
            g.run()
            if g.error is not None:
                raise g.error
        return g.out

    def device_words(self) -> Any:
        """This query's output as a device array (one slice op)."""
        out = self._out()
        return out[self.b] if self.group.batched else out

    # graftlint: materialize — FusedEval.host IS the device->host
    # boundary for fused results: the group's [B, ...] output fetches
    # once and every member slices the cached host copy.
    def host(self) -> np.ndarray:
        g = self.group
        out = self._out()
        if g.host is None:
            g.host = np.asarray(out)
        return g.host[self.b] if g.batched else g.host

    def __array__(self, dtype: Any = None, copy: Any = None) -> np.ndarray:
        a = self.host()
        return np.asarray(a, dtype=dtype) if dtype is not None else a

    def copy_to_host_async(self) -> None:
        """Start the group's (single, shared) async device->host copy
        (prefetch_pendings calls this per _Pending array)."""
        fn = getattr(self._out(), "copy_to_host_async", None)
        if fn is not None:
            fn()


class _FuseGroup:
    """All staged evals sharing one (sig, bank identity) key, plus the
    profiling contexts captured when each was staged."""

    __slots__ = ("executor", "entries", "profs", "nodes", "out", "host",
                 "batched", "error", "__weakref__")

    def __init__(self, executor: Any) -> None:
        self.executor = executor
        self.entries: List[Any] = []      # _StagedEval, batch order
        self.profs: List[Any] = []        # QueryProfile or None
        self.nodes: List[Any] = []        # ProfileNode or None
        self.out = None                   # [B, ...] (or [...] solo)
        self.host: Optional[np.ndarray] = None
        self.batched = False
        self.error: Optional[Exception] = None

    def add(self, staged: Any, prof: Any, plan_s: float) -> FusedEval:
        node = None
        if prof is not None:
            # jit hit/miss is unknown until the group compiles at
            # flush; tree_jit fills it in then. The stacked operand
            # upload is likewise charged at flush via tree_h2d.
            node = prof.tree(staged.mode, staged.sig, None, plan_s, 0,
                             staged.n_shards)
        b = len(self.entries)
        self.entries.append(staged)
        self.profs.append(prof)
        self.nodes.append(node)
        shape = ((staged.n_shards,) if staged.mode == "count"
                 else (staged.n_shards, staged.width))
        return FusedEval(self, b, shape)

    def run(self) -> None:
        """Compile (cached) + dispatch the group's single program and
        attribute it back to every member's profile. Never raises: a
        failure lands on `error` and surfaces per member when its
        request finalizes — batchmates in other groups are unharmed."""
        if self.out is not None or self.error is not None:
            return
        try:
            self._run()
        except Exception as e:
            self.error = e
        finally:
            # Resolution needs only out/host/batched/error, but every
            # result holds FusedEval -> group until its response is
            # shaped — drop the staged closure graph (exprs capture
            # plan objects and bank arrays) as soon as the program is
            # in flight.
            self.entries = []
            self.profs = []
            self.nodes = []

    def _run(self) -> None:
        import jax
        import jax.numpy as jnp

        from pilosa_tpu.executor.executor import named

        ex = self.executor
        B = len(self.entries)
        rep = self.entries[0]
        if B == 1:
            # Solo group: the exact unfused path (same program, same
            # arg cache) so a lone query costs nothing extra.
            fn, jit_hit = ex._tree_fn(rep)
            idxs, params, uploaded = ex._staged_args(rep)
            h2d = (idxs.nbytes + params.nbytes) if uploaded else 0
            with ex._dispatch_span(rep.program) as ds:
                self.out = ex._call_program(fn, rep.bank_arrays, idxs,
                                            params, None)
            self._attribute(jit_hit, ds.duration(), h2d, fused=False)
            return
        # Pad to the next power of two with the first entry's operands
        # so distinct batch sizes share O(log B) compiled variants.
        bp = 1 << (B - 1).bit_length()
        rows = self.entries + [rep] * (bp - B)
        key = f"fused{bp}|{rep.sig}"

        def build():
            # graftlint: disable=GL003 — host-list marshalling for the
            # stacked operand upload (the device transfer is
            # jnp.asarray).
            i = jnp.asarray(np.asarray([e.idxs for e in rows],
                                       np.int32))
            # graftlint: disable=GL003 — host-list upload, as above.
            p = jnp.asarray(np.asarray([e.params for e in rows],
                                       np.uint32))
            return i, p

        # Repeated batch compositions (dashboards, hot row sets) hit
        # the same LRU arg cache the solo path uses and skip both
        # stacked uploads.
        akey = (key, tuple(tuple(e.idxs) for e in rows),
                tuple(tuple(e.params) for e in rows))
        (idxs, params), uploaded = ex._cached_args(akey, build)
        fn = ex._jit_get(key)
        jit_hit = fn is not None
        program = "fused_" + rep.program
        if fn is None:
            ex._note_jit_compile(program, key)
            in_axes = (None, 0, 0, None)
            fn = jax.jit(named(jax.vmap(rep.runner(), in_axes=in_axes),
                               program))
            ex._jit_put(key, fn)
        with ex._dispatch_span(program) as ds:
            ds.set("fusedBatch", B)
            out = ex._call_program(fn, rep.bank_arrays, idxs, params,
                                   None)
        dispatch_s = ds.duration()
        if bp != B:
            out = out[:B]  # drop pad lanes before anything reads them
        self.out = out
        self.batched = True
        # Ledger the group's device output: B live lanes plus the
        # pow2 pad lanes (output + stacked operands) as padding bytes.
        # Keyed on the group object, so the entry unregisters when the
        # last member's response is shaped and the group is collected.
        lane = (int(np.prod((rep.n_shards,) if rep.mode == "count"
                            else (rep.n_shards, rep.width))) * 4)
        pad = (bp - B) * lane \
            + (idxs.nbytes + params.nbytes) * (bp - B) // bp
        LEDGER.track(self, "fusion_pad", B * lane, padded_bytes=pad,
                     batch=B, padTo=bp, sig=str(rep.sig)[:120])
        ex._note_fused(B)
        # Whole stacked upload (pad lanes included) spread over the B
        # real members, so the per-query sum equals the real traffic.
        h2d = (idxs.nbytes + params.nbytes) // B if uploaded else 0
        self._attribute(jit_hit, dispatch_s, h2d, fused=True)

    def _attribute(self, jit_hit: bool, dispatch_s: float, h2d: int,
                   fused: bool) -> None:
        B = len(self.entries)
        fence_profs = []
        for b, (prof, node) in enumerate(zip(self.profs, self.nodes)):
            if prof is None or node is None:
                continue
            prof.tree_jit(node, jit_hit)
            prof.tree_h2d(node, h2d)
            # The program ran once for the whole group: every member
            # sees the group's dispatch time, labeled with its batch
            # coordinates so readers know the cost is shared.
            prof.tree_dispatch(node, dispatch_s)
            if fused:
                node.attrs["fusedBatch"] = B
                node.attrs["batchIndex"] = b
                prof.set_fused(B)
            if prof.sample_device:
                fence_profs.append((prof, node))
        device_s = 0.0
        if fence_profs:
            from pilosa_tpu.executor.executor import _fence_device
            with TIMELINE.stage("device"):
                device_s = _fence_device(self.out)
            for prof, node in fence_profs:
                prof.tree_device(node, device_s)
        # Cache-opportunity attribution AFTER the (?profile=true) fence so
        # fused evals report the same dispatch + device cost basis as
        # the unfused path (_run_staged) — one fused dispatch covered
        # B queries, so each member's eval cost its share.
        per_eval = (dispatch_s + device_s) / max(1, B)
        for e in self.entries:
            if e.fp is not None:
                WORKLOAD.note_eval_seconds(e.fp, per_eval)


# A filter group launches as soon as it holds FILTER_GROUP_MAX members,
# and at flush(). Its program exists in these lane counts; a group of
# one runs the solo tree program, as a call outside a batch does.
FILTER_LANES = (2, 4, 8)
FILTER_GROUP_MAX = FILTER_LANES[-1]


class _FilterGroup(_FuseGroup):
    """The staged TopN filter trees of one begin half that share a
    signature and the word width their sweeps want. `out` is a tuple
    of `[S, W]` arrays, a lane each, so a member's `device_words()` is
    a tuple index on the host; alone, it is the solo program's array.
    Every member holds the bank arrays it was staged against: a later
    write in the batch, which builds new ones, changes nothing it
    reads (and members staged after it form another group: the key
    holds the shared arrays' identity)."""

    __slots__ = ("width",)

    def __init__(self, executor: Any, width: int) -> None:
        super().__init__(executor)
        self.width = width                # the sweep's word width

    @property
    def launched(self) -> bool:
        return self.out is not None or self.error is not None

    def _run(self) -> None:
        from pilosa_tpu.executor.executor import upload

        ex = self.executor
        n = len(self.entries)
        rep = self.entries[0]
        lanes = 1 if n == 1 else next(k for k in FILTER_LANES if k >= n)
        fn, jit_hit = ex._filter_group_fn(rep, lanes, self.width)
        if n == 1:
            # The exact unfused path (same program, same arg cache).
            super()._run()
            ex._note_filter_launch(1, 1)
            return
        # The last member fills the pad lanes again; nobody reads them.
        rows = self.entries + self.entries[-1:] * (lanes - n)
        # One row a lane: its slots, then its u32 scalars.
        # graftlint: disable=GL003 — host lists marshalled for the one
        # stacked upload; nothing is fetched.
        ops = np.asarray([[*e.idxs, *e.params] for e in rows], np.uint32)
        akey = (f"filters{lanes}|{self.width}|{rep.sig}", ops.tobytes())
        ops_dev, uploaded = ex._cached_args(akey, lambda: upload(ops))
        with ex._dispatch_span("tree_row_multi") as ds:
            ds.set("filters", n)
            ds.set("lanes", lanes)
            self.out = ex._call_program(
                fn, rep.shared_banks,
                tuple(e.owned_banks for e in rows), ops_dev)
        self.batched = True
        ex._note_filter_launch(lanes, n)
        # The one upload (pad lanes included) spread over the members.
        self._attribute(jit_hit, ds.duration(),
                        ops.nbytes // n if uploaded else 0, fused=True)


# A sweep group launches as soon as it holds this many filters: the
# chip starts on them while the host stages the rest of the flush. Its
# program exists in these lane counts; a group of one runs the
# one-filter sweep, as a call outside a batch does. Eight lanes are not among them:
# XLA splits sixteen outputs into two fusions, each reading the bank.
SWEEP_GROUP_MAX = 4
SWEEP_LANES = (2, 4)


class SweepLane(FusedEval):
    """One TopN's lane of a sweep group's `[K, R]` counts: the
    FusedEval surface over a `_SweepGroup`. The group's array is
    fetched once, pad lanes and all, so the pad lanes' bytes ride on
    its first member and a `d2h` span counts the array once."""

    __slots__ = ()

    @property
    def nbytes(self) -> int:
        pads = self.group.pad_lanes if self.b == 0 else 0
        return self.slice_nbytes * (1 + pads)

    def _out(self) -> Any:
        error = self.group.lane_errors[self.b]
        if error is not None:
            raise error
        return super()._out()


class _SweepGroup:
    """The filtered TopN sweeps of one begin half that read the same
    bank array. It holds the array object its members read at staging,
    so a later write in the batch, which builds a new array, changes
    nothing they sweep. A filter is a device array or a lane of a
    filter group, read when the sweep launches."""

    __slots__ = ("executor", "bank", "filters", "out", "host", "batched",
                 "error", "lane_errors", "pad_lanes", "__weakref__")

    def __init__(self, executor: Any, bank: Any) -> None:
        self.executor = executor
        self.bank = bank                  # [R, S, W] device array
        self.filters: List[Any] = []      # [S, W] arrays or FusedEvals
        self.out = None                   # [K, R] (or [R] alone)
        self.host: Optional[np.ndarray] = None
        self.batched = False
        self.error: Optional[Exception] = None
        # By lane, what its filter's launch raised: that member's alone.
        self.lane_errors: List[Optional[Exception]] = \
            [None] * SWEEP_GROUP_MAX
        self.pad_lanes = 0

    def add(self, filt: Any) -> SweepLane:
        self.filters.append(filt)
        return SweepLane(self, len(self.filters) - 1,
                         (self.bank.shape[0],))

    def ready(self) -> bool:
        """Every filter is in flight: the sweep can be queued behind
        them without launching anything else first."""
        return all(f.group.launched for f in self.filters
                   if isinstance(f, FusedEval))

    def _filter_words(self) -> List[Any]:
        """The filters as the sweep program's operands. A lane whose
        filter group failed keeps that error to itself and sweeps a
        neighbour's filter again, as a pad lane does."""
        from pilosa_tpu.executor.executor import _align_words
        width = self.bank.shape[-1]
        words: List[Any] = []
        for b, f in enumerate(self.filters):
            try:
                # A group's lanes come out at `width`: nothing to align.
                words.append(_align_words(
                    f.device_words() if isinstance(f, FusedEval) else f,
                    width))
            except Exception as e:
                self.lane_errors[b] = e
                words.append(None)
        good = [w for w in words if w is not None]
        if not good:
            raise next(e for e in self.lane_errors if e is not None)
        return [good[-1] if w is None else w for w in words]

    def run(self) -> None:
        """Launch the group's one program. Never raises (the
        _FuseGroup.run contract): a failure surfaces per member when
        its request finalizes, and harms no batchmate."""
        if self.out is not None or self.error is not None:
            return
        try:
            self.out, lanes = self.executor._dispatch_sweep_group(
                self.bank, self._filter_words())
            self.batched = lanes > 1
            self.pad_lanes = lanes - len(self.filters)
        except Exception as e:
            self.error = e
        finally:
            self.bank = None
            self.filters = []


class FusionCollector:
    """Per-batch registry of staged terminal evals, grouped by fusion
    key, of staged TopN filters, grouped by signature, and of staged
    bank sweeps, grouped by bank. Installed thread-locally by
    execute_batch (Executor._fusing); `flush()` runs every open group —
    called before a write-containing request dispatches (the fence)
    and once after the dispatch loop."""

    def __init__(self, executor: Any) -> None:
        self.executor = executor
        self.groups: Dict[tuple, _FuseGroup] = {}
        self.filters: Dict[tuple, _FilterGroup] = {}
        self.sweeps: Dict[tuple, _SweepGroup] = {}
        # Full sweep groups whose filters are not all in flight yet.
        self.waiting: List[_SweepGroup] = []

    def add(self, staged: Any, prof: Any, plan_s: float) -> FusedEval:
        """Stage one eval; returns its FusedEval handle. Grouping is
        by (sig, bank-array identity): the signature equates tree
        shape, widths and shard count, and identity equates the actual
        device operands — a write between two stages rebuilds the bank
        and so splits them even without an explicit fence."""
        key = (staged.sig, tuple(id(a) for a in staged.bank_arrays))
        group = self.groups.get(key)
        if group is None:
            group = self.groups[key] = _FuseGroup(self.executor)
        return group.add(staged, prof, plan_s)

    def add_filter(self, staged: Any, prof: Any, plan_s: float,
                   width: int) -> FusedEval:
        """Stage one TopN's filter tree, whose words its sweep wants
        `width` wide; returns its lane, which `add_sweep` takes.
        Grouping is by (sig, width, which positions are a lane's own,
        the shared banks' identity): the signature equates the tree and
        every operand's shape, the members read the same array wherever
        a position holds a view's bank, and each lane brings its own
        where it may not (a time range's views, a sparse bank, a
        row-subset bank: `_StagedEval.own_banks`), so members over
        different days or row sets share a launch. A full group
        launches here, and with it the full sweep groups that waited
        for it."""
        key = (staged.sig, width, staged.own_banks,
               tuple(id(a) for a in staged.shared_banks))
        group = self.filters.get(key)
        if group is None:
            group = self.filters[key] = _FilterGroup(self.executor, width)
        lane = group.add(staged, prof, plan_s)
        if lane.b + 1 == FILTER_GROUP_MAX:
            del self.filters[key]
            group.run()
            waiting, self.waiting = self.waiting, []
            for sweep in waiting:
                if sweep.ready():
                    sweep.run()
                else:
                    self.waiting.append(sweep)
        return lane

    def add_sweep(self, bank: Any, filt: Any) -> SweepLane:
        """Stage one filtered sweep of `bank` ([R, S, W]) under `filt`:
        `[S, W]` words, or the lane `add_filter` gave (the group aligns
        either to the bank's width when it launches). Returns the
        sweep's lane. Grouping is by
        the bank ARRAY's identity, as in `add`: what decides a group's
        size is how many sweeps of this begin half hold the same array,
        and nothing else. A full group launches here if its filters
        are all in flight, else when the last of them is."""
        key = (id(bank), bank.shape)
        group = self.sweeps.get(key)
        if group is None:
            group = self.sweeps[key] = _SweepGroup(self.executor, bank)
        lane = group.add(filt)
        if lane.b + 1 == SWEEP_GROUP_MAX:
            del self.sweeps[key]
            if group.ready():
                group.run()
            else:
                self.waiting.append(group)
        return lane

    def flush(self) -> None:
        # The filters first, then the sweeps that read them: the long
        # device work of the flush.
        filters, self.filters = self.filters, {}
        for group in filters.values():
            group.run()
        waiting, self.waiting = self.waiting, []
        sweeps, self.sweeps = self.sweeps, {}
        for sweep in (*waiting, *sweeps.values()):
            sweep.run()
        groups, self.groups = self.groups, {}
        if not groups:
            return
        if len(groups) > 1:
            # Heterogeneous flush: groups whose staged evals lowered
            # to megakernel IR pack — across signatures — into ONE
            # plan-buffer launch per shard-count cohort
            # (executor/megakernel.py); the rest run per-group below.
            from pilosa_tpu.executor.megakernel import run_megakernel
            groups = run_megakernel(self.executor, groups)
        for group in groups.values():
            group.run()
