"""PQL executor: batched device evaluation of call trees.

Reference: /root/reference/executor.go:84 (Execute), :245 (executeCall
dispatch), :2277 (mapReduce). Structural translation to TPU:

- The reference evaluates each shard in its own goroutine and merges row
  results pairwise (executor.go:2377, row.go:60). Here the operands live in
  per-view HBM banks shaped [rows, shards, words] (core/view.py ViewBank)
  and a whole PQL tree runs as ONE jitted XLA program over the stacked
  shard axis.
- Row identity and BSI predicate operands enter the program as *traced*
  gather indices / scalars, so the compile cache keys only on tree shape
  and bank shapes: `Count(Intersect(Row(f=X), Row(g=Y)))` compiles once for
  all X, Y — and fuses into a single AND+popcount pass, the generalization
  of the reference's hand-fused intersectionCountBitmapBitmap
  (roaring.go:2438) to arbitrary trees.
- Cross-shard reduction (the reference's reduceFn, HTTP scatter-gather) is
  a reduction over the shard axis inside the same program; the multi-chip
  version shard_maps these kernels over a mesh with psum on ICI
  (pilosa_tpu/parallel).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import logging
import os
import threading
import time
import types
from collections import deque
from pilosa_tpu.utils.locks import make_lock
from dataclasses import dataclass, field as dc_field
from datetime import datetime
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pilosa_tpu.core import timeq
from pilosa_tpu.core.field import (
    FIELD_TYPE_BOOL, FIELD_TYPE_INT, FIELD_TYPE_MUTEX, FIELD_TYPE_SET,
    FIELD_TYPE_TIME, Field,
)
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.view import (
    VIEW_STANDARD, device_share_bytes, view_bsi_name)
from pilosa_tpu.executor import bsi
from pilosa_tpu.executor.results import (
    FieldRow, GroupCount, PairsResult, RowIdentifiers, RowResult, ValCount,
)
from pilosa_tpu.ops.bitset import SHARD_WIDTH, WORDS_PER_SHARD, \
    pick_rows, transfer_nbytes
from pilosa_tpu.pql import Call, Condition, Query, parse_string_cached
from pilosa_tpu.pql.ast import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ
from pilosa_tpu.utils.fingerprint import request_key
from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.memledger import LEDGER
from pilosa_tpu.utils.jaxenv import COMPILES
from pilosa_tpu.utils.profile import transfer
from pilosa_tpu.utils.timeline import TIMELINE

_LOG = logging.getLogger("pilosa_tpu.executor")

_BITMAP_CALLS = {"Row", "Range", "Threshold",
                 "Intersect", "Union", "Difference", "Xor",
                 "Not", "Shift"}

# Calls that mutate fragment bitmaps. Used to decide whether a deferred
# read in the same multi-call query may lazily re-read fragment state in
# finalize (safe only when no later call writes — reference executes calls
# strictly sequentially, executor.go:245).
_WRITE_CALLS = {"Set", "Clear", "ClearRow", "Store"}
# All writes, for the max-writes-per-request limit (reference
# Query.WriteCallN counts these, pql/ast.go).
ALL_WRITE_CALLS = _WRITE_CALLS | {"SetRowAttrs", "SetColumnAttrs"}


def write_call_count(query) -> int:
    return sum(1 for c in query.calls
               if _peel_options(c).name in ALL_WRITE_CALLS)


def query_is_write(query) -> bool:
    """True when `query` (PQL string, Call, or Query) contains any write
    call. Used by the serving-path coalescer to flush its window on
    write arrival and to disable read-dedup for the flush. A parse error
    reads as False — the dispatch path reports it per-request."""
    try:
        if isinstance(query, str):
            query = parse_string_cached(query)
        if isinstance(query, Call):
            query = Query([query])
        return write_call_count(query) > 0
    except Exception:
        return False


def _peel_options(call: "Call") -> "Call":
    while call.name == "Options" and call.children:
        call = call.children[0]
    return call

# A time-range union of up to this many views is an OR-fold of slot leaves
# inside the tree program, padded to a power of two (1, 2, 4, ... this) so
# the view count moves the compile key six times, not once per count.
# Beyond it (hour-grain multi-year ranges) groups of this many views run
# the same fold as a jitted program of its own, chained through an
# accumulator, and the union enters the tree as one literal operand: the
# tree program's size stays bounded and nothing is indexed eagerly.
MAX_STATIC_RANGE_VIEWS = 32

# TopN uses the cached full view bank while ONE DEVICE's share of it
# (`Executor._bank_device_bytes`: under a mesh the shard axis is split
# over the shard devices) fits this HBM byte budget (banks are
# width-trimmed, so fingerprint-style fields with small column spans
# cache hundreds of thousands of rows); beyond it rows stream through
# transient chunk banks.
TOPN_MAX_BANK_BYTES = int(os.environ.get("PILOSA_TPU_TOPN_BANK_BYTES",
                                         2 << 30))
# Rows per streamed chunk on the over-budget TopN path. Larger chunks
# amortize dispatch/transfer overhead (100M-fingerprint sweeps want
# 64k-row chunks); the default keeps at most two ~modest chunk banks
# live at narrow widths.
TOPN_CHUNK_ROWS = int(os.environ.get("PILOSA_TPU_TOPN_CHUNK_ROWS", 1024))

# Device-resident positions bank for over-budget TopN (kill switch):
# when a narrow single-shard view outgrows TOPN_MAX_BANK_BYTES, keep
# its u16 positions resident (~2 B/set bit) and answer filtered TopN
# with one gather+cumsum pass per query instead of streaming dense
# chunk banks (view.PositionsBank).
PBANK_ENABLED = os.environ.get("PILOSA_TPU_PBANK", "1") != "0"

# Upper cap on the width of the positions-bank kernel's gather-free
# membership compare (see _pbank_kernel.bits_compare). The width itself
# is not a setting: it follows the bank (PositionsBank.qslots — its
# widest row in steps of 8, 104 slots for a library whose widest
# molecule has 103 on-bits), so a Row of the bank's own field always
# takes the compare and pays for no slot the bank cannot need. A
# filter with more on-bits than that takes the table gather: on the
# v5e 9.85 ns a position against the compare's ~0.1
# (benches/pbank_kernel_probe.py), ~4 s an answer among answers of
# ~0.1 s. While this constant WAS the width (128, PR 40) every answer
# of a library whose rows need 104 slots paid the 128-slot fan-out:
# 0.72 s an answer, 0.30 s of it the compare (PERF.md section 6).
PBANK_SPARSE_FILTER_BITS = int(os.environ.get(
    "PILOSA_TPU_PBANK_SPARSE_BITS", 128))

# Query slots one compare fan-out may have. XLA's TPU compiler lays a
# [P] x [Q] compare-reduce out differently at Q = 128 than below it:
# over 402.7 M positions it reads 56.7 ms at 104 slots and 321.1 at
# 128, where two fan-outs of 64 OR-ed together read 54.5 — they stay
# two fusions (my chip run, PR 41; 27.7 / 28.4 / 35.4 / 40.4 ms at 72 /
# 80 / 96 / 112 slots in u16, PR 40). So a width past 112 is compared
# in equal chunks, and the cliff cannot come back with a wider library.
PBANK_COMPARE_CHUNK = 112

# Membership form for the sparse-filter pbank kernel: "compare" (the
# [P] x [QCAP] equality fan-out), "search" (binary search in the
# sorted filter positions, log2(QCAP) compare-select rounds), or
# "auto" (default): search on the XLA CPU backend, where it compiles
# and runs faster, compare on devices: on the v5e the compare reads
# 0.090 ns a position and the search 0.90 at 48 query bits
# (benches/pbank_membership_probe.py; PERF.md §7 row 27).
# Selection is a compile key, resolved per backend at kernel build.
PBANK_MEMBERSHIP = os.environ.get("PILOSA_TPU_PBANK_MEMBERSHIP", "auto")
if PBANK_MEMBERSHIP not in ("auto", "compare", "search"):
    raise ValueError(
        f"PILOSA_TPU_PBANK_MEMBERSHIP={PBANK_MEMBERSHIP!r}: "
        "must be 'auto', 'compare', or 'search'")

# Max positions-bank segment programs enqueued before a sync (see
# _topn_positions): bounds how many programs' workspaces can coexist in
# HBM beside the resident bank. Compiled for a v5e, a flat segment of
# the 2^27 default keeps 2.15 GB of temporaries (the [P] membership
# words, the cumsum and its shifted copy), so 4 hold 8.6 GB; a fixed
# segment of the same positions ([104, 2.8 M] u16) keeps 0.31 GB (the
# [L, R] membership bits), so 4 hold 1.2 GB. Each wave sync is one
# blocking wait (span `pbank.wave_wait`), so the cap trades that wait
# against OOM headroom.
PBANK_INFLIGHT_SEGMENTS = int(os.environ.get(
    "PILOSA_TPU_PBANK_INFLIGHT", 4))

# Same-signature batch fusion (kill switch): N structurally identical
# queries in one execute_batch stack their traced operands and run as
# ONE vmapped XLA program (executor/fusion.py). Per-query results are
# bit-identical to the unfused path; disabling trades dispatch
# amortization back for the pre-fusion per-program pipeline.
FUSION_ENABLED = os.environ.get("PILOSA_TPU_FUSION", "1") != "0"

# Warm-cache TopN self-check sampling: 1 in this many warm hits ALSO
# runs the exact device sweep and compares (VERDICT r3 weak #5: the
# shortcut's correctness rests on every write path refreshing cached
# counts — a missed path would silently serve wrong TopN forever; the
# sample converts that into a logged counter + cache repair). 0
# disables. The first warm hit after startup is always checked.
TOPN_SELFCHECK_EVERY = int(os.environ.get("PILOSA_TPU_TOPN_SELFCHECK",
                                          256))


def named(fn: Callable, name: str) -> Callable:
    """`fn` under the function name `name` and a `jax.named_scope` of
    the same name, so that once jitted its XLA module reads
    `jit_<name>` in a profiler trace (and in JAX's compile events)
    instead of `jit_run` or `jit__lambda_`, and its ops carry the
    scope in their metadata. Instruction names — what XLA calls a
    fusion — are not touched by a scope."""
    import jax

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    scoped.__name__ = scoped.__qualname__ = name
    return scoped


def upload(host: np.ndarray):
    """One host->device put of a small operand vector, as an `h2d`
    stage of the request record (bank uploads are core/view.py's and
    are not charged to a request)."""
    import jax.numpy as jnp
    with transfer("h2d", int(host.nbytes)):
        return jnp.asarray(host)


def fetch_host(arrays) -> float:
    """The `d2h` stage: block until the host copy of every array one
    call's finalize will read is there (its async copy was started by
    prefetch_pendings); returns the seconds waited. A jax.Array caches
    the copy it fetched, a fusion handle caches its group's, so the
    finalize that follows reads host memory. A failed transfer is left
    for that finalize to raise."""
    if not arrays:
        return 0.0
    with transfer("d2h", transfer_nbytes(arrays), len(arrays)) as sp:
        for a in arrays:
            try:
                host = getattr(a, "host", None)   # fusion.FusedEval
                if host is not None:
                    host()
                else:
                    # graftlint: materialize — this IS the device->host
                    # boundary of the call: its one blocking fetch.
                    np.asarray(a)
            except Exception:
                pass
    return sp.duration()


class _Pending:
    """A dispatched-but-unfetched call result. The device program is
    already queued; finalize() blocks on the transfer and builds the
    host-side result. Lets _execute_query overlap every read call's
    device work and device→host drain across a multi-call query.

    `arrays` (optional) are the device arrays finalize will fetch.
    Exposing them lets the executor start EVERY result's device→host
    copy asynchronously before blocking on any (prefetch_pendings) —
    N calls then share one overlapped drain instead of paying N
    serial blocking fetches."""

    __slots__ = ("finalize", "arrays", "__weakref__")

    def __init__(self, finalize, arrays=()):
        self.finalize = finalize
        self.arrays = arrays
        if arrays:
            # Ledger the not-yet-fetched device outputs (category
            # "pending"): keyed on this object, auto-unregistered when
            # finalize drops the last reference — so /debug/memory
            # counts result arrays queued behind a slow drain.
            LEDGER.track(self, "pending",
                         sum(int(getattr(a, "nbytes", 0) or 0)
                             for a in arrays))


def prefetch_pendings(staged) -> None:
    """Kick off async device→host copies for every _Pending's declared
    arrays. jax.Array.copy_to_host_async is a no-op on host-resident
    (CPU backend) arrays and caches the fetched copy so the later
    np.asarray/device_get inside finalize reuses it."""
    for _, result in staged:
        if isinstance(result, _Pending):
            for a in result.arrays:
                fn = getattr(a, "copy_to_host_async", None)
                if fn is not None:
                    try:
                        fn()
                    except Exception:
                        pass  # transfer still happens in finalize


def _drive(steps):
    """Take a resumable dispatch (`Executor._dispatch_query`) to its
    end on the spot: a query that is alone blocks on each count fetch
    of its GroupBy as the level loop comes to it, with nothing queued
    behind. Returns what the generator returns."""
    try:
        while True:
            next(steps)
    except StopIteration as end:
        return end.value


class _BatchInFlight:
    """A dispatched-but-undrained execute_batch: every request's device
    programs are launched (operand banks snapshotted, fusion groups
    resolved, async prefetch started); execute_batch_finish blocks on
    the transfers and builds host results. The handle the pipelined
    serving path double-buffers on."""

    __slots__ = ("staged_q", "out", "profs", "deps_l")

    def __init__(self, staged_q, out, profs, deps_l):
        self.staged_q = staged_q
        self.out = out
        self.profs = profs
        self.deps_l = deps_l


class _ShapedInFlight:
    """execute_batch_shaped's in-flight handle: the underlying
    _BatchInFlight plus the request-cache bookkeeping the shaping half
    needs (keys/deps for fills, positions of cache hits already
    answered)."""

    __slots__ = ("flight", "out", "keys", "deps_l", "run", "requests")

    def __init__(self, flight, out, keys, deps_l, run, requests):
        self.flight = flight
        self.out = out
        self.keys = keys
        self.deps_l = deps_l
        self.run = run
        self.requests = requests


class _CacheFillEval:
    """Stands between a terminal eval's device output (device array or
    fusion FusedEval handle) and its consumers so the first HOST
    materialization also fills the result cache's eval tier — the
    "existing materialize seam": no extra fence, no extra transfer,
    the fill rides the fetch the consumer was paying anyway. Mirrors
    the slice of the FusedEval surface result/finalize code touches."""

    __slots__ = ("inner", "cache", "key", "gen", "_host")

    def __init__(self, inner, cache, key, gen):
        self.inner = inner
        self.cache = cache
        self.key = key
        self.gen = gen
        self._host = None

    @property
    def shape(self):
        return self.inner.shape

    @property
    def nbytes(self) -> int:
        return int(getattr(self.inner, "nbytes", 0) or 0)

    def device_words(self):
        """Device-side view for consumers that avoid the host bounce
        (RowResult.count)."""
        dw = getattr(self.inner, "device_words", None)
        return dw() if dw is not None else self.inner

    def copy_to_host_async(self) -> None:
        fn = getattr(self.inner, "copy_to_host_async", None)
        if fn is not None:
            fn()

    # graftlint: materialize — this IS the device->host boundary for
    # cached terminal evals (the FusedEval.host convention): the fetch
    # happens exactly once, and the host copy both serves the caller
    # and fills the cache.
    def __array__(self, dtype=None, copy=None):
        host = self._host
        if host is None:
            host = np.asarray(self.inner)
            self._host = host
            self.cache.fill(self.key, self.gen, host, host.nbytes,
                            tier="eval")
        return np.asarray(host, dtype=dtype) if dtype is not None \
            else host


# graftlint: materialize — the program's one host-clock fence: reached
# ONLY under a QueryProfile an operator asked for with ?profile=true. The
# unprofiled hot path never calls it, so the dispatch queue stays async
# (tests/test_profile.py asserts zero calls without such a profile).
# Returns the host's wait in block_until_ready after the enqueue: an
# upper bound on that program's device time only when the queue ahead
# of it was empty. Device speed comes from a profiler trace.
def _fence_device(out) -> float:
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(out)
    return time.perf_counter() - t0


class ExecutionError(ValueError):
    pass


@dataclass
class ExecOptions:
    """Per-query execution options (reference execOptions, executor.go:36,
    set by the Options() call, executor.go:317-361)."""
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    column_attrs: bool = False


def column_attr_sets(idx: Index, ids: Sequence[int],
                     resolve=None) -> List[Dict[str, Any]]:
    """Non-empty column attr sets for `ids`, key-translated when the index
    is keyed (reference readColumnAttrSets, executor.go:180-200 +
    translation :155-162). `resolve(ids) -> keys` overrides the local
    translator (cluster mode resolves through the primary so attr keys
    match the result keys in the same response)."""
    withattrs = [(int(cid), idx.column_attr_store.get(int(cid)))
                 for cid in ids]
    withattrs = [(cid, attrs) for cid, attrs in withattrs if attrs]
    if not idx.keys:
        return [{"id": cid, "attrs": attrs} for cid, attrs in withattrs]
    if resolve is None:
        resolve = idx.column_translator.translate_ids
    keys = resolve([cid for cid, _ in withattrs])
    return [({"key": key, "attrs": attrs} if key is not None
             else {"id": cid, "attrs": attrs})
            for (cid, attrs), key in zip(withattrs, keys)]


def _topn_candidates(rows_arr: np.ndarray, counts_arr: np.ndarray,
                     n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Shrink a (rows, counts) set to the rows that can appear in an
    exact top-n: everything with count >= the n-th largest count
    (boundary ties kept in full, so the later (-count, row) lexsort
    still breaks them exactly). O(N) partition instead of an O(N log N)
    full sort — 40 ms -> 2.6 ms per TopN at 500k fingerprint rows."""
    if not n or len(counts_arr) <= max(4096, 4 * n):
        return rows_arr, counts_arr
    kth = np.partition(counts_arr, len(counts_arr) - n)[len(counts_arr) - n]
    sel = counts_arr >= kth
    return rows_arr[sel], counts_arr[sel]


def _pow2(n: int) -> int:
    """The smallest power of two >= n (n >= 1)."""
    return 1 << (n - 1).bit_length()


def _bsi_sel(bank, depth: int):
    """The slots of a BSI plane bank's rows 0..depth (the bit planes,
    then not-null) as a device vector, memoized on the bank object:
    banks rebuild when fragment versions change, so the memo
    invalidates with them, and repeat Sum/Min/Max calls and GroupBy
    sums skip a host build + device upload (~1 ms/call, comparable to
    a whole device sweep)."""
    sel = getattr(bank, "_bsi_sel", None)
    if sel is None or int(sel.shape[0]) != depth + 1:
        sel = upload(np.asarray(
            [bank.slot(r) for r in range(depth + 1)], dtype=np.int32))
        bank._bsi_sel = sel
    return sel


def _gb_shape(operand) -> str:
    """A GroupBy program operand in its jit key: the shape of an array
    (of each, for a tuple), None for an operand left out. An index
    vector's shape is its length."""
    if isinstance(operand, tuple):
        return "+".join(str(a.shape) for a in operand)
    return str(None if operand is None else operand.shape)


def _gb_prefixes(w: int, src, pi, prev, si):
    """Inside a GroupBy program: the prefixes [p, S, w] of one chunk of
    a `_Frontier`, gathered and ANDed from its operands —

    - `src`: the filter's words [S, W] (one prefix, broadcast), or the
      prefix arrays [p_k, S, w] the level before wrote, as a tuple read
      end to end, `pi` [p] picking among them (None: all, in order);
    - `prev`, `si`: the level before's resident bank array and, per
      prefix, the slot of its row there.

    Either pair may be None; not both."""
    import jax.numpy as jnp
    picks = [] if prev is None else [(prev, si)]
    if not isinstance(src, tuple):      # the filter's words, or nothing
        fixed = () if src is None else (src,)
        return pick_rows(w, *picks, fixed=fixed) if picks \
            else src[None, :, :w]
    src = src[0] if len(src) == 1 else jnp.concatenate(src)
    if pi is not None:
        return pick_rows(w, (src, pi), *picks)
    src = src[..., :w]
    return jnp.bitwise_and(src, pick_rows(w, *picks)) if picks else src


@dataclass
class _Frontier:
    """The prefixes of a GroupBy that survived the levels so far, as
    operands of the next level's programs (`_gb_prefixes`): prefix i is
    src[pi[i]] ∧ prev[si[i]], and rows[i] its row-id tuple. `src` is
    the filter's words, a tuple of device arrays, or — spilled — ONE
    host array, whose chunks are gathered on the host and uploaded."""
    src: Any
    pi: Optional[np.ndarray]
    prev: Any
    si: Optional[np.ndarray]
    rows: List[tuple]

    def chunk(self, c0: int, c1: int, put) -> tuple:
        """(src, pi, prev, si) of prefixes c0:c1, index vectors
        uploaded; a spilled chunk's prefixes [p, S, w] go up through
        `put` (`Executor._put_prefixes`)."""
        src = self.src
        pi = None if self.pi is None else self.pi[c0:c1]
        if isinstance(src, np.ndarray):
            src, pi = (put(src[pi]),), None
        return (src, None if pi is None else upload(pi), self.prev,
                None if self.si is None else upload(self.si[c0:c1]))


def _align_words(words, width: int):
    """Slice or zero-pad the trailing word axis to exactly `width`
    (None passes through). Both directions are semantically safe for
    intersection-style consumers — see _dispatch_counts."""
    if words is None or words.shape[-1] == width:
        return words
    if words.shape[-1] > width:
        return words[..., :width]
    return _pad_words(words, width)


def _pad_words(words, width: int):
    """Zero-pad the trailing word axis up to `width` (no-op when equal).
    Leaves gather from width-trimmed banks (view.trimmed_words) and pad to
    the plan-wide width, so operands of one tree always align while each
    bank stays as narrow as its data."""
    import jax.numpy as jnp
    d = width - words.shape[-1]
    if d <= 0:
        return words
    return jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, d)])


class _SlicedRows:
    """A dense bank operand as a lane of a filter group's program sees
    it (`Executor._filter_group_fn`): `bank[slot]` is ONE dynamic slice
    and `bank[[slot, ...]]` a slice a row, stacked — where indexing the
    array itself by a traced vector is a gather over the whole bank,
    and by a traced scalar several equations a leaf more to trace."""

    __slots__ = ("array",)

    def __init__(self, array) -> None:
        self.array = array

    def __getitem__(self, slots):
        import jax.numpy as jnp
        from jax import lax
        if isinstance(slots, list):
            return jnp.stack([self[s] for s in slots])
        return lax.dynamic_index_in_dim(self.array, slots, 0,
                                        keepdims=False)


@dataclass
class _Plan:
    """Everything the jitted tree program needs, gathered in one host pass.
    Banks are NOT built during planning: leaves record (bank key, row id)
    references, and _eval_tree builds each bank once afterwards — with the
    exact row set the tree needs, so an over-budget view can be served by
    a row-subset bank instead of materializing every row in HBM."""
    sig_parts: List[str] = dc_field(default_factory=list)
    bank_keys: List[Tuple[str, str]] = dc_field(default_factory=list)
    bank_pos: Dict[Tuple[str, str], int] = dc_field(default_factory=dict)
    idxs: List[int] = dc_field(default_factory=list)       # traced gather slots
    params: List[int] = dc_field(default_factory=list)     # traced u32 scalars
    literals: List[Any] = dc_field(default_factory=list)   # [S, W] operands
    widths: List[int] = dc_field(default_factory=list)     # operand widths
    # slot placeholders: (position in idxs, bank key, row id), resolved
    # once banks exist; rows_for[key] = every row the tree reads from it.
    slot_refs: List[Tuple[int, Tuple[str, str], int]] = \
        dc_field(default_factory=list)
    rows_for: Dict[Tuple[str, str], set] = dc_field(default_factory=dict)
    shift_bits: int = 0    # total Shift() distance; widens the plan
    width: int = 0         # resolved by _eval_tree before tracing
    # Megakernel IR (ops/megakernel.py): a postfix record of the same
    # tree the closures trace, appended by the _plan_* recursion so a
    # heterogeneous flush can lower N different staged programs into
    # ONE opcode plan buffer. `ir_ok=False` (literal operands, Shift)
    # means the staged eval is not lowerable and takes the per-group
    # fusion path instead.
    ir: List[tuple] = dc_field(default_factory=list)
    ir_ok: bool = True
    # Hybrid layout (core/layout.py): per bank key, whether its leaves
    # serve from the view's SparseBank ("xslot" IR nodes + the
    # expand_positions program) instead of the dense ViewBank. The
    # decision snapshots the view's layout mode ONCE per key per plan
    # (a background flip mid-staging cannot split one bank between two
    # representations); force_dense carries keys whose sparse build
    # bailed so the restage plans them dense.
    bank_sparse: Dict[Tuple[str, str], bool] = \
        dc_field(default_factory=dict)
    force_dense: set = dc_field(default_factory=set)
    # bank pos -> the built SparseBank's dense expansion width, filled
    # by _stage_tree once banks exist (leaf closures read it at trace
    # time, after staging resolved every width).
    sparse_widths: Dict[int, int] = dc_field(default_factory=dict)

    # Time-range leaves staged by this plan, (path, view count) each:
    # counted once the staging settles (executor.range_leaves{path:}).
    range_leaves: List[Tuple[str, int]] = dc_field(default_factory=list)
    # Operand positions of their day, month and year views: WHICH view
    # sits there moves with the range, where every other position's
    # bank is its view's, whatever the query (_StagedEval.own_banks).
    ranged: set = dc_field(default_factory=set)

    def bank(self, key: Tuple[str, str], fresh: bool = False) -> int:
        """The operand position of `key`'s bank. `fresh` takes a new
        position for a bank the plan may hold already (the pad operands
        of a bucketed range fold): the same array at two positions, so
        the program's operand list is as long as its bucket."""
        pos = None if fresh else self.bank_pos.get(key)
        if pos is None:
            pos = len(self.bank_keys)
            self.bank_pos.setdefault(key, pos)
            self.bank_keys.append(key)
        return pos

    def resolve_width(self) -> int:
        from pilosa_tpu.ops.bitset import WORDS_PER_SHARD
        from pilosa_tpu.core.fragment import CONTAINER_BITS
        w = max(self.widths, default=CONTAINER_BITS // 32)
        if self.shift_bits:
            # Shifted bits may cross the trim boundary; widen to cover.
            extra = (self.shift_bits + CONTAINER_BITS - 1) // CONTAINER_BITS
            w += extra * (CONTAINER_BITS // 32)
        self.width = min(WORDS_PER_SHARD, w)
        return self.width


@dataclass
class _StagedEval:
    """One planned-but-not-run tree program: the output of
    Executor._stage_tree, consumed either by _run_staged (solo) or by
    the batch fusion pass (executor/fusion.py), which stacks the
    operand vectors of same-`sig` stages along a new leading batch
    axis and runs them through one vmapped program. Everything that
    differs between same-signature queries lives in `idxs`/`params`/
    `lits`; everything that must be IDENTICAL for two stages to fuse
    is covered by `sig` plus bank-array identity."""
    mode: str              # "row" -> [S, W] words | "count" -> [S]
    sig: str               # compile-cache key (tree shape + shapes)
    expr: Callable         # expr(banks, idxs, params, lits) -> [S, W]
    width: int             # resolved plan word width
    n_shards: int
    bank_arrays: tuple     # device operand banks (shared, not stacked)
    idxs: List[int]        # traced gather slots (host values)
    params: List[int]      # traced u32 scalars (host values)
    lits: Any              # tuple of [S, W] device literals or None
    # Workload-recorder AND result-cache identity: the semantic
    # fingerprint (sig + row ids + params — row IDS, not bank slots,
    # so it is stable across bank rebuilds), and the operand banks'
    # generation (fragment write versions) it was staged against —
    # together the exact (key, generation) pair the eval tier of
    # executor/result_cache.py caches under. None when both the
    # workload recorder and the result cache are off.
    fp: Any = None
    gen: Any = None
    # False when the plan carries literal operands (the
    # >MAX_STATIC_RANGE_VIEWS time-range union): literal content is
    # not named by fp/gen, so such evals must never be served from or
    # fill the result cache — nor enter a fusion group, whose members
    # share every operand but idxs/params.
    cacheable: bool = True
    # Megakernel IR: the postfix opcode record _Plan collected, or
    # None when the tree is not lowerable — such evals keep the
    # per-signature-group vmap fusion path (executor/megakernel.py).
    ir: Any = None
    # Positions of `bank_arrays` that two evals of one `sig` may fill
    # with different arrays of one shape: a time range's views, a sparse
    # bank's arrays and a row-subset bank (one a row set, of a view past
    # BANK_MAX_BYTES). A lane of a filter group brings its own of these,
    # `owned_banks`; every other position holds its view's bank, which
    # the lanes of a group share as ONE operand each, `shared_banks`
    # (the group's key says so). Both in position order.
    own_banks: Tuple[int, ...] = ()
    shared_banks: tuple = ()
    owned_banks: tuple = ()

    def runner(self) -> Callable:
        """The traceable program body: expr + the mode's reduction."""
        expr, mode = self.expr, self.mode

        def run(bank_arrays, idxs, params, lits):
            out = expr(bank_arrays, idxs, params, lits)
            if mode == "count":
                from pilosa_tpu.ops.bitset import popcount
                return popcount(out, axis=-1)  # [S]
            return out
        return named(run, self.program)

    @property
    def program(self) -> str:
        """The program's name in traces: `tree_count` / `tree_row`."""
        return f"tree_{self.mode}"


class Executor:
    """Single-controller executor. With `mesh=None` everything runs on the
    local device; with a MeshContext the shard list is padded onto the mesh
    and banks are sharded over its shard axis — the same compiled query
    programs then run SPMD with XLA-inserted ICI collectives (the TPU
    replacement for mapReduce over HTTP, executor.go:2277)."""

    def __init__(self, holder: Holder, mesh=None):
        self.holder = holder
        self.mesh = mesh
        # Devices a dispatch runs on (the `dispatch` spans carry it),
        # and how a bank is cut over them (what a device's share of one
        # is priced by).
        self.mesh_devices = int(mesh.mesh.devices.size) if mesh else 1
        self._bank_sharding = mesh.bank_sharding() if mesh else None
        # Reject queries carrying more write calls than this; 0 = no limit
        # (reference executor.MaxWritesPerRequest, executor.go:53,106).
        self.max_writes_per_request = 0
        # Compiled-program cache, shape-keyed and LRU-bounded (see
        # JIT_CACHE_MAX): holds ONLY jitted callables. Device-resident
        # placeholder banks live in _bank_cache — mixing the two in one
        # unbounded dict previously meant an eviction policy could
        # never be added without throwing ViewBanks away with programs.
        self._jit_cache: Dict[str, Callable] = {}
        self._jit_cache_lock = make_lock("Executor._jit_cache_lock")
        # Shared all-zero placeholder banks (absent views), keyed by
        # shard count + mesh. Shard counts grow with the index, so the
        # cache is LRU-bounded (BANK_CACHE_MAX, see _empty_bank) with
        # ledger unregister on evict; the lock makes the
        # pop/evict/reinsert dance atomic across request threads.
        self._bank_cache: Dict[str, Any] = {}
        self._bank_cache_lock = make_lock("Executor._bank_cache_lock")
        # Device copies of the tiny per-query idxs/params arrays, keyed
        # by their values: repeated warm queries skip two host->device
        # transfers per execution (a large share of small-query latency).
        # The executor is shared across request threads; the lock makes
        # the pop/evict/reinsert LRU dance atomic (VERDICT r3 weak #6 —
        # it previously leaned on dict-internals tolerance).
        self._arg_cache: Dict[tuple, tuple] = {}
        self._arg_cache_lock = make_lock("Executor._arg_cache_lock")
        # Per-thread dispatch context (one executor serves all request
        # threads): whether calls after the one being dispatched write.
        self._tls = threading.local()
        # Process-wide retrace counter: every shape-keyed jit-cache miss
        # (a fresh XLA trace+compile) across the instance's jit sites.
        # An unexpected climb under steady traffic means some query
        # attribute leaked into a compile key (utils/profile.py surfaces
        # it per query; /metrics exports the running total). Incremented
        # via _note_jit_compile — request threads race here.
        self.jit_compiles = 0
        self._jit_stats_lock = make_lock("Executor._jit_stats_lock")
        # Batch fusion counters (executor/fusion.py): fused program
        # dispatches (one per >=2-query group) and the queries they
        # covered. /metrics exports them as
        # pilosa_executor_fused_{dispatches,queries}_total.
        self.fused_dispatches = 0
        self.fused_queries = 0
        # Heterogeneous megakernel counters (executor/megakernel.py):
        # plan-buffer launches (one per mixed cohort), the queries they
        # covered, total plan entries interpreted and plan bytes
        # uploaded. /metrics exports them as
        # pilosa_executor_mega_{launches,queries,plan_entries,plan_bytes}_total.
        self.mega_launches = 0
        self.mega_queries = 0
        self.mega_plan_entries = 0
        self.mega_plan_bytes = 0
        # Mesh cohort launches (executor/megakernel.py under a
        # MeshContext, PILOSA_TPU_MESH): one plan buffer dispatched
        # SPMD over the mesh shard axis, reductions finished in-kernel
        # by the collective epilogue. collective_bytes is the modeled
        # ICI wire traffic (psum + all_gather, ops/megakernel.
        # plan_cost). /metrics exports pilosa_executor_mesh_
        # {launches,collective_bytes}_total.
        self.mesh_launches = 0
        self.mesh_collective_bytes = 0
        # Launch cost attribution (ops/megakernel.plan_cost): HBM
        # bytes each launch moved split by kind, plus per-opcode
        # instruction totals. /metrics exports
        # pilosa_executor_launch_bytes_total{kind=gather|compute|
        # expand|pad} and pilosa_executor_opcode_total{op=...}.
        self.launch_bytes_gather = 0
        self.launch_bytes_compute = 0
        self.launch_bytes_expand = 0
        self.launch_bytes_pad = 0
        self.opcode_counts: Dict[str, int] = {}
        # Plan-IR verification gate (ops/megakernel.verify_plan,
        # PILOSA_TPU_PLAN_VERIFY): plans checked before dispatch and
        # plans rejected (a reject means a lowering bug — the launch
        # raised instead of executing wrong bits). /metrics exports
        # pilosa_executor_plan_verify_{passes,rejects}_total.
        self.plan_verify_passes = 0
        self.plan_verify_rejects = 0
        # Plan optimizer (ops/plan_opt.py, PILOSA_TPU_PLAN_OPT):
        # plans rewritten, CSE fingerprint hits, instructions
        # eliminated, fold chains density-reordered, and slab +
        # plan-buffer bytes the rewrites dropped. /metrics exports
        # pilosa_executor_opt_{plans,cse_hits,entries_eliminated,
        # folds_reordered,bytes_saved}_total.
        self.opt_plans = 0
        self.opt_cse_hits = 0
        self.opt_entries_eliminated = 0
        self.opt_folds_reordered = 0
        self.opt_bytes_saved = 0
        # Optional stats sink (utils/stats interface) the API layer
        # attaches; batch-scoped signals (fusion group sizes) that have
        # no per-query profile to ride report through it.
        self.stats = None
        # Generation-keyed cross-request result cache (ROADMAP item
        # 3a; executor/result_cache.py): request tier keyed on the
        # coalescer's request identity, eval tier keyed on the staged
        # fingerprint + bank generations. PILOSA_TPU_RESULT_CACHE=0
        # kills it.
        from pilosa_tpu.executor.result_cache import ResultCache
        self.result_cache = ResultCache()
        # Device rank-cache counters (core/cache.RANK_CACHE holds the
        # vectors; the store is process-wide, the counters per
        # executor so tests and /metrics attribute them): hits reuse a
        # warm [R] count vector, patches recompute only written rows,
        # rebuilds pay the full sweep TopN would have paid anyway.
        self.rank_cache_hits = 0
        self.rank_cache_patches = 0
        self.rank_cache_rebuilds = 0
        # Observability: TopN answers served from warm ranked caches
        # without any device work (reference fragment.top, fragment.go:1067).
        self.topn_cache_hits = 0
        # Sampled warm-cache self-checks run / mismatches found (a
        # mismatch means some write path failed to refresh cached
        # counts; the caches involved are repaired from storage).
        self.topn_selfchecks = 0
        self.topn_selfcheck_mismatches = 0
        # Cluster mode installs a resolver that allocates keys on the
        # translation primary (reference: primary-owned TranslateFile with
        # chained replication, translate.go:56,400). None = local stores.
        self.key_resolver = None
        # Reverse (id -> key) resolver with primary fallback for replicas
        # whose translate-log replay lags the allocation.
        self.id_resolver = None

    def _resolve_col_keys(self, idx: Index, keys: List[str]) -> List[int]:
        if self.key_resolver is not None:
            return self.key_resolver(idx.name, None, keys)
        return [int(i) for i in idx.column_translator.translate_keys(keys)]

    def _resolve_row_keys(self, idx: Index, field: Field,
                          keys: List[str]) -> List[int]:
        if self.key_resolver is not None:
            return self.key_resolver(idx.name, field.name, keys)
        return [int(i) for i in field.row_translator.translate_keys(keys)]

    def _resolve_col_key(self, idx: Index, key: str) -> int:
        return self._resolve_col_keys(idx, [key])[0]

    def _resolve_col_ids(self, idx: Index, ids) -> List[Optional[str]]:
        if self.id_resolver is not None:
            return self.id_resolver(idx.name, None, list(ids))
        return idx.column_translator.translate_ids(ids)

    def _resolve_row_ids(self, idx: Index, field: Field,
                         ids) -> List[Optional[str]]:
        if self.id_resolver is not None:
            return self.id_resolver(idx.name, field.name, list(ids))
        return field.row_translator.translate_ids(ids)

    def _resolve_row_key(self, idx: Index, field: Field, key: str) -> int:
        return self._resolve_row_keys(idx, field, [key])[0]

    # --------------------------------------------------------- compile cache

    # Max cached compiled programs. Keys are shape signatures, so
    # steady-state serving traffic converges on a small working set; the
    # bound protects against signature churn (schema growth, width
    # drift, many distinct fused batch sizes) pinning dead programs —
    # and their XLA executables — forever.
    JIT_CACHE_MAX = int(os.environ.get("PILOSA_TPU_JIT_CACHE_MAX", 512))

    def _jit_get(self, key: str) -> Optional[Callable]:
        """Compile-cache lookup; a hit is re-inserted at the tail so
        plain dict insertion order doubles as LRU order."""
        with self._jit_cache_lock:
            fn = self._jit_cache.pop(key, None)
            if fn is not None:
                self._jit_cache[key] = fn
            return fn

    def _jit_put(self, key: str, fn: Callable) -> None:
        # Compiled XLA executables occupy HBM too; their sizes are not
        # introspectable from here, so the ledger carries the entry
        # COUNT (bytes 0) — eviction decrements the gauge (pinned by
        # tests/test_memledger.py). Ledger updates happen UNDER the
        # cache lock (the ledger lock is a leaf, so the nesting is
        # safe): deferring them would let an evict/recompile interleave
        # unregister another thread's freshly re-registered entry.
        with self._jit_cache_lock:
            while len(self._jit_cache) >= max(1, self.JIT_CACHE_MAX):
                old = next(iter(self._jit_cache))
                self._jit_cache.pop(old)
                LEDGER.unregister("jit_cache", old, owner=self)
            self._jit_cache[key] = fn
            LEDGER.register("jit_cache", key, 0, owner=self,
                            sig=str(key)[:120])

    def jit_cache_size(self) -> int:
        """Live compiled-program count (the pilosa_executor_jit_cache_size
        gauge on /metrics)."""
        with self._jit_cache_lock:
            return len(self._jit_cache)

    # ------------------------------------------------------- profiling hooks

    def _note_jit_compile(self, program: str = "", key: Any = "") -> None:
        """Count one fresh XLA trace+compile (jit-cache miss) of
        `program` under jit-cache key `key`. '+= 1' is not atomic and
        every request thread can land here. The key goes to the
        compile log's table and onto this thread's next `dispatch`
        span (the call that pays for the compile)."""
        with self._jit_stats_lock:
            self.jit_compiles += 1
        COMPILES.note_key(program, key)
        self._tls.jit_miss = str(key)[:200]

    def _dispatch_span(self, program: str, **attrs):
        """The `dispatch` stage around one enqueue of `program`:
        `jit=miss` plus the readable key when this thread just missed
        the jit cache (the call then traces and compiles), else
        `jit=hit`; `mesh_devices` is how many devices the one enqueue
        drives; `attrs` are the launch's own (how a GroupBy level was
        cut: `prefixes`, `rows` or `lanes`, `chunk_of`)."""
        key = self._tls.__dict__.pop("jit_miss", None)
        if key is not None:
            attrs["key"] = key
        return TIMELINE.stage("dispatch", program=program,
                              jit="hit" if key is None else "miss",
                              mesh_devices=self.mesh_devices, **attrs)

    def _profile(self):
        """The QueryProfile attached to the current thread's in-flight
        query, or None (the common, zero-overhead case)."""
        return getattr(self._tls, "profile", None)

    @contextlib.contextmanager
    def _profiled(self, profile):
        """Attach `profile` (may be None) to this thread for the
        duration — the executor's instrumentation points read it via
        _profile(). Thread-local because one executor serves every
        request thread."""
        prev = getattr(self._tls, "profile", None)
        self._tls.profile = profile
        try:
            yield
        finally:
            self._tls.profile = prev

    @contextlib.contextmanager
    def _fusing(self, collector):
        """Install a FusionCollector for this thread: terminal evals
        dispatched inside the context stage into it instead of running
        (execute_batch's dispatch loop wraps each fusible request)."""
        prev = getattr(self._tls, "fuser", None)
        self._tls.fuser = collector
        try:
            yield
        finally:
            self._tls.fuser = prev

    def _note_fused(self, group_size: int) -> None:
        """Account one fused dispatch covering `group_size` queries
        (called by FusionCollector.flush; '+=' is not atomic and
        batches can run from several threads)."""
        with self._jit_stats_lock:
            self.fused_dispatches += 1
            self.fused_queries += group_size
        if self.stats is not None:
            self.stats.count("executor.fused_dispatches", 1)
            self.stats.count("executor.fused_queries", group_size)
            self.stats.histogram("executor.fusion_group_size", group_size)

    def _note_mega(self, queries: int, plan_entries: int,
                   plan_bytes: int) -> None:
        """Account one megakernel launch covering `queries` staged
        evals via `plan_entries` interpreted instructions ('+=' is not
        atomic and batches can run from several threads)."""
        with self._jit_stats_lock:
            self.mega_launches += 1
            self.mega_queries += queries
            self.mega_plan_entries += plan_entries
            self.mega_plan_bytes += plan_bytes
        if self.stats is not None:
            self.stats.count("executor.mega_launches", 1)
            self.stats.count("executor.mega_queries", queries)
            self.stats.count("executor.mega_plan_entries", plan_entries)
            self.stats.count("executor.mega_plan_bytes", plan_bytes)
            self.stats.histogram("executor.mega_batch_size", queries)

    # How a TopN call was answered, one of these per call, counted
    # under `executor.topn_sweeps{path:<p>}`: from the fragments' ranked
    # caches on the host, from the device rank cache, from a resident
    # positions bank, by ONE sweep of the resident bank, or by streaming
    # chunk banks through the device. A bank that outgrows
    # TOPN_MAX_BANK_BYTES falls from `resident` to `streamed` without
    # another sign, so the fall is counted.
    TOPN_PATHS = ("fragment_cache", "rank_cache", "positions", "resident",
                  "streamed")

    def _note_topn(self, path: str) -> None:
        if self.stats is not None:
            self.stats.with_tags(f"path:{path}").count(
                "executor.topn_sweeps", 1)

    def _note_sweep_launch(self) -> None:
        """One bank-sweep program launched, whichever of the three:
        `executor.sweep_launches`."""
        if self.stats is not None:
            self.stats.count("executor.sweep_launches", 1)

    def _note_sweep_group(self, lanes: int, members: int) -> None:
        """One launch swept a resident bank for `members` filtered TopN
        calls in `lanes` lanes: `executor.sweep_group_filters{k:<lanes>}`
        counts the members (over every k: the filtered resident TopN
        calls), `executor.sweep_pad_lanes` the lanes nobody reads."""
        if self.stats is not None:
            self.stats.with_tags(f"k:{lanes}").count(
                "executor.sweep_group_filters", members)
            if lanes > members:
                self.stats.count("executor.sweep_pad_lanes",
                                 lanes - members)

    def _note_filter_launch(self, lanes: int, members: int) -> None:
        """One filter program launched for the sweeps of `members`
        staged TopN calls, in `lanes` lanes (1: `tree_row` alone):
        `executor.filter_launches`, the members under
        `executor.filter_group_members{k:<lanes>}`, and
        `executor.filter_pad_lanes`, the lanes nobody reads."""
        if self.stats is not None:
            self.stats.count("executor.filter_launches", 1)
            self.stats.with_tags(f"k:{lanes}").count(
                "executor.filter_group_members", members)
            if lanes > members:
                self.stats.count("executor.filter_pad_lanes",
                                 lanes - members)

    def _note_topn_rows(self, what: str, n: int) -> None:
        """What a TopN call's sweep costs by the row: `swept`, the bank
        slots its programs covered, the sweep for a bank version's
        popcounts among them (`executor.topn_rows_swept`), and
        `fetched`, the elements of per-row count and popcount vectors
        its finalize brought to the host
        (`executor.topn_rows_fetched`)."""
        if self.stats is not None and n:
            self.stats.count(f"executor.topn_rows_{what}", n)

    # How a time-range leaf's union was staged, one of these per leaf,
    # counted under `executor.range_leaves{path:<p>}`: `fold`, slot
    # leaves OR-ed inside the tree program (up to
    # MAX_STATIC_RANGE_VIEWS views), or `grouped`, programs of their
    # own ahead of it; `executor.range_views` sums the views.
    RANGE_PATHS = ("fold", "grouped")

    def _note_range(self, path: str, n_views: int) -> None:
        if self.stats is not None:
            self.stats.with_tags(f"path:{path}").count(
                "executor.range_leaves", 1)
            self.stats.count("executor.range_views", n_views)

    def _note_mesh(self, n_devices: int, collective_bytes: int) -> None:
        """Account one mesh cohort launch: the plan buffer ran SPMD
        over `n_devices` device slices and the epilogue's collectives
        moved `collective_bytes` over ICI ('+=' is not atomic and
        batches can run from several threads)."""
        with self._jit_stats_lock:
            self.mesh_launches += 1
            self.mesh_collective_bytes += collective_bytes
        if self.stats is not None:
            self.stats.count("executor.mesh_launches", 1)
            self.stats.count("executor.mesh_collective_bytes",
                             collective_bytes)
            self.stats.histogram("executor.mesh_devices", n_devices)

    def _note_launch_cost(self, cost: Dict[str, Any]) -> None:
        """Account one launch's HBM traffic attribution (ops/
        megakernel.plan_cost's byte splits and per-opcode histogram).
        '+=' is not atomic and batches can run from several threads."""
        with self._jit_stats_lock:
            self.launch_bytes_gather += cost["gatherBytes"]
            self.launch_bytes_compute += cost["computeBytes"]
            self.launch_bytes_expand += cost["expandBytes"]
            self.launch_bytes_pad += cost["padBytes"]
            for name, n in cost["opcodeHist"].items():
                # graftlint: disable=GL008 — keyed by opcode name:
                # bounded by the (8-entry) plan-IR opcode table.
                self.opcode_counts[name] = \
                    self.opcode_counts.get(name, 0) + n
        if self.stats is not None:
            for kind, key in (("gather", "gatherBytes"),
                              ("compute", "computeBytes"),
                              ("expand", "expandBytes"),
                              ("pad", "padBytes")):
                self.stats.with_tags(f"kind:{kind}").count(
                    "executor.launch_bytes", cost[key])
            for name, n in cost["opcodeHist"].items():
                self.stats.with_tags(f"op:{name}").count(
                    "executor.opcode", n)

    def _note_plan_verify(self, ok: bool) -> None:
        """Account one pre-launch plan verification (ops/megakernel.
        verify_plan). A reject is a lowering bug surfacing as a
        request error instead of wrong bits — the counter pair is the
        production signal that the gate is live and clean."""
        with self._jit_stats_lock:
            if ok:
                self.plan_verify_passes += 1
            else:
                self.plan_verify_rejects += 1
        if self.stats is not None:
            self.stats.count("executor.plan_verify_passes" if ok
                             else "executor.plan_verify_rejects", 1)

    def _note_opt(self, opt: Any) -> None:
        """Account one optimized plan launch (ops/plan_opt.OptStats —
        the before/after the megakernel leg attaches to the plan).
        '+=' is not atomic and batches can run from several
        threads."""
        with self._jit_stats_lock:
            self.opt_plans += 1
            self.opt_cse_hits += opt.cse_hits
            self.opt_entries_eliminated += opt.entries_eliminated
            self.opt_folds_reordered += opt.folds_reordered
            self.opt_bytes_saved += opt.bytes_saved
        if self.stats is not None:
            self.stats.count("executor.opt_plans", 1)
            self.stats.count("executor.opt_cse_hits", opt.cse_hits)
            self.stats.count("executor.opt_entries_eliminated",
                             opt.entries_eliminated)
            self.stats.count("executor.opt_folds_reordered",
                             opt.folds_reordered)
            self.stats.count("executor.opt_bytes_saved",
                             opt.bytes_saved)

    # -------------------------------------------- request-level result cache

    @contextlib.contextmanager
    def _dep_capture(self, deps: Optional[dict]):
        """Attach a request-tier dependency collector to this thread
        for the duration (None = no capture, zero overhead). The
        staging seam and the attr/translation read points record the
        version stamps the cached response will later be validated
        against."""
        if deps is None:
            yield
            return
        prev = getattr(self._tls, "deps", None)
        self._tls.deps = deps
        try:
            yield
        finally:
            self._tls.deps = prev

    def _request_cache_key(self, index_name: str, query, shards
                           ) -> Optional[tuple]:
        """The request tier's cache key, or None when the request is
        ineligible: cache off, mesh/cluster deployment (remote legs
        cache per node through the eval tier instead), non-string
        query, unparseable, or any call outside the staged-eval family
        (Count + bitmap calls — the flood workload; TopN rides the
        device rank cache, writes are never cacheable)."""
        if not self.result_cache.enabled or self.mesh is not None \
                or self.key_resolver is not None:
            return None
        if not isinstance(query, str):
            return None
        try:
            q = parse_string_cached(query)
        except Exception:
            return None
        calls = q.calls if isinstance(q, Query) else [q]
        for c in calls:
            if c.name != "Count" and c.name not in _BITMAP_CALLS:
                return None
        return ("req",) + request_key(index_name, query, shards)

    def _request_deps_current(self, deps: dict) -> bool:
        """Revalidate a request-tier dependency snapshot with pure
        host dict reads — the whole point: a hit touches no parser, no
        planner, no device."""
        for dk, val in deps.items():
            if not isinstance(dk, tuple):
                return False  # e.g. a stray "uncacheable" marker
            kind = dk[0]
            if kind == "view":
                _, iname, fname, vname = dk
                idx = self.holder.index(iname)
                f = idx.field(fname) if idx is not None else None
                view = f.view(vname) if f is not None else None
                cur = view.version_stamp() if view is not None else ()
            elif kind == "rattr":
                _, iname, fname = dk
                idx = self.holder.index(iname)
                f = idx.field(fname) if idx is not None else None
                cur = f.row_attr_store.gen if f is not None else -1
            elif kind == "ctrans":
                _, iname = dk
                idx = self.holder.index(iname)
                cur = idx.column_translator.size() \
                    if idx is not None else -1
            else:
                return False
            if cur != val:
                return False
        return True

    def _request_cache_get(self, key: tuple, profile=None, span=None
                           ) -> Optional[Dict[str, Any]]:
        """Request-tier lookup + hit attribution (cacheHit profile op,
        timed by the caller's open `cache.lookup` span)."""
        val = self.result_cache.lookup_request(
            key, self._request_deps_current)
        if val is None:
            return None
        if profile is not None:
            op = profile.begin_op("cache")
            op.attrs["cacheHit"] = True
            profile.end_op(op, span.duration() if span is not None
                           else 0.0)
        return val

    def _request_cache_fill(self, key: tuple, deps: dict,
                            resp: Dict[str, Any],
                            opts: Optional["ExecOptions"] = None
                            ) -> None:
        """Fill the request tier after shaping. Refused when the
        capture flagged a dependency it cannot name (literal operands)
        or the response embeds columnAttrs (shaped outside the
        capture window)."""
        if "uncacheable" in deps or not deps:
            return
        if opts is not None and opts.column_attrs:
            return
        from pilosa_tpu.executor.result_cache import approx_nbytes
        self.result_cache.fill(key, dict(deps), resp,
                               approx_nbytes(resp), tier="request")

    # ------------------------------------------------------------------ API

    def execute(self, index_name: str, query, shards: Optional[Sequence[int]]
                = None, profile=None) -> List[Any]:
        """Execute every call in `query` (reference executor.Execute,
        executor.go:84). `profile` is an optional utils/profile
        QueryProfile the run fills in."""
        results, _ = self._execute_query(index_name, query, shards,
                                         profile=profile)
        return results

    def _execute_query(self, index_name: str, query, shards, profile=None
                       ) -> Tuple[List[Any], "ExecOptions"]:
        # Two phases: dispatch every call's device program in call order
        # (jax dispatch is async — programs queue on the device), then
        # fetch/finalize. A multi-call query thus pays one pipelined
        # device→host drain instead of a blocking round trip per call —
        # the TPU analog of the reference streaming per-shard results
        # into reduceFn as they arrive (executor.go:2277).
        with self._profiled(profile):
            idx, staged, opts = _drive(self._dispatch_query(
                index_name, query, shards))
            prefetch_pendings(staged)
            return self._finalize_staged(idx, staged), opts

    def _dispatch_query(self, index_name: str, query, shards,
                        batch_tail_writes: bool = False):
        """Parse/validate/translate and dispatch every call's device
        program: a generator that returns (idx, staged, opts) with
        results still pending. `batch_tail_writes`: a later query in
        the same batch writes, so deferred reads must snapshot (see
        _tls.later_writes).

        It stops where a GroupBy's level loop hands up a count fetch
        it is about to block on (`_group_by_levels`) — only inside a
        flush whose dispatcher gives its members turns
        (`_batch_begin`), which puts the member's profile, dependency
        capture and collector on the thread around every step;
        `later_writes` is put back here, and the op's dispatchS leaves
        out the time the call stood aside for other members. Anywhere
        else a GroupBy blocks where it stands and the first step is the
        last (`_drive`)."""
        with TIMELINE.phase("plan"):
            if isinstance(query, str):
                query = parse_string_cached(query)
            if isinstance(query, Call):
                query = Query([query])
            if self.max_writes_per_request > 0 and \
                    write_call_count(query) > self.max_writes_per_request:
                raise ExecutionError("too many write commands")
            idx = self.holder.index(index_name)
            if idx is None:
                raise ExecutionError(f"index not found: {index_name}")
        opts = ExecOptions()
        staged = []
        calls = list(query.calls)
        prof = self._profile()
        if prof is not None:
            # Rebase finish_op indices: a profile may span several
            # dispatch/finalize rounds (the cluster path runs one
            # execute() per PQL call against the same profile).
            prof.mark_dispatch()
        try:
            for i, call in enumerate(calls):
                op = prof.begin_op(call.name) if prof is not None else None
                # One `plan` span per call; `h2d` and `dispatch` split
                # it. The op node's dispatchS is the span's reading:
                # its own segments plus what interrupted them.
                sp = TIMELINE.phase("plan", op=call.name)
                aside = 0.0     # seconds other members had the thread
                try:
                    with sp:
                        self._translate_call(idx, call)
                        # Deferred reads (TopN chunking) consult this to
                        # know whether lazily re-reading fragment state
                        # in finalize is still safe.
                        later = self._tls.later_writes = \
                            batch_tail_writes or any(
                                _peel_options(c).name in _WRITE_CALLS
                                for c in calls[i + 1:])
                        res = self._execute_call(idx, call, shards, opts)
                        if isinstance(res, types.GeneratorType):
                            # A GroupBy's level loop: each fetch it is
                            # about to block on goes up to the driver.
                            covered = None
                            while True:
                                try:
                                    dev = res.send(covered)
                                except StopIteration as end:
                                    res = end.value
                                    break
                                t0 = time.perf_counter()
                                covered = yield dev
                                aside += time.perf_counter() - t0
                                self._tls.later_writes = later
                        staged.append((call, res))
                finally:
                    if op is not None:
                        prof.end_op(op, sp.elapsed() - aside)
        finally:
            self._tls.later_writes = False
        return idx, staged, opts

    def _finalize_staged(self, idx: Index, staged) -> List[Any]:
        """Per call, in order: `d2h` — block on the call's result
        arrays — then `finish` — build the host-side result from them
        (k-selection, GroupBy merge, key translation). Call i's host
        work runs while the device is still on call i+1. materializeS
        of the op node is the two spans' readings."""
        prof = self._profile()
        results = []
        for i, (call, result) in enumerate(staged):
            pending = isinstance(result, _Pending)
            arrays = result.arrays if pending else ()
            fetch_s = fetch_host(arrays)
            with TIMELINE.phase("finish", op=call.name) as sp:
                if pending:
                    result = result.finalize()
                self._translate_result(idx, call, result)
            if prof is not None:
                prof.finish_op(i, fetch_s + sp.elapsed(),
                               transfer_nbytes(arrays))
            results.append(result)
        return results

    def execute_batch(self, requests: Sequence[Tuple[str, Any, Optional[
            Sequence[int]]]], profiles: Optional[Sequence[Any]] = None,
            deps: Optional[Sequence[Optional[dict]]] = None
            ) -> List[Any]:
        """Execute N independent queries with ONE pipelined device
        drain: every query's calls are dispatched before any result is
        fetched, and all pending transfers start asynchronously before
        the first blocking finalize. The cross-request extension of
        the multi-call pipeline (reference executor.go:84 evaluates a
        query's calls together; clients batch calls per request) —
        this is the API-layer amortization that makes 1 ms-class
        queries serve efficiently through a high-RTT link.

        Each element of `requests` is (index_name, query, shards).
        `profiles` (optional, aligned with `requests`) carries a
        QueryProfile per request; each request's dispatch and finalize
        phases run with its profile attached (the coalesced serving
        path feeds these).
        Returns one entry per request: a (results, opts) tuple on
        success — opts drives response shaping (columnAttrs), see
        shape_response — or the exception instance for that request
        (per-request errors don't fail the batch).

        `deps` (optional, aligned with `requests`) carries per-request
        dependency-capture dicts for the request-tier result cache:
        a non-None entry is attached to the thread while that
        request's dispatch and finalize phases run (execute_batch_
        shaped feeds these and fills the cache after shaping)."""
        return self.execute_batch_finish(
            self.execute_batch_begin(requests, profiles, deps))

    def execute_batch_begin(self, requests: Sequence[Tuple[str, Any,
            Optional[Sequence[int]]]],
            profiles: Optional[Sequence[Any]] = None,
            deps: Optional[Sequence[Optional[dict]]] = None
            ) -> "_BatchInFlight":
        """The dispatch half of execute_batch: parse, plan, fuse and
        LAUNCH every request's device programs, then start the async
        result prefetch — and return with results still pending. The
        pipelined serving path (server/coalescer.py) runs this for
        batch K+1 while batch K's execute_batch_finish is still
        draining, overlapping plan build + H2D with device time."""
        # One `plan` phase for the whole dispatch half (h2d, dispatch
        # and eval-tier cache lookups interrupt it).
        with TIMELINE.phase("plan", requests=len(requests)):
            return self._batch_begin(requests, profiles, deps)

    def _batch_begin(self, requests, profiles, deps) -> "_BatchInFlight":
        from pilosa_tpu.executor.fusion import FusionCollector
        profs = list(profiles) if profiles is not None \
            else [None] * len(requests)
        deps_l = list(deps) if deps is not None \
            else [None] * len(requests)
        out: List[Any] = [None] * len(requests)
        # Parse ONCE per request (the parsed tree is handed straight to
        # _dispatch_query — no second parse/clone) and pre-scan for
        # writes so earlier requests' deferred reads know to snapshot.
        parsed: List[Any] = [None] * len(requests)
        writes_after = [False] * len(requests)
        has_writes = [False] * len(requests)
        any_writes = False
        for j in range(len(requests) - 1, -1, -1):
            writes_after[j] = any_writes
            q = requests[j][1]
            try:
                if isinstance(q, str):
                    q = parse_string_cached(q)
                if isinstance(q, Call):
                    q = Query([q])
                parsed[j] = q
                if write_call_count(q) > 0:
                    has_writes[j] = True
                    any_writes = True
            except Exception as e:
                out[j] = e  # parse error: reported for this item only
        # Same-signature fusion across the batch's read-only requests:
        # terminal evals stage into the collector during dispatch and
        # flush as ONE vmapped program per signature group. A write-
        # containing request is a fence — groups open before it run
        # before its dispatch, and the request itself dispatches
        # uncollected — so every read observes exactly the fragment
        # state sequential execution would have shown it.
        fuser = FusionCollector(self)
        # GroupBy members take turns at their count fetches. A member
        # runs until its level loop is about to block (its program is
        # launched, its counts' copy started), waits in `turns` — the
        # earliest launched first, which is the order the device runs
        # them in — and the next member starts, up to
        # GROUPBY_INFLIGHT_MEMBERS; then the dispatcher blocks with the
        # member at the head, whose fetch completes first, while the
        # others' programs run behind it. All on this thread.
        done: List[Any] = [None] * len(requests)
        turns: deque = deque()
        self._tls.turns = True      # read by `_execute_group_by`

        def step(j, steps, covered=None):
            """Member j to its next count fetch or to its end, with
            what one member has of the thread put there around it."""
            fusing = contextlib.nullcontext() if has_writes[j] \
                else self._fusing(fuser)
            try:
                with self._profiled(profs[j]), \
                        self._dep_capture(deps_l[j]), fusing:
                    steps.send(covered)
            except StopIteration as end:
                done[j] = end.value
            except Exception as e:
                out[j] = e
            else:
                turns.append((j, steps))

        def drain(keep=0):
            while len(turns) > keep:
                j, steps = turns.popleft()
                step(j, steps, covered=bool(turns))

        try:
            for j, (index_name, _, shards) in enumerate(requests):
                if parsed[j] is None:
                    continue
                # A write is a fence for the members in flight as it is
                # for the collector's groups: all of them run to their
                # ends before it dispatches, and it to its own before
                # the next member starts.
                drain(0 if has_writes[j]
                      else self.GROUPBY_INFLIGHT_MEMBERS - 1)
                try:
                    if has_writes[j]:
                        fuser.flush()
                except Exception as e:
                    out[j] = e
                    continue
                step(j, self._dispatch_query(
                    index_name, parsed[j], shards,
                    batch_tail_writes=writes_after[j]))
                if has_writes[j]:
                    drain()
            drain()
        finally:
            self._tls.turns = False
            # Groups must resolve before any result is consumed —
            # prefetch/finalize below read through FusedEval handles.
            fuser.flush()
        # In the order of the requests, whatever order they ended in.
        staged_q = [(j, d) for j, d in enumerate(done) if d is not None]
        for _, (_, staged, _) in staged_q:
            prefetch_pendings(staged)
        return _BatchInFlight(staged_q, out, profs, deps_l)

    def execute_batch_finish(self, flight: "_BatchInFlight"
                             ) -> List[Any]:
        """The drain half of execute_batch: block on every pending
        transfer and build host results. Safe to run from a different
        thread than the begin (the pipelined coalescer's finalizer):
        profile/deps contexts re-attach per request below, and all
        device programs were dispatched with their operand banks
        snapshotted."""
        out = flight.out
        for j, (idx, staged, opts) in flight.staged_q:
            try:
                with self._profiled(flight.profs[j]), \
                        self._dep_capture(flight.deps_l[j]):
                    out[j] = (self._finalize_staged(idx, staged), opts)
            except Exception as e:
                out[j] = e
        return out

    def execute_batch_shaped(self, requests: Sequence[Tuple[
            str, Any, Optional[Sequence[int]]]],
            profiles: Optional[Sequence[Any]] = None) -> List[Any]:
        """execute_batch + per-request JSON shaping: one entry per
        request, either the shaped {"results": ...} dict or the
        exception instance for that request. Shared by API.query_batch
        (the /batch/query route) and the serving-path coalescer — one
        place owns the shape-or-error contract.

        This is the batch seam of the request-tier result cache:
        eligible requests are answered from cache before anything
        dispatches, and misses execute under dependency capture and
        fill after shaping. A request positioned AFTER a
        write-containing batchmate never consults the cache — its
        lookup would run before that write does, and sequential
        semantics demand it observe post-write state."""
        return self.execute_batch_shaped_finish(
            self.execute_batch_shaped_begin(requests, profiles))

    def execute_batch_shaped_begin(self, requests: Sequence[Tuple[
            str, Any, Optional[Sequence[int]]]],
            profiles: Optional[Sequence[Any]] = None) -> "_ShapedInFlight":
        """Cache lookups + the dispatch half of the shaped batch (see
        execute_batch_begin); execute_batch_shaped_finish drains,
        shapes and fills the cache — possibly from another thread."""
        n = len(requests)
        profs = list(profiles) if profiles is not None else [None] * n
        out: List[Any] = [None] * n
        keys: List[Optional[tuple]] = [None] * n
        deps_l: List[Optional[dict]] = [None] * n
        run: List[int] = []
        write_seen = False
        with TIMELINE.stage("cache.lookup") as sp:
            for j, (index_name, q, shards) in enumerate(requests):
                forced = profs[j] is not None and getattr(
                    profs[j], "forced", False)
                key = None
                if not write_seen and not forced:
                    key = self._request_cache_key(index_name, q, shards)
                if not write_seen and query_is_write(q):
                    write_seen = True
                if key is not None:
                    hit = self._request_cache_get(key, profs[j], sp)
                    if hit is not None:
                        out[j] = hit
                        continue
                    keys[j] = key
                    deps_l[j] = {}
                run.append(j)
            sp.set("hits", n - len(run))
            sp.set("misses", sum(1 for j in run if keys[j] is not None))
        flight = self.execute_batch_begin(
            [requests[j] for j in run],
            profiles=[profs[j] for j in run],
            deps=[deps_l[j] for j in run])
        return _ShapedInFlight(flight, out, keys, deps_l, run,
                               list(requests))

    def execute_batch_shaped_finish(self, sh: "_ShapedInFlight"
                                    ) -> List[Any]:
        out, keys, deps_l, run, requests = (sh.out, sh.keys, sh.deps_l,
                                            sh.run, sh.requests)
        res = self.execute_batch_finish(sh.flight)
        # One `finish` phase for shaping every request's response.
        with TIMELINE.phase("finish", op="shape", requests=len(run)):
            for j, r in zip(run, res):
                index_name = requests[j][0]
                if isinstance(r, Exception):
                    out[j] = r
                    continue
                results, opts = r
                try:
                    shaped = self.shape_response(index_name, results,
                                                 opts)
                except Exception as e:
                    out[j] = e
                    continue
                if deps_l[j] is not None:
                    self._request_cache_fill(keys[j], deps_l[j], shaped,
                                             opts)
                out[j] = shaped
        return out

    def execute_full(self, index_name: str, query,
                     shards: Optional[Sequence[int]] = None, profile=None
                     ) -> Dict[str, Any]:
        """Execute and return the full JSON-shaped response, including
        `columnAttrs` when an Options(columnAttrs=true) call requested them
        (reference executor.Execute, executor.go:134-165).

        Eligible read-only requests ride the request tier of the
        result cache: a generation-valid repeat returns the cached
        shaped response without parsing, planning, compiling or
        dispatching anything; misses execute under dependency capture
        and fill after shaping. Forced (?profile=true) profiles bypass
        the lookup — their tree must describe a real execution — but
        still refresh the fill."""
        forced = profile is not None and getattr(profile, "forced",
                                                 False)
        with TIMELINE.stage("cache.lookup") as sp:
            key = self._request_cache_key(index_name, query, shards)
            hit = None
            if key is not None and not forced:
                hit = self._request_cache_get(key, profile, sp)
            sp.set("hit", hit is not None)
        if hit is not None:
            return hit
        deps: Optional[dict] = {} if key is not None else None
        with self._dep_capture(deps):
            results, opts = self._execute_query(index_name, query,
                                                shards, profile=profile)
            with TIMELINE.phase("finish", op="shape"):
                resp = self.shape_response(index_name, results, opts)
                if deps is not None:
                    self._request_cache_fill(key, deps, resp, opts)
        return resp

    def shape_response(self, index_name: str, results, opts: "ExecOptions"
                       ) -> Dict[str, Any]:
        """JSON-shape executed results, attaching columnAttrs via the
        LOCAL translator when requested (shared by execute_full and the
        single-node batch path)."""
        from pilosa_tpu.executor.results import result_to_json
        resp: Dict[str, Any] = {"results": [result_to_json(r)
                                            for r in results]}
        if opts.column_attrs:
            idx = self.holder.index(index_name)
            ids = sorted({int(c) for r in results if isinstance(r, RowResult)
                          for c in r.columns().tolist()})
            resp["columnAttrs"] = column_attr_sets(
                idx, ids, resolve=lambda xs: self._resolve_col_ids(idx, xs))
        return resp

    # ------------------------------------------------------- key translation

    def _translate_call(self, idx: Index, call: Call) -> None:
        """String keys -> ids in place (reference translateCall,
        executor.go:2417-2505). Translation is call-shape-aware: only the
        row/column-bearing args of each call form are touched — generic
        string args (e.g. SetRowAttrs attribute values) pass through even
        when an equally-named keyed field exists. Keys are allocated on
        first use (TranslateColumnsToUint64 get-or-create semantics)."""
        col = call.args.get("_col")
        if isinstance(col, str):
            if not idx.keys:
                raise ExecutionError(
                    f"index {idx.name} does not use column keys")
            call.args["_col"] = self._resolve_col_key(idx, col)
        row = call.args.get("_row")
        fname = call.args.get("_field")
        if isinstance(row, str):
            field = idx.field(fname) if fname else None
            if field is None or not field.options.keys:
                raise ExecutionError(
                    f"string row value not allowed on field {fname}")
            call.args["_row"] = self._resolve_row_key(idx, field, row)
        # The one field=row arg of Row/Range/Set/Clear/ClearRow/Store.
        if call.name in ("Row", "Range", "Set", "Clear", "ClearRow",
                         "Store"):
            try:
                k, v = self._row_call_field(call)
            except ExecutionError:
                k, v = None, None
            if isinstance(v, str):
                field = idx.field(k)
                if field is None or not field.options.keys:
                    raise ExecutionError(
                        f"string row value not allowed on field {k}")
                call.args[k] = self._resolve_row_key(idx, field, v)
        # Rows(previous=..., column=...) (reference executor.go:2443-2460).
        if call.name in ("Rows", "TopN"):
            field = idx.field(fname) if fname else None
            prev = call.args.get("previous")
            if isinstance(prev, str):
                if field is None or not field.options.keys:
                    raise ExecutionError(
                        f"string previous not allowed on field {fname}")
                call.args["previous"] = self._resolve_row_key(idx, field,
                                                              prev)
            column = call.args.get("column")
            if isinstance(column, str):
                if not idx.keys:
                    raise ExecutionError(
                        f"index {idx.name} does not use column keys")
                call.args["column"] = self._resolve_col_key(idx, column)
        # GroupBy(previous=[...]): one entry per Rows child, translated
        # against that child's field (reference translateGroupByCall,
        # executor.go:2522-2577).
        if call.name == "GroupBy":
            prev = call.args.get("previous")
            if prev is not None:
                if not isinstance(prev, list):
                    raise ExecutionError(
                        "'previous' argument must be a list")
                if len(prev) != len(call.children):
                    raise ExecutionError(
                        f"mismatched lengths for previous: {len(prev)} "
                        f"and children: {len(call.children)}")
                for i, (p, child) in enumerate(zip(prev, call.children)):
                    if isinstance(p, str):
                        field = idx.field(child.args.get("_field"))
                        if field is None or not field.options.keys:
                            raise ExecutionError(
                                "prev value must be a row id (int) when "
                                "field doesn't have keys")
                        prev[i] = self._resolve_row_key(idx, field, p)
        filt = call.args.get("filter")
        if isinstance(filt, Call):
            self._translate_call(idx, filt)
        for child in call.children:
            self._translate_call(idx, child)

    def _translate_result(self, idx: Index, call: Call, result) -> None:
        """Ids -> string keys on results (reference translateResults,
        executor.go:2577)."""
        while call.name == "Options" and call.children:
            call = call.children[0]
        if isinstance(result, RowResult) and idx.keys:
            cap = getattr(self._tls, "deps", None)
            if cap is not None:
                # The response embeds translated column keys. The
                # store is append-only (an allocated mapping never
                # changes), but an id unresolved at fill time can gain
                # a key later — the size stamp invalidates then.
                # Stamp-then-read (first stamp wins): taken BEFORE the
                # resolve, so a key allocated mid-resolve leaves the
                # stored size behind and the entry fails validation
                # instead of caching the decimal fallback as current.
                cap.setdefault(("ctrans", idx.name),
                               idx.column_translator.size())
            cols = result.columns()  # cached on the result for to_json
            # Keep 1:1 alignment with columns; ids set outside the
            # translator (raw-id imports) fall back to their decimal form.
            result.keys = [k if k is not None else str(int(c))
                           for c, k in zip(
                               cols, self._resolve_col_ids(idx, cols))]
            return
        fname = call.args.get("_field")
        field = idx.field(fname) if fname else None
        keyed = field is not None and field.options.keys
        if isinstance(result, PairsResult) and keyed:
            result.keys = [k or str(r) for (r, _), k in zip(
                result.pairs,
                self._resolve_row_ids(idx, field,
                                      [r for r, _ in result.pairs]))]
        elif isinstance(result, RowIdentifiers) and keyed:
            result.keys = [k or str(r) for r, k in zip(
                result.rows, self._resolve_row_ids(idx, field, result.rows))]
        elif isinstance(result, list):
            for gc in result:
                if isinstance(gc, GroupCount):
                    for fr in gc.group:
                        gf = idx.field(fr.field)
                        if gf is not None and gf.options.keys:
                            fr.row_key = gf.row_translator.translate_id(
                                fr.row_id)

    # -------------------------------------------------------- call dispatch

    def _execute_call(self, idx: Index, call: Call,
                      shards: Optional[Sequence[int]],
                      opts: Optional["ExecOptions"] = None) -> Any:
        """The call's result, a `_Pending` over it, or — a GroupBy,
        bare or under Options, inside a flush that gives its members
        turns — its level loop, a generator that `_dispatch_query`
        runs."""
        name = call.name
        cap = getattr(self._tls, "deps", None)
        if cap is not None and name != "Count" \
                and name not in _BITMAP_CALLS:
            # Belt and braces: _request_cache_key already filters to
            # the staged-eval call family, but any path that slips a
            # non-staged read under capture must poison the fill, not
            # cache with incomplete dependencies.
            cap["uncacheable"] = True
        if name == "Options":
            return self._execute_options(idx, call, shards, opts)
        if name == "Count":
            return self._execute_count(idx, call, shards)
        if name in _BITMAP_CALLS:
            return self._execute_bitmap(idx, call, shards, opts)
        if name == "TopN":
            return self._execute_topn(idx, call, shards)
        if name == "Rows":
            return self._execute_rows(idx, call, shards)
        if name == "GroupBy":
            return self._execute_group_by(idx, call, shards)
        if name in ("Sum", "Min", "Max"):
            return self._execute_val_count(idx, call, shards, name)
        if name in ALL_WRITE_CALLS:
            return self._execute_write(idx, call, shards)
        raise ExecutionError(f"unknown call: {name}")

    def _execute_write(self, idx: Index, call: Call, shards) -> Any:
        """One write call applied where it stands in its query: the
        fragment (or attribute store), its op log, and — through the
        version it bumps — every cache and bank that held the old
        state, which the NEXT read of each repairs (core/view.py:
        `plan.bank_patch` or a rebuild). Span `write.apply`, counter
        `executor.writes{call:...}`."""
        name = call.name
        with TIMELINE.stage(
                "write.apply", call=name,
                counts=((f"executor.writes{{call:{name}}}", 1),)):
            if name == "Set":
                return self._execute_set(idx, call)
            if name == "Clear":
                return self._execute_clear(idx, call)
            if name == "ClearRow":
                return self._execute_clear_row(idx, call, shards)
            if name == "Store":
                return self._execute_store(idx, call, shards)
            if name == "SetRowAttrs":
                return self._execute_set_row_attrs(idx, call)
            return self._execute_set_column_attrs(idx, call)

    def _shards(self, idx: Index, shards, pad: bool = True) -> List[int]:
        available = idx.available_shards()
        out = list(shards) if shards is not None else (available or [0])
        if pad and self.mesh is not None:
            # Padding ids must be absent from the whole index, not just
            # the requested subset.
            floor = (max(available) + 1) if available else 0
            out = self.mesh.pad_shards(out, floor=floor)
        return out

    def _referenced_fields(self, idx: Index, call: Call,
                           out: set) -> bool:
        """Collect every field a row-call tree reads; False when the
        tree contains a construct this walk doesn't model (caller then
        keeps the full shard list)."""
        name = call.name
        if name in ("Row", "Range"):
            try:
                fname, _ = self._row_call_field(call)
            except ExecutionError:
                return False
            f = idx.field(fname)
            if f is None:
                return False
            out.add(f)
            return True
        if name == "Not":
            ef = idx.existence_field()
            if ef is None:
                return False
            out.add(ef)
            return all(self._referenced_fields(idx, c, out)
                       for c in call.children)
        if name in ("Intersect", "Union", "Difference", "Xor", "Shift",
                    "Threshold"):
            return bool(call.children) and all(
                self._referenced_fields(idx, c, out)
                for c in call.children)
        return False

    def _restrict_shards(self, idx: Index, call: Call,
                         shards: List[int]) -> List[int]:
        """Drop shards where NO referenced field has data — a leaf over
        an absent fragment is all-zeros, and zeros through any bitmap
        expression stay zeros, so dropped shards cannot contribute
        columns or counts. This is what keeps a narrow field (e.g. a
        time field covering one shard) from sweeping every shard of a
        wide index (the reference's executeRowShard likewise skips
        absent fragments, executor.go:1265). Field granularity: one
        availableShards union per field, no per-view walk."""
        fields: set = set()
        if not self._referenced_fields(idx, call, fields) or not fields:
            return shards
        covered: set = set()
        for f in fields:
            covered.update(f.available_shards())
        out = [s for s in shards if s in covered]
        # Keep one shard when nothing is covered: zero-size device
        # shapes are not worth the special-casing for an all-empty
        # result.
        return out or shards[:1]

    # ----------------------------------------------------- bitmap call eval

    def _execute_options(self, idx: Index, call: Call, shards,
                         opts: Optional["ExecOptions"]) -> Any:
        """Options(child, columnAttrs=…, excludeRowAttrs=…,
        excludeColumns=…, shards=[…]) — reference executeOptionsCall,
        executor.go:317-361. `columnAttrs` mutates the *outer* options (it
        shapes the whole response); the exclude flags apply to a copy used
        for the child only."""
        if len(call.children) != 1:
            raise ExecutionError("Options() takes exactly one child call")
        child_opts = ExecOptions(**vars(opts)) if opts is not None \
            else ExecOptions()
        for arg in ("columnAttrs", "excludeRowAttrs", "excludeColumns"):
            if arg in call.args and not isinstance(call.args[arg], bool):
                raise ExecutionError(f"Query(): {arg} must be a bool")
        if call.args.get("columnAttrs") and opts is not None:
            opts.column_attrs = True
        if "excludeRowAttrs" in call.args:
            child_opts.exclude_row_attrs = call.args["excludeRowAttrs"]
        if "excludeColumns" in call.args:
            child_opts.exclude_columns = call.args["excludeColumns"]
        if "shards" in call.args:
            arg = call.args["shards"]
            if not isinstance(arg, (list, tuple)) or not all(
                    isinstance(s, int) and not isinstance(s, bool)
                    and s >= 0 for s in arg):
                raise ExecutionError(
                    "Query(): shards must be a list of unsigned integers")
            shards = [int(s) for s in arg]
        return self._execute_call(idx, call.children[0], shards, child_opts)

    def _execute_bitmap(self, idx: Index, call: Call, shards,
                        opts: Optional["ExecOptions"] = None) -> RowResult:
        shards = self._shards(idx, self._restrict_shards(
            idx, call, self._shards(idx, shards, pad=False)))
        words = self._eval_tree(idx, call, shards, mode="row",
                                fusible=True)
        res = RowResult(shards, words)
        if opts is not None and opts.exclude_row_attrs:
            res.attrs = {}
        else:
            self._attach_row_attrs(idx, call, res)
        if opts is not None and opts.exclude_columns:
            res.clear_columns()
        return res

    def _execute_count(self, idx: Index, call: Call, shards) -> "_Pending":
        if len(call.children) != 1:
            raise ExecutionError("Count() takes exactly one row argument")
        shards = self._shards(idx, self._restrict_shards(
            idx, call.children[0], self._shards(idx, shards, pad=False)))
        # `counts` may be a FusedEval handle under execute_batch; both
        # it and a plain device array resolve through np.asarray (the
        # handle shares ONE host fetch across its whole fusion group).
        counts = self._eval_tree(idx, call.children[0], shards,
                                 mode="count", fusible=True)
        return _Pending(
            lambda: int(np.asarray(counts, dtype=np.int64).sum()),
            arrays=(counts,))

    def _eval_tree(self, idx: Index, call: Call, shards: List[int],
                   mode: str, fusible: bool = False):
        """Plan + compile (cached by shape) + run the call tree.

        `fusible=True` marks a TERMINAL eval: the program's output
        feeds only result finalization, never another device
        expression of the same query (Count's tree, a top-level
        bitmap call). When a fusion collector is installed
        (execute_batch) such evals stage instead of running — same-
        signature stages from different batched queries later run as
        ONE vmapped XLA program (executor/fusion.py) and the returned
        FusedEval handle resolves to this query's slice."""
        staged, prof, plan_s = self._stage_eval(idx, call, shards, mode)
        ckey = None
        rc = self.result_cache
        forced = prof is not None and getattr(prof, "forced", False)
        if fusible and rc.enabled and not forced \
                and self.mesh is None \
                and staged.fp is not None and staged.cacheable:
            # Eval-tier result cache (executor/result_cache.py): the
            # lookup sits BEFORE the fusion collector, so a hit skips
            # compile, dispatch and fetch — and a fusion group whose
            # members all hit simply never forms, let alone launches.
            # The key adds the index name (fp's operand keys are only
            # (field, view) — two indexes with same-named fields and
            # matching bank shapes would otherwise share one key and
            # evict each other on every lookup) and the concrete shard
            # tuple (fp covers shard COUNT via the signature; identity
            # must cover shard IDS); generation equality against the
            # operand banks' fragment versions is the implicit write
            # invalidation.
            ckey = ("eval", idx.name, staged.fp,
                    tuple(int(s) for s in shards))
            with TIMELINE.stage("cache.lookup", tier="eval") as cs:
                hit = rc.lookup(ckey, staged.gen)
                cs.set("hit", hit is not None)
            if hit is not None:
                if prof is not None:
                    node = prof.tree(staged.mode, staged.sig, None,
                                     plan_s, 0, staged.n_shards)
                    node.attrs["cacheHit"] = True
                return hit
        if fusible and FUSION_ENABLED and staged.lits is None and (
                self.mesh is None or self._mesh_fusion_enabled()):
            fuser = getattr(self._tls, "fuser", None)
            if fuser is not None:
                out = fuser.add(staged, prof, plan_s)
                return _CacheFillEval(out, rc, ckey, staged.gen) \
                    if ckey is not None else out
        out = self._run_staged(staged, prof, plan_s)
        return _CacheFillEval(out, rc, ckey, staged.gen) \
            if ckey is not None else out

    def _stage_eval(self, idx: Index, call: Call, shards: List[int],
                    mode: str):
        """Plan the call tree under a `plan.stage` span; returns what
        `_run_staged` and the batch's collector take: (staged eval,
        this thread's profile, the staging seconds)."""
        # planS of the eval node: tree staging, one reading.
        with TIMELINE.stage("plan.stage", mode=mode) as ps:
            staged = self._stage_tree(idx, call, shards, mode)
        return staged, self._profile(), ps.duration()

    def _mesh_fusion_enabled(self) -> bool:
        """Mesh requests enter the fusion collector exactly when the
        mesh megakernel path can take the staged evals (executor/
        megakernel.py's MESH_ENABLED + MEGAKERNEL_ENABLED switches):
        the collector is the gateway to the mesh cohort launch, and
        groups the launch doesn't take run per-group — the solo path
        is byte-identical to the unfused mesh path. With
        PILOSA_TPU_MESH=0 (or the megakernel off) mesh requests skip
        the collector entirely, the pre-mesh behavior."""
        from pilosa_tpu.executor import megakernel as megamod
        return megamod.MEGAKERNEL_ENABLED and megamod.MESH_ENABLED

    def _stage_tree(self, idx: Index, call: Call, shards: List[int],
                    mode: str) -> "_StagedEval":
        """Plan phase: walk the tree, build banks, resolve slots and
        the shape signature. Stages everything the compiled program
        needs without running (or even compiling) it — the seam the
        batch fusion pass groups on."""
        from pilosa_tpu.core.view import SparseBank

        # Hybrid layout restage loop: a sparse-planned key whose
        # SparseBank build bails (the view densified since the layout
        # decision) self-heals the view to dense and replans ONCE with
        # that key forced dense — bounded by the key count, and in
        # practice one extra host walk on a rare transition. The deps
        # stamps inside the loop keep the STAMP-THEN-READ order (first
        # stamp wins, so a restage cannot move a stamp past a read).
        force_dense: set = set()
        while True:
            plan = _Plan()
            plan.force_dense = force_dense
            expr = self._plan_call(idx, call, shards, plan)
            self._capture_deps(idx, plan)
            known = len(force_dense)
            banks, retry = self._stage_banks(idx, plan, shards,
                                             force_dense)
            if not retry:
                break
            if len(force_dense) == known:  # pragma: no cover
                # Each retry forces one MORE key dense, so the loop is
                # bounded by the plan's distinct sparse keys; a bail
                # that adds nothing would mean _stage_banks broke that
                # contract — fail loudly instead of spinning.
                raise ExecutionError(
                    "hybrid-layout staging failed to settle on a "
                    "bank representation")
        for i, key, row in plan.slot_refs:
            plan.idxs[i] = banks[plan.bank_pos[key]].slot(row)
        # Width resolves AFTER banks are built: a write landing between
        # planning and bank build can widen a view, and the plan width
        # must cover every actual bank width or _align_words would slice
        # off real set bits (plan-time widths alone are a TOCTOU). A
        # SparseBank's width is the dense width its rows expand to.
        plan.widths.extend(
            b.width if isinstance(b, SparseBank) else b.array.shape[-1]
            for b in banks)
        plan.resolve_width()
        bank_arrays = tuple(
            b.arrays if isinstance(b, SparseBank) else b.array
            for b in banks)
        # Literal operands go in as they are: their leaves align them to
        # the plan width inside the program.
        lits = tuple(plan.literals) or None
        for path, n in plan.range_leaves:
            self._note_range(path, n)
        # Sparse operands show as their (pos, starts) shape pair: a
        # layout flip must land in a DIFFERENT signature (different
        # program) even when the dense bank shape matches. Their dense
        # EXPANSION widths are part of the signature too — the leaf
        # closure bakes plan.sparse_widths[pos] as a trace constant,
        # and a view widening can change the width while leaving every
        # array SHAPE (pow2 pos pad, row capacity) and plan.width
        # untouched, so without this a stale compiled program would
        # silently drop the widened bits (dense leaves are covered
        # because their bank width IS the array's last dim).
        bshapes = [tuple(x.shape for x in a) if isinstance(a, tuple)
                   else a.shape for a in bank_arrays]
        xw = sorted(plan.sparse_widths.items())
        sig = (f"{mode}|{''.join(plan.sig_parts)}|W{plan.width}"
               f"|B{bshapes}{f'|XW{xw}' if xw else ''}"
               f"|L{lits and [a.shape for a in lits]}|S{len(shards)}")
        fp = gen = None
        if WORKLOAD.enabled or self.result_cache.enabled:
            # The fingerprint uses ROW IDS from slot_refs (bank slots
            # are append-order-dependent across rebuilds); the
            # generation is the operand banks' fragment-version map —
            # together the key BOTH the workload recorder's repeat
            # tracking and the result cache's eval tier use (one
            # identity, so /debug/hotspots' predicted savings and the
            # observed hit ratio describe the same keys). Host dict
            # work only, no device interaction (GL003-clean).
            fp = (sig, tuple((key, row) for _, key, row in
                             plan.slot_refs), tuple(plan.params))
            gen = tuple(tuple(sorted(b.versions.items())) for b in banks)
        if WORKLOAD.enabled:
            WORKLOAD.record_query(fp, gen, index=idx.name, mode=mode,
                                  n_shards=len(shards), sig=sig)
            prof = self._profile()
            for key in plan.bank_pos:
                WORKLOAD.record_read(idx.name, key[0], key[1], shards,
                                     rows=plan.rows_for.get(key))
                if prof is not None:
                    prof.touch_fragments(idx.name, key[0], key[1],
                                         shards)
        own = tuple(pos for pos, b in enumerate(banks)
                    if pos in plan.ranged or isinstance(b, SparseBank)
                    or b.subset)
        return _StagedEval(mode=mode, sig=sig, expr=expr,
                           width=plan.width, n_shards=len(shards),
                           bank_arrays=bank_arrays,
                           idxs=list(plan.idxs), params=list(plan.params),
                           lits=lits, fp=fp, gen=gen,
                           cacheable=not plan.literals,
                           ir=tuple(plan.ir) if plan.ir_ok else None,
                           own_banks=own,
                           shared_banks=tuple(
                               a for pos, a in enumerate(bank_arrays)
                               if pos not in own),
                           owned_banks=tuple(bank_arrays[pos]
                                             for pos in own))

    def _capture_deps(self, idx: Index, plan: _Plan) -> None:
        """Request-tier dependency capture, STAMP-THEN-READ: the
        version stamp is taken BEFORE the banks are fetched, so a
        write racing the build leaves the stored stamp behind the
        current one and the entry fails validation (a harmless
        spurious invalidation). Stamping after the read would let that
        race cache pre-write data under a post-write stamp — stale
        forever. First stamp wins across a multi-call query (and
        across hybrid-layout restages) for the same reason. One stamp
        per operand VIEW (coarser than the per-shard bank versions —
        any write or new fragment anywhere in the view invalidates —
        which is exactly what makes it airtight: shard-restriction
        (_restrict_shards) and default-shard growth cannot leak a
        stale hit past it)."""
        cap = getattr(self._tls, "deps", None)
        if cap is None:
            return
        for key in plan.bank_pos:
            dk = ("view", idx.name, key[0], key[1])
            if dk not in cap:
                f = idx.field(key[0])
                view = f.view(key[1]) if f is not None else None
                cap[dk] = view.version_stamp() \
                    if view is not None else ()
        if plan.literals:
            # Literal operand content is not named by the deps.
            cap["uncacheable"] = True

    def _stage_banks(self, idx: Index, plan: _Plan, shards,
                     force_dense: set):
        """Build every operand bank the plan names — SparseBanks for
        sparse-planned keys, dense (possibly row-subset) ViewBanks for
        the rest. Returns (banks, retry): retry=True means a sparse
        build bailed, the offending key is now in `force_dense`, and
        the caller must replan."""
        built: Dict[Tuple[str, str], Any] = {}
        banks: List[Any] = []
        for pos, key in enumerate(plan.bank_keys):
            sparse = plan.bank_sparse.get(key)
            bank = built.get(key)   # a range fold's pads repeat a key
            if bank is None:
                if sparse:
                    bank = self._get_sparse_bank(idx, key, shards)
                    if bank is None:
                        force_dense.add(key)
                        return banks, True
                else:
                    bank = self._get_bank(
                        idx, key, shards,
                        rows_needed=plan.rows_for.get(key))
                built[key] = bank
            if sparse:
                plan.sparse_widths[pos] = bank.width
            banks.append(bank)
        return banks, False

    def _get_sparse_bank(self, idx: Index, key: Tuple[str, str],
                         shards):
        """The SparseBank operand for a sparse-planned leaf, or None
        when the build bails (too dense / view gone) — in which case
        the view self-heals to dense so staging stops asking."""
        field = idx.field(key[0])
        view = field.view(key[1]) if field is not None else None
        if view is None:
            return None
        bank = view.sparse_bank(tuple(shards))
        if bank is None:
            view.set_layout("dense")
        return bank

    def _tree_fn(self, staged: "_StagedEval") -> Tuple[Callable, bool]:
        """Compile phase: the jitted program for a staged eval, from
        the shape-keyed cache when present. Returns (fn, jit_hit)."""
        import jax
        fn = self._jit_get(staged.sig)
        hit = fn is not None
        if fn is None:
            self._note_jit_compile(staged.program, staged.sig)
            fn = jax.jit(staged.runner())
            self._jit_put(staged.sig, fn)
        return fn, hit

    def _filter_group_fn(self, rep: "_StagedEval", lanes: int,
                         width: int) -> Tuple[Optional[Callable], bool]:
        """jit: (the banks the lanes share, a tuple of its own banks a
        lane, operands [lanes, n]) -> `lanes` filter rows [S, width],
        one array each: `rep`'s tree as ONE lane body, called a lane —
        lane b over the shared banks, its own (`_StagedEval.own_banks`)
        and row b of the operands (its slots, then its u32 scalars), so
        a leaf's read by a slot is a dynamic slice as in `tree_row` and
        never a gather over a bank. A view's bank goes in ONCE: the
        compiler adds up a program's operands as if no two were one
        buffer, and eight lanes that each brought the 2 GiB grid bank
        were 16 GiB to it. Returns (fn, jit_hit); `lanes` = 1 asks
        for nothing but the rule below (the caller runs `tree_row`).

        Every lane count of a signature — one, the solo program, among
        them — is built, and compiled, the first time a resident
        TopN's filter of that signature is met, in a batch or outside
        one (one discarded launch each, on `rep`'s operands): a
        flush's remainders reach the rarer ones late, and a compile
        belongs to warm-up, where `retraces` says it happened."""
        import jax
        from pilosa_tpu.executor.fusion import FILTER_LANES
        # `sig` does not say which positions are a lane's own (a range
        # fold of two days and a Union of two fields' rows may share
        # one); the program does, so its key does.
        head = f"filters{{}}|W{width}|O{rep.own_banks}|{rep.sig}"
        fn = self._jit_get(head.format(max(lanes, FILTER_LANES[0])))
        if fn is not None:
            return fn, True
        run, n_idx = rep.runner(), len(rep.idxs)
        # Lengths and positions only: a program in the jit cache that
        # closed over `rep` would keep the arrays `rep` was staged
        # against alive for good, a 2 GiB bank version among them.
        own, n_banks = rep.own_banks, len(rep.bank_arrays)

        def lane(lane_banks, row):
            # The slots go in as a list of scalars (u32: a dynamic
            # slice by an unsigned index wraps nothing around) and the
            # dense banks as _SlicedRows (a sparse bank is a tuple of
            # arrays, read as it is), so that no leaf indexes a bank by
            # a vector.
            return _align_words(run(
                [a if isinstance(a, tuple) else _SlicedRows(a)
                 for a in lane_banks],
                [row[j] for j in range(n_idx)], row[n_idx:], None), width)

        # One traced body for every lane of every lane count: a jit
        # inside the program's is a call to ONE sub-computation, which
        # XLA inlines — K unrolled copies would cost K times the
        # tracing and keep K times the equations alive for the
        # collector to walk.
        lane = jax.jit(named(lane, "tree_row_lane"))

        def multi(shared, owned, ops):
            def banks_of(mine):
                rest, mine = iter(shared), iter(mine)
                return [next(mine if pos in own else rest)
                        for pos in range(n_banks)]
            return tuple(lane(banks_of(mine), ops[b])
                         for b, mine in enumerate(owned))

        if lanes > 1:
            solo, hit = self._tree_fn(rep)
            if not hit:
                idxs, params, _ = self._staged_args(rep)
                solo(rep.bank_arrays, idxs, params, None)
        # graftlint: disable=GL003 — host lists marshalled for upload.
        row = np.asarray([*rep.idxs, *rep.params], np.uint32)
        for k in FILTER_LANES:
            self._note_jit_compile("tree_row_multi", head.format(k))
            built = jax.jit(named(multi, "tree_row_multi"))
            self._jit_put(head.format(k), built)
            if k == lanes:
                fn = built
            else:
                built(rep.shared_banks, (rep.owned_banks,) * k,
                      upload(np.tile(row, (k, 1))))
        # The caller's `dispatch` span pays for the one compile left:
        # that of the program it launches.
        if fn is None:
            self._tls.__dict__.pop("jit_miss", None)
        else:
            self._tls.jit_miss = head.format(lanes)[:200]
        return fn, False

    def _cached_args(self, akey: tuple, build: Callable):
        """LRU arg-cache get-or-build: returns (arrays, uploaded).
        `build()` — the `upload` of each operand vector — runs OUTSIDE
        the lock (device puts can block on the transfer); two threads
        racing the same new key just put twice, and last-insert
        wins."""
        with self._arg_cache_lock:
            cached = self._arg_cache.pop(akey, None)
        uploaded = cached is None
        if cached is None:
            cached = build()
        with self._arg_cache_lock:
            while len(self._arg_cache) >= 1024:
                # Evict oldest (dicts iterate in insertion order; the
                # pop-and-reinsert on hit makes this an LRU).
                self._arg_cache.pop(next(iter(self._arg_cache)))
            self._arg_cache[akey] = cached
        return cached, uploaded

    def _staged_args(self, staged: "_StagedEval"):
        """Device copies of a staged eval's idxs/params operand
        vectors, via the LRU arg cache. Returns (idxs, params,
        uploaded) — uploaded=True when this call paid the two
        host->device puts."""
        import jax.numpy as jnp

        def build():
            # graftlint: disable=GL003 — staged.idxs/params are host
            # lists; np.asarray here marshals them for upload, it
            # fetches nothing.
            idxs = upload(np.asarray(staged.idxs, dtype=np.int32))
            # graftlint: disable=GL003 — host-list upload, as above.
            params = upload(np.asarray(staged.params, dtype=np.uint32))
            return idxs, params

        akey = (staged.sig, tuple(staged.idxs), tuple(staged.params))
        (idxs, params), uploaded = self._cached_args(akey, build)
        return idxs, params, uploaded

    def _call_program(self, fn, *args):
        """Run phase: the single funnel every compiled tree-program
        invocation goes through — fused and unfused alike. Tests stub
        this to count real XLA dispatches. Callers bracket it with
        `_dispatch_span(program)`: the `dispatch` stage is the host's
        enqueue (an async call on a jit hit, trace + compile on a
        miss), never the device's execution."""
        return fn(*args)

    def _run_staged(self, staged: "_StagedEval", prof, plan_s: float):
        """Compile + run one staged eval on its own (the unfused
        path). `prof`/`plan_s` carry the profiling context and the
        staging seconds of the `plan.stage` span."""
        fn, jit_hit = self._tree_fn(staged)
        idxs, params, uploaded = self._staged_args(staged)
        # planS is the tree staging; dispatchS is the fn() call itself
        # (async enqueue on a cache hit, trace+compile on a miss);
        # deviceS is the fenced wait (_fence_device) — ?profile=true
        # queries only, so the unprofiled path keeps its fully-async
        # dispatch queue.
        with self._dispatch_span(staged.program) as ds:
            out = self._call_program(fn, staged.bank_arrays, idxs,
                                     params, staged.lits)
        if prof is None:
            return out
        dispatch_s = ds.duration()
        h2d = transfer_nbytes((idxs, params)) if uploaded else 0
        node = prof.tree(staged.mode, staged.sig, jit_hit, plan_s, h2d,
                         staged.n_shards)
        prof.tree_dispatch(node, dispatch_s)
        device_s = 0.0
        if prof.sample_device:
            # A `device` span exists ONLY when the profiler already
            # fenced this query (?profile=true) — the record adds zero
            # fences of its own.
            with TIMELINE.stage("device"):
                device_s = _fence_device(out)
            prof.tree_device(node, device_s)
        if staged.fp is not None:
            # Feed the cache-opportunity estimator: what one eval of
            # this signature actually cost (dispatch enqueue + the
            # fenced wait under ?profile=true) — the seconds a
            # result-cache hit would have saved.
            WORKLOAD.note_eval_seconds(staged.fp, dispatch_s + device_s)
        return out

    # -- planning: one host walk resolving banks/slots/params ---------------

    def _plan_call(self, idx: Index, call: Call, shards, plan: _Plan):
        """Returns expr(banks, idxs, params, lits) -> [S, W], appending to
        the plan. Mirrors executeBitmapCallShard's recursion
        (executor.go:540)."""
        import jax.numpy as jnp
        name = call.name

        if name in ("Row", "Range"):
            return self._plan_row_leaf(idx, call, shards, plan)
        if name in ("Not", "Shift") and len(call.children) != 1:
            raise ExecutionError(f"{name}() takes exactly one row argument")
        if name == "Not":
            ef = idx.existence_field()
            if ef is None:
                raise ExecutionError(
                    f"index {idx.name} does not support existence (Not)")
            ex = self._plan_slot_leaf(ef, VIEW_STANDARD, 0, shards, plan)
            sub = self._plan_call(idx, call.children[0], shards, plan)
            plan.sig_parts.append("!")
            # Not(x) IS existence \ x: the same left-fold "diff" node
            # the Difference lowering uses (operands pushed in order).
            plan.ir.append(("fold", "diff", 2))
            return lambda b, i, p, l: jnp.bitwise_and(
                ex(b, i, p, l), jnp.bitwise_not(sub(b, i, p, l)))
        if name == "Shift":
            n = call.uint_arg("n") or 1
            sub = self._plan_call(idx, call.children[0], shards, plan)
            plan.sig_parts.append(f"S{n}")
            plan.shift_bits += n  # widen the plan so bits can't fall off
            plan.ir_ok = False  # word-carry shifts have no mega opcode
            from pilosa_tpu.ops.bitset import shift_bits
            return lambda b, i, p, l: shift_bits(sub(b, i, p, l), n)
        if name in ("Intersect", "Union", "Difference", "Xor"):
            if not call.children:
                raise ExecutionError(f"{name}() requires row arguments")
            subs = [self._plan_call(idx, c, shards, plan)
                    for c in call.children]
            plan.sig_parts.append(f"{name[0]}{len(subs)}")
            ops = {"Intersect": jnp.bitwise_and, "Union": jnp.bitwise_or,
                   "Xor": jnp.bitwise_xor,
                   "Difference": lambda a, c: jnp.bitwise_and(
                       a, jnp.bitwise_not(c))}
            fold = {"Intersect": "and", "Union": "or", "Xor": "xor",
                    "Difference": "diff"}[name]
            plan.ir.append(("fold", fold, len(subs)))
            op = ops[name]
            return lambda b, i, p, l: functools.reduce(
                op, [s(b, i, p, l) for s in subs])
        if name == "Threshold":
            # Threshold(k=K, r1, ..., rN): columns set in at least K of
            # the N operand rows (the N-of-M / θ-threshold operator of
            # the bitmap-index literature). K=1 degenerates to Union,
            # K=N to Intersect; both reuse the fold node so they CSE
            # with real folds of the same operands.
            if not call.children:
                raise ExecutionError("Threshold() requires row arguments")
            k = call.args.get("k")
            # Strict integer: uint_arg would silently truncate k=1.5,
            # and an off-by-one threshold is a silent wrong answer.
            if isinstance(k, bool) or not isinstance(k, int) or k < 1:
                raise ExecutionError(
                    "Threshold() requires an integer argument k >= 1")
            n = len(call.children)
            subs = [self._plan_call(idx, c, shards, plan)
                    for c in call.children]
            plan.sig_parts.append(f"T{k}n{n}")
            if k > n:
                # More votes required than operands supplied: the
                # empty row. The operands were still planned (deps
                # capture and width resolution stay uniform), so the
                # lowering consumes them via the thresh node, which
                # maps k > n to a zeroed register.
                plan.ir.append(("thresh", k, n))
                return lambda b, i, p, l: jnp.zeros_like(
                    subs[0](b, i, p, l))
            if k == 1:
                plan.ir.append(("fold", "or", n))
                return lambda b, i, p, l: functools.reduce(
                    jnp.bitwise_or, [s(b, i, p, l) for s in subs])
            if k == n:
                plan.ir.append(("fold", "and", n))
                return lambda b, i, p, l: functools.reduce(
                    jnp.bitwise_and, [s(b, i, p, l) for s in subs])
            plan.ir.append(("thresh", k, n))

            def _thresh(b, i, p, l, _k=k, _subs=subs):
                # Thermometer accumulate: t[j] holds "at least j+1 of
                # the operands seen so far" — word-parallel, no
                # per-bit popcount (cf. bit-sliced N-of-M evaluation).
                vals = [s(b, i, p, l) for s in _subs]
                t = [jnp.zeros_like(vals[0]) for _ in range(_k)]
                for x in vals:
                    for j in range(_k - 1, 0, -1):
                        t[j] = jnp.bitwise_or(
                            t[j], jnp.bitwise_and(t[j - 1], x))
                    t[0] = jnp.bitwise_or(t[0], x)
                return t[_k - 1]
            return _thresh
        raise ExecutionError(f"{name} is not a row query")

    def _view_width(self, field: Field, view_name: str) -> int:
        """Bank word width without building the bank (matches what
        device_bank(trim=True) / _empty_bank will produce)."""
        from pilosa_tpu.core.fragment import CONTAINER_BITS
        view = field.view(view_name)
        if view is None:
            return CONTAINER_BITS // 32
        return view.trimmed_words()

    def _leaf_sparse(self, field: Field, view_name: str, key,
                     plan: _Plan) -> bool:
        """Hybrid-layout decision for one bank key: True when the
        view's leaves serve from its SparseBank. Snapshot of the
        view's layout mode — the plan's choice stays authoritative for
        this staging even if the background pass flips the mode
        mid-flight (both representations hold the same bits, so the
        only cost of racing is which correct program compiles)."""
        from pilosa_tpu.core import layout as layout_mod
        from pilosa_tpu.core.fragment import CONTAINER_BITS
        if not layout_mod.HYBRID_LAYOUT_ENABLED or self.mesh is not None:
            return False
        if key in plan.force_dense:
            return False
        view = field.view(view_name)
        if view is None or view.layout_mode != "sparse":
            return False
        return view.trimmed_words() * 32 <= CONTAINER_BITS

    def _plan_slot_leaf(self, field: Field, view_name: str, row_id: int,
                        shards, plan: _Plan, fresh: bool = False):
        """A single-row leaf: bank[slot] with the slot traced, padded to
        the plan width (banks are width-trimmed per view). The slot value
        is a placeholder until _eval_tree builds the bank. Over a
        sparse-resident view (hybrid layout) the leaf instead stages an
        "xslot": the program scatter-expands the SparseBank row to the
        dense register on device (ops/megakernel.expand_positions) —
        bit-identical to the dense gather, under a distinct signature
        so the two layouts never share a compiled program or a cached
        result entry. `fresh` gives the leaf an operand position of its
        own (_Plan.bank)."""
        key = (field.name, view_name)
        pos = plan.bank(key, fresh)
        sparse = plan.bank_sparse.get(key)
        if sparse is None:
            sparse = self._leaf_sparse(field, view_name, key, plan)
            plan.bank_sparse[key] = sparse
        plan.widths.append(self._view_width(field, view_name))
        i = len(plan.idxs)
        plan.idxs.append(0)
        plan.slot_refs.append((i, key, row_id))
        plan.rows_for.setdefault(key, set()).add(row_id)
        if sparse:
            from pilosa_tpu.ops.megakernel import expand_positions
            plan.sig_parts.append(f"x{pos}")
            plan.ir.append(("xslot", pos, i))
            n_shards = len(shards)
            return lambda b, idxs, p, l: _align_words(
                expand_positions(b[pos][0], b[pos][1], idxs[i],
                                 n_shards, plan.sparse_widths[pos]),
                plan.width)
        plan.sig_parts.append(f"r{pos}")
        plan.ir.append(("slot", pos, i))
        return lambda b, idxs, p, l: _align_words(b[pos][idxs[i]],
                                                  plan.width)

    def _plan_row_leaf(self, idx: Index, call: Call, shards, plan: _Plan):
        import jax.numpy as jnp
        field_name, row_ref = self._row_call_field(call)
        field = idx.field(field_name)
        if field is None:
            raise ExecutionError(f"field not found: {field_name}")
        if isinstance(row_ref, Condition):
            return self._plan_bsi_leaf(field, row_ref, shards, plan)
        if field.options.type == FIELD_TYPE_INT:
            raise ExecutionError(
                f"int field {field_name} requires a comparison, not =")
        row_id = self._row_id(field, row_ref)
        frm, to = call.arg("from"), call.arg("to")
        if frm is not None or to is not None:
            if field.options.type != FIELD_TYPE_TIME:
                raise ExecutionError(f"from/to on non-time field {field_name}")
            start = timeq.parse_timestamp(frm) if frm else datetime.min
            end = timeq.parse_timestamp(to) if to else datetime.max
            views = [v for v in field.views_for_range(start, end)
                     if field.view(v) is not None]
            if not views:
                plan.ir.append(("zero",))
                return (lambda b, i, p, l:
                        jnp.zeros((len(shards), plan.width), jnp.uint32))
            n = len(views)
            if n <= MAX_STATIC_RANGE_VIEWS:
                # An OR-fold of slot leaves, padded to a power of two
                # with the last view's leaf again (x | x = x) at operand
                # positions of its own: the signature and the operand
                # list then move with the bucket, not with n.
                plan.range_leaves.append(("fold", n))
                subs = [self._plan_slot_leaf(field, vn, row_id, shards, plan)
                        for vn in views]
                plan.ranged.update(plan.bank_pos[(field.name, vn)]
                                   for vn in views)
                pads = len(plan.bank_keys)
                subs += [self._plan_slot_leaf(field, views[-1], row_id,
                                              shards, plan, fresh=True)
                         for _ in range(_pow2(n) - n)]
                plan.ranged.update(range(pads, len(plan.bank_keys)))
                plan.sig_parts.append(f"U{len(subs)}")
                plan.ir.append(("fold", "or", len(subs)))
                return lambda b, i, p, l: functools.reduce(
                    jnp.bitwise_or, [s(b, i, p, l) for s in subs])
            # Literal: the grouped fold's union, passed as one operand.
            plan.range_leaves.append(("grouped", n))
            arr = self._range_union_grouped(field, views, row_id, shards)
            plan.widths.append(arr.shape[-1])
            k = len(plan.literals)
            plan.literals.append(arr)
            plan.sig_parts.append(f"l{k}")
            plan.ir_ok = False  # literal content is not plan-buffer data
            return lambda b, i, p, l: _align_words(l[k], plan.width)
        return self._plan_slot_leaf(field, VIEW_STANDARD, row_id, shards,
                                    plan)

    def _range_union_grouped(self, field: Field, views: List[str],
                             row_id: int, shards):
        """The [S, W] union of `row_id` over more views than one tree
        program folds: groups of MAX_STATIC_RANGE_VIEWS run the same
        OR-fold (bank operand + traced slot, a short last group padded
        to a power of two) as a jitted program of its own, each taking
        the one before's result as its accumulator. Subset banks of
        exactly one row per time view — a multi-year hourly range must
        not materialize every row of every view."""
        import jax
        import jax.numpy as jnp
        banks = [self._get_bank_for(field, vn, shards, rows_needed={row_id})
                 for vn in views]
        width = max(bk.array.shape[-1] for bk in banks)

        def fold(arrays, slots, acc):
            rows = [_pad_words(a[slots[j]], width)
                    for j, a in enumerate(arrays)]
            return functools.reduce(
                jnp.bitwise_or, rows if acc is None else [acc] + rows)

        acc = None
        for g in range(0, len(banks), MAX_STATIC_RANGE_VIEWS):
            group = banks[g:g + MAX_STATIC_RANGE_VIEWS]
            group += group[-1:] * (_pow2(len(group)) - len(group))
            arrays = tuple(bk.array for bk in group)
            key = (f"range_fold|W{width}|B{[a.shape for a in arrays]}"
                   f"|A{acc is not None}")
            fn = self._jit_get(key)
            if fn is None:
                self._note_jit_compile("range_fold", key)
                fn = jax.jit(named(fold, "range_fold"))
                self._jit_put(key, fn)
            # graftlint: disable=GL003 — host slot list marshalled for
            # upload; nothing is fetched.
            slots = upload(np.asarray([bk.slot(row_id) for bk in group],
                                      dtype=np.int32))
            with self._dispatch_span("range_fold"):
                acc = self._call_program(fn, arrays, slots, acc)
        return acc

    def _plan_bsi_leaf(self, field: Field, cond: Condition, shards,
                       plan: _Plan):
        """BSI comparison leaf: planes gathered from the bsig view bank via
        traced indices; the predicate operand rides in params."""
        import jax.numpy as jnp
        bsig = field.bsi_groups.get(field.name)
        if bsig is None:
            raise ExecutionError(f"field {field.name} is not an int field")
        depth = bsig.bit_depth
        view_name = view_bsi_name(field.name)
        key = (field.name, view_name)
        pos = plan.bank(key)
        # BSI plane banks stay dense: each leaf gathers depth+1 rows,
        # which the hybrid layout's per-row expansion has no win on.
        plan.bank_sparse.setdefault(key, False)
        plan.widths.append(self._view_width(field, view_name))
        i0 = len(plan.idxs)
        rows_set = plan.rows_for.setdefault(key, set())
        for off, r in enumerate(range(depth + 1)):
            plan.idxs.append(0)
            plan.slot_refs.append((i0 + off, key, r))
            rows_set.add(r)

        def planes_of(b, idxs):
            return _align_words(b[pos][idxs[i0:i0 + depth + 1]],
                                plan.width)

        op = cond.op

        def zeros_leaf():
            plan.ir.append(("zero",))
            return (lambda b, i, p, l:
                    jnp.zeros((len(shards), plan.width), jnp.uint32))

        def push_value(base: int) -> int:
            """Base values ride as two u32 limbs in the traced params
            (depth can reach 63 planes; reference int fields span int64,
            field.go:1360)."""
            j = len(plan.params)
            plan.params.extend([base & 0xFFFFFFFF,
                                (base >> 32) & 0xFFFFFFFF])
            return j

        def limbs(p, j):
            return (p[j], p[j + 1])

        if op == BETWEEN:
            lo_hi = cond.int_slice()
            lo, ok_lo = bsig.base_value_clamped(lo_hi[0], ">=")
            hi, ok_hi = bsig.base_value_clamped(lo_hi[1], "<=")
            if not (ok_lo and ok_hi) or lo > hi:
                plan.sig_parts.append("z")
                return zeros_leaf()
            j = push_value(lo)
            k = push_value(hi)
            plan.sig_parts.append(f"c><{pos}d{depth}")
            plan.ir.append(("bsi", "between", pos, i0, depth, j, k, True))
            return lambda b, i, p, l: bsi.between(
                planes_of(b, i), limbs(p, j), limbs(p, k))
        value = int(cond.value)
        base, in_range = bsig.base_value_clamped(value, op)
        if op in (EQ, NEQ) and not in_range:
            if op == EQ:
                plan.sig_parts.append("z")
                return zeros_leaf()
            plan.sig_parts.append(f"cn{pos}d{depth}")
            plan.ir.append(("bsi", "notnull", pos, i0, depth, 0, 0, False))
            return lambda b, i, p, l: bsi.not_null(planes_of(b, i))
        if op in (LT, LTE, GT, GTE) and not in_range:
            plan.sig_parts.append("z")
            return zeros_leaf()
        if op in (LT, LTE):
            allow_eq = (op == LTE) or (value > bsig.max)
        elif op in (GT, GTE):
            allow_eq = (op == GTE) or (value < bsig.min)
        else:
            allow_eq = False
        j = push_value(base)
        kernels = {
            EQ: lambda pl, v: bsi.eq(pl, v),
            NEQ: lambda pl, v: bsi.neq(pl, v),
            LT: lambda pl, v: bsi.lt(pl, v, allow_eq=allow_eq),
            LTE: lambda pl, v: bsi.lt(pl, v, allow_eq=True),
            GT: lambda pl, v: bsi.gt(pl, v, allow_eq=allow_eq),
            GTE: lambda pl, v: bsi.gt(pl, v, allow_eq=True),
        }
        kern = kernels[op]
        # The megakernel lowering (ops/megakernel.py lower_bsi) expands
        # these into the exact AND/OR/ANDNOT scan executor/bsi.py runs,
        # branch decisions taken on the HOST param values the unfused
        # path feeds the traced jnp.where selects.
        ir_kind = {EQ: "eq", NEQ: "neq", LT: "lt", LTE: "lt",
                   GT: "gt", GTE: "gt"}[op]
        ir_allow = allow_eq if op in (LT, GT) else True
        if op in (EQ, NEQ):
            ir_allow = False
        plan.ir.append(("bsi", ir_kind, pos, i0, depth, j, 0, ir_allow))
        plan.sig_parts.append(f"c{op}{int(allow_eq)}{pos}d{depth}")
        return lambda b, i, p, l: kern(planes_of(b, i), limbs(p, j))

    # ----------------------------------------------------------- bank fetch

    # Per-bank HBM cap: a view whose FULL bank would exceed this is served
    # by a cached row-subset bank holding only the rows the query needs
    # (VERDICT r1 missing #4: unbounded device_bank on the general path).
    BANK_MAX_BYTES = int(os.environ.get("PILOSA_TPU_BANK_BYTES", 2 << 30))

    def _bank_device_bytes(self, shape) -> int:
        """What ONE device holds of a [rows, shards, words] bank this
        executor would build: the number every per-device limit
        (TOPN_MAX_BANK_BYTES, BANK_MAX_BYTES, the BankBudget) is compared
        with. Without a mesh it is the whole array."""
        return device_share_bytes(shape, self._bank_sharding)

    def _get_bank(self, idx: Index, key: Tuple[str, str], shards,
                  rows_needed=None):
        field = idx.field(key[0])
        return self._get_bank_for(field, key[1], shards,
                                  rows_needed=rows_needed)

    def _get_bank_for(self, field: Field, view_name: str, shards,
                      rows_needed=None):
        view = field.view(view_name)
        if view is None:
            # Reads must not create views; absent view = all-zero rows.
            return self._empty_bank(len(shards))
        shards = tuple(shards)
        if rows_needed is not None:
            from pilosa_tpu.core.view import bank_capacity
            # The full bank holds the rows the view HAS: the union over
            # the shards, which the view keeps per fragment versions (a
            # TopN prices its bank by the same tuple). A sum over the
            # shards would call a 77-row field on 16 shards 1,232 rows.
            n_rows = len(view.merged_row_ids(shards))
            full_bytes = self._bank_device_bytes(
                (bank_capacity(n_rows), len(shards), view.trimmed_words()))
            if full_bytes > self.BANK_MAX_BYTES \
                    and len(rows_needed) < n_rows:
                return view.device_bank(shards, rows=sorted(rows_needed),
                                        mesh=self.mesh, trim=True,
                                        cache_rows=True)
        return view.device_bank(shards, mesh=self.mesh, trim=True)

    # Placeholder zero banks are keyed by shard count, which GROWS
    # with the index: without a bound, every resize strands the old
    # count's bank (and its ledger row) in HBM forever. A handful of
    # live entries is plenty — queries only ever need the current
    # shard counts.
    BANK_CACHE_MAX = 8

    def _empty_bank(self, n_shards: int):
        import jax.numpy as jnp
        from pilosa_tpu.core.view import ViewBank
        mesh_key = self.mesh.cache_key() if self.mesh else None
        key = f"emptybank:{n_shards}:{mesh_key}"
        # Pop-and-reinsert on hit: dict insertion order doubles as LRU
        # order (the _jit_cache idiom). The build runs OUTSIDE the lock
        # (a device put can block on the transfer); two threads racing
        # the same new key both build, first-insert wins and the loser
        # adopts it. Ledger updates happen under the cache lock (the
        # ledger lock is a leaf) so an evict/rebuild interleave cannot
        # unregister another thread's freshly registered entry.
        with self._bank_cache_lock:
            bank = self._bank_cache.pop(key, None)
            if bank is not None:
                self._bank_cache[key] = bank
                return bank
        from pilosa_tpu.core.fragment import CONTAINER_BITS
        host = np.zeros((1, n_shards, CONTAINER_BITS // 32), np.uint32)
        arr = self.mesh.put_bank(host) if self.mesh \
            else upload(host)
        built = ViewBank(arr, {}, 0, {})
        with self._bank_cache_lock:
            bank = self._bank_cache.pop(key, None)
            if bank is None:
                bank = built
                while len(self._bank_cache) >= max(1, self.BANK_CACHE_MAX):
                    old = next(iter(self._bank_cache))
                    self._bank_cache.pop(old)
                    LEDGER.unregister("bank", old, owner=self)
                LEDGER.register("bank", key, host.nbytes, owner=self,
                                view="(placeholder)", nShards=n_shards,
                                rows=0)
            self._bank_cache[key] = bank
        return bank

    def _row_call_field(self, call: Call) -> Tuple[str, Any]:
        """Extract (field, row-or-condition) from a Row()/Range() call."""
        for k, v in call.args.items():
            if k in ("from", "to", "_field") or k.startswith("_"):
                continue
            return k, v
        raise ExecutionError(f"{call.name}() requires a field argument")

    def _row_id(self, field: Field, row_ref) -> int:
        if isinstance(row_ref, bool):
            return 1 if row_ref else 0
        if isinstance(row_ref, int):
            return row_ref
        if isinstance(row_ref, str):
            raise ExecutionError(
                f"field {field.name}: row keys require keys=True "
                "(translation handled at the API layer)")
        raise ExecutionError(f"invalid row reference {row_ref!r}")

    # ----------------------------------------------------------------- TopN

    def _counts_fn(self, with_filter: bool, shape) -> Callable:
        """jit: bank chunk [R, S, W] (∧ filter [S, W]) -> counts [R]:
        |row ∧ filter| per row, or without a filter |row| — every
        unfiltered TopN's counts, and the rows' own popcounts that a
        tanimoto call reads, which this program computes once a bank
        version (`_bank_popcounts`) and the bank keeps."""
        import jax
        from pilosa_tpu.ops.bitset import masked_row_counts, popcount
        program = self._counts_program(with_filter)
        key = f"topn:{with_filter}:{shape}"
        fn = self._jit_get(key)
        if fn is None:
            self._note_jit_compile(program, key)
            if with_filter:
                def run(chunk, filt):
                    return masked_row_counts(chunk, filt)
            else:
                def run(chunk, filt):
                    return popcount(chunk, axis=(-2, -1))
            fn = jax.jit(named(run, program))
            self._jit_put(key, fn)
        return fn

    @staticmethod
    def _counts_program(with_filter: bool) -> str:
        """The TopN bank sweep's name in traces: which of the two
        one-filter programs a call's arguments selected (a group of
        sweeps runs a third, `topn_sweep_multi`)."""
        return "topn_sweep" if with_filter else "topn_sweep_unfiltered"

    def _counts_multi_fn(self, bank_array, filt, lanes: int) -> Callable:
        """jit: bank [R, S, W], `lanes` filters [S, W] each -> counts
        [lanes, R] from one pass over the bank. Every lane count of a
        bank shape is built, and compiled, the first time a group of
        that shape forms (one discarded launch each, on that group's
        bank and `filt`): a flush's remainders reach the rarer ones
        late, and a compile belongs to warm-up, where `retraces` says
        it happened."""
        import jax
        from pilosa_tpu.executor.fusion import SWEEP_LANES
        from pilosa_tpu.ops.bitset import masked_row_counts_multi
        fn = self._jit_get(f"topn_multi:{lanes}:{bank_array.shape}")
        if fn is None:
            for k in SWEEP_LANES:
                key = f"topn_multi:{k}:{bank_array.shape}"
                self._note_jit_compile("topn_sweep_multi", key)
                built = jax.jit(
                    named(masked_row_counts_multi, "topn_sweep_multi"))
                self._jit_put(key, built)
                if k == lanes:
                    fn = built
                else:
                    built(bank_array, *[filt] * k)
        return fn

    def _dispatch_sweep_group(self, bank_array, filters):
        """Queue ONE program for the filtered sweeps of one bank that a
        begin half staged (fusion.FusionCollector.add_sweep); returns
        (unfetched device output, lanes). One filter: `topn_sweep`, as
        outside a batch, output [R], lanes 1. More: `topn_sweep_multi`
        at the next lane count, output [lanes, R]; the last filter
        fills the pad lanes again, at operand positions of their own,
        and nobody reads them."""
        from pilosa_tpu.executor.fusion import SWEEP_LANES
        n = len(filters)
        if n == 1:
            self._note_sweep_group(1, 1)
            return self._dispatch_counts(bank_array, filters[0]), 1
        lanes = next(k for k in SWEEP_LANES if k >= n)
        fn = self._counts_multi_fn(bank_array, filters[0], lanes)
        with self._dispatch_span("topn_sweep_multi") as ds:
            ds.set("filters", n)
            ds.set("lanes", lanes)
            out = self._call_program(
                fn, bank_array, *filters, *[filters[-1]] * (lanes - n))
        self._note_sweep_launch()
        self._note_sweep_group(lanes, n)
        return out, lanes

    def _dispatch_counts(self, bank_array, filter_words):
        """Queue the counts kernel; returns unfetched device output: the
        counts [R]. Width-trimmed banks intersect against the same
        prefix of the filter: slicing a wider filter is safe (bank rows
        have no bits past their width), and padding a narrower one is
        safe (zeros cannot intersect)."""
        filter_words = _align_words(filter_words, bank_array.shape[-1])
        with_filter = filter_words is not None
        fn = self._counts_fn(with_filter, bank_array.shape)
        # Through the _call_program funnel: TopN sweeps are device
        # dispatches too.
        with self._dispatch_span(self._counts_program(with_filter)) as ds:
            ds.set("rows", bank_array.shape[0])
            ds.set("words", bank_array.shape[-1])
            out = self._call_program(fn, bank_array, filter_words)
        self._note_sweep_launch()
        return out

    # Where a tanimoto call's |row| came from, one of these per call
    # and bank, counted under `executor.bank_popcounts{path:<p>}`.
    POPCOUNT_PATHS = ("kept", "swept")

    def _bank_popcounts(self, bank):
        """The rows' own popcounts of `bank` (the tanimoto denominator's
        |row|) for one tanimoto call, and whether this call launched
        their sweep. They are the bank version's, not the query's: the
        first call to meet a version launches `topn_sweep_unfiltered`
        over it (`swept`), every later one finds the pending or the
        fetched vector (`kept`)."""
        raw, swept = bank.row_popcounts(
            lambda array: self._dispatch_counts(array, None))
        if swept:
            self._note_topn_rows("swept", bank.array.shape[0])
        if self.stats is not None:
            self.stats.with_tags(
                f"path:{'swept' if swept else 'kept'}").count(
                "executor.bank_popcounts", 1)
        return raw, swept

    def _popcount_row(self, words):
        """Dispatch a total popcount over row words [S, W] (device)."""
        import jax
        from pilosa_tpu.ops.bitset import popcount
        fn = self._jit_get("popcount_row")
        if fn is None:
            self._note_jit_compile("popcount_row", "popcount_row")
            fn = jax.jit(named(lambda w: popcount(w, axis=(-2, -1)),
                               "popcount_row"))
            self._jit_put("popcount_row", fn)
        with self._dispatch_span("popcount_row"):
            return self._call_program(fn, words)

    def _tanimoto_source(self, filter_words):
        """A tanimoto call's |filter row|, dispatched and left on the
        device; counts the call (`executor.tanimoto_sweeps`)."""
        if self.stats is not None:
            self.stats.count("executor.tanimoto_sweeps", 1)
        return self._popcount_row(filter_words)

    def _execute_topn(self, idx: Index, call: Call, shards) -> PairsResult:
        """Exact TopN (reference executeTopN 2-phase approximation,
        executor.go:694-733, fragment.top :1067). On TPU exact per-row
        counts are one batched popcount over the view bank, so no candidate
        phase or ranked-cache dependency is needed — strictly stronger than
        the reference's cache-approximate result. Row sets larger than
        TOPN_CHUNK_ROWS stream through the device in chunks."""
        field_name = call.arg("_field")
        field = idx.field(field_name)
        if field is None:
            raise ExecutionError(f"field not found: {field_name}")
        n = call.uint_arg("n") or 0
        shards = self._shards(idx, shards)
        view = field.view(VIEW_STANDARD)
        if view is None:
            return PairsResult([])
        # Sweep only shards this field's view covers (absent fragments
        # contribute zero to every row count, filtered or not) — a
        # narrow field on a wide index must not upload empty bank
        # columns. Restriction happens BEFORE the filter tree runs so
        # filter words stay shard-aligned with the bank.
        covered = [s for s in shards if view.fragment(s) is not None]
        if not covered:
            return PairsResult([])
        if len(covered) < len(shards):
            shards = self._shards(idx, covered)

        tanimoto = call.uint_arg("tanimotoThreshold") or 0
        fuser = getattr(self._tls, "fuser", None)
        filter_words = None
        # A filter whose only reader is a staged sweep is staged too:
        # inside a batch a filter tree that carries no literal operand
        # waits, planned, for the resident branch below, where the
        # batch's collector takes it with the sweep
        # (fusion.FusionCollector.add_filter). Any other branch, and a
        # call outside a batch, runs it at once. A tanimoto call reads
        # its filter at once (`_popcount_row`), so it never waits.
        staged_filter = None    # (staged eval, profile, plan seconds)
        groupable = False       # a filter a batch stages with its sweep
        if call.children:
            staged_filter = self._stage_eval(idx, call.children[0],
                                             shards, "row")
            groupable = not tanimoto and staged_filter[0].lits is None
            if fuser is None or not groupable:
                filter_words = self._run_staged(*staged_filter)
        attr_name = call.arg("attrName")
        allowed_rows = None
        if attr_name is not None:
            allowed_rows = set(field.row_attr_store.ids_matching(
                attr_name, call.arg("attrValues", [])))
        # The rule applies only WITH a filter (filterless it would zero
        # every denominator and empty the result); only then does the
        # answer read the rows' own popcounts.
        similar = bool(tanimoto) and filter_words is not None
        # Candidate restriction + absolute count floor (reference
        # topOptions.RowIDs / MinThreshold, fragment.go:1248,
        # executor.go:698).
        ids_arg = call.arg("ids")
        min_threshold = call.uint_arg("threshold") or 0

        # Merged row list is cached on the view per shard set, keyed on
        # fragment versions — repeat queries alias the same tuple (the
        # per-query union/sort is host work linear in the row count,
        # seconds at tens of millions of rows). Never mutated
        # downstream — every refinement rebinds.
        view_rows = view.merged_row_ids(shards)
        all_rows = view_rows
        if allowed_rows is not None:
            all_rows = [r for r in all_rows if r in allowed_rows]
        if ids_arg:
            wanted = {int(i) for i in ids_arg}
            all_rows = [r for r in all_rows if r in wanted]
        if not all_rows:
            return PairsResult([])
        if WORKLOAD.enabled:
            # Heatmap the sweep BEFORE the warm-cache shortcut: a
            # cache-served TopN is still workload (host dict work only).
            # Small candidate sets (ids=... leaderboard refreshes)
            # record row identities; full-view sweeps record the
            # aggregate scan size.
            WORKLOAD.record_read(idx.name, field_name, VIEW_STANDARD,
                                 shards, rows=all_rows)
            prof = self._profile()
            if prof is not None:
                prof.touch_fragments(idx.name, field_name,
                                     VIEW_STANDARD, shards)

        # Warm-cache shortcut (reference fragment.top over rankCache,
        # fragment.go:1067, cache.go:136): when every fragment's cache
        # still holds EVERY present row (cardinality within the cache
        # bound, so nothing was ever evicted), the cached per-row counts
        # are exact — every write path refreshes them — and TopN needs no
        # device work at all. Filters and tanimoto need real bitmaps, so
        # they always take the sweep.
        selfcheck_pairs = None  # warm answer being verified this query
        if not call.children and not tanimoto:
            cached = self._topn_cached_counts(view, shards)
            if cached is not None:
                self.topn_cache_hits += 1
                rows_arr = np.asarray(all_rows, dtype=np.uint64)
                counts_arr = np.fromiter(
                    (cached.get(r, 0) for r in all_rows),
                    dtype=np.int64, count=len(all_rows))
                keep = counts_arr > max(0, min_threshold - 1)
                rows_arr, counts_arr = rows_arr[keep], counts_arr[keep]
                rows_arr, counts_arr = _topn_candidates(rows_arr,
                                                        counts_arr, n)
                order = np.lexsort((rows_arr, -counts_arr))
                if n:
                    order = order[:n]
                warm = [(int(rows_arr[o]), int(counts_arr[o]))
                        for o in order]
                # == 1 % EVERY, not == 1: at EVERY=1 (check every hit)
                # the residue is 0 and a literal ==1 would never match.
                if not (TOPN_SELFCHECK_EVERY and self.topn_cache_hits
                        % TOPN_SELFCHECK_EVERY == 1 % TOPN_SELFCHECK_EVERY):
                    self._note_topn("fragment_cache")
                    return PairsResult(warm)
                # Sampled self-check: fall through to the exact sweep
                # and compare in finalize (both orderings are the same
                # deterministic (-count, row) lexsort, so list equality
                # is the correct test).
                self.topn_selfchecks += 1
                selfcheck_pairs = warm

        # Device rank cache (ROADMAP item 3b; core/cache.RANK_CACHE):
        # filterless TopN over a warm bank answers from a cached [R]
        # per-row count vector in HBM — a device top-k (or one tiny
        # host fetch for restricted candidate sets) instead of the
        # [R, S, W] popcount sweep below. Version-validated against
        # the bank's fragment generations: unchanged reuses, small
        # churn patches only the written rows, anything else rebuilds
        # with the sweep this path would have paid anyway. The sampled
        # self-check deliberately bypasses it — its exact leg must
        # exercise the real sweep.
        if not call.children and not tanimoto and self.mesh is None \
                and selfcheck_pairs is None:
            from pilosa_tpu.core.cache import RANK_CACHE
            if RANK_CACHE.enabled:
                res = self._topn_rank_cached(view, shards, view_rows,
                                             all_rows, n, min_threshold)
                if res is not None:
                    self._note_topn("rank_cache")
                    return res

        # Dispatch phase: queue every device program (counts sweeps, and
        # the tanimoto denominator popcount); nothing is fetched yet.
        # The HBM bound must consider the *bank* size (all view rows), not
        # the attr-filtered subset — the full-bank path materializes every
        # view row.
        dispatched = []  # (rows, bank, counts_out, the bank's popcounts)
        arrays = ()
        swept = False   # this call's `arrays` list the bank's popcounts
        chunked: List[List[int]] = []
        # Banks are width-trimmed for the sweep: only whole-row popcounts
        # are computed, and the dropped word tail is all-zero.
        from pilosa_tpu.core.view import bank_capacity
        width = view.trimmed_words()
        bank_bytes = self._bank_device_bytes(
            (bank_capacity(len(view_rows)), len(shards), width))
        if bank_bytes <= TOPN_MAX_BANK_BYTES:
            # Hot path: one fused popcount sweep over the whole cached bank
            # (no gather); rows map to slots host-side, unused slots are
            # zero rows and drop out naturally.
            self._note_topn("resident")
            bank = view.device_bank(tuple(shards), mesh=self.mesh,
                                    trim=True)
            if fuser is not None and call.children and not tanimoto:
                # Inside a batch the sweep waits for its bankmates: the
                # filtered sweeps of one bank array share one pass
                # (fusion.FusionCollector.add_sweep). The lane holds the
                # array read here, as a dispatch would. A tanimoto
                # sweep stays a launch of its own: a group on one-lane
                # rows is not measured (ROADMAP A8).
                if filter_words is None:
                    filter_words = fuser.add_filter(
                        *staged_filter, bank.array.shape[-1])
                out = fuser.add_sweep(bank.array, filter_words)
            else:
                if groupable:
                    # Outside a batch the programs a batch would group
                    # this filter's signature into are built all the
                    # same, once: a server's first requests come one by
                    # one, and its compiles belong there.
                    self._filter_group_fn(staged_filter[0], 1,
                                          bank.array.shape[-1])
                out = self._dispatch_counts(bank.array, filter_words)
                if filter_words is not None:
                    self._note_sweep_group(1, 1)
            self._note_topn_rows("swept", bank.array.shape[0])
            # A sweep of the whole view reads the bank's own rows in
            # slot order (None): no per-row mapping in finalize.
            raw = None
            if similar:
                raw, swept = self._bank_popcounts(bank)
            dispatched.append((None if all_rows is view_rows else all_rows,
                               bank, out, raw))
            # What finalize will fetch: the sweep's counts and, when this
            # call is the one that swept them, the bank's popcounts (a
            # call that found them pending leaves the fetch to that one).
            arrays = (out, raw) if swept else (out,)
        else:
            if call.children and filter_words is None:
                filter_words = self._run_staged(*staged_filter)
            if PBANK_ENABLED and self.mesh is None and len(shards) == 1 \
                    and allowed_rows is None and not ids_arg \
                    and (n or similar) and selfcheck_pairs is None:
                # Positions-resident fast path: the whole view's sorted
                # positions live on device; no streaming, no expansion.
                # A tanimoto call without `n` (the source's own query:
                # every molecule past the threshold) is answered here
                # too — its survivors are counted on the device.
                pb = view.positions_bank(shards[0], width)
                if pb is not None:
                    src_pb = self._tanimoto_source(filter_words) \
                        if similar else None
                    self._note_topn("positions")
                    return self._topn_positions(
                        pb, filter_words, width, n,
                        tanimoto if similar else 0, min_threshold, src_pb)
            # Huge row sets stream through transient chunk banks to bound
            # HBM (the 50k-row ranked-cache shape). Chunks are uploaded
            # lazily in finalize with one-chunk lookahead — dispatching
            # them all here would materialize every chunk bank in HBM at
            # once, the exact blow-up chunking exists to avoid.
            self._note_topn("streamed")
            chunked = [all_rows[c0:c0 + TOPN_CHUNK_ROWS]
                       for c0 in range(0, len(all_rows), TOPN_CHUNK_ROWS)]
        src_dev = self._tanimoto_source(filter_words) if similar else None

        # Chunk banks are admitted to the BANK_BUDGET HBM LRU only when
        # the WHOLE stream fits in half the budget: a repeat query over
        # an unchanged fragment then skips every chunk re-upload (the
        # host->device upload moves far more slowly than the sweep
        # reads HBM). An over-budget
        # stream would be a sequential scan over an LRU — ~0% repeat
        # hits while evicting every other view's banks — so it stays
        # transient. Row churn shifts chunk boundaries and orphans old
        # keys; orphans are bounded by (and aged out of) the budget.
        from pilosa_tpu.core.view import BANK_BUDGET
        cache_chunks = bank_bytes <= BANK_BUDGET.budget // 2

        def dispatch_chunk(rows):
            bank = view.device_bank(tuple(shards), rows=rows,
                                    mesh=self.mesh, trim=True,
                                    cache_rows=cache_chunks)
            self._note_topn_rows("swept", bank.array.shape[0])
            # A chunk bank holds exactly its chunk's rows.
            return (None, bank,
                    self._dispatch_counts(bank.array, filter_words),
                    self._bank_popcounts(bank)[0] if similar else None)

        def finalize() -> PairsResult:
            parts = []  # (rows_arr, counts_arr[, raws_arr])
            pending = list(dispatched)
            if chunked:
                pending.append(dispatch_chunk(chunked[0]))
            i = 0
            fetched_rows = 0
            while pending:
                rows, bank, counts_out, raw = pending.pop(0)
                # One-chunk lookahead: overlap the next upload+sweep with
                # this fetch while keeping at most two chunk banks live.
                i += 1
                if i < len(chunked):
                    pending.append(dispatch_chunk(chunked[i]))
                with TIMELINE.stage("finish.slot_map") as sm:
                    # The bank's rows in slot order ARE the sweep's rows:
                    # slots past them are zero rows. A restricted call
                    # (attrName, ids) keeps its candidates among them.
                    rows_arr = bank.slot_rows()
                    sm.set("rows", len(rows_arr))
                    vectors = [np.asarray(counts_out)]
                    fetched_rows += vectors[0].size
                    if similar:
                        # The rows' own popcounts are the bank's, not the
                        # query's: fetched by the first tanimoto answer
                        # of a bank version and kept with the bank.
                        if not isinstance(raw, np.ndarray):
                            if dispatched and not swept and \
                                    not isinstance(bank.popcounts,
                                                   np.ndarray):
                                # The call that swept them was dropped
                                # before its finalize: the fetch is this
                                # one's, a `d2h` of its own.
                                fetch_host((raw,))
                            raw, fetched = bank.host_popcounts()
                            if fetched:
                                fetched_rows += raw.size
                        vectors.append(raw)
                    sel = slice(0, len(rows_arr))
                    if rows is not None:
                        sel = np.flatnonzero(np.isin(
                            rows_arr, np.asarray(rows, dtype=np.uint64)))
                        rows_arr = rows_arr[sel]
                    parts.append((rows_arr, *(v[sel].astype(np.int64)
                                              for v in vectors)))
            self._note_topn_rows("fetched", fetched_rows)
            with TIMELINE.stage("finish.select") as fs:
                # One sweep (the resident bank) is one part: no copy of
                # its million-row vectors.
                rows_arr, counts_arr, *raws = parts[0] if len(parts) == 1 \
                    else (np.concatenate(col) for col in zip(*parts))
                fs.set("rows", len(rows_arr))
                if similar:
                    raws_arr = raws[0]
                    src_total = int(np.asarray(src_dev))
                    # The reference's rule (fragment.go:1146-1150): a row
                    # stays when ceil(100 * |A and B| / |A or B|) exceeds
                    # the threshold, so a ratio of exactly T is out.
                    keep = counts_arr * 100 > tanimoto * (
                        raws_arr + src_total - counts_arr)
                    rows_arr, counts_arr = rows_arr[keep], counts_arr[keep]
                keep = counts_arr > max(0, min_threshold - 1)
                rows_arr, counts_arr = rows_arr[keep], counts_arr[keep]
                rows_arr, counts_arr = _topn_candidates(rows_arr,
                                                        counts_arr, n)
                # Sort by (-count, row) — vectorized; Python-loop-free
                # even for 10^6-row fingerprint sweeps.
                order = np.lexsort((rows_arr, -counts_arr))
                if n:
                    order = order[:n]
                pairs = [(int(rows_arr[o]), int(counts_arr[o]))
                         for o in order]
            if selfcheck_pairs is not None and selfcheck_pairs != pairs:
                self.topn_selfcheck_mismatches += 1
                _LOG.error(
                    "TopN warm-cache self-check MISMATCH on %s/%s: "
                    "cached %r != exact %r; repairing ranked caches "
                    "from storage", idx.name, field_name,
                    selfcheck_pairs[:5], pairs[:5])
                self._repair_topn_caches(view, shards)
            return PairsResult(pairs)

        if chunked and getattr(self._tls, "later_writes", False):
            # A later call in this query writes fragments. Chunk banks
            # upload lazily inside finalize — which would run AFTER those
            # writes and read post-write state, breaking sequential
            # semantics (reference executes calls in order,
            # executor.go:245). Materialize now, before any write runs;
            # the full-bank path needs no such care because its device
            # arrays snapshot at dispatch.
            return finalize()
        if similar:
            arrays += (src_dev,)  # the filter's own popcount
        return _Pending(finalize, arrays=arrays)

    _PBANK_KERNELS: Dict[tuple, Callable] = {}

    @classmethod
    def _pbank_kernel(cls, k: int, has_filter: bool,
                      fixed: bool = False, width: Optional[int] = None,
                      survivors: bool = False,
                      qslots: Optional[int] = None):
        """Jitted per-segment TopN over a PositionsBank: |row ∧ filter|
        = Σ_{p ∈ row} filter_bit[p]. Two layouts (view.py flush):

        - flat (pos [P], starts [R+1]): membership bits + a cumsum
          differenced at row starts (u32 wrap subtraction is exact —
          per-row counts fit u16);
        - fixed (pos [L, R] slot-major, lens [R]): membership summed
          over the L slot planes, rows on the lanes — no O(P) cumsum,
          no starts gathers, no cross-lane reduce. The 0xFFFF row pad
          matches nothing (compare) / gathers fill-0.

        No dense expansion, no streaming: one pass over the resident
        positions. Unfiltered TopN skips even that — counts are the
        start diffs / lens. Tanimoto/threshold ride as traced params;
        lax.top_k breaks ties by lower index, which IS the (-count,
        row) order because rows are stored ascending.

        The program takes what the call already holds on the device —
        `kernel(fw, pos, aux, params, src)`: the filter's words as the
        tree program wrote them (`[1, W]`, cut to the bank's `width`
        words inside), the segment's two arrays, the uploaded
        `[threshold, tanimoto]` and, for a tanimoto call, the
        filter row's own popcount as `_popcount_row` left it — so a
        launch is this program and no eager helper beside it.
        `survivors` adds an output, the number of rows the rule
        keeps: what a call without `n` sizes its top_k against. A
        filtered program's LAST output is the form its gate took
        (1 the sparse membership, 0 the table gather): `finalize`
        counts it, `executor.pbank_form{form:compare|gather}`.

        `qslots` is the width of the sparse membership: how many filter
        positions it holds. The bank's widest row
        (`PositionsBank.qslots`), capped by PBANK_SPARSE_FILTER_BITS. A
        Row of the bank's own field can have no more on-bits, so it
        always takes that form; a filter with more (a Union, another
        field) takes the gather, exact. A filtered program needs the
        bank's `width`: the filter is cut to it, and `positions_bank`
        admits no width of 2048 words, so every real position is under
        65504 — what the u16 compare's filter pad (0xFFFE) relies on."""
        import jax
        import jax.numpy as jnp

        membership = PBANK_MEMBERSHIP
        if membership == "auto":
            membership = ("search"
                          if jax.devices()[0].platform == "cpu"
                          else "compare")
        assert width is not None or not has_filter
        qslots = min(qslots or PBANK_SPARSE_FILTER_BITS,
                     PBANK_SPARSE_FILTER_BITS)
        key = (k, has_filter, fixed, membership, width, survivors,
               qslots if has_filter else None)
        fn = cls._PBANK_KERNELS.get(key)
        if fn is not None:
            return fn

        def bits_gather(fw, pos):
            # Pad sentinel 0xFFFF gathers out of range -> fill 0. Casts
            # stay inline (no materialized i32 copy of the whole bank).
            return (jnp.take(fw, (pos >> 5).astype(jnp.int32),
                             mode="fill", fill_value=0)
                    >> (pos & 31).astype(jnp.uint32)) & jnp.uint32(1)

        def bits_compare(fw, pos):
            # Sparse-filter membership WITHOUT the positions gather: a
            # tanimoto query's filter is one fingerprint (~48 set bits),
            # and an element-wise [P] x [Q] compare-reduce against its
            # extracted set positions is VPU-shaped where the P-sized
            # dynamic gather is not: on the v5e ~0.1 ns a position-slot
            # at 104 query slots against the gather's 9.85
            # (benches/pbank_kernel_probe.py, PERF.md §7 row 27; the
            # two-stage top-k variant showed no gain on an earlier
            # machine, so top_k stays flat). Extraction: enumerate the
            # filter's 32*W bit positions, keep set ones, take the
            # `qslots` smallest (pad 2^30 sorts last; a real position
            # is < 2^16).
            w = jnp.arange(fw.shape[0], dtype=jnp.int32)
            allpos = w[:, None] * 32 + jnp.arange(32, dtype=jnp.int32)
            setmask = ((fw[:, None] >> jnp.arange(32, dtype=jnp.uint32))
                       & jnp.uint32(1)).astype(bool)
            qpos = jnp.where(setmask, allpos, 1 << 30).reshape(-1)
            # Clamp to the filter's bit width: top_k(k > size) raises at
            # TRACE time and lax.cond traces both branches, so a narrow
            # filter row would crash every filtered query. The clamp is
            # exact: popcount(fw) <= 32*W == the clamped k, so the gate
            # below still guarantees every set position is captured.
            qk = min(qslots, int(qpos.shape[0]))
            qtop = -jax.lax.top_k(-qpos, qk)[0]
            if membership == "search":
                # qtop is sorted ascending: binary-search each position
                # in log2(qk) compare-select rounds instead of a qk-wide
                # compare fan-out (the r4-measured ~1 ns/position floor
                # is this fan-out; VERDICT r5 #2). Positions are < 2^16
                # and the 2^30 pad sorts last, so equality at the found
                # slot is exact membership.
                idx = jnp.clip(jnp.searchsorted(qtop,
                                                pos.astype(jnp.int32)),
                               0, qk - 1)
                return jnp.take(qtop, idx) == pos.astype(jnp.int32)
            # pos is [P] (flat layout) or [L, R] (fixed layout); the
            # trailing broadcast axis makes membership layout-agnostic.
            # The fan-out is as wide as `qslots` and no wider, in
            # chunks that stay under the compiler's cliff
            # (PBANK_COMPARE_CHUNK); its time is linear in the width.
            # Compared as the u16 the bank stores (in i32 the program
            # first wrote a 4-byte copy of every position: 1.17 GB of
            # workspace a program at 2.8 M rows): a filter pad becomes
            # 0xFFFE, which is neither a row pad (0xFFFF) nor a real
            # position (< width * 32 <= 65504).
            q16 = jnp.where(qtop < (1 << 16), qtop,
                            0xFFFE).astype(jnp.uint16)
            n_chunks = -(-qk // PBANK_COMPARE_CHUNK)
            step = -(-qk // n_chunks)
            member = None
            for c0 in range(0, qk, step):
                part = (pos[..., None] == q16[c0:c0 + step]).any(-1)
                member = part if member is None else member | part
            return member

        def kernel(fw, pos, aux, params, src=None):
            # aux: starts [R+1] (flat) | lens [R] (fixed)
            raw = aux if fixed else aux[1:] - aux[:-1]
            if has_filter:
                if fw.ndim == 2:
                    fw = fw[0]      # the one shard's row of [1, W]
                if width is not None:
                    # Cut the filter row to the BANK's width: a plan
                    # can be wider than the bank (Not() rides the
                    # existence view, Shift(), a wider sibling field),
                    # and a set bit at word 2047 would otherwise match
                    # the fixed layout's 0xFFFF row pads — the gather's
                    # OOB-fill and the compare's qtop extraction both
                    # become pad-safe once fw stops at the bank width
                    # (real positions are < width*32 <= 65503, so no
                    # real count changes; the tanimoto denominator
                    # `src` deliberately keeps the FULL row's popcount,
                    # matching the dense path's semantics).
                    fw = fw[:width]

                def c_from(bits):
                    # Reduce to per-row counts INSIDE the cond branch:
                    # the branch output is then [R] i32 instead of a
                    # bank-sized bits array — at 100M rows the cond's
                    # branch buffers next to the resident bank were the
                    # difference between fitting HBM and
                    # RESOURCE_EXHAUSTED.
                    if fixed:
                        return bits.sum(axis=0, dtype=jnp.int32)
                    s = jnp.concatenate(
                        [jnp.zeros(1, jnp.uint32),
                         jnp.cumsum(bits, dtype=jnp.uint32)])
                    # ONE gather of the R + 1 row starts, differenced:
                    # two gathers of R (`s[aux[1:]] - s[aux[:-1]]`)
                    # read 362.9 ms with their cumsum over 402.7 M
                    # positions and 8.4 M rows, one reads 211.3 (the
                    # cumsum alone 93.7; my chip run, PR 41).
                    g = s[aux]
                    return (g[1:] - g[:-1]).astype(jnp.int32)

                def gather_fixed(fw, pos):
                    # The gather form over a fixed segment, one slot
                    # plane a step: its index and word temporaries are
                    # [R] then, not [L, R] — three of those were the
                    # whole program's workspace (3.36 GB at [104, 2.8 M],
                    # compiled for a v5e; 0.31 GB so), and a branch's
                    # workspace is reserved whichever branch runs.
                    def plane(i, acc):
                        return acc + bits_gather(fw, pos[i]).astype(
                            jnp.int32)
                    return jax.lax.fori_loop(
                        0, pos.shape[0], plane,
                        jnp.zeros(pos.shape[1], jnp.int32))

                # Exactness gate ON DEVICE (no extra host round trip):
                # the compare form only sees the `qslots` smallest filter
                # positions, so any denser filter falls back to the
                # gather form inside the same compiled program.
                fwpop = jnp.sum(
                    jax.lax.population_count(fw)).astype(jnp.int32)
                sparse = fwpop <= qslots
                c = jax.lax.cond(
                    sparse,
                    lambda: c_from(bits_compare(fw, pos)),
                    lambda: (gather_fixed(fw, pos) if fixed
                             else c_from(bits_gather(fw, pos))))
                form = (sparse.astype(jnp.int32),)
            else:
                c = raw
                form = ()
            thresh, tani = (params[0].astype(jnp.int32),
                            params[1].astype(jnp.int32))
            # Without a tanimoto rule there is no source count, and
            # `tani` = 0 keeps `denom` out of the answer.
            src = jnp.int32(0) if src is None else src.astype(jnp.int32)
            keep = c >= jnp.maximum(1, thresh)
            denom = raw + src - c
            keep &= jnp.where(tani > 0,
                              c * 100 > tani * denom,
                              True)
            score = jnp.where(keep, c, -1)
            top = jax.lax.top_k(score, k)
            if survivors:
                return (*top, keep.sum(dtype=jnp.int32), *form)
            return (*top, *form)

        # graftlint: disable=GL006 — class-level kernel cache (benches
        # monkeypatch _pbank_kernel as a classmethod, so no instance is
        # available to note compiles on); keys are (k, filter, layout,
        # membership, width, survivors, qslots) — a bounded, shape-stable
        # set per deployment (a call without `n` takes k from powers of
        # two; qslots moves in steps of 8 with the bank's widest row).
        # The compile log (utils/jaxenv.py) counts it all the same.
        kernel = cls._PBANK_KERNELS[key] = jax.jit(
            named(kernel, "topn_positions"))
        return kernel

    # A tanimoto call without `n` asks for every row past the
    # threshold: each segment's survivors are counted on the device
    # and its top_k starts at this bound; a segment with more runs
    # again at the next power of two that holds them all (exact: never
    # an approximate top-k, never a cut answer).
    PBANK_EVERY_K = 256

    def _topn_positions(self, pb, filter_words, width: int, n: int,
                        tanimoto: int, min_threshold: int,
                        src_dev) -> "_Pending":
        """TopN over a device-resident PositionsBank (see
        view.PositionsBank): per segment one kernel dispatch, host
        merge of k-candidates across segments. `n` = 0 (a tanimoto
        call only) is every row the rule keeps."""
        import jax

        fw = filter_words   # [1, W'] u32 (single shard), or None
        filtered = fw is not None
        # The membership compare is as wide as the bank's widest row (a
        # compile key): a Row of this field always fits it.
        qslots = min(pb.qslots, PBANK_SPARSE_FILTER_BITS)
        every = n == 0
        # Params are identical for every segment — build/upload ONCE.
        # (Per-segment rebuilds were one host->device put per segment
        # per query.) The filter row's own popcount stays where
        # `_popcount_row` left it and rides as an operand of its own.
        params = upload(np.asarray([min_threshold, tanimoto], np.uint32))
        src = src_dev if tanimoto else None
        wave = []   # launches since the last sync

        def launch(si: int, k: int):
            _lo, _n, pos, aux, p_real = pb.segments[si]
            fixed = pos.ndim == 2
            kern = self._pbank_kernel(
                k, filtered, fixed=fixed,
                width=width if filtered else None, survivors=every,
                qslots=qslots)
            rows = int(aux.shape[0]) - (0 if fixed else 1)
            with self._dispatch_span("topn_positions") as ds:
                ds.set("segment", si)
                ds.set("rows", rows)
                ds.set("positions", p_real)
                ds.set("layout", "fixed" if fixed else "flat")
                ds.set("k", k)
                if filtered:
                    ds.set("qslots", qslots)
                out = self._call_program(kern, fw, pos, aux, params, src)
            if self.stats is not None:
                self.stats.count("executor.pbank_launches", 1)
            self._note_topn_rows("swept", rows)
            # Bound enqueued-program concurrency: each segment program
            # needs GBs of workspace next to the resident bank (2.15 GB
            # at 2^27 flat positions, compiled for a v5e), and letting
            # all segments queue at once OOMed the chip at 100M rows
            # (9 x ~4 GB transients + the 9.6 GB bank). A wave sync
            # caps coexisting workspaces; outputs are k-sized so
            # keeping them all is free.
            wave.append(out)
            if len(wave) >= PBANK_INFLIGHT_SEGMENTS:
                # A blocking wait of the begin half, as `d2h` is of the
                # finish: a stage of its own.
                with TIMELINE.stage("pbank.wave_wait",
                                    segments=len(wave)):
                    # graftlint: disable=GL003 — deliberate wave sync:
                    # caps coexisting segment workspaces in HBM (see
                    # comment above); removing it re-introduces the
                    # 100M-row OOM.
                    jax.block_until_ready(wave)
                del wave[:]
            return out

        outs = []   # (segment, k, the launch's outputs)
        for si, (_lo, n_rows, _pos, _aux, _p) in enumerate(pb.segments):
            k = min(self.PBANK_EVERY_K if every else n, n_rows)
            if k:
                outs.append((si, k, launch(si, k)))

        def finalize() -> PairsResult:
            # ONE batched transfer for all segments' k-candidates
            # (sequential per-segment np.asarray fetches each paid a
            # blocking RTT; the results are ~k ints per segment).
            got = jax.device_get([out for _, _, out in outs])
            # values, indices[, survivors]; a filtered program's next
            # output is the form its gate took, not a row: counted
            # apart.
            n_out = 3 if every else 2
            forms = [int(out[n_out]) for out in got] if filtered else []
            fetched = sum(a.size for out in got for a in out[:n_out])
            for j, (si, k, _) in enumerate(outs):
                n_rows = pb.segments[si][1]
                left = int(got[j][2]) if every else 0
                if left > k:
                    # More rows past the threshold than the bound: the
                    # segment runs again, wide enough for all of them.
                    k = min(n_rows, 1 << (left - 1).bit_length())
                    out = launch(si, k)
                    fetch_host(out)
                    got[j] = jax.device_get(out)
                    fetched += sum(a.size for a in got[j][:n_out])
                    if filtered:
                        forms.append(int(got[j][n_out]))
                    if self.stats is not None:
                        self.stats.count(
                            "executor.pbank_overflow_reruns", 1)
            self._note_topn_rows("fetched", fetched)
            if self.stats is not None:
                for name, took in (("compare", sum(forms)),
                                   ("gather", len(forms) - sum(forms))):
                    if took:
                        self.stats.with_tags(f"form:{name}").count(
                            "executor.pbank_form", took)
            pairs = []
            for (si, _, _), (v, ix, *_) in zip(outs, got):
                row_lo = pb.segments[si][0]
                for val, i in zip(v.tolist(), ix.tolist()):
                    if val > 0:
                        pairs.append((int(pb.row_ids[row_lo + i]),
                                      int(val)))
            pairs.sort(key=lambda rc: (-rc[1], rc[0]))
            return PairsResult(pairs if every else pairs[:n])

        return _Pending(finalize,
                        arrays=tuple(x for _, _, out in outs for x in out))

    # Row-churn bound for incremental rank-vector patches: more changed
    # rows than this and the full sweep rebuild is cheaper than the
    # gather+scatter (and compiles fewer patch-kernel shapes).
    RANK_PATCH_MAX = int(os.environ.get("PILOSA_TPU_RANK_PATCH_MAX",
                                        4096))

    def _note_rank(self, kind: str) -> None:
        names = {"hit": "hits", "patch": "patches",
                 "rebuild": "rebuilds"}
        with self._jit_stats_lock:
            if kind == "hit":
                self.rank_cache_hits += 1
            elif kind == "patch":
                self.rank_cache_patches += 1
            else:
                self.rank_cache_rebuilds += 1
        if self.stats is not None:
            self.stats.count(f"rank_cache.{names[kind]}", 1)

    def _rank_counts(self, view, bank, shards):
        """Get-or-refresh the device-resident per-row count vector for
        `bank` (RankEntry in core/cache.py): [Rcap] counts aligned
        with the bank's slot layout, validated against its fragment
        versions. Returns the device array (dispatch queued; nothing
        fetched)."""
        import jax
        import jax.numpy as jnp
        from pilosa_tpu.core.cache import RANK_CACHE, RankEntry
        from pilosa_tpu.ops.bitset import popcount

        key = (tuple(int(s) for s in shards),
               int(bank.array.shape[-1]))
        # SLOT-ordered row tuple (dict insertion order == slot order:
        # fresh builds enumerate the sorted row set, _patch_bank
        # appends at len(slots)). Equality must prove SLOT alignment,
        # not just row-set equality — an append-grown layout and a
        # freshly sorted rebuild hold the same rows in different slots,
        # and patching one with indices from the other would scatter
        # counts into the wrong rows.
        bank_rows = tuple(bank.slots)
        entry = RANK_CACHE.get(view, key)
        if entry is not None and entry.versions == bank.versions \
                and entry.row_ids == bank_rows \
                and int(entry.counts.shape[0]) == int(bank.array.shape[0]):
            self._note_rank("hit")
            return entry.counts
        counts = None
        if entry is not None and entry.row_ids == bank_rows \
                and int(entry.counts.shape[0]) == int(bank.array.shape[0]):
            # Same row set, moved versions: patch only the rows the
            # writes touched (Fragment._row_versions names them).
            changed: set = set()
            ok = True
            for s, newv in bank.versions.items():
                old = entry.versions.get(s)
                if old == newv:
                    continue
                frag = view.fragment(s)
                if frag is None or old is None or old < 0 \
                        or (old >> 48) != (newv >> 48):
                    # Epoch mismatch: the fragment was recreated since
                    # the entry was built (pop + reload across a
                    # resize). Its _row_versions died with the old
                    # incarnation, so rows_changed_since(old) cannot
                    # name writes made before the recreation — the
                    # patch set is unprovable. Rebuild.
                    ok = False
                    break
                ch = frag.rows_changed_since(old)
                if not ch:
                    # Version moved without row attribution: cannot
                    # prove the patch set — rebuild.
                    ok = False
                    break
                changed.update(int(r) for r in ch)
            if ok and changed and len(changed) <= self.RANK_PATCH_MAX \
                    and all(r in bank.slots for r in changed):
                sel = sorted(bank.slots[r] for r in changed)
                # Pow2-pad repeating the first slot (idempotent: the
                # duplicate scatter writes the same recount) so patch
                # kernels compile O(log churn) shapes, the fused-batch
                # padding idiom.
                pad = _pow2(len(sel))
                sel = sel + [sel[0]] * (pad - len(sel))
                sel_dev = upload(np.asarray(sel, np.int32))
                pkey = f"rankpatch:{bank.array.shape}:{pad}"
                fn = self._jit_get(pkey)
                if fn is None:
                    self._note_jit_compile("rank_patch", pkey)

                    def patch(c, bank_arr, sel_ix):
                        new = popcount(bank_arr[sel_ix], axis=(-2, -1))
                        return c.at[sel_ix].set(
                            new.astype(c.dtype))
                    fn = jax.jit(named(patch, "rank_patch"))
                    self._jit_put(pkey, fn)
                with self._dispatch_span("rank_patch"):
                    counts = self._call_program(fn, entry.counts,
                                                bank.array, sel_dev)
                self._note_rank("patch")
        if counts is None:
            counts = self._dispatch_counts(bank.array, None)
            self._note_rank("rebuild")
        RANK_CACHE.put(view, key,
                       RankEntry(dict(bank.versions), bank_rows, counts,
                                 # graftlint: disable=GL003 — .nbytes
                                 # is shape metadata (rows * 4), not a
                                 # transfer; no device sync happens.
                                 int(getattr(counts, "nbytes", 0) or 0)))
        return counts

    def _topn_rank_cached(self, view, shards, view_rows, all_rows,
                          n: int, min_threshold: int):
        """Filterless TopN over the device rank cache, or None when
        the bank is over budget (the pbank/chunked paths own that
        regime). Unrestricted leaderboards run a device top-k over the
        cached counts; candidate-restricted or n=0 calls fetch the [R]
        vector (4 B/row — negligible next to the sweep it replaces)
        and reuse the host merge."""
        import jax
        import jax.numpy as jnp
        from pilosa_tpu.core.view import bank_capacity

        bank_bytes = self._bank_device_bytes(
            (bank_capacity(len(view_rows)), len(shards),
             view.trimmed_words()))
        if bank_bytes > TOPN_MAX_BANK_BYTES:
            return None
        bank = view.device_bank(tuple(shards), mesh=self.mesh,
                                trim=True)
        counts = self._rank_counts(view, bank, shards)
        restricted = all_rows is not view_rows
        # Slot-ordered rows (insertion order == slot order). The device
        # top-k leg requires slots to ASCEND with row id: lax.top_k
        # breaks count ties by lower index, which is (-count, row)
        # order — the uncached path's lexsort — only then. An
        # append-grown bank (_patch_bank places new rows at the END)
        # violates it, so that layout takes the host-merge leg below,
        # which maps slots explicitly and is exact for any layout.
        slot_rows = np.fromiter(bank.slots, np.uint64, len(bank.slots))
        ascending = slot_rows.size < 2 \
            or bool(np.all(slot_rows[1:] > slot_rows[:-1]))
        if n and not restricted and ascending:
            k = min(n, len(bank.slots))
            if k == 0:
                return PairsResult([])
            tkey = f"ranktopk:{counts.shape}:{k}"
            fn = self._jit_get(tkey)
            if fn is None:
                self._note_jit_compile("rank_topk", tkey)

                def topk(c, params):
                    thr = params[0].astype(jnp.int32)
                    ci = c.astype(jnp.int32)
                    # Zero slots (and sub-threshold rows) score -1 and
                    # are dropped in finalize.
                    score = jnp.where(ci >= jnp.maximum(1, thr),
                                      ci, -1)
                    return jax.lax.top_k(score, k)
                fn = jax.jit(named(topk, "rank_topk"))
                self._jit_put(tkey, fn)
            params = upload(np.asarray([min_threshold], np.uint32))
            with self._dispatch_span("rank_topk"):
                out = self._call_program(fn, counts, params)

            def finalize() -> PairsResult:
                vals, idxs = (np.asarray(x) for x in out)
                return PairsResult(
                    [(int(slot_rows[i]), int(v))
                     for v, i in zip(vals.tolist(), idxs.tolist())
                     if v > 0])

            return _Pending(finalize, arrays=tuple(out))

        def finalize() -> PairsResult:
            c = np.asarray(counts).astype(np.int64)
            slot_idx = np.fromiter(
                map(bank.slots.get, all_rows,
                    itertools.repeat(bank.zero_slot)),
                dtype=np.int64, count=len(all_rows))
            rows_arr = np.asarray(all_rows, dtype=np.uint64)
            counts_arr = c[slot_idx]
            keep = counts_arr > max(0, min_threshold - 1)
            rows_arr, counts_arr = rows_arr[keep], counts_arr[keep]
            rows_arr, counts_arr = _topn_candidates(rows_arr,
                                                    counts_arr, n)
            order = np.lexsort((rows_arr, -counts_arr))
            if n:
                order = order[:n]
            return PairsResult([(int(rows_arr[o]), int(counts_arr[o]))
                                for o in order])

        return _Pending(finalize, arrays=(counts,))

    def _repair_topn_caches(self, view, shards) -> None:
        """Rebuild every fragment's cached per-row counts from storage —
        the recovery action when a sampled self-check catches stale
        counts. Restores the warm-path invariant instead of disabling
        the cache."""
        for s in shards:
            frag = view.fragment(s)
            if frag is None:
                continue
            with frag._lock:
                frag.cache.invalidate()
                for r in frag.row_ids():
                    frag.cache.add(r, frag.row_count(r))

    def _topn_cached_counts(self, view, shards) -> Optional[Dict[int, int]]:
        """Summed per-row counts from fragment caches, or None when any
        fragment's cache cannot prove completeness (cache disabled, rows
        evicted, or counts missing)."""
        from pilosa_tpu.core import cache as cache_mod

        total: Dict[int, int] = {}
        for s in shards:
            frag = view.fragment(s)
            if frag is None:
                continue
            if frag.cache_type == cache_mod.CACHE_TYPE_NONE:
                return None
            if getattr(frag.cache, "saturated", False):
                # Saturated caches stop tracking writes entirely, so
                # their counts may be stale even when len() happens to
                # match (e.g. after mass clears).
                return None
            counts = getattr(frag.cache, "counts", None)
            if counts is None:
                return None
            rows = frag.row_ids()
            if len(counts) < len(rows):
                return None
            for r in rows:
                c = counts.get(r)
                if c is None:  # evicted: cache incomplete for this frag
                    return None
                total[r] = total.get(r, 0) + c
        return total

    # ----------------------------------------------------------------- Rows

    def _execute_rows(self, idx: Index, call: Call, shards
                      ) -> RowIdentifiers:
        """Row-id enumeration with previous/limit/column filters and, for
        time fields, a from/to view-range filter (reference
        executeRowsShard, executor.go:1143; time-view selection
        executor.go:1160-1218)."""
        field_name = call.arg("_field")
        field = idx.field(field_name)
        if field is None:
            raise ExecutionError(f"field not found: {field_name}")
        shards = self._shards(idx, shards)
        previous = call.arg("previous")
        limit = call.uint_arg("limit")
        column = call.arg("column")
        frm, to = call.arg("from"), call.arg("to")
        if (frm is not None or to is not None) and \
                field.options.type != FIELD_TYPE_TIME:
            raise ExecutionError(f"from/to on non-time field {field_name}")

        view_names = [VIEW_STANDARD]
        if field.options.type == FIELD_TYPE_TIME and (
                frm is not None or to is not None
                or field.options.no_standard_view):
            # Clamp the requested range to the min/max existing time
            # views, then take the minimal view cover — exactly the
            # reference's shape (minMaxViews + viewsByTimeRange).
            q = field.options.time_quantum
            if not q:
                return RowIdentifiers([])
            vmin, vmax = timeq.min_max_views(list(field.views), q)
            if not vmin or not vmax:
                return RowIdentifiers([])
            start = timeq.parse_timestamp(frm) if frm else None
            end = timeq.parse_timestamp(to) if to else None
            min_t = timeq.time_of_view(vmin, False)
            max_t = timeq.time_of_view(vmax, True)
            if start is None or start < min_t:
                start = min_t
            if end is None or end > max_t:
                end = max_t
            view_names = field.views_for_range(start, end)

        rows: set = set()
        for vname in view_names:
            view = field.view(vname)
            if view is None:
                continue
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                if column is not None:
                    if column // SHARD_WIDTH != shard:
                        continue
                    for r in frag.row_ids():
                        if frag.bit(r, column):
                            rows.add(r)
                else:
                    rows.update(frag.row_ids())
        out = sorted(rows)
        if previous is not None:
            out = [r for r in out if r > previous]
        if limit is not None:
            out = out[:limit]
        if WORKLOAD.enabled:
            for vname in view_names:
                if field.view(vname) is not None:
                    WORKLOAD.record_read(idx.name, field_name, vname,
                                         shards,
                                         rows_scanned=len(out))
        return RowIdentifiers(out)

    # -------------------------------------------------------------- GroupBy

    # Device bytes one GroupBy expansion chunk may materialize. Bounds the
    # [P*R, S, W] intermediate: prefixes stream through in chunks of
    # GROUPBY_CHUNK_BYTES / (R*S*W*4) at a time.
    GROUPBY_CHUNK_BYTES = int(os.environ.get("PILOSA_TPU_GROUPBY_CHUNK_BYTES",
                                             256 << 20))

    # GroupBy members of one flush that may stand at a count fetch at
    # once (`_batch_begin`): each holds its level's prefixes (up to
    # GROUPBY_CHUNK_BYTES) and a queued `groupby_sum` its count vectors
    # (50 MB at 512 lanes of 25 planes; where a device's shards are no
    # multiple of eight also the launch's distinct rows, GROUPSUM_CHUNK_BYTES
    # at most). Read on `ssb-chip.flights` with PR 45's sum program
    # (PERF.md §6, PR 45): 2 → 40.0, 4 → 42.6, 8 → 44.5 answers a
    # second (the parent 35.0), `hbm_peak_gb` 4.49 → 5.1 of 16 at 8.
    GROUPBY_INFLIGHT_MEMBERS = 8

    # The groups of one `groupby_sum` launch, as bytes of operand rows:
    # a device's share of one row [S, W] u32 a group — 512 groups of 16
    # shards. It bounds how long one launch holds the device while a
    # flush's other members queue behind it, and what a launch may copy: where XLA keeps a bank eight rows to a
    # tile (a device's shards no multiple of eight) the launch's distinct
    # rows are cut out first, a row a group at most (`ops/groupsum.py`).
    GROUPSUM_CHUNK_BYTES = 1 << 30

    def _group_by_aggregate(self, idx: Index, call: Call):
        """(field, bsiGroup) of a GroupBy's `aggregate=Sum(field=f)`,
        None without the argument; any other value is an error, never a
        silent count."""
        agg = call.arg("aggregate")
        if agg is None:
            return None
        if not isinstance(agg, Call) or agg.name != "Sum" or agg.children \
                or set(agg.args) - {"field", "_field"}:
            raise ExecutionError(
                "GroupBy aggregate must be Sum(field=<int field>)")
        fname = agg.arg("field") or agg.arg("_field")
        if not isinstance(fname, str):
            raise ExecutionError(
                "GroupBy aggregate must be Sum(field=<int field>)")
        field = idx.field(fname)
        if field is None:
            raise ExecutionError(f"field not found: {fname}")
        bsig = field.bsi_groups.get(fname)
        if bsig is None:
            raise ExecutionError(f"field {fname} is not an int field")
        return field, bsig

    def _execute_group_by(self, idx: Index, call: Call, shards):
        """GroupBy(...): its level loop (`_group_by_levels`) taken to
        its end here and now, every count fetch blocking as the loop
        comes to it — unless a flush's dispatcher is giving its members
        turns on this thread (`_batch_begin`): then the loop itself,
        for `_dispatch_query` to hand its fetches up."""
        levels = self._group_by_levels(idx, call, shards)
        if getattr(self._tls, "turns", False):
            return levels
        return _drive(levels)

    # graftlint: materialize — GroupBy is level-synchronous by design:
    # the host reads each depth's [P, R] count matrix to prune empty
    # prefixes, page (`previous`), and decide HBM spills before
    # expanding the next level. Those per-level fetches ARE the
    # algorithm's materialization boundary (see docstring below): each
    # blocks in `_host`, after the loop has handed the array to its
    # driver and been resumed — `_drive` for a lone GroupBy, the turns
    # of `_batch_begin` inside a flush.
    def _group_by_levels(self, idx: Index, call: Call, shards):
        """Cross-product of Rows() children with intersection counts
        (reference executeGroupByShard, executor.go:1062 + groupByIterator
        :2820). TPU shape: level-synchronous — ALL prefixes at a depth
        expand against ALL of the next field's rows in one batched
        [P, R, S, W] AND+popcount kernel (chunked over P to bound HBM),
        instead of one device dispatch per prefix row. Empty prefixes are
        pruned between levels, which the reference's iterator cannot do
        (it re-walks the full cross product, executor.go:2820-2996).

        `aggregate=Sum(field=f)` (upstream's argument from v1.4 on) adds
        a `sum` to every group: the signed sum of int field `f` over the
        group's columns THAT HAVE A VALUE in `f`. A group is what it is
        without the argument — the columns in the intersection of its
        rows and the filter — and so is its `count`: a column with no
        value in `f` counts and adds nothing (assumed of upstream, whose
        tree this sandbox does not hold; docs/query-language.md says
        so). Groups with count 0 are left out, the order is row ids
        ascending child by child, `limit` and `previous` act on groups
        as ever. Anything but `Sum(field=<int field>)` is an error.
        The sums are computed on the device after the last level, for
        the groups the answer holds and no others (`groupby_sum`, up to
        GROUPSUM_CHUNK_BYTES of groups a launch, never a launch a
        group): a group's mask (prefix ∧ row ∧ not-null) is formed on
        chip a word tile at a time from rows read where they lie, and
        counted against every bit plane of `f` before the tile is
        dropped — no mask is written to HBM (`ops/groupsum.py`); the
        host weighs the plane counts.

        With a filter, a child whose row stack is larger than a chunk
        (GROUPBY_CHUNK_BYTES) is first counted against the filter in ONE
        pass over its bank (`groupby_prune`, the sweep cells' kernel)
        and keeps the rows the filter meets: a level then expands the
        40 brands of a category, not the field's 1,000.

        From the filter's words on, the level loop launches its own
        programs and nothing else (`groupby_prune`, `groupby_cnt0`,
        `groupby_exp`, `groupby_cntN`, `groupby_sum`), and what it moves
        is index vectors up and count matrices down. A level program
        takes arrays that are already resident — a child's bank, the
        filter's words, the prefixes the level before wrote — and int32
        index vectors; the row gathers, the cut to the narrowest width
        and the ANDs happen inside it. `groupby_exp` / `groupby_cntN`
        return the chunk's prefixes [p, S, W] beside the counts [p, R]:
        the survivors of a level are (prefix, row) index pairs into
        them and into the level's bank (`_Frontier`), and the [p*R, S, W]
        cross product is never written. No `jnp` call and no indexing
        of a device array happens outside a jit here
        (tests/test_groupby_programs.py holds it to that).

        A generator: the ONE level loop, resumable at its blocking
        fetches. Before each — the pruning sweep's counts,
        `groupby_cnt0`'s, every chunk's of `groupby_exp` and
        `groupby_cntN`, a spilling level's prefixes — the program is
        launched and the copy of its output started, and the loop
        yields the device array; it blocks on it (`d2h`) when it is
        resumed, and is sent whether another member's level program was
        queued behind it then (`executor.groupby_fetches{covered:…}`).
        A GroupBy that is alone is resumed at once (`_drive`, from
        `_execute_group_by`: execute(), the coalescer's direct path, a
        cluster node's per-shard call): the same launches and fetches
        in the same order as a loop that never stopped. In a flush
        `_batch_begin` resumes the member whose fetch was launched
        earliest, so the device runs the other members' programs
        through this one's round trip. Returns, as the generator's
        value, the groups, or a `_Pending` over the sums of
        `aggregate=Sum`."""
        import jax
        import jax.numpy as jnp
        from pilosa_tpu.ops.bitset import masked_row_counts, popcount

        if not call.children or any(c.name != "Rows" for c in call.children):
            raise ExecutionError("GroupBy requires Rows() arguments")
        aggregate = self._group_by_aggregate(idx, call)
        shards = self._shards(idx, shards, pad=False)
        # GroupBy only ANDs, so a group's count is zero on any shard
        # some child field doesn't cover — restrict to the INTERSECTION
        # of the children's availableShards (narrow fields keep a wide
        # index's empty shards out of the [P, R, S, W] expansions).
        child_fields = [idx.field(c.arg("_field")) for c in call.children]
        if all(f is not None for f in child_fields):
            covered = set(child_fields[0].available_shards())
            for f in child_fields[1:]:
                covered &= set(f.available_shards())
            shards = [s for s in shards if s in covered]
            if not shards:
                return []
        shards = self._shards(idx, shards)
        limit = call.uint_arg("limit") or 0
        previous = call.arg("previous")
        if previous is not None:
            if not isinstance(previous, list) or \
                    len(previous) != len(call.children):
                raise ExecutionError(
                    "'previous' must be a list with one entry per Rows "
                    "child")
            previous = tuple(int(p) for p in previous)
        filter_call = call.arg("filter")
        filter_words = None
        if isinstance(filter_call, Call):
            filter_words = self._eval_tree(idx, filter_call, shards,
                                           mode="row")

        child_rows: List[Tuple[str, List[int]]] = []
        for child in call.children:
            ids = self._execute_rows(idx, child, shards).rows
            child_rows.append((child.arg("_field"), ids))
            if not ids:
                return []
        if WORKLOAD.enabled:
            # Each child's rows feed the [P, R, S, W] expansion sweep.
            for fname, ids_ in child_rows:
                WORKLOAD.record_read(idx.name, fname, VIEW_STANDARD,
                                     shards, rows=ids_)

        # Keyed by child INDEX, not field name: GroupBy(Rows(f), Rows(f))
        # is legal, and with subset banks the two children may need
        # different row sets of the same field.
        banks = []
        for fname, ids_ in child_rows:
            f = idx.field(fname)
            banks.append(self._get_bank_for(f, VIEW_STANDARD, shards,
                                            rows_needed=set(ids_)))
        # GroupBy only intersects, so all operands can slice down to the
        # NARROWEST width: bits past the narrowest operand AND to zero.
        # Every program cuts its operands to it inside the jit.
        wmin = min(b.array.shape[-1] for b in banks)
        if filter_words is not None:
            wmin = min(wmin, filter_words.shape[-1])

        levels = [0]    # level programs launched (executor.groupby_levels)

        def _jit(key, builder, span="groupby", **cut):
            fn = self._jit_get(key)
            if fn is None:
                # "gb_cnt0:(3, 16, 48)" -> program "groupby_cnt0".
                program = "groupby_" + key.split(":", 1)[0][3:]
                self._note_jit_compile(program, key)
                fn = jax.jit(named(builder, program))
                self._jit_put(key, fn)

            def call(*args):
                levels[0] += span == "groupby"
                with self._dispatch_span(span, **cut):
                    return fn(*args)
            return call

        fetches = [0, 0]    # blocking fetches: [uncovered, covered]

        def _host(dev):
            # GroupBy iterates on the host: each depth's counts are
            # fetched before the next is planned. The program is
            # launched and its output's copy started; the loop stops
            # here until its driver says this fetch is the one to block
            # on (at once when the GroupBy is alone; in a flush, when
            # it is the earliest launched of the members' fetches), and
            # is told whether another member's program is queued behind.
            dev.copy_to_host_async()
            covered = yield dev
            fetches[bool(covered)] += 1
            with transfer("d2h", int(dev.nbytes)):
                # graftlint: disable=GL003 — GroupBy frontier pruning
                # is a host decision by design: one count vector per
                # depth gates which prefixes expand.
                return np.asarray(dev)

        n_shards, depth_n = len(shards), len(child_rows)
        # Bytes ONE device holds of a prefix [S, wmin]: the prefix
        # arrays [p, S, W] and the group masks [g, S, W] are split along
        # S like the banks they are cut from, so every chunk below is
        # priced as a bank is (`_bank_device_bytes`) and a chip of a
        # host cuts a level as a lone chip with its shards does.
        per_prefix = max(1, self._bank_device_bytes((1, n_shards, wmin)))
        # child_slots[d]: the bank slots of child d's rows, beside
        # child_rows[d]'s ids. A child pruned by the filter keeps the
        # rows the filter meets, padded to a multiple of eight with the
        # bank's zero slot (row id -1: an all-zero row is in no group),
        # so that the level programs meet few shapes.
        child_slots = [np.asarray([b.slot(r) for r in ids], dtype=np.int32)
                       for b, (_, ids) in zip(banks, child_rows)]
        if filter_words is not None:
            for d, (bank, (fname, ids)) in enumerate(zip(banks, child_rows)):
                if len(ids) * per_prefix <= self.GROUPBY_CHUNK_BYTES:
                    continue
                sweep = _jit(
                    f"gb_prune:{bank.array.shape}:{wmin}",
                    lambda b, f: masked_row_counts(b[..., :wmin],
                                                   f[..., :wmin]),
                    level="prune", rows=len(ids))
                met = (yield from _host(sweep(bank.array, filter_words)))[
                    child_slots[d]] > 0
                kept = [r for r, m in zip(ids, met) if m]
                if not kept:
                    self._note_group_by(0, levels[0], fetches)
                    return []
                pad = -len(kept) % 8
                child_rows[d] = (fname, kept + [-1] * pad)
                child_slots[d] = np.concatenate(
                    [child_slots[d][met],
                     np.full(pad, bank.zero_slot, np.int32)])

        def count_rows(bank, slots):
            """`groupby_cnt0`: |row| of a child's rows, no prefix."""
            return _jit(
                f"gb_cnt0:{bank.shape}:{slots.shape[0]}:{wmin}",
                lambda b, sl: popcount(pick_rows(wmin, (b, sl)),
                                       axis=(-2, -1)),
                level="cnt0", rows=int(slots.shape[0])
            )(bank, slots)

        def count_level(name, frontier, c0, chunk_p, bank, slots):
            """One level program over the chunk of `chunk_p` prefixes
            of the frontier from c0 on: (prefixes [p, S, wmin], counts
            [p, R]) — the chunk's prefixes gathered and ANDed
            (`_gb_prefixes`) from its resident operands, and
            |prefix ∧ row| for the R rows at `slots` of the level's
            resident `bank`. Keyed by every operand's shape and every
            index vector's length; its `dispatch` span says how the
            level was cut."""
            n = len(frontier.rows)
            chunk = frontier.chunk(c0, c0 + chunk_p, self._put_prefixes)
            shapes = ":".join(_gb_shape(a) for a in (*chunk, bank, slots))

            def run(src, pi, prev, si, b, sl):
                pre = _gb_prefixes(wmin, src, pi, prev, si)
                return pre, popcount(
                    jnp.bitwise_and(pre[:, None],
                                    pick_rows(wmin, (b, sl))[None]),
                    axis=(-2, -1))
            return _jit(
                f"gb_{name}:{shapes}:{wmin}", run, level=name,
                prefixes=min(chunk_p, n - c0), rows=int(slots.shape[0]),
                chunk_of=f"{c0 // chunk_p + 1}/{-(-n // chunk_p)}")(
                *chunk, bank, slots)

        # The frontier: the prefixes that survived the levels so far, as
        # index vectors into arrays that are already resident
        # (`_Frontier`) — a level's program gathers its chunk's
        # prefixes itself. None means the full universe.
        frontier = None if filter_words is None else _Frontier(
            filter_words, None, None, None, [()])

        for depth in range(depth_n - 1):
            bank, ids = banks[depth].array, child_rows[depth][1]
            R = len(ids)
            slots = upload(child_slots[depth])
            if frontier is None:
                keep_idx = np.flatnonzero(
                    (yield from _host(count_rows(bank, slots))))
                frontier = _Frontier(
                    None, None, bank, child_slots[depth][keep_idx],
                    [(int(ids[i]),) for i in keep_idx])
                continue
            # A chunk's (prefix, row) pairs fit GROUPBY_CHUNK_BYTES as
            # prefixes: what the level after may have to hold of them.
            chunk_p = max(1, self.GROUPBY_CHUNK_BYTES // (per_prefix * R))
            # What this level hands on: the prefixes its chunks'
            # programs wrote (`outs`: this level's frontier, dense) and,
            # per surviving (prefix, row) pair, the prefix's place in
            # them and the row's place in `ids`. They stay on the
            # device while their bytes fit GROUPBY_CHUNK_BYTES and are
            # collected in host memory beyond that (the frontier of a
            # deep high-cardinality GroupBy is P*S*W words and must not
            # live unbudgeted in HBM; the reference iterates host-side
            # throughout, executor.go:2820-2996).
            outs, kept_pi, kept_ri, kept_rows = [], [], [], []
            n_out = kept_bytes = 0
            spilled = False
            for c0 in range(0, len(frontier.rows), chunk_p):
                pre, counts = count_level("exp", frontier, c0, chunk_p,
                                          bank, slots)
                nz = (yield from _host(counts)).ravel() > 0
                keep_idx = np.flatnonzero(nz)
                if len(keep_idx) == 0:
                    continue
                # Where some pair died, one of the dead pairs — its AND
                # is all zeros — pads the survivors to a multiple of
                # eight: the next level's programs meet a few frontier
                # sizes, not one a draw (a shape met first under load
                # compiles there, for seconds). A pad prefix counts
                # zero against every row and is in no group.
                pad = -len(keep_idx) % 8 if len(keep_idx) < len(nz) \
                    else 0
                take = np.concatenate(
                    [keep_idx, np.full(pad, np.argmin(nz))])
                kept_pi.append(n_out + take // R)
                kept_ri.append(take % R)
                kept_rows.extend(
                    frontier.rows[c0 + int(k) // R] + (int(ids[k % R]),)
                    for k in keep_idx)
                kept_rows.extend([(-1,) * (depth + 1)] * pad)
                n_out += pre.shape[0]
                kept_bytes += self._bank_device_bytes(pre.shape)
                if not spilled and kept_bytes > self.GROUPBY_CHUNK_BYTES:
                    spilled = True
                    self._count("executor.groupby_spills", 1)
                    for k, o in enumerate(outs):
                        outs[k] = yield from _host(o)
                outs.append((yield from _host(pre)) if spilled else pre)
            if not outs:
                self._note_group_by(0, levels[0], fetches)
                return []
            frontier = _Frontier(
                np.concatenate(outs) if spilled else tuple(outs),
                np.concatenate(kept_pi).astype(np.int32), bank,
                child_slots[depth][np.concatenate(kept_ri)], kept_rows)

        # Final depth: count every (prefix × row) pair in chunked batches.
        bank, ids = banks[-1].array, child_rows[-1][1]
        slots = upload(child_slots[-1])
        fields = [f for f, _ in child_rows]
        results: List[GroupCount] = []
        sums = None if aggregate is None else self._GroupSums(
            self, aggregate, shards, bank, child_slots[-1], wmin, _jit)
        if frontier is None:
            counts = (yield from _host(
                count_rows(bank, slots)))[None, :]      # [1, R]
            prefix_rows = [()]
        else:
            counts = None
            prefix_rows = frontier.rows
        # The count program fuses its AND into the reduction — one
        # fusion and no [p, R, S, W] temporary (compiled for a described
        # v5e at [20, 16, 32768] x [40, 16, 32768]) — so a chunk is
        # bounded by the prefixes it reads, not by prefixes x rows.
        chunk_p = max(1, self.GROUPBY_CHUNK_BYTES // per_prefix)
        for c0 in range(0, len(prefix_rows), chunk_p):
            if limit and len(results) >= limit:
                break
            pre = None
            if counts is None:
                pre, dev = count_level("cntN", frontier, c0, chunk_p,
                                       bank, slots)
                chunk_counts = yield from _host(dev)    # [p, R]
            else:
                chunk_counts = counts[c0:c0 + chunk_p]
            first = len(results)
            picked = []     # (prefix in the chunk, row) of each group kept
            for pi in range(chunk_counts.shape[0]):
                row_pre = prefix_rows[c0 + pi]
                # Paging: results are lexicographic by row-id tuple, so a
                # prefix strictly below previous's prefix can't produce
                # anything after `previous` (reference groupByIterator
                # seek, executor.go:2878-2900).
                if previous is not None and \
                        row_pre < previous[:len(row_pre)]:
                    continue
                crow = chunk_counts[pi]
                for ri in np.nonzero(crow)[0]:
                    if limit and len(results) >= limit:
                        break
                    tup = row_pre + (int(ids[ri]),)
                    if previous is not None and tup <= previous:
                        continue
                    group = [FieldRow(f, rid) for f, rid in
                             zip(fields, tup)]
                    results.append(GroupCount(group, int(crow[ri])))
                    picked.append((pi, int(ri)))
            if sums is not None and picked:
                sums.launch(pre, picked, results[first:])
        self._note_group_by(len(results), levels[0], fetches)
        if sums is None or not results:
            return results

        def finalize() -> List[GroupCount]:
            sums.finalize()
            return results

        return _Pending(finalize, arrays=sums.arrays())

    def _put_prefixes(self, host: np.ndarray):
        """A spilled chunk of GroupBy prefixes [p, S, w] back on the
        device: under a mesh split along S as the level programs' other
        operands are, never whole onto device 0 for the jit to
        re-shard."""
        if self.mesh is None:
            return upload(host)
        with transfer("h2d", int(host.nbytes)):
            return self.mesh.put_row(host)

    def _count(self, name: str, n: int) -> None:
        if self.stats is not None:
            self.stats.count(name, n)

    def _note_group_by(self, groups: int, levels: int, fetches) -> None:
        """A GroupBy's counters: groups answered, level programs
        launched (a pruning sweep, an expansion, a count), and the
        blocking fetches of its level loop as `executor.groupby_fetches
        {covered:no|yes}` — `yes` where, as the loop blocked, another
        member of its flush had a level program queued on the device."""
        self._count("executor.groupby_groups", groups)
        self._count("executor.groupby_levels", levels)
        if self.stats is not None:
            for covered, n in zip(("no", "yes"), fetches):
                if n:
                    self.stats.with_tags(f"covered:{covered}").count(
                        "executor.groupby_fetches", n)

    class _GroupSums:
        """The `sum` of a GroupBy's groups (`aggregate=Sum(field=f)`):
        launched chunk by chunk of the last level as its groups are
        picked, fetched together when the answer is finalized.

        One `groupby_sum` launch takes up to GROUPSUM_CHUNK_BYTES of
        groups. Its program (`ops/groupsum.group_plane_counts`, a
        Pallas kernel; interpreted off a TPU) reads the last level's
        prefixes, the last child's resident bank and f's plane bank by
        three index vectors, a word tile at a time: a tile of f's
        planes is held on chip, each group's mask — prefix ∧ row ∧ f's
        not-null plane — is formed there from its two operand tiles,
        and |mask ∧ plane| of every bit plane and |mask| are added into
        per-(group, plane) count vectors before the tile is dropped;
        lanes are reduced once a launch. No [g, S, W] mask array is
        written; f's planes are fetched once a block of groups, a
        group's row and prefix once a run of groups that name the same
        one (`executor.groupsum_operand_rows` counts those fetches).
        Under a mesh each device runs it over its own shards and one
        `psum` adds the counts. g is padded to a few sizes, so a family
        of queries meets a few shapes. The counts come back as u32
        [planes + 1, g] (the last row |mask|, the columns with a
        value); the host weighs them: a plane's count << its bit, plus
        the field's offset (bsiGroup.min, which a signed field's is
        negative) times the columns with a value."""

        def __init__(self, ex, aggregate, shards, bank, slots, wmin, jit):
            field, self.bsig = aggregate
            self.ex, self.jit = ex, jit
            self.bank, self.slots = bank, slots   # the last child's
            self.depth = self.bsig.bit_depth
            planes = ex._get_bank_for(field, view_bsi_name(field.name),
                                      shards)
            self.planes, self.sel = planes.array, _bsi_sel(planes,
                                                           self.depth)
            # Every operand is ANDed: the narrowest width is enough.
            self.width = min(wmin, planes.array.shape[-1])
            self.pending = []   # (device counts [planes + 1, g], groups)

        def launch(self, pre, picked, groups) -> None:
            import jax
            from pilosa_tpu.ops.groupsum import (MAX_LANES,
                                                 group_plane_counts,
                                                 rows_fetched, tile_words)
            depth, w = self.depth, self.width
            # A launch's groups, by a device's share of one row [S, w]
            # a group — and, where rows are narrow, by the kernel's own
            # limit.
            row_bytes = self.ex._bank_device_bytes(
                (1, self.bank.shape[-2], w))
            g_max = min(MAX_LANES, max(
                1, Executor.GROUPSUM_CHUNK_BYTES // row_bytes))
            # Word tiles the kernel walks on a device.
            tiles = w // tile_words(w, row_bytes // (4 * w),
                                    depth + 2 + (pre is not None))
            mesh = self.ex.mesh
            over = {} if mesh is None else {"mesh": mesh.mesh,
                                            "axis": mesh.SHARD_AXIS}
            # Off a TPU the same kernel runs interpreted: results, no
            # speed.
            interpret = jax.devices()[0].platform != "tpu"

            def run(pre, pi, bank, si, plane_bank, sel):
                return group_plane_counts(
                    pre, pi, bank, si, plane_bank, sel, w,
                    interpret=interpret, **over)    # [depth + 1, g]

            with TIMELINE.stage("groupby.aggregate", groups=len(picked),
                                planes=depth + 1) as span:
                launches = operand_rows = 0
                for g0 in range(0, len(picked), g_max):
                    part = picked[g0:g0 + g_max]
                    g = len(part)
                    # g is padded to a power of two up to 128 and to a
                    # multiple of 128 past it: a family of queries
                    # meets a few shapes (this program compiles for
                    # seconds). Pad lanes recompute the chunk's first
                    # group; nobody reads them.
                    lanes = max(8, _pow2(g)) if g <= 128 \
                        else -(-g // 128) * 128
                    idx = np.zeros((2, lanes), np.int32)
                    idx[:, :g] = np.asarray(part, dtype=np.int32).T
                    idx[:, g:] = idx[:, :1]
                    fn = self.jit(
                        f"gb_sum:{lanes}:{_gb_shape(pre)}:"
                        f"{self.bank.shape}:{self.planes.shape}:"
                        f"d{depth}:{w}",
                        run, span="groupby_sum", lanes=lanes,
                        prefixes=0 if pre is None else int(pre.shape[0]),
                        chunk_of=f"{g0 // g_max + 1}/"
                                 f"{-(-len(picked) // g_max)}")
                    out = fn(pre, None if pre is None else upload(idx[0]),
                             self.bank, upload(self.slots[idx[1]]),
                             self.planes, self.sel)
                    self.pending.append((out, groups[g0:g0 + g]))
                    launches += 1
                    # The rows the launch's kernel fetches.
                    operand_rows += rows_fetched(
                        None if pre is None else idx[0], idx[1],
                        depth + 1, tiles)
                span.set("launches", launches)
            self.ex._count("executor.groupsum_launches", launches)
            self.ex._count("executor.groupsum_plane_rows",
                           len(picked) * (depth + 1))
            self.ex._count("executor.groupsum_operand_rows", operand_rows)

        def arrays(self) -> tuple:
            return tuple(out for out, _ in self.pending)

        def finalize(self) -> None:
            for out, groups in self.pending:
                counts = np.asarray(out)[:, :len(groups)]
                valued = counts[-1].astype(np.int64)
                if self.depth <= 32:    # 2^30 columns << 32 fits int64
                    base = (counts[:-1].astype(np.int64)
                            << np.arange(self.depth,
                                         dtype=np.int64)[:, None]).sum(0)
                    base = base.tolist()
                else:
                    base = [sum(c << i for i, c in enumerate(col))
                            for col in counts[:-1].T.tolist()]
                for gc, b, n in zip(groups, base, valued.tolist()):
                    gc.sum = b + self.bsig.min * n

    # -------------------------------------------------------- Sum/Min/Max

    def _execute_val_count(self, idx: Index, call: Call, shards, op: str
                           ) -> ValCount:
        """(reference executeSumCountShard :569, executeMinShard :610,
        executeMaxShard :651)."""
        import jax
        import jax.numpy as jnp

        field_name = call.arg("field") or call.arg("_field")
        if field_name is None:
            raise ExecutionError(f"{op}() requires a field argument")
        field = idx.field(field_name)
        if field is None:
            raise ExecutionError(f"field not found: {field_name}")
        bsig = field.bsi_groups.get(field_name)
        if bsig is None:
            raise ExecutionError(f"field {field_name} is not an int field")
        shards = self._shards(idx, shards)
        depth = bsig.bit_depth
        bank = self._get_bank_for(field, view_bsi_name(field_name), shards)
        sel = _bsi_sel(bank, depth)
        filter_words = None
        if call.children:
            filter_words = _align_words(
                self._eval_tree(idx, call.children[0], shards, mode="row"),
                bank.array.shape[-1])

        key = f"val:{op}:{bank.array.shape}:d{depth}:" \
              f"{filter_words is not None}"
        program = f"bsi_{op.lower()}"
        fn = self._jit_get(key)
        if fn is None:
            self._note_jit_compile(program, key)
            from pilosa_tpu.ops.bitset import popcount
            if op == "Sum":
                def run(bank_arr, sel, filt):
                    return bsi.sum_count(bank_arr[sel], filt)
            else:
                kernel = bsi.min_mask if op == "Min" else bsi.max_mask

                def run(bank_arr, sel, filt):
                    bits, cand = kernel(bank_arr[sel], filt)
                    return bits, popcount(cand, axis=(-2, -1))
            fn = jax.jit(named(run, program))
            self._jit_put(key, fn)
        with self._dispatch_span(program):
            a, b = fn(bank.array, sel, filter_words)

        def finalize() -> ValCount:
            if op == "Sum":
                counts = np.asarray(a, dtype=np.int64)
                cnt = int(np.asarray(b))
                total = sum(int(c) << i
                            for i, c in enumerate(counts.tolist()))
                return ValCount(total + bsig.min * cnt, cnt)
            count = int(np.asarray(b))
            if count == 0:
                return ValCount(0, 0)
            base = sum(int(v) << i
                       for i, v in enumerate(np.asarray(a).tolist()))
            return ValCount(base + bsig.min, count)

        return _Pending(finalize, arrays=(a, b))

    # --------------------------------------------------------------- writes

    def _set_args(self, idx: Index, call: Call) -> Tuple[Field, int, Any]:
        col = call.arg("_col")
        if not isinstance(col, int):
            raise ExecutionError("column keys require keys=True (API layer)")
        fname, row_ref = self._row_call_field(call)
        field = idx.field(fname)
        if field is None:
            raise ExecutionError(f"field not found: {fname}")
        return field, col, row_ref

    def _execute_set(self, idx: Index, call: Call) -> bool:
        """(reference executeSet, executor.go:1889)."""
        field, col, row_ref = self._set_args(idx, call)
        if field.options.type == FIELD_TYPE_INT:
            changed = field.set_value(col, int(row_ref))
        else:
            ts = call.arg("_timestamp")
            timestamp = timeq.parse_timestamp(ts) if ts else None
            row_id = self._row_id(field, row_ref)
            changed = field.set_bit(row_id, col, timestamp=timestamp)
        idx.add_existence(np.array([col], dtype=np.uint64))
        return changed

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        field, col, row_ref = self._set_args(idx, call)
        if field.options.type == FIELD_TYPE_INT:
            bsig = field.bsi_groups[field.name]
            view = field.view(view_bsi_name(field.name))
            if view is None:
                return False
            frag = view.fragment(col // SHARD_WIDTH)
            return frag.clear_value(col, bsig.bit_depth) if frag else False
        row_id = self._row_id(field, row_ref)
        return field.clear_bit(row_id, col)

    def _execute_clear_row(self, idx: Index, call: Call, shards) -> bool:
        """(reference executeClearRowShard, executor.go:1761)."""
        fname, row_ref = self._row_call_field(call)
        field = idx.field(fname)
        if field is None:
            raise ExecutionError(f"field not found: {fname}")
        if field.options.type not in (FIELD_TYPE_SET, FIELD_TYPE_TIME,
                                      FIELD_TYPE_MUTEX, FIELD_TYPE_BOOL):
            raise ExecutionError(
                f"ClearRow() is not supported on {field.options.type} fields")
        row_id = self._row_id(field, row_ref)
        shards = self._shards(idx, shards, pad=False)  # host-side write
        changed = False
        for view in field.views.values():
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                cols = frag.row_columns(row_id)
                if len(cols):
                    frag.bulk_import(np.full(len(cols), row_id, np.uint64),
                                     cols, clear=True)
                    changed = True
        return changed

    def _execute_store(self, idx: Index, call: Call, shards) -> bool:
        """Store(Row(...), f=row): write a computed row (reference
        executeSetRowShard, executor.go:1834)."""
        if len(call.children) != 1:
            raise ExecutionError("Store() takes exactly one row argument")
        fname, row_ref = self._row_call_field(call)
        field = idx.field(fname)
        if field is None:
            field = idx.create_field(fname)
        elif field.options.type not in (FIELD_TYPE_SET, FIELD_TYPE_TIME):
            raise ExecutionError(
                f"Store() is not supported on {field.options.type} fields")
        row_id = self._row_id(field, row_ref)
        real_shards = self._shards(idx, shards, pad=False)
        padded = self._shards(idx, shards)
        words = np.asarray(self._eval_tree(idx, call.children[0], padded,
                                           mode="row"))
        view = field.create_view_if_not_exists(VIEW_STANDARD)
        # Write only real shards — mesh padding appends at the tail and
        # must never materialize phantom fragments.
        for i, shard in enumerate(real_shards):
            frag = view.create_fragment_if_not_exists(shard)
            frag.set_row(row_id, words[i])
        return True

    def _execute_set_row_attrs(self, idx: Index, call: Call) -> None:
        """(reference executeSetRowAttrs, executor.go:2029)."""
        fname = call.arg("_field")
        field = idx.field(fname)
        if field is None:
            raise ExecutionError(f"field not found: {fname}")
        row_id = call.arg("_row")
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        field.row_attr_store.set(int(row_id), attrs)

    def _execute_set_column_attrs(self, idx: Index, call: Call) -> None:
        col = call.arg("_col")
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        idx.column_attr_store.set(int(col), attrs)

    # ------------------------------------------------------------ row attrs

    def _attach_row_attrs(self, idx: Index, call: Call, res: RowResult
                          ) -> None:
        if call.name not in ("Row", "Range"):
            return
        try:
            fname, row_ref = self._row_call_field(call)
        except ExecutionError:
            return
        field = idx.field(fname)
        if field is None or isinstance(row_ref, Condition):
            return
        if isinstance(row_ref, int) and not isinstance(row_ref, bool):
            cap = getattr(self._tls, "deps", None)
            if cap is not None:
                # The response embeds row attrs, whose mutations do
                # NOT bump fragment generations — stamp the attr
                # store's own counter into the request deps.
                # Stamp-then-read (first stamp wins): a set_bulk racing
                # the get() below leaves the stored gen behind, so the
                # fill can never validate pre-write attrs as current.
                cap.setdefault(("rattr", idx.name, field.name),
                               field.row_attr_store.gen)
            res.attrs = field.row_attr_store.get(row_ref)
