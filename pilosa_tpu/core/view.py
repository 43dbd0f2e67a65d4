"""View: a named collection of fragments, one per shard.

Reference: /root/reference/view.go:41. View names: "standard", time views
"standard_YYYY[MM[DD[HH]]]", and BSI views "bsig_<field>" (view.go:35-37).
Fragments are created lazily on first write (CreateFragmentIfNotExists,
view.go:207).
"""

from __future__ import annotations

import os
from pilosa_tpu.utils.locks import make_lock, make_rlock
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pilosa_tpu.core.fragment import CONTAINER_BITS, Fragment
from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.utils.hotspots import WORKLOAD
from pilosa_tpu.utils.memledger import LEDGER
from pilosa_tpu.utils.profile import transfer
from pilosa_tpu.utils.timeline import TIMELINE

VIEW_STANDARD = "standard"
VIEW_BSI_PREFIX = "bsig_"

# Sparse chunk upload (kill switch): single-shard narrow-layout chunk
# banks ship u16 bit POSITIONS (~2 B/set bit) and expand to the dense
# bank on device with one scatter — ~5x less host->device traffic for
# fingerprint-shaped fields, where the host->device transfer (not the
# sweep) dominates.
SPARSE_UPLOAD = os.environ.get("PILOSA_TPU_SPARSE_UPLOAD", "1") != "0"

# Demotion-ranked BankBudget eviction (hybrid layout satellite): under
# HBM pressure the sparsest-coldest cached bank is evicted first
# instead of the merely-oldest. 0 restores pure LRU.
SMART_EVICT = os.environ.get("PILOSA_TPU_LAYOUT_EVICT", "1") != "0"

_EXPAND_FN = None
_EXPAND_SENTINEL = 0xFFFFFFFF


def _expand_sparse_chunk(pos16: np.ndarray, lens: np.ndarray,
                         rows_at: np.ndarray, cap: int, width: int):
    """Device [cap, 1, width] u32 bank from concatenated per-row sorted
    UNIQUE positions. Uniqueness matters: the expansion scatter uses
    add, and two set bits only OR because distinct powers of two add
    without carries — container arrays guarantee it. Position arrays
    pad to power-of-two buckets so XLA compiles O(log P) variants, not
    one per chunk cardinality; sentinel entries land on a scratch word
    past the bank and add zero."""
    global _EXPAND_FN
    import functools

    import jax
    import jax.numpy as jnp

    if _EXPAND_FN is None:
        # graftlint: disable=GL006 — process-global build memoized in
        # _EXPAND_FN; static (cap, width) + pow2-padded positions keep
        # the variant count O(log P), not per-query churn.
        @functools.partial(jax.jit, static_argnums=(2, 3))
        def expand(pos, row_of, cap, width):
            total = cap * width
            sent = pos == jnp.uint32(_EXPAND_SENTINEL)
            word = jnp.where(
                sent, total,
                row_of * width + (pos >> 5)).astype(jnp.int32)
            bit = jnp.where(
                sent, jnp.uint32(0),
                jnp.left_shift(jnp.uint32(1),
                               (pos & 31).astype(jnp.uint32)))
            flat = jnp.zeros((total + 1,), jnp.uint32)
            flat = flat.at[word].add(bit, mode="drop",
                                     unique_indices=False)
            return flat[:total].reshape(cap, 1, width)

        _EXPAND_FN = expand
    n = len(pos16)
    padded = 1 << max(10, (n - 1).bit_length() if n else 0)
    pos = np.full(padded, _EXPAND_SENTINEL, np.uint32)
    pos[:n] = pos16
    row_of = np.zeros(padded, np.uint32)
    if n:
        row_of[:n] = np.repeat(rows_at.astype(np.uint32), lens)
    return _EXPAND_FN(jnp.asarray(pos), jnp.asarray(row_of), cap, width)


# A bank patch writes whole (row slot, shard) cells into a cached bank:
# one program per (bank shape, lane bucket). Lanes are a power of two up
# to this; a longer patch is a chain of launches of the top bucket.
PATCH_LANES_MAX = 64
_PATCH_PROGRAMS: Dict[tuple, Any] = {}
_PATCH_LOCK = make_lock("view._PATCH_LOCK")


def _patch_programs(array) -> Dict[Tuple[int, bool], Any]:
    """{(lanes, donating): compiled `bank_patch`} for banks of this
    array's shape, dtype and placement. EVERY lane bucket is compiled
    when the first patch of a shape runs — ahead of time, nothing is
    executed — so a later patch of any cell count finds its program:
    the cell count is what write traffic happens to leave between two
    reads of a bank, and a count first met inside a serving window
    must not compile there. The donating variant (top bucket only)
    continues a chain over an array this module made itself; the
    first launch of a patch never donates, because a staged filter or
    a sweep group may still hold the cached bank's array."""
    import jax

    # The placement a mesh gave the bank, or none: an array the host
    # uploaded plainly is uncommitted, and a program lowered FOR its
    # device would hand back a committed one — another jit cache key
    # for every program that takes the patched bank (the sweeps and
    # filter programs compiled anew inside a window: 9 compiles, 11.7 s;
    # PERF.md §6 PR 38).
    sharding = array.sharding if array.committed else None
    key = (array.shape, str(array.dtype), sharding)
    with _PATCH_LOCK:
        progs = _PATCH_PROGRAMS.get(key)
        if progs is not None:
            return progs

        def bank_patch(bank, rows_idx, shard_idx, words):
            return bank.at[rows_idx, shard_idx].set(words)

        def compiled(lanes: int, donate: bool):
            # graftlint: disable=GL006 — process-global build memoized
            # in _PATCH_PROGRAMS, as _expand_sparse_chunk's: the view
            # layer has no executor to ask; `xla.compiles` and
            # /debug/queries' xla.byName count it (jit(bank_patch)).
            fn = jax.jit(bank_patch,
                         donate_argnums=(0,) if donate else ())
            idx = jax.ShapeDtypeStruct((lanes,), np.int32)
            return fn.lower(
                jax.ShapeDtypeStruct(array.shape, array.dtype,
                                     sharding=sharding),
                idx, idx,
                jax.ShapeDtypeStruct((lanes, array.shape[-1]),
                                     array.dtype)).compile()

        progs = {}
        lanes = 1
        while lanes <= PATCH_LANES_MAX:
            progs[(lanes, False)] = compiled(lanes, False)
            lanes *= 2
        progs[(PATCH_LANES_MAX, True)] = compiled(PATCH_LANES_MAX, True)
        # graftlint: disable=GL008 — one entry per distinct bank SHAPE
        # (capacity is a power of two, shards and width the index's):
        # grows with the schema, not with traffic.
        _PATCH_PROGRAMS[key] = progs
        return progs


class BankBudget:
    """Process-wide accounting of cached device banks, bounding the HBM
    that ONE device spends on operand banks: a bank split over a mesh's
    shard devices is accounted by the share each of them holds
    (`device_share_bytes`), which without a mesh is the whole array.
    The reference never needs this because it
    streams one shard at a time from mmap (executor.go:2377); here banks
    persist in HBM across queries for reuse, so an explicit budget decides
    what stays resident. Evicted banks drop out of their view's cache (the
    device array frees once the last query referencing it drains).

    Eviction is demotion-ranked, not pure LRU: under pressure the
    victim is the entry with the highest workload-plane demotion score
    ((1 - live density) * bytes / (1 + read rate), the same ranking
    /debug/hotspots serves) so the sparsest-coldest bank goes first;
    entries the ledger/workload plane cannot score fall back to score
    0, and ties break LRU (oldest insertion wins) — a process with no
    workload data evicts exactly as the old pure-LRU budget did.
    PILOSA_TPU_LAYOUT_EVICT=0 restores pure LRU outright."""

    # Ledger categories a view registers its cached entries under; an
    # eviction must clear whichever one the key belongs to (keys are
    # disjoint across categories, and unregister is idempotent, so
    # clearing each is one cheap dict miss per non-owner).
    LEDGER_CATEGORIES = ("bank", "pbank", "sparse_bank", "host_block")

    def __init__(self, budget_bytes: int, cache_attr: str = "_bank_cache"):
        self.budget = budget_bytes
        self.cache_attr = cache_attr
        self._lock = make_lock("BankBudget._lock")
        # (id(view), key) -> (view, nbytes), in LRU order (oldest first).
        from collections import OrderedDict
        self._entries: "OrderedDict" = OrderedDict()
        self.total = 0
        self.evictions = 0

    def _eviction_scores(self):
        """Demotion scores for the current entries (computed ONCE per
        admit's eviction run, under the lock — scores cannot move
        mid-admit while the lock is held). The scorer reads the memory
        ledger + workload recorder — both leaf locks acquired strictly
        after this one (BankBudget -> Ledger/Workload is the only
        nesting direction, so the order graph stays acyclic under
        PILOSA_TPU_LOCK_CHECK)."""
        if not SMART_EVICT or len(self._entries) < 2:
            return {}
        try:
            from pilosa_tpu.core.layout import demotion_scores
            return demotion_scores(self._entries)
        except Exception:
            return {}

    def _pick_victim(self, scores):
        """Key of the entry to evict (called under the lock): highest
        demotion score wins, ties (and unscorable entries) resolve to
        the LRU-oldest."""
        if scores:
            best_ek, best = None, -1.0
            for ek in self._entries:  # oldest first -> LRU ties
                s = scores.get(ek, 0.0)
                if s > best:
                    best_ek, best = ek, s
            if best_ek is not None and best > 0.0:
                return best_ek
        return next(iter(self._entries))

    def admit(self, view: "View", key, nbytes: Optional[int] = None
              ) -> None:
        cache = getattr(view, self.cache_attr)
        if nbytes is None:
            bank = cache.get(key)
            if bank is None:
                return
            nbytes = device_share_bytes(bank.array.shape,
                                        bank.array.sharding)
        ek = (id(view), key)
        with self._lock:
            old = self._entries.pop(ek, None)
            if old is not None:
                self.total -= old[1]
            scores = None
            while self._entries and self.total + nbytes > self.budget:
                if scores is None:
                    scores = self._eviction_scores()
                vid, vkey = self._pick_victim(scores)
                scores.pop((vid, vkey), None)
                v, nb = self._entries.pop((vid, vkey))
                self.total -= nb
                self.evictions += 1
                getattr(v, self.cache_attr).pop(vkey, None)
                for cat in self.LEDGER_CATEGORIES:
                    LEDGER.unregister(cat, (vid, vkey))
            self._entries[ek] = (view, nbytes)
            self.total += nbytes

    def touch(self, view: "View", key) -> None:
        ek = (id(view), key)
        with self._lock:
            if ek in self._entries:
                self._entries.move_to_end(ek)

    def forget(self, view: "View", key) -> None:
        with self._lock:
            old = self._entries.pop((id(view), key), None)
            if old is not None:
                self.total -= old[1]
        for cat in self.LEDGER_CATEGORIES:
            LEDGER.unregister(cat, (id(view), key))


# Default sized for a v5e-class chip (16 GiB HBM), per device: 12 GiB of
# resident banks leaves ~4 GiB for transient chunk banks, filter rows, sparse
# expansions, and XLA scratch. The 100M-fingerprint positions bank
# (~9.6 GiB) must fit WITH its filter banks or the LRU thrashes it on
# every query — the round-3 8 GiB default did exactly that.
BANK_BUDGET = BankBudget(
    int(os.environ.get("PILOSA_TPU_HBM_BUDGET_BYTES", 12 << 30)))

# Process-wide host-RAM budget for cached packed chunk blocks (the
# chunked-TopN repeat-query shortcut). 0 disables caching.
HOST_BLOCK_BUDGET = BankBudget(
    int(os.environ.get("PILOSA_TPU_HOST_BLOCK_CACHE_BYTES", 1 << 30)),
    cache_attr="_host_blocks")


class ViewBank:
    """A view's rows stacked across shards as ONE device array
    [row_capacity, n_shards, WORDS_PER_SHARD] (uint32) in HBM.

    This is the executor's operand format: a row leaf is `bank[slot]` with
    the slot passed as a *traced* index, so an entire PQL tree over any rows
    of any shards compiles once and runs as a single device program — the
    TPU replacement for goroutine-per-shard fan-out (executor.go:2377).
    The last slot is always all-zeros (rows absent from the view resolve
    there). Capacity is padded to a power of two so adding rows rarely
    changes the compiled shape.
    """

    def __init__(self, array, slots, zero_slot, versions, subset=False):
        self.array = array          # jnp [Rcap, S, W]
        self.slots = slots          # row id -> slot
        self.zero_slot = zero_slot
        self.versions = versions    # {shard: fragment.version} at build time
        self.subset = subset        # built for a caller's `rows`, not all
        self._slot_rows = None
        # The rows' own popcounts [Rcap], computed FROM `array` by the
        # first tanimoto TopN that meets this bank (one unfiltered
        # sweep, executor._bank_popcounts): None until then, that
        # sweep's device vector until a finalize fetches it, the host's
        # np.ndarray from then on. A write builds a new ViewBank, so
        # they live one bank version and are never patched.
        self.popcounts = None
        self._popcounts_lock = make_lock("ViewBank._popcounts_lock")

    def slot(self, row_id: int) -> int:
        return self.slots.get(row_id, self.zero_slot)

    def row_popcounts(self, sweep) -> Tuple[Any, bool]:
        """(the rows' own popcounts, whether this call swept them): the
        kept vector, pending or fetched, which `sweep(array)` launches
        the first time anybody asks. A bank version is swept once,
        however many calls meet it before the first fetch."""
        with self._popcounts_lock:
            swept = self.popcounts is None
            if swept:
                self.popcounts = sweep(self.array)
            return self.popcounts, swept

    def host_popcounts(self) -> Tuple[np.ndarray, bool]:
        """(the swept vector on the host, whether this call fetched
        it): the first finalize to get here swaps the device's vector
        for its host copy. The wait is outside the lock, so a call
        that meets the bank meanwhile takes the pending vector."""
        host = self.popcounts
        if isinstance(host, np.ndarray):
            return host, False
        # graftlint: materialize — the one fetch of a bank version's
        # popcounts, inside the finalize that reads them.
        host = np.asarray(host)
        with self._popcounts_lock:
            fetched = not isinstance(self.popcounts, np.ndarray)
            if fetched:
                self.popcounts = host
            return self.popcounts, fetched

    def slot_rows(self) -> np.ndarray:
        """Row ids in slot order, uint64 [len(slots)]: slot i holds row
        `slot_rows()[i]` (slots are dense from 0; a patched bank
        appends its new rows). What a sweep of the whole bank maps its
        count vector by — one array per bank version instead of a dict
        probe per row per query. Built on first use, from the dict, so
        every way a bank is made agrees with its `slots`."""
        rows = self._slot_rows
        if rows is None:
            n = len(self.slots)
            rows = np.empty(n, dtype=np.uint64)
            rows[np.fromiter(self.slots.values(), dtype=np.int64, count=n)] \
                = np.fromiter(self.slots.keys(), dtype=np.uint64, count=n)
            self._slot_rows = rows
        return rows


class PositionsBank:
    """Device-RESIDENT sparse view for single-shard narrow layouts:
    rows' sorted u16 bit positions — ~2 bytes per SET bit instead of 64
    per bit-slot, so a 100M-row fingerprint field (~10 GB) stays
    resident in one chip's HBM where its dense banks (~51 GB) cannot.
    Filtered TopN then needs NO per-query upload or chunk streaming
    (executor._topn_positions). Two segment layouts, distinguished by
    the position array's RANK (every consumer must dispatch on it):

    - flat:  (row_lo, n_rows, pos u16 [Ppad], starts i32 [n_rows+1],
      p_real) — |row ∧ filter| = membership bits + cumsum differenced
      at starts; handles arbitrary per-row lengths.
    - fixed: (row_lo, n_rows, pos u16 [L, n_rows], lens i32 [n_rows],
      p_real) — rows padded to L slots with 0xFFFF and stored SLOT-MAJOR
      (slot l of every row is one contiguous plane, rows on the device's
      lanes); counts are an add over the L planes, no cumsum, no
      gathers, no cross-lane reduce. Chosen per segment when every row
      fits PBANK_FIXED_ROW_SLOTS, density clears
      PBANK_FIXED_MIN_DENSITY and the bank so padded fits its share of
      the HBM budget (pbank_fixed_fits).

    Segmented on row boundaries so every segment's position count fits
    i32 offsets. `row_widths` holds each segment's longest row: a Row of
    this field can have no more on-bits than the largest of them, so
    the segment program's membership compare is that wide (`qslots`)
    and no wider."""

    __slots__ = ("segments", "row_ids", "versions", "nbytes",
                 "row_widths")

    def __init__(self, segments, row_ids, versions, nbytes, row_widths):
        self.segments = segments
        self.row_ids = row_ids      # global sorted row ids
        self.versions = versions
        self.nbytes = nbytes
        self.row_widths = row_widths    # per segment: its longest row

    @property
    def qslots(self) -> int:
        """Query slots of the membership compare: what the bank's widest
        row needs as the filter, rounded up to a multiple of 8 so that
        a write which lengthens that row by a bit or two compiles
        nothing (the width is a compile key)."""
        return max(8, -(-max(self.row_widths, default=0) // 8) * 8)


class SparseBank:
    """First-class QUERY-SERVABLE sparse device bank (the hybrid
    layout's compact representation): every row's SET bit positions as
    one encoded uint32 array plus a per-row-slot offset table —
    ~4 bytes per set bit instead of ``4 * width`` per row slot, which
    is the shards-per-chip capacity win for sparse/cold views. This
    generalizes :class:`PositionsBank` (a TopN-sweep special case)
    into the executor's operand format: a Row leaf over a sparse-
    resident view stages an ``("xslot", ...)`` IR node whose program
    scatter-expands ``rows[slot]`` to the dense ``[S, W]`` register on
    device (ops/megakernel.expand_positions) — bit-identical to the
    dense bank row because expansion is exactly the inverse of the
    positions gather.

    Encoding: ``pos[k] = (shard_idx << 16) | bitpos`` (bitpos < 2^16
    because sparse banks exist only for trimmed widths within one
    container, the same constraint as Fragment.rows_positions);
    ``starts`` has ``capacity + 1`` i32 offsets with rows beyond the
    real set left empty, so absent rows resolve to the zero register
    through ``zero_slot`` exactly like a dense bank's all-zero slot.
    ``arrays`` is a stable ``(pos, starts)`` tuple — fusion groups and
    the megakernel lowering key operand identity on it."""

    __slots__ = ("arrays", "slots", "zero_slot", "versions", "nbytes",
                 "width", "n_shards", "n_rows")

    def __init__(self, arrays, slots, zero_slot, versions, nbytes,
                 width, n_shards, n_rows):
        self.arrays = arrays        # (pos u32 [Ppad], starts i32 [cap+1])
        self.slots = slots          # row id -> slot
        self.zero_slot = zero_slot
        self.versions = versions    # {shard: fragment.version} at build
        self.nbytes = nbytes
        self.width = width          # the dense width expansion targets
        self.n_shards = n_shards
        self.n_rows = n_rows

    def slot(self, row_id: int) -> int:
        return self.slots.get(row_id, self.zero_slot)


# Positions per device segment. The TopN kernel's cumsum array is
# i32-indexed (x64 stays off), so segment position counts must stay
# well under 2^31; the build enforces the cap EXACTLY by splitting
# gather chunks on row boundaries (a row contributes at most 2^16
# positions, so no single row can break it). At 2^27 a flat segment's
# program compiles, for a v5e, to 2.15 GB of temporaries, so a wave of
# four queues 8.6 GB beside the resident bank (2^29 segments put
# multi-GB transients next to a ~10 GB bank and OOMed a 100M-row run on
# an earlier machine); a fixed segment's keeps 0.31 GB (PERF.md §7 row
# 30). The extra dispatches are cheap — results fetch as one batched
# device_get. The host gather chunk bounds the build's temporaries.
PBANK_SEGMENT_POSITIONS = int(os.environ.get(
    "PILOSA_TPU_PBANK_SEGMENT", 1 << 27))
PBANK_GATHER_ROWS = 1 << 20
# Fixed-width segment eligibility: every row in the segment must fit
# this many position slots, real positions must fill at least this
# fraction of the padded matrix, and the whole bank so padded must fit
# its share of the HBM budget (pbank_fixed_fits). Over 8.4 M rows of
# 48 real positions in 104 slots (density 0.46) the padded compare +
# row sum reads 91 ms, ~0.1 ns a SLOT, where the flat compare + cumsum
# + one gather reads 265 ms, ~0.66 ns a POSITION (417 with the two
# gathers it had; my chip run, PR 41, benches/pbank_kernel_probe.py).
# By those two rates the padded form would stay the faster one down to
# a density of ~0.16, but nothing was read below 0.46: the floor sits
# just under the sparsest segment measured as served (a fingerprint
# library's, 0.38-0.47). It was 0.5, which refused that library the
# form that answers it 4.6 times as fast.
PBANK_FIXED_ROW_SLOTS = int(os.environ.get(
    "PILOSA_TPU_PBANK_FIXED_SLOTS", 128))
PBANK_FIXED_MIN_DENSITY = 0.35
PBANK_FIXED_FILL_ROWS = 4096    # rows a block of the host fill
# Segment row counts round up to this multiple so kernel shapes repeat
# across segments (one compile per bank instead of one per segment).
PBANK_FIXED_ROW_PAD = int(os.environ.get(
    "PILOSA_TPU_PBANK_ROW_PAD", 1 << 16))


def pbank_fixed_bytes(slots: int, n_rows: int) -> int:
    """HBM a fixed segment's `[slots, n_rows]` u16 matrix takes: the
    device tiles it 16 slots by 128 rows."""
    return (-(-slots // 16) * 16) * (-(-n_rows // 128) * 128) * 2


def pbank_segment_bytes(pos, aux) -> int:
    """HBM one segment holds: its positions (a fixed segment's as the
    device pads them) and its i32 aux vector (lens or starts, possibly
    row-padded: its own size is the truth)."""
    pos_bytes = pbank_fixed_bytes(*pos.shape) if pos.ndim == 2 \
        else int(pos.size) * 2
    return pos_bytes + int(aux.size) * 4


def pbank_fixed_fits(n_rows: int) -> bool:
    """Whether a bank of `n_rows` rows may lay its segments out fixed:
    padded to the layout's widest admitted row (PBANK_FIXED_ROW_SLOTS:
    the build learns a segment's own L only as it gathers it) the bank
    must stay inside half of one device's bank budget — the other half
    is what the budget's other banks and the wave of segment programs
    keep (the same half `_execute_topn` asks of a stream of chunk banks
    before it caches them). A bank past it stays flat, 2 B a
    position."""
    padded = pbank_fixed_bytes(PBANK_FIXED_ROW_SLOTS, n_rows) + n_rows * 4
    return padded <= BANK_BUDGET.budget // 2


def view_bsi_name(field: str) -> str:
    return VIEW_BSI_PREFIX + field


def bank_capacity(n_rows: int) -> int:
    """Slot capacity for a bank of n_rows: next power of two above
    n_rows + 1 (one all-zero slot) — the single source of truth shared
    with the executor's HBM budget check."""
    cap = 1
    while cap < n_rows + 1:
        cap *= 2
    return cap


def device_share_bytes(shape: Sequence[int], sharding=None) -> int:
    """Bytes of a uint32 bank [rows, shards, words] that ONE device
    holds: `shape` cut by `sharding` (a mesh's `bank_sharding()` before
    the bank is built, a built array's own `.sharding` after), the whole
    array when there is none. Every per-device limit prices a bank by
    this — the resident-sweep limit, the row-subset limit and the bank
    budget — so a bank split over four devices costs each a quarter."""
    if sharding is not None:
        shape = sharding.shard_shape(tuple(int(x) for x in shape))
    return int(np.prod(shape, dtype=np.int64)) * 4


class View:
    def __init__(self, path: str, index: str, field: str, name: str,
                 cache_type: str = cache_mod.CACHE_TYPE_RANKED,
                 cache_size: int = cache_mod.DEFAULT_CACHE_SIZE,
                 max_columns: int = 0):
        self.path = path  # .../<field>/views/<name>
        self.index = index
        self.field = field
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.max_columns = max_columns  # declared column bound (0 = full)
        self.fragments: Dict[int, Fragment] = {}
        self._lock = make_rlock("View._lock")
        self.on_new_shard = None  # callback(shard) for shard broadcasts
        # Hybrid device layout (core/layout.py): "dense" serves Row
        # leaves from ViewBanks, "sparse" from SparseBanks (set by the
        # background re-layout pass or an operator). Planning snapshots
        # the mode once per staged query; a flip mid-flight only
        # changes which (correct) representation the NEXT staging
        # picks, never the bits — cache safety needs no layout epoch
        # because the two layouts compile under DISTINCT signatures
        # (the x-vs-r sig parts + sparse expansion widths) and data
        # validity is already guarded by the fragment versions.
        self.layout_mode = "dense"
        self._bank_cache: Dict[tuple, ViewBank] = {}
        # Host-side packed blocks for transient row-subset banks (the
        # chunked-TopN stream): repeated sweeps over an unchanged
        # fragment skip the whole container gather and go straight to
        # device_put. LRU-bounded process-wide by HOST_BLOCK_BUDGET.
        self._host_blocks: Dict[tuple, tuple] = {}  # key -> (arr, vers)
        # Merged row-id tuples per shard set, keyed on fragment
        # versions: multi-shard TopN was re-unioning + re-sorting every
        # per-fragment row list PER QUERY — O(N log N) Python at
        # millions of rows (code-review r4 / VERDICT #7).
        self._merged_rows: Dict[tuple, tuple] = {}  # shards -> (vers, rows)

    def open(self) -> None:
        frag_dir = os.path.join(self.path, "fragments")
        if not os.path.isdir(frag_dir):
            return
        for name in os.listdir(frag_dir):
            if name.endswith(".cache") or name.endswith(".snapshotting"):
                continue
            try:
                shard = int(name)
            except ValueError:
                continue
            frag = self._new_fragment(shard)
            frag.open()
            # graftlint: disable=GL008 — one fragment per shard of
            # stored data: the map IS the view's contents, bounded by
            # the dataset, not by request traffic.
            self.fragments[shard] = frag

    def close(self) -> None:
        with self._lock:
            for key in list(self._bank_cache):
                BANK_BUDGET.forget(self, key)
            self._bank_cache.clear()
            for key in list(self._host_blocks):
                HOST_BLOCK_BUDGET.forget(self, key)
            self._host_blocks.clear()
            # Rank-cache vectors are keyed on this view's identity;
            # drop them (and their ledger rows) with the banks.
            cache_mod.RANK_CACHE.forget_view(self)
            for frag in self.fragments.values():
                frag.close()

    def _new_fragment(self, shard: int) -> Fragment:
        return Fragment(
            os.path.join(self.path, "fragments", str(shard)),
            self.index, self.field, self.name, shard,
            cache_type=self.cache_type, cache_size=self.cache_size)

    def fragment(self, shard: int) -> Optional[Fragment]:
        return self.fragments.get(shard)

    def version_stamp(self) -> tuple:
        """Every fragment's write version as one orderable tuple — the
        generation stamp the request-level result cache validates
        against. ANY mutation anywhere in the view changes it: every
        write funnels through Fragment._touch_row (version bump), and
        a fragment created or recreated starts at a fresh process-
        unique epoch, so a stamp can never read as current across a
        resize."""
        with self._lock:
            return tuple(sorted((s, f.version)
                                for s, f in self.fragments.items()))

    def create_fragment_if_not_exists(self, shard: int) -> Fragment:
        with self._lock:
            frag = self.fragments.get(shard)
            if frag is None:
                frag = self._new_fragment(shard)
                frag.open()
                self.fragments[shard] = frag
                if self.on_new_shard is not None:
                    self.on_new_shard(shard)
            return frag

    def available_shards(self) -> List[int]:
        return sorted(self.fragments)

    # -- device bank --------------------------------------------------------

    def _ledger_bank(self, cache_key, bank: "ViewBank", n_rows: int,
                     live_density=None) -> None:
        """Register a cached dense bank with the HBM ledger: total vs
        pow2-pad bytes (capacity rows beyond n_rows + the zero slot),
        tagged so /debug/memory's top-K names the occupant, plus the
        popcount-sampled TRUE live-bit density of the real rows (the
        hotspots demotion quadrants' input — pow2-pad share alone
        scores a full-width-but-sparse row as dense). Keyed identically
        to the BankBudget entry, which unregisters it on eviction."""
        cap, s, w = (int(x) for x in bank.array.shape)
        row_bytes = s * w * 4
        meta = dict(index=self.index, field=self.field, view=self.name,
                    nShards=s, rows=n_rows)
        if live_density is not None:
            meta["liveDensity"] = round(float(live_density), 6)
            # Feed the plan optimizer's cost model: fold operands sort
            # cheapest-first by this sampled density (order-only — a
            # stale value can cost speed, never bits).
            from pilosa_tpu.ops import plan_opt
            plan_opt.note_bank_density(bank.array, live_density)
        LEDGER.register(
            "bank", cache_key, cap * row_bytes,
            padded_bytes=max(0, cap - n_rows - 1) * row_bytes,
            owner=self, **meta)

    # Rows popcount-sampled per bank build for the true-density meta:
    # enough to place a bank in its density quadrant, cheap enough
    # (storage count_range, no device work) to ride every build/patch.
    DENSITY_SAMPLE_ROWS = 256

    def _sampled_live_density(self, frags, row_set, width, shards):
        """Fraction of the bank's REAL row slots' bits that are set,
        estimated from an even sample of rows (popcount via the
        fragments' storage count — host-side only). None when there is
        nothing to sample."""
        if not row_set or not shards or width <= 0:
            return None
        step = max(1, len(row_set) // self.DENSITY_SAMPLE_ROWS)
        sample = row_set[::step][:self.DENSITY_SAMPLE_ROWS]
        try:
            bits = 0
            for s in shards:
                f = frags.get(s) if isinstance(frags, dict) else None
                if f is None:
                    continue
                for r in sample:
                    bits += f.row_count(r)
            denom = len(sample) * len(shards) * width * 32
            return min(1.0, bits / denom) if denom else None
        except Exception:
            return None  # density is telemetry; never fail a build

    # Word granularity of declared-bound trims: 128 u32 words = 4096
    # bits = one full VPU lane row, and exactly a Morgan fingerprint.
    TRIM_GRANULE = 128

    def trimmed_words(self) -> int:
        """Bank word width (uint32) covering every set column of every
        fragment. With a declared max_columns the width is exact to a
        128-word granule (a 4096-bit fingerprint field stores 512 B/row
        in HBM); otherwise it derives from fragment container keys,
        rounded up to whole containers (2048 u32 words = 2^16 bits — the
        container granularity of the key-based bound)."""
        from pilosa_tpu.core.fragment import CONTAINER_BITS
        from pilosa_tpu.ops.bitset import WORDS_PER_SHARD
        if self.max_columns:
            words = (self.max_columns + 31) // 32
            g = self.TRIM_GRANULE
            return min(WORDS_PER_SHARD, (words + g - 1) // g * g)
        cwords = CONTAINER_BITS // 32
        with self._lock:
            frags = list(self.fragments.values())
        max_off = -1
        for f in frags:
            max_off = max(max_off, f.max_column_offset())
        if max_off < 0:
            return cwords
        words = (max_off // 32) + 1
        return min(WORDS_PER_SHARD, ((words + cwords - 1) // cwords)
                   * cwords)

    def _cache_host_block(self, hb_key, host, versions, slots, cap,
                          row_set) -> None:
        """Keep a row-subset build's packed host block (HOST_BLOCK_BUDGET)
        so that a device-side eviction rebuilds by re-upload. A
        full-view build has no such key and keeps nothing."""
        if hb_key is None:
            return
        shards, width = hb_key[0], hb_key[1]
        # The slots dict is real host RAM too (~100 B/entry of dict
        # overhead + int pair; several MB at 65k rows): account it, or a
        # budget-full cache overshoots by the sum of its mappings
        # (ADVICE r2).
        entry_bytes = host.nbytes + 100 * len(row_set)
        if not 0 < entry_bytes <= HOST_BLOCK_BUDGET.budget:
            return
        self._host_blocks[hb_key] = (host, versions, slots)
        HOST_BLOCK_BUDGET.admit(self, hb_key, nbytes=entry_bytes)
        LEDGER.register(
            "host_block", hb_key, entry_bytes,
            padded_bytes=max(0, cap - len(row_set) - 1)
            * len(shards) * width * 4,
            owner=self, index=self.index, field=self.field,
            view=self.name, nShards=len(shards), rows=len(row_set))

    def device_bank(self, shards, rows=None, mesh=None,
                    trim: bool = False, cache_rows: bool = False
                    ) -> ViewBank:
        """Bank for `shards` covering `rows` (default: all rows present in
        any of the shards). Cached per (shard tuple, mesh, trim); rebuilt
        when any fragment's write version moved. `rows` subsets build
        transient banks unless cache_rows=True, which caches them under a
        rows-inclusive key — used by the executor's Row-leaf path when
        the FULL view bank would blow the HBM budget (a single Row(f=x)
        on a million-row field must not upload the whole field; reference
        never faces this because it streams per-shard, executor.go:2377)
        and by chunked TopN when its whole stream fits the budget.
        Either way the packed HOST block is cached (HOST_BLOCK_BUDGET)
        so a device-side eviction rebuilds by re-upload, not re-gather. All cached banks are LRU-accounted against
        BANK_BUDGET. trim=True narrows the word axis to trimmed_words() —
        valid only for whole-row consumers since the dropped tail is
        all-zero by construction. With a MeshContext the array is
        sharded over the mesh's shard axis, which is all the executor
        needs to run SPMD; a full-view bank is gathered and uploaded one
        device's block at a time (`MeshContext.put_bank_blocks`), so the
        host never stages the whole array. Each uncached dense build is
        a `plan.bank_upload` span (`bytes`, `devices`, `blocks`) of the
        request that caused it."""
        import jax.numpy as jnp
        from pilosa_tpu.ops.bitset import WORDS_PER_SHARD

        shards = tuple(shards)
        mesh_key = mesh.cache_key() if mesh else None
        # Why a bank that WAS cached is built anew, if it is: a full-view
        # bank that could not be patched counts executor.bank_rebuilds
        # and {cause:capacity|epoch|half|width}, a stale row-subset bank
        # executor.bank_subset_rebuilds.
        rebuild = None
        with self._lock:
            frags = {s: self.fragments.get(s) for s in shards}
            versions = {s: (f.version if f else -1) for s, f in frags.items()}
            # Width AFTER the version snapshot: a write racing in between
            # bumps a version, so a bank truncated by the pre-write width
            # reads as stale and rebuilds — never silently wrong.
            width = self.trimmed_words() if trim else WORDS_PER_SHARD
            if rows is None:
                cache_key = (shards, mesh_key, trim)
                cached = self._bank_cache.get(cache_key)
                if cached is not None and cached.array.shape[-1] == width \
                        and cached.versions == versions:
                    # Unchanged versions imply an unchanged row set
                    # (every mutation bumps its fragment's version), so
                    # the bank provably covers every present row — no
                    # per-row membership scan on the warm path (it cost
                    # ~150 ms/query at 500k rows).
                    BANK_BUDGET.touch(self, cache_key)
                    return cached
                if cached is not None:
                    # Write churn just cost a device-bank patch/rebuild
                    # — record WHICH fragments moved (the shards whose
                    # version diverged) for the workload plane's churn
                    # ranking (utils/hotspots.py).
                    moved = [s for s, v in versions.items()
                             if cached.versions.get(s) != v]
                    WORKLOAD.record_invalidation(
                        self.index, self.field, self.name,
                        moved or list(shards))
                row_set = sorted({r for f in frags.values() if f
                                  for r in f.row_ids()})
                if cached is not None:
                    patched, rebuild = None, "width"
                    if cached.array.shape[-1] == width:
                        patched, rebuild = self._patch_bank(
                            cached, frags, versions, row_set, shards, width)
                    if patched is not None:
                        # Patch path: carry the PRIOR density estimate
                        # forward — a <=half-bank cell patch moves the
                        # true density negligibly, and resampling here
                        # would put 256 x nShards row popcounts on the
                        # incremental fast path the patch exists for.
                        prior = LEDGER.entry_info(
                            ("bank",), (id(self), cache_key))
                        self._bank_cache[cache_key] = patched
                        BANK_BUDGET.touch(self, cache_key)
                        self._ledger_bank(
                            cache_key, patched, len(row_set),
                            live_density=(prior or {}).get(
                                "liveDensity"))
                        return patched
            else:
                row_set = sorted(set(rows))
                cache_key = (shards, mesh_key, trim, tuple(row_set))
                if cache_rows:
                    cached = self._bank_cache.get(cache_key)
                    if cached is not None \
                            and cached.array.shape[-1] == width \
                            and cached.versions == versions:
                        BANK_BUDGET.touch(self, cache_key)
                        # Keep the backing host block warm too: if HBM
                        # pressure later evicts this bank, the rebuild
                        # should re-upload, not re-gather.
                        HOST_BLOCK_BUDGET.touch(
                            self, (shards, width, tuple(row_set)))
                        return cached
                    if cached is not None:
                        # A row-subset bank has no patch branch: any
                        # write to a shard of its view builds it anew.
                        rebuild = "subset"
            if rebuild == "subset":
                TIMELINE.count("executor.bank_subset_rebuilds")
            elif rebuild is not None:
                TIMELINE.count("executor.bank_rebuilds")
                TIMELINE.count(f"executor.bank_rebuilds{{cause:{rebuild}}}")
            cap = bank_capacity(len(row_set))
            # Host blocks back ALL row-subset builds (cache_rows device
            # banks included): when HBM pressure evicts the device bank,
            # the rebuild skips the container gather and only re-uploads.
            hb_key = None
            host = slots = None
            if rows is not None:
                hb_key = (shards, width, tuple(row_set))
                entry = self._host_blocks.get(hb_key)
                if entry is not None:
                    if entry[1] == versions:
                        host, _v, slots = entry
                        HOST_BLOCK_BUDGET.touch(self, hb_key)
                    else:
                        self._host_blocks.pop(hb_key, None)
                        HOST_BLOCK_BUDGET.forget(self, hb_key)
            array = None
            if host is None and SPARSE_UPLOAD \
                    and mesh is None and len(shards) == 1 \
                    and trim and width * 32 <= CONTAINER_BITS \
                    and cap * width < (1 << 31):
                # (cap*width bound: the expansion scatter indexes with
                # i32 — an operator-raised bank budget must fall back
                # to the dense path, not wrap indices.)
                # Sparse upload (chunk AND full-bank builds): ship
                # positions, expand to the dense bank on device.
                f = frags[shards[0]]
                sp = (f.rows_positions(row_set, width)
                      if f is not None else
                      (np.empty(0, np.uint16), np.empty(0, np.int64),
                       np.empty(0, np.int64)))
                if sp is not None:
                    # A bank built is a bank built, whichever way its
                    # bits travel: the span and the counter of a dense
                    # build (`bytes`: the array the device then holds).
                    nbytes = cap * width * 4
                    with TIMELINE.stage(
                            "plan.bank_upload", bytes=nbytes, devices=1,
                            blocks=1, form="positions",
                            counts=(("executor.bank_upload_bytes",
                                     nbytes),)):
                        array = _expand_sparse_chunk(*sp, cap, width)
                    slots = {r: i for i, r in enumerate(row_set)}
            if array is None:
                def gather(positions):
                    """Host block [cap, len(positions), width] of the
                    shards at those positions of the shard list."""
                    block = np.zeros((cap, positions.stop - positions.start,
                                      width), dtype=np.uint32)
                    for bi, s in enumerate(shards[positions]):
                        f = frags[s]
                        if f is not None:
                            block[:len(row_set), bi] = f.rows_dense(
                                row_set, width)
                    return block

                if slots is None:
                    # Kept with a cached host block, so a hit is O(1)
                    # host-side — no 65k-entry dict rebuild per chunk
                    # per repeat query.
                    slots = {r: i for i, r in enumerate(row_set)}
                shape = (cap, len(shards), width)
                nbytes = cap * len(shards) * width * 4
                # Under a mesh a full-view bank is gathered and uploaded
                # one device's block at a time (the whole array is 8 GiB
                # where a device's share is 2). Row-subset builds stay
                # whole: their packed host block is what is cached.
                blockwise = mesh is not None and hb_key is None
                with TIMELINE.stage(
                        "plan.bank_upload", bytes=nbytes,
                        devices=mesh.n_shard_devices if mesh else 1,
                        blocks=mesh.n_shard_devices if blockwise else 1,
                        counts=(("executor.bank_upload_bytes", nbytes),)):
                    if blockwise:
                        # graftlint: disable=GL009 — the wait inside is
                        # for a block's host->device copy (a fraction of
                        # a second, while the next block's gather takes
                        # seconds under this same lock); it is what
                        # bounds host staging to two blocks, and the
                        # lock is held for the whole build either way,
                        # as it always was, so that one thread builds a
                        # bank and the others find it cached.
                        array = mesh.put_bank_blocks(shape, gather)
                    else:
                        if host is None:
                            host = gather(slice(0, len(shards)))
                            self._cache_host_block(hb_key, host, versions,
                                                   slots, cap, row_set)
                        array = mesh.put_bank(host) if mesh \
                            else jnp.asarray(host)
            bank = ViewBank(array, slots, cap - 1, versions,
                            subset=rows is not None)
            if rows is None or cache_rows:
                self._bank_cache[cache_key] = bank
                BANK_BUDGET.admit(self, cache_key)
                self._ledger_bank(
                    cache_key, bank, len(row_set),
                    live_density=self._sampled_live_density(
                        frags, row_set, width, shards))
            return bank

    def _build_pbank_segments(self, frag, rows: list, width: int,
                              row_lo0: int, fixed_fits: bool):
        """Gather `rows` (sorted) into device segments starting at
        global row index `row_lo0`: [(row_lo, n_rows, pos_dev,
        starts_dev, p_real)], total nbytes and each segment's longest
        row — or None when too dense. `fixed_fits`: whether the
        WHOLE bank these rows belong to may be padded
        (pbank_fixed_fits)."""
        import jax.numpy as jnp

        segments: list = []
        row_widths: list = []
        nbytes = 0
        pos_parts: list = []
        lens_parts: list = []
        cur_p = 0
        row_lo = row_lo0

        def flush():
            nonlocal pos_parts, lens_parts, cur_p, row_lo, nbytes
            if not lens_parts:
                return
            pos16 = (np.concatenate(pos_parts) if pos_parts
                     else np.empty(0, np.uint16))
            lens = np.concatenate(lens_parts)
            p = len(pos16)
            n = len(lens)
            # FIXED-WIDTH layout when the segment's rows are uniform
            # enough: positions as [L, n_rows] (0xFFFF pad) + per-row
            # real lengths. The TopN kernel then row-sums with an add
            # over the L slot planes — no O(P) cumsum, no starts
            # gathers. Slot-major, so that rows lie on the device's
            # lanes whatever L is (left to itself the TPU compiler
            # stores a [n_rows, L] array that way below 128 slots and
            # row-major at 128: the layout, and the reduce with it,
            # would turn on the longest row). L is that row rounded up
            # to a multiple of 8, so that segments of one library share
            # a shape. Kind is carried by array rank (pos 2D = fixed),
            # so every 5-tuple consumer — patcher, tests, benches — is
            # untouched.
            # Row-count pad (both layouts): kernels compile per array
            # SHAPE — a 36-segment bank with 36 distinct row counts
            # cost 36 cold compiles.
            # Padding rows to a 2^16 multiple collapses the shapes to
            # one or two per bank (+<3% rows). Pad rows carry zero
            # lengths, so their counts are 0 and can never rank.
            # The multiple is the largest power of two (1024..2^16)
            # whose padding stays <= n/8: interior segments at scale
            # still land on the big multiple (shapes repeat, compile
            # reuse holds), while row counts just above a multiple
            # (e.g. n=65537) no longer pad toward 2x their HBM
            # (advisor r4 — the old two-point 1024/65536 rule).
            # Tension accepted: mixed-density banks whose segments'
            # row counts straddle the 65536..8*65536 band can see a
            # few more distinct padded shapes (=cold compiles) than
            # the old always-65536 rule; real banks split segments at
            # the POSITION cap, so same-density interior segments
            # share one shape either way.
            row_pad = min(1024, PBANK_FIXED_ROW_PAD)
            cand = row_pad * 2
            while cand <= PBANK_FIXED_ROW_PAD:
                if -n % cand <= n // 8:
                    row_pad = cand
                cand *= 2
            n_pad = -n % row_pad
            longest = int(lens.max()) if n else 0
            row_widths.append(longest)
            L = -(-longest // 8) * 8
            if fixed_fits and 0 < longest <= PBANK_FIXED_ROW_SLOTS \
                    and p >= PBANK_FIXED_MIN_DENSITY * n * L:
                # Filled a block of rows at a time: the block's padded
                # rows and its transpose stay in the cache (the whole
                # matrix masked and transposed at once took six times
                # as long at 2.8 M rows).
                mat = np.empty((L, n + n_pad), np.uint16)
                mat[:, n:] = 0xFFFF
                ends = np.cumsum(lens)
                slot = np.arange(L)[None, :]
                for b0 in range(0, n, PBANK_FIXED_FILL_ROWS):
                    b1 = min(n, b0 + PBANK_FIXED_FILL_ROWS)
                    blk = np.full((b1 - b0, L), 0xFFFF, np.uint16)
                    blk[slot < lens[b0:b1, None]] = pos16[
                        int(ends[b0 - 1]) if b0 else 0:int(ends[b1 - 1])]
                    mat[:, b0:b1] = blk.T
                lens32 = np.zeros(n + n_pad, np.int32)
                lens32[:n] = lens
                seg = (row_lo, n, jnp.asarray(mat),
                       jnp.asarray(lens32), p)
                del mat
                segments.append(seg)
                nbytes += pbank_segment_bytes(seg[2], seg[3])
            else:
                starts = np.zeros(n + n_pad + 1, np.int64)
                np.cumsum(lens, out=starts[1:n + 1])
                starts[n + 1:] = starts[n]  # pad rows: empty ranges
                # Pad to a 1M multiple, NOT a power of two: segments
                # build once (per version), so compile reuse matters
                # little, and pow2 padding nearly doubled a ~10 GiB
                # bank — pushing it over the HBM budget and into
                # rebuild-per-query thrash (caught by a 100M-row run on
                # an earlier machine; on the v5e: PERF.md §4, chem-lib-chip).
                padded = max(1 << 20, -(-p // (1 << 20)) * (1 << 20))
                buf = np.full(padded, 0xFFFF, np.uint16)  # OOB pad
                buf[:p] = pos16
                seg = (row_lo, n, jnp.asarray(buf),
                       jnp.asarray(starts.astype(np.int32)), p)
                segments.append(seg)
                nbytes += pbank_segment_bytes(seg[2], seg[3])
            pos_parts, lens_parts = [], []
            cur_p = 0
            row_lo += n

        for c0 in range(0, len(rows), PBANK_GATHER_ROWS):
            chunk = rows[c0:c0 + PBANK_GATHER_ROWS]
            rp = frag.rows_positions(chunk, width)
            if rp is None:
                return None  # too dense for the sparse layout
            pos16, lens, rows_at = rp
            # Align lens to EVERY chunk row (a present row always has
            # real positions, but stay defensive about empties).
            if len(rows_at) != len(chunk):
                full = np.zeros(len(chunk), np.int64)
                full[rows_at] = lens
                lens = full
                # positions already concatenated in rows_at order ==
                # ascending row order; empties contribute nothing.
            # Enforce the segment cap EXACTLY, splitting this chunk on
            # row boundaries if needed — checking only after a whole
            # chunk appends would let dense-heavy rows blow a segment
            # past the kernel's i32 index space (up to 2^16
            # positions/row x 2^20 rows/chunk).
            ends = np.cumsum(lens)
            taken = 0
            while taken < len(lens):
                room = PBANK_SEGMENT_POSITIONS - cur_p
                # Rows of this chunk (beyond `taken`) that fit in room.
                hi = int(np.searchsorted(ends, ends[taken - 1] + room
                                         if taken else room, "right"))
                if hi <= taken:
                    flush()
                    continue
                lo_p = int(ends[taken - 1]) if taken else 0
                hi_p = int(ends[hi - 1])
                pos_parts.append(pos16[lo_p:hi_p])
                lens_parts.append(lens[taken:hi])
                cur_p += hi_p - lo_p
                taken = hi
                if cur_p >= PBANK_SEGMENT_POSITIONS:
                    flush()
        flush()
        return segments, nbytes, row_widths

    def merged_row_ids(self, shards) -> tuple:
        """Sorted union of row_ids() across `shards`, cached per shard
        set and invalidated by any member fragment's version bump —
        repeat queries over unchanged fragments alias the same tuple
        (no per-query union/sort; reference fragment.top reads its
        rankCache per fragment, fragment.go:1067). The merge itself is
        one C-speed np.unique over the concatenated sorted lists."""
        key = tuple(shards)
        frags = [f for s in key for f in [self.fragment(s)]
                 if f is not None]
        versions = tuple(f.version for f in frags)
        with self._lock:
            ent = self._merged_rows.get(key)
            if ent is not None and ent[0] == versions:
                # Refresh LRU order on hit (dict preserves insertion
                # order; re-inserting moves this key to the back, so
                # eviction below pops the genuinely coldest entry).
                self._merged_rows.pop(key)
                self._merged_rows[key] = ent
                return ent[1]
        per = [f.row_ids() for f in frags]
        per = [p for p in per if p]
        if not per:
            merged: tuple = ()
        elif len(per) == 1:
            merged = per[0]  # already a sorted immutable tuple
        else:
            merged = tuple(np.unique(np.concatenate(
                [np.asarray(p, dtype=np.uint64) for p in per])).tolist())
        with self._lock:
            self._merged_rows.pop(key, None)  # re-insert at the back
            self._merged_rows[key] = (versions, merged)
            while len(self._merged_rows) > 8:  # a few live shard sets
                self._merged_rows.pop(next(iter(self._merged_rows)))
        return merged

    def positions_bank(self, shard: int, width: int
                       ) -> Optional[PositionsBank]:
        """Device-resident PositionsBank for one shard, or None when
        the layout doesn't qualify: no fragment, width spanning a full
        container (the 0xFFFF pad sentinel must gather out of range),
        or a genuinely dense field (>25% dense-encoded containers in
        some gather chunk — a FEW densified rows, e.g. from point
        writes, are extracted and stay in-bank). Cached per
        (shard, width) under the HBM budget. A write invalidates by
        version; the rebuild is INCREMENTAL when the row set is
        unchanged — only segments containing written rows regather,
        the rest reuse their device arrays (at 100M rows a point write
        costs ~1/segment-count of the full build, not minutes)."""
        if width * 32 >= CONTAINER_BITS:
            return None
        key = ("pbank", shard, width)
        with self._lock:
            frag = self.fragments.get(shard)
            versions = {shard: (frag.version if frag else -1)}
            cached = self._bank_cache.get(key)
            if isinstance(cached, PositionsBank) \
                    and cached.versions == versions:
                BANK_BUDGET.touch(self, key)
                return cached
            if frag is None:
                return None
        # The build is a stage of the request that met the stale or
        # absent bank (the first TopN after every start, and after a
        # write): a host gather of every row, or of the written
        # segments' rows, and their uploads.
        with TIMELINE.stage("plan.pbank_build") as sp:
            row_ids = frag.row_ids()  # sorted immutable tuple (contract)
            built = None
            kind = "patch"
            # graftlint: disable=GL015 — deliberate lock-free rebuild: the
            # bank is stamped with the versions read under the first
            # acquisition, so a write landing during the build makes the
            # stamp stale and the next probe rebuilds (write-back is
            # last-writer-wins; a stale bank is never SERVED, only stored).
            if isinstance(cached, PositionsBank) \
                    and cached.row_ids == row_ids:
                # graftlint: disable=GL015 — same version-stamp argument.
                built = self._patch_pbank(cached, frag, width)
            if built is None:
                kind = "full"
                # graftlint: disable=GL015 — same version-stamp argument.
                built = self._build_pbank_segments(
                    frag, row_ids, width, 0, pbank_fixed_fits(len(row_ids)))
            if built is None:
                sp.set("kind", "none")  # too dense: the caller streams
                return None
            segments, nbytes, row_widths = built
            # Ideal (pad-free) footprint: 2 B per real position + one i32
            # aux word per row (+1); the rest is pow2 / fixed-width / row
            # padding — the number the padding gauge exists to surface.
            ideal = sum(p * 2 + (n + 1) * 4 for _, n, _, _, p in segments)
            for attr, value in (
                    ("kind", kind), ("rows", len(row_ids)),
                    ("positions", sum(s[4] for s in segments)),
                    ("segments", len(segments)), ("bytes", nbytes),
                    ("pad_bytes", max(0, nbytes - ideal))):
                sp.set(attr, value)
            TIMELINE.count("executor.pbank_builds")
            TIMELINE.count(f"executor.pbank_builds{{kind:{kind}}}")
        bank = PositionsBank(segments, row_ids, versions, nbytes,
                             tuple(row_widths))
        with self._lock:
            self._bank_cache[key] = bank
        BANK_BUDGET.admit(self, key, nbytes=nbytes)
        LEDGER.register(
            "pbank", key, nbytes,
            padded_bytes=max(0, nbytes - ideal), owner=self,
            index=self.index, field=self.field, view=self.name,
            shard=shard, rows=len(row_ids))
        return bank

    def _patch_pbank(self, cached: PositionsBank, frag, width: int):
        """Regather only the segments whose row ranges contain rows
        written since the cached build; clean segments carry over with
        their device arrays. Same-row-set only (the caller checked):
        global row indexes then stay aligned except where segment
        boundaries move, handled by rebuilding dirty ranges in place.
        Returns (segments, nbytes, row_widths) or None to force a full
        rebuild."""
        changed = frag.rows_changed_since(
            next(iter(cached.versions.values())))
        if not changed or len(changed) > len(cached.row_ids) // 4:
            return None  # nothing known, or patch ~= rebuild
        dirty = set(changed)
        segments: list = []
        row_widths: list = []
        nbytes = 0
        row_lo = 0
        fixed_fits = pbank_fixed_fits(len(cached.row_ids))
        for seg, seg_widths in zip(cached.segments, cached.row_widths):
            s_lo, n_rows, pos_dev, starts_dev, p_real = seg
            seg_rows = cached.row_ids[s_lo:s_lo + n_rows]
            if dirty.isdisjoint(seg_rows):
                # Clean: reuse the device arrays; only the global row
                # offset may have shifted if an earlier dirty range
                # re-split (row COUNT per range is unchanged, so it
                # cannot — assert the invariant cheaply).
                segments.append((row_lo, n_rows, pos_dev, starts_dev,
                                 p_real))
                row_widths.append(seg_widths)
                nbytes += pbank_segment_bytes(pos_dev, starts_dev)
                row_lo += n_rows
                continue
            rebuilt = self._build_pbank_segments(frag, seg_rows, width,
                                                 row_lo, fixed_fits)
            if rebuilt is None:
                return None
            new_segs, nb, new_widths = rebuilt
            # The clean-segment reuse above depends on every dirty
            # range rebuilding to the SAME real row count (row_lo
            # offsets of later clean segments assume it). A mismatch
            # falls back to the full rebuild — same path as
            # rebuilt-is-None — rather than serving misaligned rows.
            if sum(s[1] for s in new_segs) != n_rows:
                return None
            segments.extend(new_segs)
            row_widths.extend(new_widths)
            nbytes += nb
            row_lo += n_rows
        return segments, nbytes, row_widths

    # -- hybrid layout (driven by core/layout.py) ----------------------------

    def set_layout(self, mode: str) -> bool:
        """Flip this view's serving layout ("dense" | "sparse").
        Returns True when the mode actually changed. The flip drops
        the OTHER representation's cached device banks so the HBM
        frees immediately (the byte delta the re-layout pass proves
        against the ledger); host blocks stay —
        they are host RAM and make a later promotion re-upload instead
        of re-gather. Data is never touched, so a stale *hit* is
        impossible: a query staged before the flip keeps serving from
        the representation it planned against, both of which hold the
        same bits (pinned by tests/test_layout.py)."""
        if mode not in ("dense", "sparse"):
            raise ValueError(f"unknown layout mode {mode!r}")
        with self._lock:
            if self.layout_mode == mode:
                return False
            self.layout_mode = mode
            drop = []
            for key in list(self._bank_cache):
                tagged = isinstance(key, tuple) and key \
                    and isinstance(key[0], str)
                sparse_key = tagged and key[0] == "sbank"
                pbank_key = tagged and key[0] == "pbank"
                if mode == "sparse" and not (sparse_key or pbank_key):
                    drop.append(key)
                elif mode == "dense" and sparse_key:
                    drop.append(key)
            for key in drop:
                self._bank_cache.pop(key, None)
                BANK_BUDGET.forget(self, key)
        return True

    def sparse_bank(self, shards) -> Optional["SparseBank"]:
        """Device-resident :class:`SparseBank` over `shards` covering
        every present row, or None when the layout does not qualify
        (width spanning a full container — the u16 bitpos encoding
        needs sub-container trim — or a genuinely dense view, where
        ``rows_positions`` bails and dense banks win anyway). Cached
        per (shard tuple, width) under the HBM budget with the same
        stamp-then-read version discipline as ``device_bank``: a write
        racing the build bumps a fragment version, the cached versions
        read stale, and the next query rebuilds — spurious miss
        allowed, stale hit never. A None return self-heals the layout
        back to dense so staging stops asking."""
        import jax.numpy as jnp

        shards = tuple(shards)
        with self._lock:
            frags = {s: self.fragments.get(s) for s in shards}
            versions = {s: (f.version if f else -1)
                        for s, f in frags.items()}
            width = self.trimmed_words()
            if width * 32 > CONTAINER_BITS:
                return None
            key = ("sbank", shards, width)
            cached = self._bank_cache.get(key)
            if isinstance(cached, SparseBank) \
                    and cached.versions == versions:
                BANK_BUDGET.touch(self, key)
                return cached
            row_set = sorted({r for f in frags.values() if f
                              for r in f.row_ids()})
            n_rows = len(row_set)
            per_shard = []
            for si, s in enumerate(shards):
                f = frags[s]
                if f is None:
                    per_shard.append((np.empty(0, np.uint32),
                                      np.zeros(n_rows, np.int64)))
                    continue
                rp = f.rows_positions(row_set, width)
                if rp is None:
                    return None  # too dense for the sparse layout
                pos16, lens, rows_at = rp
                if len(rows_at) != n_rows:
                    full = np.zeros(n_rows, np.int64)
                    full[rows_at] = lens
                    lens = full
                per_shard.append(
                    (pos16.astype(np.uint32) | np.uint32(si << 16),
                     lens.astype(np.int64)))
            cap = bank_capacity(n_rows)
            if per_shard and n_rows:
                lens_mat = np.stack([ls for _, ls in per_shard])
            else:
                lens_mat = np.zeros((len(shards), n_rows), np.int64)
            row_tot = lens_mat.sum(axis=0)
            total = int(row_tot.sum())
            if total >= (1 << 31):
                return None  # starts are i32; such a view is not sparse
            starts = np.zeros(cap + 1, np.int64)
            np.cumsum(row_tot, out=starts[1:n_rows + 1])
            starts[n_rows + 1:] = starts[n_rows]
            # Per-(row, shard) destination: row start + the exclusive
            # prefix of earlier shards' lengths for that row, so each
            # row's positions concatenate shard-ascending (the encoded
            # shard index keeps them decodable either way).
            prior = np.cumsum(lens_mat, axis=0) - lens_mat
            p_pad = 1 << max(10, (total - 1).bit_length() if total
                             else 0)
            pos = np.zeros(p_pad, np.uint32)
            for si, (enc, _ls) in enumerate(per_shard):
                if not len(enc):
                    continue
                ls = lens_mat[si]
                dst0 = starts[:n_rows] + prior[si]
                within = np.arange(len(enc)) \
                    - np.repeat(np.cumsum(ls) - ls, ls)
                pos[np.repeat(dst0, ls) + within] = enc
            starts32 = starts.astype(np.int32)
            arrays = (jnp.asarray(pos), jnp.asarray(starts32))
            nbytes = int(pos.nbytes + starts32.nbytes)
            slots = {r: i for i, r in enumerate(row_set)}
            bank = SparseBank(arrays, slots, cap - 1, versions, nbytes,
                              width, len(shards), n_rows)
            self._bank_cache[key] = bank
            BANK_BUDGET.admit(self, key, nbytes=nbytes)
            # Ideal footprint: 4 B per real position + one i32 offset
            # per real row (+1); the rest is pow2 pos/row-capacity pad.
            ideal = total * 4 + (n_rows + 1) * 4
            LEDGER.register(
                "sparse_bank", key, nbytes,
                padded_bytes=max(0, nbytes - ideal), owner=self,
                index=self.index, field=self.field, view=self.name,
                nShards=len(shards), rows=n_rows, positions=total,
                liveDensity=1.0, width=width)
            return bank

    def _patch_bank(self, cached: "ViewBank", frags, versions, row_set,
                    shards, width) -> Tuple[Optional["ViewBank"], str]:
        """Incrementally refresh a cached bank: re-upload only (row, shard)
        cells whose fragment reports a newer row version, through the
        `bank_patch` program of the bank's shape (`_patch_programs`).
        Returns (bank, "") or (None, why a rebuild is required):
        `capacity` (new rows exceed it), `epoch` (a fragment was
        recreated), `half` (the patch would touch most of the bank
        anyway). The patched bank is a new ViewBank over a new array —
        the cached array is never donated, whoever staged it keeps
        reading the version they took; it keeps the slot-ordered row
        array when no row was added and drops the rows' popcounts,
        which belong to one bank version."""
        new_rows = [r for r in row_set if r not in cached.slots]
        if len(cached.slots) + len(new_rows) + 1 > cached.array.shape[0]:
            return None, "capacity"
        for s, newv in versions.items():
            old = cached.versions.get(s, -1)
            if old != newv and (old < 0 or (old >> 48) != (newv >> 48)):
                # Version epoch moved: the fragment was recreated since
                # this bank was built (pop + reload), so its
                # _row_versions no longer attributes writes made in the
                # old incarnation — rows_changed_since below would
                # under-patch. Rebuild.
                return None, "epoch"
        patches = []  # (slot, shard_idx, words)
        for si, s in enumerate(shards):
            f = frags[s]
            if f is None or f.version == cached.versions.get(s):
                continue
            for r in f.rows_changed_since(cached.versions.get(s, -1)):
                if r in cached.slots:
                    patches.append((cached.slots[r], si,
                                    f.row_dense(r, u32_words=width)))
        slots = dict(cached.slots)
        for r in new_rows:
            slot = len(slots)
            slots[r] = slot
            for si, s in enumerate(shards):
                f = frags[s]
                if f is not None:
                    patches.append((slot, si,
                                    f.row_dense(r, u32_words=width)))
        total_cells = cached.array.shape[0] * cached.array.shape[1]
        if len(patches) > max(16, total_cells // 2):
            return None, "half"
        array = cached.array
        if patches:
            array = self._launch_patches(array, patches)
        bank = ViewBank(array, slots, cached.zero_slot, versions)
        if not new_rows:
            bank._slot_rows = cached._slot_rows
        return bank, ""

    def _launch_patches(self, array, patches):
        """`array` with the cells of `patches` written: one `bank_patch`
        launch for up to PATCH_LANES_MAX cells, lanes the next power of
        two and the pad lanes repeating the last real cell (the same
        words to the same place), then donating launches of the top
        bucket over the array the launch before made. A
        `plan.bank_patch` span of the request that met the stale bank."""
        import jax.numpy as jnp

        progs = _patch_programs(array)
        n = len(patches)
        first = n % PATCH_LANES_MAX or PATCH_LANES_MAX
        chunks = [patches[:first]] + [
            patches[i:i + PATCH_LANES_MAX]
            for i in range(first, n, PATCH_LANES_MAX)]
        lanes_of = [1 << (len(c) - 1).bit_length() for c in chunks]
        pad = sum(lanes_of) - n
        with TIMELINE.stage(
                "plan.bank_patch", cells=n, lanes=sum(lanes_of),
                bytes=n * array.shape[-1] * array.dtype.itemsize,
                bank=f"{self.field}/{self.name}",
                counts=(("executor.bank_patches", 1),
                        ("executor.bank_patch_cells", n),
                        ("executor.bank_patch_pad_lanes", pad))):
            for k, (chunk, lanes) in enumerate(zip(chunks, lanes_of)):
                chunk = chunk + [chunk[-1]] * (lanes - len(chunk))
                rows_idx = np.asarray([p[0] for p in chunk], dtype=np.int32)
                shard_idx = np.asarray([p[1] for p in chunk], dtype=np.int32)
                words = np.stack([p[2] for p in chunk])
                with transfer("h2d", rows_idx.nbytes + shard_idx.nbytes
                              + words.nbytes, 3):
                    operands = (jnp.asarray(rows_idx),
                                jnp.asarray(shard_idx), jnp.asarray(words))
                with TIMELINE.stage("dispatch", program="bank_patch",
                                    lanes=lanes):
                    array = progs[(lanes, k > 0)](array, *operands)
        return array

    # Pass-throughs (reference view.go:294-421).

    def set_bit(self, row_id: int, column_id: int) -> bool:
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        return self.create_fragment_if_not_exists(
            column_id // SHARD_WIDTH).set_bit(row_id, column_id)

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        frag = self.fragment(column_id // SHARD_WIDTH)
        return frag.clear_bit(row_id, column_id) if frag else False

    def set_value(self, column_id: int, bit_depth: int, value: int) -> bool:
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        return self.create_fragment_if_not_exists(
            column_id // SHARD_WIDTH).set_value(column_id, bit_depth, value)

    def value(self, column_id: int, bit_depth: int):
        from pilosa_tpu.ops.bitset import SHARD_WIDTH
        frag = self.fragment(column_id // SHARD_WIDTH)
        if frag is None:
            return 0, False
        return frag.value(column_id, bit_depth)
