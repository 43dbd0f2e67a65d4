"""Pilosa roaring bitmap: host implementation + file format codec.

File format (binary-compatible with the reference; spec per
/root/reference/docs/architecture.md "Roaring bitmap storage format" and
writer/reader /root/reference/roaring/roaring.go:963-1126):

    bytes 0-1   magic number 12348 (little-endian uint16)
    bytes 2-3   storage version (0)
    bytes 4-7   container count N (uint32)
    N x 12      descriptive header: uint64 key, uint16 container type
                (1=array, 2=bitmap, 3=run), uint16 cardinality-1
    N x 4       offset header: absolute uint32 byte offset of each container
    ...         container payloads:
                  array : n x uint16 sorted values
                  bitmap: 1024 x uint64 words
                  run   : uint16 run count, then (uint16 start, uint16 last)*
    ...         ops log until EOF (op format roaring.go:3628-3691):
                  byte type (0 add, 1 remove, 2 addBatch, 3 removeBatch)
                  uint64 value-or-count, uint32 fnv1a checksum,
                  batch ops: count x uint64 values
                Extension type 4 (addRoaring; NOT in the reference's
                format — reference-written files never contain it, so
                read compatibility is unaffected): uint64 payload byte
                length, uint32 zlib-crc32 over header+payload, then a
                self-contained roaring snapshot of the batch. ~2 bytes
                per sparse bit vs 8 for addBatch, and crc32 streams at
                GB/s where byte-serial fnv1a was the import bottleneck.

In-memory representation: every non-empty container is held *dense* as
uint64[1024] in a dict keyed by the 48-bit container key. Dense-only is a
deliberate divergence from the reference's three-encoding polymorphism: the
host bitmap exists for mutation, durability and the CPU baseline, not as the
query hot path (that's HBM), and dense numpy makes every mutation a vector op.
The three encodings are still produced on write (smallest wins, mirroring
Optimize, roaring.go:1745) and accepted on read.
"""

from __future__ import annotations

import io
import struct
from typing import (Callable, Dict, Iterable, Iterator, List, Optional,
                    Tuple)

import numpy as np

from pilosa_tpu import native

MAGIC_NUMBER = 12348
STORAGE_VERSION = 0
COOKIE = MAGIC_NUMBER | (STORAGE_VERSION << 16)
HEADER_BASE_SIZE = 8

CONTAINER_ARRAY = 1
CONTAINER_BITMAP = 2
CONTAINER_RUN = 3

CONTAINER_BITS = 1 << 16
CONTAINER_WORDS = CONTAINER_BITS // 64  # 1024 uint64 words
ARRAY_MAX_SIZE = 4096  # below this an array encoding beats a bitmap
RUN_COUNT_HEADER_SIZE = 2
MAX_CONTAINER_KEY = (1 << 48) - 1

OP_ADD = 0
OP_REMOVE = 1
OP_ADD_BATCH = 2
OP_REMOVE_BATCH = 3
OP_ADD_ROARING = 4  # extension: roaring-snapshot payload, crc32 checksum

# Maximum OP_ADD_ROARING nesting depth. A roaring-record payload is a
# self-contained file, so crafted input can nest records inside records;
# unbounded recursion would exhaust the stack on attacker-controlled
# depth. Legitimate writers emit snapshot-only payloads (depth 1). The
# native codec enforces the same bound (pilosa_native.cpp kMaxOpNesting)
# so both readers agree on adversarial input.
MAX_OP_NESTING = 4

_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193


def fnv1a32(*chunks: bytes) -> int:
    """FNV-1a 32-bit, matching Go's hash/fnv.New32a used for op checksums
    (roaring.go:3647-3650). The native C++ path matters: large batch ops
    hash their whole payload, and the Python loop dominates bulk-import
    time otherwise."""
    if native.available():
        h = native.fnv1a32(chunks)
        if h is not None:
            return h
    h = _FNV_OFFSET
    for chunk in chunks:
        for byte in chunk:
            h = ((h ^ byte) * _FNV_PRIME) & 0xFFFFFFFF
    return h


# numpy >= 2.0 has a native popcount ufunc; keep a table fallback.
if hasattr(np, "bitwise_count"):
    def _popcount_words(words: np.ndarray) -> int:
        return int(np.bitwise_count(words).sum())
else:  # pragma: no cover
    _POP_TABLE = np.array([bin(i).count("1") for i in range(256)], dtype=np.uint16)

    def _popcount_words(words: np.ndarray) -> int:
        return int(_POP_TABLE[words.view(np.uint8)].sum())


def _write_all(w: io.RawIOBase, data: bytes) -> None:
    """Write the whole record or raise. The op log is an UNBUFFERED
    raw file (one syscall per op, Go file-write durability), and raw
    writes may be short (e.g. ENOSPC writes what fits): an
    acknowledged op must never be a truncated record, so loop and
    fail loudly on no progress."""
    view = memoryview(data)
    while view:
        n = w.write(view)
        if not n:
            raise OSError("op-log write made no progress "
                          f"({len(view)} bytes unwritten)")
        view = view[n:]


def _new_container() -> np.ndarray:
    return np.zeros(CONTAINER_WORDS, dtype=np.uint64)


# Cardinality at or below which a container may use the sorted-u16 array
# encoding in memory (reference ArrayMaxSize, roaring.go:55). In-memory
# containers are DENSE u64[1024] (dtype uint64) or ARRAY-encoded sorted
# positions (dtype uint16) — the second, update-optimized-for-sparse
# backend of SURVEY component #3 (reference Containers implementations,
# roaring/containers.go). Mutations materialize dense via _container();
# reads handle both; optimize() re-compresses (reference Bitmap.Optimize,
# roaring.go:1745).
ARRAY_MAX_SIZE = 4096


def _is_array(c: np.ndarray) -> bool:
    return c.dtype == np.uint16


def _as_dense(c: np.ndarray) -> np.ndarray:
    return _array_to_dense(c) if c.dtype == np.uint16 else c


def _dense_to_array(dense: np.ndarray) -> np.ndarray:
    bits = np.unpackbits(dense.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint16)


def _low_mask(low: np.ndarray) -> np.ndarray:
    """Dense u64[1024] mask from in-container positions. Size-adaptive:
    bool-scatter + packbits beats np.bitwise_or.at (~100 ns/element)
    once groups get dense — the fragment bulk-import hot path."""
    if len(low) >= 256:
        bits = np.zeros(CONTAINER_BITS, dtype=bool)
        bits[low] = True
        return np.packbits(bits, bitorder="little").view(np.uint64).copy()
    dense = _new_container()
    if len(low):
        v = low.astype(np.uint32)
        np.bitwise_or.at(
            dense, v >> 6, np.left_shift(np.uint64(1), (v & 63).astype(np.uint64))
        )
    return dense


def _array_to_dense(values: np.ndarray) -> np.ndarray:
    return _low_mask(np.asarray(values))


def _runs_to_dense(runs: np.ndarray) -> np.ndarray:
    """runs: (n, 2) uint16 [start, last] inclusive pairs."""
    dense = _new_container()
    bits = np.zeros(CONTAINER_BITS, dtype=np.uint8)
    for start, last in runs:
        bits[int(start) : int(last) + 1] = 1
    dense |= np.packbits(bits, bitorder="little").view(np.uint64)
    return dense


def _dense_to_runs(dense: np.ndarray) -> np.ndarray:
    bits = np.unpackbits(dense.view(np.uint8), bitorder="little")
    diff = np.diff(np.concatenate(([0], bits, [0])).astype(np.int8))
    starts = np.nonzero(diff == 1)[0]
    ends = np.nonzero(diff == -1)[0] - 1
    return np.stack([starts, ends], axis=1).astype(np.uint16)


class Bitmap:
    """A 64-bit-keyed roaring bitmap, dense-container host implementation.

    Mirrors the public surface of the reference's roaring.Bitmap
    (roaring.go:119) that the rest of the framework uses: Add/Remove/Contains/
    Count/CountRange/Max/Slice/ForEach, set algebra, OffsetRange, Shift, Flip,
    serialization, and the append-only ops log (OpWriter, roaring.go:1128).
    """

    __slots__ = ("containers", "_counts", "op_writer", "op_n",
                 "op_n_small", "oplog_bytes", "snapshot_bytes",
                 "tail_dropped")

    def __init__(self, positions: Optional[Iterable[int]] = None) -> None:
        self.containers: Dict[int, np.ndarray] = {}
        self._counts: Dict[int, int] = {}
        self.op_writer: Optional[io.RawIOBase] = None
        self.op_n = 0
        self.op_n_small = 0   # single-bit op records (types 0/1) only
        self.oplog_bytes = 0  # bytes of op records (replayed + appended)
        self.snapshot_bytes = 0  # size of the snapshot section on read
        self.tail_dropped = 0  # torn-tail bytes discarded by read_bytes
        if positions is not None:
            self.direct_add_n(np.asarray(list(positions), dtype=np.uint64))

    # -- container plumbing -------------------------------------------------

    def _container(self, key: int, create: bool = False) -> Optional[np.ndarray]:
        """Mutable (dense) view of a container: array-encoded containers
        materialize in place, so every existing mutation path works
        unchanged."""
        c = self.containers.get(key)
        if c is None:
            if not create:
                return None
            c = _new_container()
            self.containers[key] = c
        elif c.dtype == np.uint16:
            c = _array_to_dense(c)
            self.containers[key] = c
        return c

    def _invalidate(self, key: int) -> None:
        self._counts.pop(key, None)

    def container_count(self, key: int) -> int:
        n = self._counts.get(key)
        if n is None:
            c = self.containers.get(key)
            if c is None:
                n = 0
            elif c.dtype == np.uint16:
                n = len(c)
            else:
                n = _popcount_words(c)
            self._counts[key] = n
        return n

    def optimize(self) -> int:
        """Re-encode low-cardinality dense containers as sorted-u16
        arrays (reference Bitmap.Optimize, roaring.go:1745): 16-80x less
        host memory for sparse rows (a 48-bit fingerprint container costs
        96 B instead of 8 KiB). Returns the number converted."""
        # Gather candidates first, then extract every position in ONE
        # native ctz sweep and split per container — the per-container
        # unpackbits+nonzero loop made open() O(200 ms) on a 1600-dense-
        # container fragment.
        cand_keys: List[int] = []
        cand_words: List[np.ndarray] = []
        counts: List[int] = []
        converted = 0
        for key, c in list(self.containers.items()):
            if c.dtype == np.uint16:
                continue
            n = self.container_count(key)
            if n == 0:
                del self.containers[key]
                self._invalidate(key)
            elif n <= ARRAY_MAX_SIZE:
                cand_keys.append(key)
                cand_words.append(c)
                counts.append(n)
        if not cand_keys:
            return 0
        pos = native.dense_positions_of(
            cand_words, np.zeros(len(cand_words), np.uint64))
        if pos is None:
            for key, c in zip(cand_keys, cand_words):
                self.containers[key] = _dense_to_array(c)
                converted += 1
            return converted
        # bases were zero, so every value is the in-container position.
        for key, arr in zip(cand_keys,
                            np.split(pos, np.cumsum(counts)[:-1])):
            self.containers[key] = arr.astype(np.uint16)
            converted += 1
        return converted

    def _drop_empty(self, key: int) -> None:
        if key in self.containers and self.container_count(key) == 0:
            del self.containers[key]
            self._invalidate(key)

    # -- point ops ----------------------------------------------------------

    def add(self, *positions: int) -> bool:
        """Add with op-log append (reference Add, roaring.go:161)."""
        changed = False
        for p in positions:
            if self._direct_add(int(p)):
                changed = True
                self._write_op(OP_ADD, value=p)
        return changed

    def _direct_add(self, p: int) -> bool:
        key, low = p >> 16, p & 0xFFFF
        c = self._container(key, create=True)
        w, b = low >> 6, np.uint64(1 << (low & 63))
        if c[w] & b:
            return False
        c[w] |= b
        self._invalidate(key)
        return True

    def direct_add(self, p: int) -> bool:
        return self._direct_add(int(p))

    def remove(self, *positions: int) -> bool:
        changed = False
        for p in positions:
            if self._direct_remove(int(p)):
                changed = True
                self._write_op(OP_REMOVE, value=p)
        return changed

    def _direct_remove(self, p: int) -> bool:
        key, low = p >> 16, p & 0xFFFF
        if key not in self.containers:
            return False
        if not self.contains(p):
            # No-op remove must not materialize an array-encoded
            # container dense (mutex clear_bit probes do this per write).
            return False
        c = self._container(key)
        w, b = low >> 6, np.uint64(1 << (low & 63))
        if not (c[w] & b):
            return False
        c[w] &= ~b
        self._invalidate(key)
        self._drop_empty(key)
        return True

    def contains(self, p: int) -> bool:
        p = int(p)
        c = self.containers.get(p >> 16)
        if c is None:
            return False
        low = p & 0xFFFF
        if c.dtype == np.uint16:
            i = int(np.searchsorted(c, low))
            return i < len(c) and int(c[i]) == low
        return bool(c[low >> 6] & np.uint64(1 << (low & 63)))

    # -- batch ops (the import path; reference DirectAddN / bulkImport) -----

    def direct_add_n(self, positions: np.ndarray,
                     presorted: bool = False) -> int:
        """Bulk add without op-log (reference DirectAddN). Returns
        #changed. presorted=True asserts positions are already sorted
        unique uint64 (bulk_import sorts once and reuses it for the
        touched-row scan)."""
        if len(positions) == 0:
            return 0
        if presorted:
            # Contract: sorted unique; the dtype half is enforced here
            # (an int64 array would break the uint64 shifts below).
            positions = np.ascontiguousarray(positions, dtype=np.uint64)
        else:
            positions = np.unique(np.asarray(positions, dtype=np.uint64))
        changed = 0
        keys = (positions >> np.uint64(16)).astype(np.int64)
        # positions are sorted, so group boundaries come from one
        # unique(return_index) pass — O(N), not O(N x keys).
        uniq, starts = np.unique(keys, return_index=True)
        bounds = np.append(starts, len(positions))
        # Native path: ONE C pass builds every group's dense mask (the
        # data-loader hot loop); Python then only merges per container.
        masks = None
        # Gate on group density and count: the mask block is m x 8 KiB,
        # so a key-sparse import (a bit or two per container) must keep
        # the in-place scatter path instead of allocating gigabytes.
        if len(positions) >= 4096 and len(uniq) <= 65536 and \
                len(positions) >= 64 * len(uniq):
            built = native.build_masks(positions, len(uniq))
            if built is not None:
                masks = built[1]
        for i, key in enumerate(uniq.tolist()):
            group_len = int(bounds[i + 1] - bounds[i])
            if key not in self.containers:
                # New container + unique positions: count is group_len,
                # no popcounts needed.
                if masks is not None:
                    self.containers[key] = masks[i].copy()
                else:
                    group = positions[bounds[i]:bounds[i + 1]]
                    low = (group & np.uint64(0xFFFF)).astype(np.uint32)
                    self.containers[key] = _low_mask(low)
                self._counts[key] = group_len
                changed += group_len
                continue
            c = self._container(key)
            before = self.container_count(key)
            if masks is not None:
                c |= masks[i]
            elif group_len >= 256:
                group = positions[bounds[i]:bounds[i + 1]]
                c |= _low_mask((group & np.uint64(0xFFFF))
                               .astype(np.uint32))
            else:
                group = positions[bounds[i]:bounds[i + 1]]
                low = (group & np.uint64(0xFFFF)).astype(np.uint32)
                # Sparse group into an existing container: scatter in
                # place, no 8 KiB temp mask.
                np.bitwise_or.at(
                    c, low >> 6,
                    np.left_shift(np.uint64(1),
                                  (low & 63).astype(np.uint64)))
            self._invalidate(key)
            changed += self.container_count(key) - before
        return changed

    def direct_remove_n(self, positions: np.ndarray) -> int:
        if len(positions) == 0:
            return 0
        positions = np.unique(np.asarray(positions, dtype=np.uint64))
        changed = 0
        keys = (positions >> np.uint64(16)).astype(np.int64)
        uniq, starts = np.unique(keys, return_index=True)
        bounds = np.append(starts, len(positions))
        for i, key in enumerate(uniq.tolist()):
            if key not in self.containers:
                continue
            c = self._container(key)
            group = positions[bounds[i]:bounds[i + 1]]
            low = (group & np.uint64(0xFFFF)).astype(np.uint32)
            mask = _low_mask(low)
            before = self.container_count(key)
            c &= ~mask
            self._invalidate(key)
            changed += before - self.container_count(key)
            self._drop_empty(key)
        return changed

    def add_batch(self, positions: np.ndarray,
                  presorted: bool = False, log_op: bool = True) -> int:
        """Bulk add *with* one batch op-log record (op type 2).
        log_op=False skips the record — only valid when the caller
        synchronously snapshots before returning (the record would be
        rewritten away immediately; see Fragment.bulk_import)."""
        n = self.direct_add_n(positions, presorted=presorted)
        if len(positions):
            if log_op:
                self._write_op(OP_ADD_BATCH,
                               values=np.asarray(positions,
                                                 dtype=np.uint64))
            else:
                self.op_n += len(positions)
        return n

    def remove_batch(self, positions: np.ndarray) -> int:
        n = self.direct_remove_n(positions)
        if len(positions):
            self._write_op(OP_REMOVE_BATCH, values=np.asarray(positions, dtype=np.uint64))
        return n

    def import_batch(self, row_ids: np.ndarray, col_ids: np.ndarray,
                     swidth_exp: int) -> np.ndarray:
        """Fused bulk import (replaces the reference's bulkImportStandard
        sort + DirectAddN shape, fragment.go:1494-1604): scatter
        (row, col) pairs into dense per-container masks WITHOUT sorting
        (native radix bucket; numpy unique-group fallback), append ONE
        compact OP_ADD_ROARING record whose payload is the batch's own
        roaring snapshot, then merge the masks in. Returns the sorted
        touched container keys. Duplicates within the batch are
        harmless (mask OR)."""
        row_ids = np.ascontiguousarray(row_ids, dtype=np.uint64)
        col_ids = np.ascontiguousarray(col_ids, dtype=np.uint64)
        if len(row_ids) == 0:
            return np.empty(0, dtype=np.uint64)
        nat = None
        if native.available():
            nat = native.import_build(row_ids, col_ids, swidth_exp)
        if nat is not None:
            keys, masks, counts, payload, n_bits = nat
            self._append_roaring_record(payload, n_bits)
            # Merge. Rows of `masks` are views into one freshly-allocated
            # block no one else holds, so when most keys are NEW the
            # containers adopt the views copy-free; when most keys
            # already exist, adopted rows are copied instead so a few
            # survivors don't pin the whole m x 8 KiB parent alive.
            key_list = [int(k) for k in keys.tolist()]
            n_new = sum(1 for k in key_list if k not in self.containers)
            adopt_views = n_new * 2 >= len(key_list)
            count_list = counts.tolist()
            for i, key in enumerate(key_list):
                if key not in self.containers:
                    self.containers[key] = (masks[i] if adopt_views
                                            else masks[i].copy())
                    # Batch cardinality is exact for a fresh container —
                    # seed the count cache instead of re-popcounting on
                    # the row_count pass that follows every import.
                    self._counts[key] = int(count_list[i])
                else:
                    c = self._container(key)
                    c |= masks[i]
                    self._invalidate(key)
            return keys
        # Grouped path (no native library, or a batch shape unsuited to
        # dense scatter — sparse/wide row ranges): sort+unique once,
        # then work per group as sorted-u16 arrays — no dense mask
        # block, so a pathologically sparse batch (a bit per container)
        # stays O(batch) in memory.
        positions = np.unique(
            (row_ids << np.uint64(swidth_exp))
            + (col_ids & np.uint64((1 << swidth_exp) - 1)))
        gkeys = (positions >> np.uint64(16)).astype(np.int64)
        starts = np.concatenate(
            ([0], np.flatnonzero(gkeys[1:] != gkeys[:-1]) + 1))
        bounds = np.append(starts, len(positions)).astype(np.uint64)
        keys = positions[starts] >> np.uint64(16)
        key_list = [int(k) for k in keys.tolist()]
        lows = (positions & np.uint64(0xFFFF)).astype(np.uint16)
        counts_arr = np.diff(bounds.astype(np.int64))
        groups = [lows[bounds[i]:bounds[i + 1]]
                  for i in range(len(starts))]
        payload = None
        if native.available():
            payload = native.serialize_groups(keys, lows, bounds)
        if payload is None:
            payload = _serialize_container_seq(
                ((k, g, len(g)) for k, g in zip(key_list, groups)),
                len(key_list))
        self._append_roaring_record(payload, len(positions))
        if self.containers.keys().isdisjoint(key_list) and \
                int(counts_arr.max(initial=0)) <= ARRAY_MAX_SIZE:
            # All-new sorted-unique array containers (the
            # fingerprint-import shape: a million one-container rows):
            # one C-level dict build instead of a per-key Python loop,
            # counts seeded from the group lengths.
            self.containers.update(zip(key_list, groups))
            self._counts.update(zip(key_list, counts_arr.tolist()))
            return keys
        for k, g in zip(key_list, groups):
            if k not in self.containers:
                if len(g) <= ARRAY_MAX_SIZE:
                    # Sorted unique in-container positions — a valid
                    # array-encoded container as-is.
                    self.containers[k] = g
                else:
                    # Above the array bound the u16 encoding costs up
                    # to 16x a dense container — keep the invariant.
                    self.containers[k] = _low_mask(g.astype(np.uint32))
            else:
                c = self._container(k)
                c |= _low_mask(g.astype(np.uint32))
            self._invalidate(k)
        return keys

    def _append_roaring_record(self, payload: bytes, n_bits: int) -> None:
        """Append an OP_ADD_ROARING record for an already-built batch
        payload; bumps the op accounting the snapshot policy reads."""
        rec = encode_op_roaring(payload)
        self.op_n += n_bits
        self.oplog_bytes += len(rec)
        if self.op_writer is not None:
            _write_all(self.op_writer, rec)

    # -- queries ------------------------------------------------------------

    def count(self) -> int:
        return sum(self.container_count(k) for k in self.containers)

    def any(self) -> bool:
        return any(self.container_count(k) for k in self.containers)

    @staticmethod
    def _positions(c: np.ndarray) -> np.ndarray:
        """Sorted in-container positions for either encoding."""
        return c if c.dtype == np.uint16 else _dense_to_array(c)

    def max(self) -> int:
        if not self.containers:
            return 0
        key = max(self.containers)
        arr = self._positions(self.containers[key])
        return (key << 16) | int(arr[-1])

    def min(self) -> int:
        if not self.containers:
            return 0
        key = min(self.containers)
        arr = self._positions(self.containers[key])
        return (key << 16) | int(arr[0])

    def slice(self) -> np.ndarray:
        """All set positions, sorted (reference Slice, roaring.go:393).
        Runs of consecutive dense containers extract through one native
        ctz sweep (pn_dense_positions_ptrs) instead of per-container
        unpackbits+nonzero — the anti-entropy checksum hot path."""
        keys = sorted(self.containers)
        out: List[np.ndarray] = []
        i = 0
        while i < len(keys):
            c = self.containers[keys[i]]
            if _is_array(c):
                if len(c):
                    out.append(np.uint64(keys[i] << 16)
                               + c.astype(np.uint64))
                i += 1
                continue
            j = i
            while j < len(keys) and not _is_array(self.containers[keys[j]]):
                j += 1
            run = keys[i:j]
            pos = native.dense_positions_of(
                [self.containers[k] for k in run],
                np.array(run, np.uint64) << np.uint64(16))
            if pos is None:  # numpy fallback
                for k in run:
                    arr = _dense_to_array(self.containers[k])
                    if len(arr):
                        out.append(np.uint64(k << 16)
                                   + arr.astype(np.uint64))
            elif len(pos):
                out.append(pos)
            i = j
        if not out:
            return np.empty(0, dtype=np.uint64)
        return np.concatenate(out)

    def __iter__(self) -> Iterator[int]:
        return iter(self.slice().tolist())

    def count_range(self, start: int, end: int) -> int:
        """Count of bits in [start, end) (reference CountRange, roaring.go:335)."""
        if end <= start:
            return 0
        total = 0
        k0, k1 = start >> 16, (end - 1) >> 16
        # Walk whichever key set is smaller: the range span (row reads are
        # 16 containers) or the populated containers — never both.
        if k1 - k0 + 1 <= len(self.containers):
            keys = (k for k in range(k0, k1 + 1) if k in self.containers)
        else:
            keys = (k for k in self.containers if k0 <= k <= k1)
        for key in keys:
            lo = start - (key << 16) if key == k0 else 0
            hi = end - (key << 16) if key == k1 else CONTAINER_BITS
            lo, hi = max(lo, 0), min(hi, CONTAINER_BITS)
            if lo == 0 and hi == CONTAINER_BITS:
                total += self.container_count(key)
            else:
                arr = self._positions(self.containers[key])
                total += int(np.count_nonzero((arr >= lo) & (arr < hi)))
        return total

    def offset_range(self, offset: int, start: int, end: int) -> "Bitmap":
        """Slice bits in [start, end) and rebase them at `offset` (reference
        OffsetRange, roaring.go:439 — the fragment row-read primitive,
        fragment.go:378). offset/start/end must be container-aligned."""
        assert offset & 0xFFFF == 0 and start & 0xFFFF == 0 and end & 0xFFFF == 0
        other = Bitmap()
        off_key = offset >> 16
        hi0, hi1 = start >> 16, end >> 16
        for key, c in self.containers.items():
            if hi0 <= key < hi1:
                if self.container_count(key):
                    other.containers[off_key + (key - hi0)] = c.copy()
        return other

    def dense_range(self, start: int, end: int) -> np.ndarray:
        """Dense uint64 words for bits [start, end) (container-aligned) —
        the host->HBM handoff: returns ((end-start)//64) words."""
        assert start & 0xFFFF == 0 and end & 0xFFFF == 0
        n_containers = (end - start) >> 16
        out = np.zeros(n_containers * CONTAINER_WORDS, dtype=np.uint64)
        k0 = start >> 16
        for i in range(n_containers):
            c = self.containers.get(k0 + i)
            if c is None:
                continue
            seg = out[i * CONTAINER_WORDS:(i + 1) * CONTAINER_WORDS]
            if c.dtype == np.uint16:
                # Decode straight into the output — no 8 KiB temp.
                v = c.astype(np.uint32)
                np.bitwise_or.at(
                    seg, v >> 6,
                    np.left_shift(np.uint64(1), (v & 63).astype(np.uint64)))
            else:
                seg[:] = c
        return out

    def set_dense_range(self, start: int, dense: np.ndarray) -> None:
        """Overwrite container-aligned region from dense uint64 words."""
        assert start & 0xFFFF == 0 and len(dense) % CONTAINER_WORDS == 0
        k0 = start >> 16
        for i in range(len(dense) // CONTAINER_WORDS):
            chunk = dense[i * CONTAINER_WORDS : (i + 1) * CONTAINER_WORDS]
            key = k0 + i
            if chunk.any():
                self.containers[key] = np.array(chunk, dtype=np.uint64)
                self._invalidate(key)
            elif key in self.containers:
                del self.containers[key]
                self._invalidate(key)

    def for_each_range(self, start: int, end: int) -> np.ndarray:
        # Touch only containers intersecting [start, end): block-scoped
        # callers (checksum_blocks walks 100-row blocks) must not pay a
        # whole-bitmap extraction per block.
        k0, k1 = start >> 16, (end - 1) >> 16
        sub = Bitmap()
        sub.containers = {k: c for k, c in self.containers.items()
                          if k0 <= k <= k1}
        s = sub.slice()
        if len(s) and (start & 0xFFFF or end & 0xFFFF):
            s = s[(s >= start) & (s < end)]
        return s

    # -- set algebra (host path / CPU baseline) -----------------------------

    def _binary(self, other: "Bitmap", op: Callable[..., np.ndarray],
                keys: Iterable[int]) -> "Bitmap":
        out = Bitmap()
        zero = None
        for key in keys:
            a = self.containers.get(key)
            b = other.containers.get(key)
            if a is None or b is None:
                if zero is None:
                    zero = _new_container()
                a = a if a is not None else zero
                b = b if b is not None else zero
            res = op(_as_dense(a), _as_dense(b))
            if res.any():
                out.containers[key] = res
        return out

    def intersect(self, other: "Bitmap") -> "Bitmap":
        keys = self.containers.keys() & other.containers.keys()
        return self._binary(other, np.bitwise_and, keys)

    def union(self, other: "Bitmap") -> "Bitmap":
        keys = self.containers.keys() | other.containers.keys()
        return self._binary(other, np.bitwise_or, keys)

    def difference(self, other: "Bitmap") -> "Bitmap":
        keys = self.containers.keys()
        return self._binary(other, lambda a, b: a & ~b, keys)

    def xor(self, other: "Bitmap") -> "Bitmap":
        keys = self.containers.keys() | other.containers.keys()
        return self._binary(other, np.bitwise_xor, keys)

    def intersection_count(self, other: "Bitmap") -> int:
        total = 0
        for key in self.containers.keys() & other.containers.keys():
            a, b = self.containers[key], other.containers[key]
            if a.dtype == np.uint16 and b.dtype != np.uint16:
                a, b = b, a
            if b.dtype == np.uint16:
                if a.dtype == np.uint16:
                    total += len(np.intersect1d(a, b, assume_unique=True))
                else:
                    # Probe the dense side at the array's positions.
                    v = b.astype(np.uint32)
                    bits = (a[v >> 6] >> (v & 63).astype(np.uint64)) \
                        & np.uint64(1)
                    total += int(bits.sum())
            else:
                total += _popcount_words(a & b)
        return total

    def union_in_place(self, *others: "Bitmap") -> None:
        """N-way in-place union (reference UnionInPlace, roaring.go:536)."""
        for other in others:
            for key, b in other.containers.items():
                if key not in self.containers:
                    self.containers[key] = b.copy()
                else:
                    a = self._container(key)
                    a |= _as_dense(b)
                self._invalidate(key)

    def _absorb(self, other: "Bitmap") -> None:
        """union_in_place for a bitmap nobody else holds (a replayed
        record's payload): its containers move, counts and all, so a
        file that ends in records opens into the views of a few load
        blocks that a snapshot opens into — not into a small array a
        container (0.7 M of them put chem-chip's `finish.select` into
        its slow mode, 428 ms a flush for 66: PERF.md section 6, PR
        40)."""
        if not self.containers:
            self.containers, self._counts = other.containers, other._counts
            return
        mine, counts = self.containers, other._counts
        for key, b in other.containers.items():
            if key not in mine:
                mine[key] = b
                if key in counts:
                    self._counts[key] = counts[key]
            else:
                a = self._container(key)
                a |= _as_dense(b)
                self._invalidate(key)

    def copy(self) -> "Bitmap":
        out = Bitmap()
        out.containers = {k: v.copy() for k, v in self.containers.items()}
        out._counts = dict(self._counts)
        return out

    def shift(self, n: int = 1) -> "Bitmap":
        """Shift all bit positions up by n (reference Shift, roaring.go:865)."""
        return Bitmap(self.slice() + np.uint64(n))

    def flip(self, start: int, end: int) -> "Bitmap":
        """Flip bits in [start, end] inclusive (reference Flip, roaring.go:1185).
        Vectorized: XOR each touched container with a range mask; only the two
        boundary containers need partial masks."""
        out = self.copy()
        k0, k1 = start >> 16, end >> 16
        for key in range(k0, k1 + 1):
            lo = start - (key << 16) if key == k0 else 0
            hi = end - (key << 16) + 1 if key == k1 else CONTAINER_BITS
            c = out._container(key, create=True)
            if lo == 0 and hi == CONTAINER_BITS:
                c ^= np.uint64(0xFFFFFFFFFFFFFFFF)
            else:
                bits = np.zeros(CONTAINER_BITS, dtype=np.uint8)
                bits[lo:hi] = 1
                c ^= np.packbits(bits, bitorder="little").view(np.uint64)
            out._invalidate(key)
            out._drop_empty(key)
        return out

    # -- ops log ------------------------------------------------------------

    def _write_op(self, typ: int, value: int = 0,
                  values: Optional[np.ndarray] = None) -> None:
        self.op_n += 1 if values is None else len(values)
        if values is None:
            self.op_n_small += 1
        # Record length is closed-form — don't encode (fnv over the
        # whole payload) just for accounting when nothing is logging.
        self.oplog_bytes += 13 if values is None else 13 + 8 * len(values)
        if self.op_writer is None:
            return
        _write_all(self.op_writer, encode_op(typ, value, values))

    # -- serialization ------------------------------------------------------

    def write_bytes(self) -> bytes:
        """Serialize in the reference's file format (roaring.go:963).
        Uses the native C++ codec (native/pilosa_native.cpp
        rb_serialize_ptrs — per-container pointers, no stacking copy)
        when available; the Python path is the reference semantics and
        produces byte-identical output."""
        keys = [k for k in sorted(self.containers) if self.container_count(k) > 0]
        n_u16 = sum(1 for k in keys
                    if self.containers[k].dtype == np.uint16)
        if native.available() and n_u16 * 4 > len(keys):
            # u16-heavy (fingerprint-shaped) bitmaps: serialize from
            # sorted position groups — densifying every array container
            # first costs ~30 us each and dominated snapshot time at
            # ~16k sparse containers.
            out = self._write_bytes_groups(keys)
            if out is not None:
                return out
        # Dense-heavy: per-container pointers, temps only for the few
        # array-encoded ones; cap their footprint so an all-sparse
        # million-container bitmap can't materialize gigabytes at once.
        if native.available() and n_u16 * 8 * CONTAINER_WORDS <= (256 << 20):
            dense = [_as_dense(self.containers[k]) for k in keys]
            out = native.roaring_serialize_ptrs(
                np.array(keys, dtype=np.uint64), dense)
            if out is not None:
                return out
        return _serialize_container_seq(
            ((key, self.containers[key], self.container_count(key))
             for key in keys), len(keys))

    def _write_bytes_groups(self, keys: List[int]) -> Optional[bytes]:
        """Native groups serializer over mixed containers: u16 arrays
        contribute their positions verbatim; dense containers extract
        through one native ctz sweep. Returns None if unavailable.

        Note: groups with >=4096 positions are written bitmap-encoded
        (pn_serialize_groups never picks run encoding — for the dense
        side this matches rb_serialize only when runs wouldn't win, so
        this path is gated to u16-heavy bitmaps where dense containers
        are rare and byte-exactness of encoding CHOICE is not part of
        the format contract — any valid encoding reads back equal)."""
        lows_parts: List[np.ndarray] = []
        counts: List[int] = []
        dense_chunks: List[np.ndarray] = []
        dense_slots: List[int] = []
        for i, k in enumerate(keys):
            c = self.containers[k]
            if c.dtype == np.uint16:
                lows_parts.append(c)
                counts.append(len(c))
            else:
                lows_parts.append(None)  # patched below
                dense_chunks.append(c)
                dense_slots.append(i)
                counts.append(self.container_count(k))
        if dense_chunks:
            pos = native.dense_positions_of(
                dense_chunks, np.zeros(len(dense_chunks), np.uint64))
            if pos is None:
                return None
            dcounts = [self.container_count(keys[i]) for i in dense_slots]
            for arr, slot in zip(
                    np.split(pos.astype(np.uint16),
                             np.cumsum(dcounts)[:-1]), dense_slots):
                lows_parts[slot] = arr
        lows = (np.concatenate(lows_parts) if lows_parts
                else np.empty(0, dtype=np.uint16))
        bounds = np.concatenate(
            ([0], np.cumsum(counts, dtype=np.uint64)))
        return native.serialize_groups(
            np.array(keys, dtype=np.uint64), lows, bounds)

    @classmethod
    def from_bytes(cls, data: bytes,
                   tolerate_torn_tail: bool = False,
                   _depth: int = 0) -> "Bitmap":
        """Deserialize (reference unmarshalPilosaRoaring, roaring.go:1037),
        including ops-log replay from the file tail."""
        b = cls()
        b.read_bytes(data, tolerate_torn_tail=tolerate_torn_tail,
                     _depth=_depth)
        return b

    def read_bytes(self, data: bytes,
                   tolerate_torn_tail: bool = False,
                   _depth: int = 0) -> None:
        """Deserialize. tolerate_torn_tail=True (Fragment.open recovering
        its OWN file after a crash) drops a final op record torn at EOF
        and reports it via self.tail_dropped; the default keeps fail-hard
        semantics for wire-received bytes (a truncated import payload
        must error, not silently half-apply)."""
        self.tail_dropped = 0
        if native.available():
            # Encoding-split load: array-eligible containers arrive as
            # u16 position spans of ONE compact buffer (the in-memory
            # encoding optimize() would produce anyway), dense ones as
            # rows of one block — a sparse fingerprint-shaped fragment
            # loads its ~2 MB of real data instead of materializing
            # 8 KiB per tiny container and re-optimizing.
            # That holds for a file WITHOUT an op tail only: with one
            # the native parser replays into dense containers, 8 KiB
            # each, every container of the snapshot included. So the
            # snapshot section is loaded by itself (compactly) and the
            # tail replayed here, record by record.
            tail_at = _split_load_at(data)
            loaded = native.roaring_load_ex(
                bytes(data) if tail_at is None
                else memoryview(data)[:tail_at],
                split_max_card=ARRAY_MAX_SIZE)
            if loaded is not None:
                if loaded["tail_dropped"] and not tolerate_torn_tail:
                    raise OpTruncatedError(
                        f"op data truncated ({loaded['tail_dropped']} "
                        "tail bytes)")
                counts = loaded["counts"]
                lows, dense = loaded["lows"], loaded["dense"]
                # Containers are VIEWS into the two exactly-sized load
                # blocks (deliberate: per-container copies were the
                # sparse-open bottleneck). Trade-off: dropping a
                # container keeps its parent block alive while any
                # sibling view survives — acceptable because the blocks
                # hold only real data and fragments rarely shrink;
                # mutation is safe (u16 views densify into fresh arrays
                # via _container(); dense rows are disjoint).
                self.containers = {}
                self._counts = {}
                lo = dn = 0
                for i, k in enumerate(loaded["keys"]):
                    c = int(counts[i])
                    if c <= ARRAY_MAX_SIZE:
                        self.containers[k] = lows[lo:lo + c]
                        lo += c
                    else:
                        self.containers[k] = dense[dn]
                        dn += 1
                    self._counts[k] = c
                self.op_n = loaded["op_n"]
                self.op_n_small = loaded["op_n_small"]
                self.oplog_bytes = loaded["ops_bytes"]
                self.snapshot_bytes = loaded["snapshot_bytes"]
                self.tail_dropped = loaded["tail_dropped"]
                if tail_at is not None:
                    self._replay_ops(memoryview(data)[tail_at:],
                                     tolerate_torn_tail, _depth)
                return
        if len(data) < HEADER_BASE_SIZE:
            raise ValueError("data too small")
        magic, version = struct.unpack_from("<HH", data, 0)
        if magic != MAGIC_NUMBER:
            raise ValueError(f"invalid roaring file, magic number {magic}")
        if version != STORAGE_VERSION:
            raise ValueError(f"wrong roaring version v{version}")
        (n,) = struct.unpack_from("<I", data, 4)
        self.containers.clear()
        self._counts.clear()
        metas: List[Tuple[int, int, int]] = []
        pos = HEADER_BASE_SIZE
        prev_key = -1
        for _ in range(n):
            key, typ, card_minus_1 = struct.unpack_from("<QHH", data, pos)
            # Strictly-increasing keys are a format invariant; a
            # duplicate would make "last container wins" semantics that
            # the native reader (and the reference) reject. Fuzz corpus
            # div-unsorted-keys pinned the divergence where this reader
            # silently accepted out-of-order keys.
            if key <= prev_key:
                raise ValueError("container keys not sorted")
            prev_key = key
            metas.append((key, typ, card_minus_1 + 1))
            pos += 12
        ops_offset = pos + 4 * n
        for i, (key, typ, card) in enumerate(metas):
            (offset,) = struct.unpack_from("<I", data, pos + 4 * i)
            if offset >= len(data):
                raise ValueError(f"offset out of bounds: {offset}")
            if typ == CONTAINER_ARRAY:
                vals = np.frombuffer(data, dtype="<u2", count=card, offset=offset)
                # Stays array-encoded in memory: a snapshot full of
                # sparse rows opens at ~its file size, not 8 KiB per
                # container. unique() enforces the sorted-distinct
                # invariant the encoding relies on (untrusted input).
                self.containers[key] = np.unique(vals).astype(np.uint16)
                end = offset + 2 * card
            elif typ == CONTAINER_BITMAP:
                words = np.frombuffer(
                    data, dtype="<u8", count=CONTAINER_WORDS, offset=offset
                )
                self.containers[key] = np.array(words, dtype=np.uint64)
                end = offset + 8 * CONTAINER_WORDS
            elif typ == CONTAINER_RUN:
                (run_n,) = struct.unpack_from("<H", data, offset)
                runs = np.frombuffer(
                    data, dtype="<u2", count=run_n * 2,
                    offset=offset + RUN_COUNT_HEADER_SIZE,
                ).reshape(-1, 2)
                self.containers[key] = _runs_to_dense(runs)
                end = offset + RUN_COUNT_HEADER_SIZE + 4 * run_n
            else:
                raise ValueError(f"unknown container type {typ}")
            del card  # header cardinality untrusted; payload is authoritative
            c = self.containers[key]
            if (len(c) == 0 if c.dtype == np.uint16 else not c.any()):
                # Never materialize empty containers (max/min assume every
                # present container has at least one bit).
                del self.containers[key]
            ops_offset = max(ops_offset, end)
        self.op_n = 0
        self.op_n_small = 0
        self.oplog_bytes = 0
        self.snapshot_bytes = ops_offset
        self._replay_ops(memoryview(data)[ops_offset:],
                         tolerate_torn_tail, _depth)

    def _replay_ops(self, buf: memoryview, tolerate_torn_tail: bool,
                    _depth: int) -> None:
        """Ops log replay, onto the containers the snapshot section
        left. A record extending past EOF is a torn tail append (crash
        mid-write): tolerated, dropped, and reported via tail_dropped so
        the owner can truncate the file. Checksum mismatches on complete
        records still raise (data corruption; reference fails on both,
        op.UnmarshalBinary roaring.go:3659)."""
        while len(buf):
            try:
                op_typ, value, values, size = decode_op(buf)
            except OpTruncatedError:
                if not tolerate_torn_tail:
                    raise
                self.tail_dropped = len(buf)
                break
            if op_typ == OP_ADD:
                self._direct_add(value)
                self.op_n += 1
                self.op_n_small += 1
            elif op_typ == OP_REMOVE:
                self._direct_remove(value)
                self.op_n += 1
                self.op_n_small += 1
            elif op_typ == OP_ADD_BATCH:
                self.direct_add_n(values)
                self.op_n += len(values)
            elif op_typ == OP_REMOVE_BATCH:
                self.direct_remove_n(values)
                self.op_n += len(values)
            elif op_typ == OP_ADD_ROARING:
                if _depth + 1 >= MAX_OP_NESTING:
                    raise ValueError("op nesting too deep")
                batch = Bitmap.from_bytes(values, _depth=_depth + 1)
                self.op_n += batch.count()
                self._absorb(batch)
            self.oplog_bytes += size
            buf = buf[size:]


def _serialize_container_seq(items: Iterable[Tuple[int, np.ndarray, int]],
                             n: int) -> bytes:
    """Serialize (key, container, count) triples — sorted, non-empty —
    to the file format, one dense temp at a time (the Python writer
    shared by write_bytes and the import-batch fallback). Encoding
    choice mirrors Optimize, roaring.go:1745-1805."""
    header = io.BytesIO()
    header.write(struct.pack("<II", COOKIE, n))
    payloads: List[bytes] = []
    for key, c, card in items:
        dense = _as_dense(c)  # 8 KiB temp at most
        runs = _dense_to_runs(dense)
        run_size = RUN_COUNT_HEADER_SIZE + 4 * len(runs)
        array_size = 2 * card
        if run_size < min(array_size, 8192):
            typ = CONTAINER_RUN
            payloads.append(
                struct.pack("<H", len(runs)) + runs.astype("<u2").tobytes())
        elif array_size < 8192:
            typ = CONTAINER_ARRAY
            payloads.append(_dense_to_array(dense).astype("<u2").tobytes())
        else:
            typ = CONTAINER_BITMAP
            payloads.append(dense.astype("<u8").tobytes())
        header.write(struct.pack("<QHH", int(key), typ, card - 1))
    offset = HEADER_BASE_SIZE + n * 12 + n * 4
    for p in payloads:
        header.write(struct.pack("<I", offset))
        offset += len(p)
    return header.getvalue() + b"".join(payloads)


# The native parser loads a file that ends with its snapshot section
# compactly (containers stay references into the input), and one with
# an op tail DENSE: 8 KiB a container, the snapshot's included, before
# the replay. A fragment of 16.7 M one-row containers whose tail held
# a third of them asked for 137 GB that way (builder, PR 40). So a file
# with a tail has its two sections loaded apart (Bitmap.read_bytes):
# the snapshot section by the native parser, the tail by _replay_ops.
_META_DTYPE = np.dtype([("key", "<u8"), ("typ", "<u2"), ("card", "<u2")])


def _split_load_at(data) -> Optional[int]:
    """Where the op tail of a roaring file starts, from its header
    alone; None for a file without a tail (None too for a header this
    cannot read: the parser that then loads the whole file says what is
    wrong with it). The section's end is the parsers' own: the largest
    container end."""
    if len(data) < HEADER_BASE_SIZE:
        return None
    magic, version, n = struct.unpack_from("<HHI", data, 0)
    offsets_at = HEADER_BASE_SIZE + 12 * n
    end = offsets_at + 4 * n
    if magic != MAGIC_NUMBER or version != STORAGE_VERSION \
            or end > len(data):
        return None
    if n:
        meta = np.frombuffer(data, dtype=_META_DTYPE, count=n,
                             offset=HEADER_BASE_SIZE)
        at = np.frombuffer(data, dtype="<u4", count=n,
                           offset=offsets_at).astype(np.int64)
        typ = meta["typ"]
        ends = np.where(typ == CONTAINER_ARRAY,
                        at + 2 * (meta["card"].astype(np.int64) + 1),
                        at + 8 * CONTAINER_WORDS)
        runs = np.flatnonzero(typ == CONTAINER_RUN)
        if len(runs):
            if int(at[runs].max()) + RUN_COUNT_HEADER_SIZE > len(data):
                return None
            raw = np.frombuffer(data, dtype=np.uint8)
            run_n = raw[at[runs]].astype(np.int64) \
                | (raw[at[runs] + 1].astype(np.int64) << 8)
            ends[runs] = at[runs] + RUN_COUNT_HEADER_SIZE + 4 * run_n
        if ((typ < CONTAINER_ARRAY) | (typ > CONTAINER_RUN)).any():
            return None
        end = max(end, int(ends.max()))
    return end if end < len(data) else None


def encode_op(typ: int, value: int = 0, values: Optional[np.ndarray] = None) -> bytes:
    """Encode one ops-log record (reference op.WriteTo, roaring.go:3628)."""
    if typ in (OP_ADD, OP_REMOVE):
        head = struct.pack("<BQ", typ, int(value))
        chk = fnv1a32(head)
        return head + struct.pack("<I", chk)
    vals = np.asarray(values, dtype="<u8").tobytes()
    head = struct.pack("<BQ", typ, len(values))
    chk = fnv1a32(head, vals)
    return head + struct.pack("<I", chk) + vals


def encode_op_roaring(payload: bytes) -> bytes:
    """Encode an OP_ADD_ROARING record: crc32 (zlib) over head+payload —
    fnv1a is byte-serial and too slow for multi-MB batch payloads."""
    import zlib

    head = struct.pack("<BQ", OP_ADD_ROARING, len(payload))
    chk = zlib.crc32(payload, zlib.crc32(head))
    return head + struct.pack("<I", chk) + payload


class OpTruncatedError(ValueError):
    """An op record extends past EOF — a torn tail append."""


def decode_op(buf: bytes) -> Tuple[int, int, Optional[np.ndarray], int]:
    """Decode one op record; returns (type, value, values, encoded_size).
    For OP_ADD_ROARING, `values` is the raw payload bytes."""
    if len(buf) < 13:
        raise OpTruncatedError(f"op data out of bounds: len={len(buf)}")
    typ, value = struct.unpack_from("<BQ", buf, 0)
    (chk,) = struct.unpack_from("<I", buf, 9)
    if typ in (OP_ADD, OP_REMOVE):
        if chk != fnv1a32(bytes(buf[0:9])):
            raise ValueError("op checksum mismatch")
        return typ, value, None, 13
    if typ in (OP_ADD_BATCH, OP_REMOVE_BATCH):
        n = value
        size = 13 + 8 * n
        if len(buf) < size:
            raise OpTruncatedError("op data truncated")
        if chk != fnv1a32(bytes(buf[0:9]), bytes(buf[13:size])):
            raise ValueError("op checksum mismatch")
        values = np.frombuffer(buf, dtype="<u8", count=n, offset=13).copy()
        return typ, 0, values, size
    if typ == OP_ADD_ROARING:
        import zlib

        size = 13 + value
        if len(buf) < size:
            raise OpTruncatedError("op data truncated")
        payload = bytes(buf[13:size])
        if chk != zlib.crc32(payload, zlib.crc32(bytes(buf[0:9]))):
            raise ValueError("op checksum mismatch")
        return typ, 0, payload, size
    raise ValueError(f"invalid op type {typ}")
