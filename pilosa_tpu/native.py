"""ctypes bindings for the native C++ host-runtime library.

The library (native/pilosa_native.cpp) implements the host storage hot
path — roaring file parse/serialize (reference roaring.go:963-1126) with
ops-log replay (roaring.go:3628-3691), and packed-word popcount kernels
(the host analog of roaring.go:2438's intersection-count loop).

The Python implementations in storage/roaring.py remain the reference
semantics and the fallback: if the shared library is missing it is built
on first import with `make` (g++ is in the image); if that fails, callers
get None from load() and use the numpy paths. Set PILOSA_TPU_NO_NATIVE=1
to force the fallback (used by tests to cross-check both paths).

Sanitizer variants (the native correctness plane, docs/development.md):
PILOSA_TPU_NATIVE_SAN=asan|ubsan|tsan selects a
libpilosa_native.{san}.so built with `make SAN=...`
(-fsanitize=... -fno-omit-frame-pointer -g). Availability-gated like
the default build: if the variant cannot be built/loaded, load()
returns None and callers take the Python paths (an unrecognized value
also yields None — silently loading the uninstrumented library would
defeat the point of asking for a sanitizer). ASan/TSan runtimes must be
preloaded into the python process (tools/check.sh --san does this);
under a sanitizer, untrusted input bytes are staged in exact-size libc
malloc buffers so one-byte over-reads land in a redzone instead of
slack inside a Python object.
"""

from __future__ import annotations

import contextlib
import ctypes
import logging
import os
import subprocess
from pilosa_tpu.utils.locks import make_lock
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")

_SAN_VARIANTS = ("asan", "ubsan", "tsan")

_lock = make_lock("native._lock")
# Load results keyed by requested sanitizer variant ('' = plain build):
# a PILOSA_TPU_NATIVE_SAN set AFTER the plain library was first loaded
# must get the instrumented .so, not the cached uninstrumented one (and
# a failed sanitizer load must not poison a later plain request). The
# key space is closed: '' plus _SAN_VARIANTS.
_libs: Dict[str, Optional[ctypes.CDLL]] = {}
_load_errors: Dict[str, str] = {}
_libc: Optional[ctypes.CDLL] = None
_force_python = 0

CONTAINER_WORDS = 1024


def active_san() -> str:
    """The requested sanitizer variant ('' = the plain build). Values
    outside the matrix read as a bogus request: load() then returns
    None rather than silently serving the uninstrumented library."""
    return os.environ.get("PILOSA_TPU_NATIVE_SAN", "").strip().lower()


def _so_path(san: str) -> str:
    name = f"libpilosa_native.{san}.so" if san else "libpilosa_native.so"
    return os.path.join(_NATIVE_DIR, name)


def _build(san: str) -> Optional[str]:
    """Run make; None on success, else why it failed (make's stderr)."""
    if not os.path.isdir(_NATIVE_DIR):
        return f"{_NATIVE_DIR} is not a directory"
    cmd = ["make", "-C", _NATIVE_DIR]
    if san:
        cmd.append(f"SAN={san}")
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except subprocess.CalledProcessError as e:
        return (e.stderr or b"").decode("utf-8", "replace").strip() \
            or f"make exited {e.returncode}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return repr(e)
    if not os.path.exists(_so_path(san)):
        return f"make succeeded but {_so_path(san)} is missing"
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64 = ctypes.c_uint64
    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    p_u64 = ctypes.POINTER(u64)
    p_u16 = ctypes.POINTER(ctypes.c_uint16)
    lib.rb_load.argtypes = [p_u8, u64]
    lib.rb_load.restype = ctypes.c_void_p
    lib.rb_error.argtypes = [ctypes.c_void_p]
    lib.rb_error.restype = ctypes.c_char_p
    lib.rb_container_count.argtypes = [ctypes.c_void_p]
    lib.rb_container_count.restype = u64
    lib.rb_op_count.argtypes = [ctypes.c_void_p]
    lib.rb_op_count.restype = u64
    lib.rb_op_small_count.argtypes = [ctypes.c_void_p]
    lib.rb_op_small_count.restype = u64
    lib.rb_ops_bytes.argtypes = [ctypes.c_void_p]
    lib.rb_ops_bytes.restype = u64
    lib.rb_snapshot_bytes.argtypes = [ctypes.c_void_p]
    lib.rb_snapshot_bytes.restype = u64
    lib.rb_tail_dropped.argtypes = [ctypes.c_void_p]
    lib.rb_tail_dropped.restype = u64
    lib.rb_copy_out.argtypes = [ctypes.c_void_p, p_u64, p_u64]
    lib.rb_copy_out.restype = None
    lib.rb_keys.argtypes = [ctypes.c_void_p, p_u64]
    lib.rb_keys.restype = None
    lib.rb_counts.argtypes = [ctypes.c_void_p, p_u64]
    lib.rb_counts.restype = None
    lib.rb_export_split.argtypes = [ctypes.c_void_p, u64, p_u16, p_u64]
    lib.rb_export_split.restype = None
    lib.rb_free.argtypes = [ctypes.c_void_p]
    lib.rb_free.restype = None
    lib.rb_serialize_cap.argtypes = [u64]
    lib.rb_serialize_cap.restype = u64
    lib.rb_serialize.argtypes = [p_u64, p_u64, u64, p_u8]
    lib.rb_serialize.restype = u64
    lib.rb_serialize_ptrs.argtypes = [p_u64, p_u64, u64, p_u8]
    lib.rb_serialize_ptrs.restype = u64
    lib.pn_crc32.argtypes = [p_u8, u64, ctypes.c_uint32]
    lib.pn_crc32.restype = ctypes.c_uint32
    lib.pn_import_build.argtypes = [p_u64, p_u64, u64, ctypes.c_uint32]
    lib.pn_import_build.restype = ctypes.c_void_p
    lib.ib_error.argtypes = [ctypes.c_void_p]
    lib.ib_error.restype = ctypes.c_char_p
    lib.ib_count.argtypes = [ctypes.c_void_p]
    lib.ib_count.restype = u64
    lib.ib_nbits.argtypes = [ctypes.c_void_p]
    lib.ib_nbits.restype = u64
    lib.ib_payload_size.argtypes = [ctypes.c_void_p]
    lib.ib_payload_size.restype = u64
    lib.ib_keys_counts.argtypes = [ctypes.c_void_p, p_u64, p_u64]
    lib.ib_keys_counts.restype = None
    lib.ib_words.argtypes = [ctypes.c_void_p, p_u64]
    lib.ib_words.restype = None
    lib.ib_payload.argtypes = [ctypes.c_void_p, p_u8]
    lib.ib_payload.restype = None
    lib.ib_free.argtypes = [ctypes.c_void_p]
    lib.ib_free.restype = None
    lib.pn_serialize_groups_cap.argtypes = [u64, u64]
    lib.pn_serialize_groups_cap.restype = u64
    lib.pn_serialize_groups.argtypes = [p_u64, p_u16, p_u64, u64, p_u8]
    lib.pn_serialize_groups.restype = u64
    lib.pn_fnv1a32.argtypes = [p_u8, u64, ctypes.c_uint32]
    lib.pn_fnv1a32.restype = ctypes.c_uint32
    lib.pn_popcount.argtypes = [p_u64, u64]
    lib.pn_popcount.restype = u64
    lib.pn_intersection_count.argtypes = [p_u64, p_u64, u64]
    lib.pn_intersection_count.restype = u64
    lib.pn_row_popcounts.argtypes = [p_u64, u64, u64, p_u64]
    lib.pn_row_popcounts.restype = None
    lib.pn_build_masks.argtypes = [p_u64, u64, u64, p_u64, p_u64]
    lib.pn_build_masks.restype = u64
    lib.pn_scatter_rows.argtypes = [p_u16, p_u64, u64, p_u64, u64, p_u64]
    lib.pn_scatter_rows.restype = None
    # The chunk-pointer arrays ride as uint64 address arrays (same ABI as
    # const uint64_t* const* and ~100x cheaper than building per-element
    # ctypes pointer objects).
    lib.pn_popcount_ptrs.argtypes = [p_u64, u64, u64]
    lib.pn_popcount_ptrs.restype = u64
    lib.pn_dense_positions_ptrs.argtypes = [p_u64, u64, u64, p_u64, p_u64]
    lib.pn_dense_positions_ptrs.restype = u64
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Return the bound native library, building it if needed; None if
    unavailable (missing toolchain), disabled via PILOSA_TPU_NO_NATIVE,
    or an unbuildable/unknown PILOSA_TPU_NATIVE_SAN variant was
    requested."""
    if os.environ.get("PILOSA_TPU_NO_NATIVE"):
        return None
    san = active_san()
    if san and san not in _SAN_VARIANTS:
        return None
    with _lock:
        if san in _libs:
            return _libs[san]
        so_path = _so_path(san)
        lib: Optional[ctypes.CDLL] = None
        # Always run make: it is mtime-based (a no-op when fresh) and
        # rebuilds a stale .so whose symbols predate these bindings.
        # graftlint: disable=GL009 — build-once critical section: the
        # lock EXISTS to make every caller wait for the single
        # first-touch make; there is nothing useful to do before the
        # library is bound, so blocking under it is the point.
        error = _build(san)
        if error is None or os.path.exists(so_path):
            try:
                lib = _bind(ctypes.CDLL(so_path))
                error = None
            except (OSError, AttributeError) as e:
                # AttributeError = missing symbol in a stale library
                # that make could not refresh; OSError also covers a
                # sanitizer runtime that is not preloaded into this
                # process. Fall back to the Python paths either way.
                error = f"{error or 'make ok'}; bind failed: {e!r}"
        if lib is None:
            # Once per variant (the result is cached below): storage
            # takes the numpy paths from here on, which is several
            # times slower on ingest — the operator must see why.
            # graftlint: disable=GL008 — same closed key space as _libs
            _load_errors[san] = error or "unknown"
            logging.getLogger(__name__).warning(
                "native library %s unavailable, storage falls back to "
                "numpy paths: %s", os.path.basename(so_path),
                _load_errors[san])
        # graftlint: disable=GL008 — closed key space ('' + 3 variants)
        _libs[san] = lib
        return lib


def status() -> Tuple[bool, str]:
    """(loaded, reason-when-not) for the start line, GET /info and
    chip_smoke.py — a server on the numpy fallback says so."""
    if os.environ.get("PILOSA_TPU_NO_NATIVE"):
        return False, "disabled by PILOSA_TPU_NO_NATIVE"
    if load() is not None:
        return True, ""
    return False, _load_errors.get(active_san(),
                                   "unknown sanitizer variant")


def available() -> bool:
    return _force_python == 0 and load() is not None


@contextlib.contextmanager
def force_python() -> Iterator[None]:
    """Make available() report False inside the block, routing every
    caller that gates on it (storage/roaring.py) onto the pure-Python
    paths. Direct entry points (roaring_load_ex etc.) keep working: the
    differential oracle parses natively while forcing the Python
    reader. Reentrant; used by the fuzzer and the differential tests."""
    global _force_python
    _force_python += 1
    try:
        yield
    finally:
        _force_python -= 1


def _as_u64_ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


class _StagedBytes:
    """Untrusted input bytes staged for a native call.

    Plain build: a ctypes copy of the data (the pre-existing path).
    Sanitizer build: an EXACT-size libc malloc block instead — ASan
    intercepts malloc and places redzones at the precise boundary, so a
    one-past-the-end read in the parser faults immediately. A ctypes
    array cannot give that: its bytes sit inline in the Python object
    (or inside a pymalloc arena), where an over-read lands in
    uninstrumented slack and is silent.
    """

    def __init__(self, data: bytes):
        self._raw = None
        self._libc = None
        if active_san():
            global _libc
            if _libc is None:
                libc = ctypes.CDLL(None)
                libc.malloc.argtypes = [ctypes.c_size_t]
                libc.malloc.restype = ctypes.c_void_p
                libc.free.argtypes = [ctypes.c_void_p]
                libc.free.restype = None
                _libc = libc
            raw = _libc.malloc(max(len(data), 1))
            if raw:
                self._raw = raw
                self._libc = _libc
                ctypes.memmove(raw, data, len(data))
                self.ptr = ctypes.cast(
                    raw, ctypes.POINTER(ctypes.c_uint8))
                return
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        self._buf = buf  # keepalive
        self.ptr = ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))

    def __enter__(self) -> "ctypes.POINTER(ctypes.c_uint8)":
        return self.ptr

    def __exit__(self, *exc) -> None:
        if self._raw is not None:
            self._libc.free(self._raw)
            self._raw = None


def _as_u8_ptr(buf) -> "ctypes.POINTER(ctypes.c_uint8)":
    return ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8))


class NativeParseError(ValueError):
    pass


def roaring_load(data: bytes
                 ) -> Optional[Tuple[List[int], np.ndarray, int, int]]:
    """Parse a roaring file (snapshot + ops log) natively.

    Returns (sorted container keys, dense words [n, 1024] uint64, op count,
    torn-tail bytes dropped), or None when the native library is
    unavailable. Raises NativeParseError on malformed input (same
    conditions as the Python reader; a truncated FINAL op is tolerated
    and reported via the last tuple element instead)."""
    ex = roaring_load_ex(data)
    if ex is None:
        return None
    return ex["keys"], ex["words"], ex["op_n"], ex["tail_dropped"]


def roaring_load_ex(data: bytes,
                    split_max_card: Optional[int] = None
                    ) -> Optional[dict]:
    """roaring_load plus the op-log accounting the snapshot policy needs:
    {keys, op_n, op_n_small, ops_bytes, snapshot_bytes, tail_dropped}
    and the container payload. None when unavailable.

    Default payload: "words" — every container dense [n, 1024]. With
    split_max_card set, the payload is encoding-split instead: "counts"
    (u64[n]), "lows" (u16 positions of all containers whose cardinality
    is <= split_max_card, concatenated in key order) and "dense"
    ([n_dense, 1024] for the rest) — a sparse 16k-container fragment
    then loads ~2 MB instead of materializing 128 MB dense and
    re-optimizing."""
    lib = load()
    if lib is None:
        return None
    # The staged buffer must outlive rb_free: compact-mode handles keep
    # refs into the input bytes across the accessor calls below.
    with _StagedBytes(data) as buf:
        h = lib.rb_load(buf, len(data))
        if not h:
            raise MemoryError("rb_load allocation failed")
        try:
            err = lib.rb_error(h)
            if err:
                raise NativeParseError(err.decode())
            n = lib.rb_container_count(h)
            keys = np.empty(n, dtype=np.uint64)
            out = {
                "op_n": int(lib.rb_op_count(h)),
                "op_n_small": int(lib.rb_op_small_count(h)),
                "ops_bytes": int(lib.rb_ops_bytes(h)),
                "snapshot_bytes": int(lib.rb_snapshot_bytes(h)),
                "tail_dropped": int(lib.rb_tail_dropped(h)),
            }
            if split_max_card is None:
                words = np.empty((n, CONTAINER_WORDS), dtype=np.uint64)
                if n:
                    lib.rb_copy_out(h, _as_u64_ptr(keys),
                                    _as_u64_ptr(words))
                out["keys"] = [int(k) for k in keys]
                out["words"] = words
                return out
            counts = np.empty(n, dtype=np.uint64)
            if n:
                lib.rb_keys(h, _as_u64_ptr(keys))
                lib.rb_counts(h, _as_u64_ptr(counts))
            arr_mask = counts <= split_max_card
            lows = np.empty(int(counts[arr_mask].sum()), dtype=np.uint16)
            dense = np.empty((int((~arr_mask).sum()), CONTAINER_WORDS),
                             dtype=np.uint64)
            if n:
                lib.rb_export_split(
                    h, split_max_card,
                    lows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
                    _as_u64_ptr(dense))
            out["keys"] = [int(k) for k in keys]
            out["counts"] = counts
            out["lows"] = lows
            out["dense"] = dense
            return out
        finally:
            lib.rb_free(h)


def roaring_serialize(keys: np.ndarray, words: np.ndarray) -> Optional[bytes]:
    """Serialize sorted non-empty dense containers to the file format.
    keys: uint64[n]; words: uint64[n, 1024]. None when unavailable."""
    lib = load()
    if lib is None:
        return None
    n = len(keys)
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    words = np.ascontiguousarray(words, dtype=np.uint64)
    # numpy buffer + one slicing copy out — (ctypes array; bytearray(out))
    # copied the full worst-case capacity twice and dominated snapshot
    # time for large fragments.
    out = np.empty(int(lib.rb_serialize_cap(n)), dtype=np.uint8)
    size = lib.rb_serialize(_as_u64_ptr(keys), _as_u64_ptr(words), n,
                            out.ctypes.data_as(
                                ctypes.POINTER(ctypes.c_uint8)))
    if size == 0 and n > 0:
        raise ValueError("rb_serialize: empty container passed")
    return out[:size].tobytes()


def roaring_serialize_ptrs(keys: np.ndarray, containers) -> Optional[bytes]:
    """Like roaring_serialize but over independently-allocated dense
    containers (a list of uint64[1024] arrays) — no stacking copy."""
    lib = load()
    if lib is None:
        return None
    n = len(keys)
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    addrs = np.fromiter(
        (c.__array_interface__["data"][0] for c in containers),
        dtype=np.uint64, count=n)
    out = np.empty(int(lib.rb_serialize_cap(n)), dtype=np.uint8)
    size = lib.rb_serialize_ptrs(_as_u64_ptr(keys), _as_u64_ptr(addrs), n,
                                 out.ctypes.data_as(
                                     ctypes.POINTER(ctypes.c_uint8)))
    if size == 0 and n > 0:
        raise ValueError("rb_serialize_ptrs: empty container passed")
    return out[:size].tobytes()


def import_build(row_ids: np.ndarray, col_ids: np.ndarray,
                 swidth_exp: int):
    """Fused bulk import build: positions = row*2^swidth_exp +
    (col mod 2^swidth_exp) scattered into dense container masks (no
    sort), popcounted, and pre-serialized as an OP_ADD_ROARING payload
    — one native call. Returns (keys uint64[m] sorted,
    words uint64[m, 1024], counts uint64[m], payload bytes, n_bits) or
    None when unavailable / the batch's row range is unsuited to dense
    scatter (caller falls back to the grouped numpy path)."""
    lib = load()
    if lib is None:
        return None
    row_ids = np.ascontiguousarray(row_ids, dtype=np.uint64)
    col_ids = np.ascontiguousarray(col_ids, dtype=np.uint64)
    h = lib.pn_import_build(_as_u64_ptr(row_ids), _as_u64_ptr(col_ids),
                            len(row_ids), swidth_exp)
    if not h:
        raise MemoryError("pn_import_build allocation failed")
    try:
        if lib.ib_error(h):
            return None
        m = int(lib.ib_count(h))
        keys = np.empty(m, dtype=np.uint64)
        counts = np.empty(m, dtype=np.uint64)
        words = np.empty((m, CONTAINER_WORDS), dtype=np.uint64)
        payload = np.empty(int(lib.ib_payload_size(h)), dtype=np.uint8)
        if m:
            lib.ib_keys_counts(h, _as_u64_ptr(keys), _as_u64_ptr(counts))
            lib.ib_words(h, _as_u64_ptr(words))
            lib.ib_payload(h, payload.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint8)))
        return keys, words, counts, payload.tobytes(), int(lib.ib_nbits(h))
    finally:
        lib.ib_free(h)


def serialize_groups(keys: np.ndarray, lows: np.ndarray,
                     bounds: np.ndarray) -> Optional[bytes]:
    """Roaring snapshot payload from pre-grouped sorted-unique
    positions: keys uint64[m] ascending, lows uint16[n] (all groups
    back to back), bounds uint64[m+1] offsets. None when unavailable."""
    lib = load()
    if lib is None:
        return None
    m = len(keys)
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    lows = np.ascontiguousarray(lows, dtype=np.uint16)
    bounds = np.ascontiguousarray(bounds, dtype=np.uint64)
    out = np.empty(int(lib.pn_serialize_groups_cap(m, len(lows))),
                   dtype=np.uint8)
    size = lib.pn_serialize_groups(
        _as_u64_ptr(keys),
        lows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        _as_u64_ptr(bounds), m,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if size == (1 << 64) - 1:
        # Native execution failure (OOM/thread spawn) — distinct from
        # bad bounds; None routes callers to the Python serializer.
        return None
    if size == 0 and m > 0:
        raise ValueError("pn_serialize_groups: bad group bounds")
    return out[:size].tobytes()


def fnv1a32(chunks, seed: int = 0x811C9DC5) -> Optional[int]:
    """Chained fnv1a32 over byte chunks; None when unavailable."""
    lib = load()
    if lib is None:
        return None
    h = seed
    for c in chunks:
        # Zero-copy: bytes objects pin their buffer; cast the address
        # directly instead of copying multi-MB batch payloads.
        c = bytes(c) if not isinstance(c, bytes) else c
        buf = ctypes.cast(ctypes.c_char_p(c),
                          ctypes.POINTER(ctypes.c_uint8))
        h = lib.pn_fnv1a32(buf, len(c), h)
    return h


def popcount(words: np.ndarray) -> Optional[int]:
    lib = load()
    if lib is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint64)
    return int(lib.pn_popcount(_as_u64_ptr(words), words.size))


def intersection_count(a: np.ndarray, b: np.ndarray) -> Optional[int]:
    lib = load()
    if lib is None:
        return None
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    assert a.size == b.size
    return int(lib.pn_intersection_count(_as_u64_ptr(a), _as_u64_ptr(b),
                                         a.size))


def row_popcounts(words: np.ndarray) -> Optional[np.ndarray]:
    """words: uint64[rows, words_per_row] → uint64[rows] popcounts."""
    lib = load()
    if lib is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint64)
    rows, wpr = words.shape
    out = np.empty(rows, dtype=np.uint64)
    lib.pn_row_popcounts(_as_u64_ptr(words), rows, wpr, _as_u64_ptr(out))
    return out


def build_masks(positions: np.ndarray, m: int):
    """Dense container masks for sorted positions grouped by pos>>16.
    Returns (keys uint64[m], words uint64[m, 1024]) or None when the
    native library is unavailable. `m` = distinct key count (callers have
    it from np.unique)."""
    lib = load()
    if lib is None:
        return None
    positions = np.ascontiguousarray(positions, dtype=np.uint64)
    keys = np.empty(m, dtype=np.uint64)
    words = np.zeros((m, CONTAINER_WORDS), dtype=np.uint64)
    got = lib.pn_build_masks(_as_u64_ptr(positions), len(positions), m,
                             _as_u64_ptr(keys), _as_u64_ptr(words))
    if got != m:
        raise ValueError(f"pn_build_masks: {got} groups, expected {m}")
    return keys, words


def dense_positions_of(containers, bases: np.ndarray
                       ) -> Optional[np.ndarray]:
    """Like dense_positions but over a list of independently-allocated
    dense containers (uint64, C-contiguous, equal length) — avoids
    stacking them into one copy. None when unavailable."""
    lib = load()
    if lib is None or not containers:
        return None if lib is None else np.empty(0, dtype=np.uint64)
    wpc = containers[0].size
    # __array_interface__ hands back the raw address without building a
    # ctypes pointer object per container (the hot-loop cost at ~10k
    # containers per call).
    addrs = np.fromiter(
        (c.__array_interface__["data"][0] for c in containers),
        dtype=np.uint64, count=len(containers))
    ptrs = _as_u64_ptr(addrs)
    bases = np.ascontiguousarray(bases, dtype=np.uint64)
    n = int(lib.pn_popcount_ptrs(ptrs, len(containers), wpc))
    out = np.empty(n, dtype=np.uint64)
    got = lib.pn_dense_positions_ptrs(ptrs, len(containers), wpc,
                                      _as_u64_ptr(bases), _as_u64_ptr(out))
    if got != n:
        raise ValueError(f"pn_dense_positions_ptrs wrote {got}, "
                         f"expected {n}")
    return out


def scatter_rows(pos: np.ndarray, lens: np.ndarray, row_index: np.ndarray,
                 words64: int, out: np.ndarray) -> bool:
    """Scatter concatenated per-row u16 positions into `out` (u64,
    row-major, width words64). Returns False when unavailable."""
    lib = load()
    if lib is None:
        return False
    pos = np.ascontiguousarray(pos, dtype=np.uint16)
    lens = np.ascontiguousarray(lens, dtype=np.uint64)
    row_index = np.ascontiguousarray(row_index, dtype=np.uint64)
    lib.pn_scatter_rows(
        pos.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        _as_u64_ptr(lens), len(lens), _as_u64_ptr(row_index),
        words64, _as_u64_ptr(out))
    return True
