"""Dense packed-bitset kernels for TPU.

This is the L0 of the framework: where the reference implements nine families
of pairwise container kernels over three container encodings
(/root/reference/roaring/roaring.go:2313-3607 — intersect*/union*/difference*/
xor*/intersectionCount*/shift*/flip* for array/bitmap/run), we keep exactly one
device encoding — a dense packed bitset — and let every op be a fused XLA
elementwise + reduction over uint32 words.

Layout
------
A *shard row* is one row of one fragment restricted to a 2^20-column shard
(ShardWidth, /root/reference/fragment.go:50). On device it is a
`uint32[WORDS_PER_SHARD]` array (32768 words = 128 KiB). uint32 rather than
uint64 because the TPU VPU has 32-bit lanes; XLA legalizes u64 bitwise ops into
u32 pairs anyway, so we store u32 natively and avoid the round trip.

Bit p (0 <= p < 2^20) lives in word p >> 5, bit p & 31 — identical to the
little-endian uint64 layout viewed as pairs of uint32, so host numpy uint64
buffers convert with a zero-copy ``.view('<u4')``.

All ops are pure jnp functions over arrays whose *last* axis is words; any
leading axes (rows, shards) batch for free. Compositions are jitted at the
executor layer so XLA fuses e.g. Count(Intersect(a,b)) into a single
AND+popcount pass without materializing the intersection — the moral
equivalent of the reference's fused `intersectionCountBitmapBitmap`
(/root/reference/roaring/roaring.go:2438), generalized to every op pair.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.typing import ArrayLike

# Shard geometry. ShardWidth mirrors /root/reference/fragment.go:50-51
# (2^20 columns per shard); it must stay a power of two and a multiple of
# the container width 2^16 so host roaring containers tile it exactly.
SHARD_WIDTH_EXP = 20
SHARD_WIDTH = 1 << SHARD_WIDTH_EXP  # 1,048,576 columns per shard
WORD_BITS = 32
WORDS_PER_SHARD = SHARD_WIDTH // WORD_BITS  # 32,768 uint32 words (128 KiB)

WORD_DTYPE = jnp.uint32
NP_WORD_DTYPE = np.uint32

# ---------------------------------------------------------------------------
# Elementwise set algebra. Last axis = words; leading axes batch.
# ---------------------------------------------------------------------------


def b_and(a: ArrayLike, b: ArrayLike) -> jax.Array:
    """Intersect (reference: roaring.go:497 Intersect / :2630 bitmap∧bitmap)."""
    return jnp.bitwise_and(a, b)


def b_or(a: ArrayLike, b: ArrayLike) -> jax.Array:
    """Union (reference: roaring.go:522)."""
    return jnp.bitwise_or(a, b)


def b_xor(a: ArrayLike, b: ArrayLike) -> jax.Array:
    """Xor (reference: roaring.go:837)."""
    return jnp.bitwise_xor(a, b)


def b_andnot(a: ArrayLike, b: ArrayLike) -> jax.Array:
    """Difference a \\ b (reference: roaring.go:810)."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def b_not(a: ArrayLike, existence: ArrayLike) -> jax.Array:
    """Not(a) relative to an existence row (reference executor computes Not as
    existence-difference, /root/reference/executor.go:1556-1587)."""
    return jnp.bitwise_and(existence, jnp.bitwise_not(a))


def union_many(stack: jax.Array, axis: int = 0) -> jax.Array:
    """N-way union over a stacked axis (reference UnionInPlace,
    roaring.go:536 — the bulk union used by time-range row reads)."""
    return jax.lax.reduce(
        stack,
        jnp.zeros((), dtype=stack.dtype),
        jnp.bitwise_or,
        (axis if axis >= 0 else stack.ndim + axis,),
    )


def intersect_many(stack: jax.Array, axis: int = 0) -> jax.Array:
    """N-way intersection over a stacked axis."""
    return jax.lax.reduce(
        stack,
        jnp.bitwise_not(jnp.zeros((), dtype=stack.dtype)),
        jnp.bitwise_and,
        (axis if axis >= 0 else stack.ndim + axis,),
    )


def pick_rows(w: int, *picks, fixed=()) -> jax.Array:
    """Inside a program: [n, S, w] whose row i is the AND of
    arr[idx[i]][:, :w] over the `picks` (arr [N, S, W], idx int32 [n])
    and of every [S, W] array of `fixed` — one row an iteration of a
    loop, each operand row read in place (a dynamic slice) and the
    result written once. Not a gather: for rows past 1 MiB XLA's TPU
    gather first copies its WHOLE operand in two halves of the word
    axis (2 GiB of temporaries and ~9 ms for the 2 GiB `p_brand1` bank,
    what an eager `bank[slots]` paid; PERF.md §6 PR 33), and a `vmap`
    of a dynamic slice lowers to a `stablehlo.gather` too."""
    n = picks[0][1].shape[0]
    s = picks[0][0].shape[-2]

    def body(i, out):
        row = None
        for arr, idx in picks:
            r = jax.lax.dynamic_slice(arr, (idx[i], 0, 0), (1, s, w))[0]
            row = r if row is None else jnp.bitwise_and(row, r)
        for f in fixed:
            row = jnp.bitwise_and(row, f[..., :w])
        return jax.lax.dynamic_update_index_in_dim(out, row, i, 0)
    return jax.lax.fori_loop(0, n, body,
                             jnp.zeros((n, s, w), picks[0][0].dtype))


# ---------------------------------------------------------------------------
# Counting. popcount reduces the word axis; XLA fuses it into whatever
# elementwise op produced the words.
# ---------------------------------------------------------------------------


def popcount(a: ArrayLike,
             axis: Union[int, Tuple[int, ...]] = -1) -> jax.Array:
    """Total set bits, reduced over `axis` (reference Count, roaring.go:319).

    Returns uint32: one reduced axis covers at most one shard row
    (2^20 bits), and even a full 1024-shard stack is 2^30 < 2^32. Promote
    on the host when aggregating across many reductions."""
    return jnp.sum(jax.lax.population_count(a).astype(jnp.uint32), axis=axis,
                   dtype=jnp.uint32)


def count_and(a: ArrayLike, b: ArrayLike) -> jax.Array:
    """|a ∧ b| fused (reference IntersectionCount, roaring.go:472/2438)."""
    return popcount(jnp.bitwise_and(a, b))


# A filtered bank sweep's filter is cut into word-axis pieces of this many
# words per shard, and into no more pieces than this: the one fusion that
# reads the bank has an output a piece and filter, and with sixteen it
# loses a third of its rate (see masked_row_counts).
SWEEP_PIECE_WORDS = 8192
SWEEP_MAX_PIECES = 4


def sweep_filter_pieces(n_words: int, filters: int = 1) -> int:
    """How many equal word-axis pieces masked_row_counts cuts a filter of
    `n_words` words per shard into: pieces of SWEEP_PIECE_WORDS, at least
    two and at most SWEEP_MAX_PIECES, each whole 128-word lanes. Where no
    such count divides the lanes (a prime number of them, say) the pieces
    are halves, and an odd last lane is left over as a short piece of its
    own. One — the uncut body — under two whole lanes. A sweep of several
    `filters` in one pass has an output a filter and piece, all of them
    reduced in every window step, so it takes that many times fewer
    pieces, and never under two."""
    lanes = n_words // 128
    if n_words % 128 or lanes < 2:
        return 1
    most = max(2, SWEEP_MAX_PIECES // filters)
    want = max(2, min(most, n_words // SWEEP_PIECE_WORDS))
    return next((k for k in range(want, most + 1) if lanes % k == 0), 2)


def _sweep_cuts(n_words: int, filters: int = 1):
    """The word-axis slices of sweep_filter_pieces' pieces, the odd last
    lane's short piece included."""
    pieces = sweep_filter_pieces(n_words, filters)
    step = n_words if pieces == 1 else n_words // 128 // pieces * 128
    cuts = [slice(w0, w0 + step) for w0 in range(0, pieces * step, step)]
    if pieces * step < n_words:
        cuts.append(slice(pieces * step, n_words))
    return cuts


def _masked_counts(bank: jax.Array, filt: jax.Array, cuts) -> jax.Array:
    """|row ∧ filt| per row, summed over the word-axis pieces `cuts`."""
    return functools.reduce(jnp.add, (
        popcount(jnp.bitwise_and(bank[..., c], filt[..., c]), axis=(-2, -1))
        for c in cuts))


def masked_row_counts(bank: jax.Array, filt: jax.Array) -> jax.Array:
    """|row ∧ filt| per row of a bank: ([R, S, W], [S, W]) -> uint32[R].

    The sum runs over word-axis pieces of the filter rather than over the
    whole [S, W] at once. The answer is the same; the program is not.
    Given the filter as ONE broadcast operand, XLA's TPU fusion reuses an
    8 KiB filter tile across 512 rows, and reduces each row's two vregs to
    a scalar in every one of 256 steps: 271 GB/s on a v5e whatever else
    the body computes. Sliced inside the program, the pieces are
    temporaries that XLA keeps in on-chip memory, and the one fusion that
    reads the bank (`popcnt_reduce_fusion`, one output per piece) takes
    windows of a few dozen rows x 64 KiB each: 755 GB/s, the rate of
    the unfiltered sweep (PERF.md §6, PR 25: the table of variants, shapes
    and piece counts). Equal pieces make
    one fusion, a piece of another width a fusion of its own (the odd
    lane's: a third of the bank's bytes at three lanes, 1/251 at most at
    a shard's width). The word axis is the one to cut: under a mesh the shard axis
    is split over devices, and a slice along it would move data between
    them."""
    return _masked_counts(bank, filt, _sweep_cuts(filt.shape[-1]))


def masked_row_counts_multi(bank: jax.Array, *filts: jax.Array):
    """masked_row_counts of K filters from ONE pass over the bank:
    ([R, S, W], K x [S, W]) -> uint32[K, R], lane k bit for bit the
    counts of filter k. The same algorithm with K the caller's: every
    piece of the bank is ANDed with that piece of each filter while it is
    on chip, so the fusion that reads the bank has K outputs a piece and
    the pieces are fewer (sweep_filter_pieces). The filters come in as K
    operands, each where its own program left it, and meet here."""
    cuts = _sweep_cuts(bank.shape[-1], len(filts))
    return jnp.stack([_masked_counts(bank, f, cuts) for f in filts])


def count_or(a: ArrayLike, b: ArrayLike) -> jax.Array:
    return popcount(jnp.bitwise_or(a, b))


def count_xor(a: ArrayLike, b: ArrayLike) -> jax.Array:
    return popcount(jnp.bitwise_xor(a, b))


def count_andnot(a: ArrayLike, b: ArrayLike) -> jax.Array:
    return popcount(jnp.bitwise_and(a, jnp.bitwise_not(b)))


# ---------------------------------------------------------------------------
# Shifts and masks.
# ---------------------------------------------------------------------------


def shift_bits(a: jax.Array, n: int = 1) -> jax.Array:
    """Shift every bit position up by n within the shard (reference
    roaring.Shift, roaring.go:865, used by executeShiftShard,
    executor.go:1591). Bits shifted past the top of the shard are dropped —
    matching the reference's per-rowSegment shift (row.go:180-197), which
    does not carry across shard boundaries either.
    """
    if n == 0:
        return a
    word_shift = n // WORD_BITS
    bit_shift = n % WORD_BITS
    # Move whole words by padding at the low end of the word axis.
    if word_shift:
        pad = [(0, 0)] * (a.ndim - 1) + [(word_shift, 0)]
        a = jnp.pad(a, pad)[..., : a.shape[-1]]
    if bit_shift:
        hi = jnp.left_shift(a, jnp.uint32(bit_shift))
        carry = jnp.right_shift(a, jnp.uint32(WORD_BITS - bit_shift))
        pad = [(0, 0)] * (a.ndim - 1) + [(1, 0)]
        carry = jnp.pad(carry, pad)[..., : a.shape[-1]]
        a = jnp.bitwise_or(hi, carry)
    return a


def range_mask_np(start: int, end: int, words: int = WORDS_PER_SHARD) -> np.ndarray:
    """Host-built uint32 mask with bits [start, end) set. Used for
    CountRange/OffsetRange-style column windows; built once per query on the
    host, so plain numpy."""
    mask = np.zeros(words, dtype=np.uint32)
    start = max(0, start)
    end = min(end, words * WORD_BITS)
    if end <= start:
        return mask
    w0, b0 = divmod(start, WORD_BITS)
    w1, b1 = divmod(end, WORD_BITS)
    if w0 == w1:
        mask[w0] = (np.uint64((1 << b1) - (1 << b0))).astype(np.uint32)
    else:
        mask[w0] = np.uint32(((1 << WORD_BITS) - (1 << b0)) & 0xFFFFFFFF)
        mask[w0 + 1 : w1] = np.uint32(0xFFFFFFFF)
        if b1:
            mask[w1] = np.uint32((1 << b1) - 1)
    return mask


# ---------------------------------------------------------------------------
# Host <-> device packing helpers (numpy; the storage layer owns durability).
# ---------------------------------------------------------------------------


def pack_positions(positions: Union[Sequence[int], np.ndarray],
                   width: int = SHARD_WIDTH) -> np.ndarray:
    """Pack sorted/unsorted bit positions (< width) into a uint32 word array."""
    words = np.zeros(width // WORD_BITS, dtype=np.uint32)
    if len(positions) == 0:
        return words
    pos = np.asarray(positions, dtype=np.uint64)
    # graftlint: disable=GL005 — w is a word-INDEX vector for
    # np.bitwise_or.at (numpy requires signed indices), not word data.
    w = (pos >> np.uint64(5)).astype(np.int64)
    b = (pos & np.uint64(31)).astype(np.uint32)
    np.bitwise_or.at(words, w, np.left_shift(np.uint32(1), b))
    return words


def unpack_positions(words: np.ndarray) -> np.ndarray:
    """Inverse of pack_positions: word array -> sorted uint64 bit positions."""
    words = np.ascontiguousarray(words, dtype=np.uint32)
    bytes_ = words.view(np.uint8)
    bits = np.unpackbits(bytes_, bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint64)


def u64_to_words(buf: np.ndarray) -> np.ndarray:
    """Zero-copy view of a little-endian uint64 bitmap buffer as u32 words."""
    return np.ascontiguousarray(buf).view("<u4")


def words_to_u64(words: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(words).view("<u8")


def transfer_nbytes(arrays) -> int:
    """Sum of .nbytes over an iterable of (device or host) arrays,
    skipping entries without the attribute. Shape metadata only — never
    touches array contents, so it is safe on unfetched device arrays
    (the profiler's H2D/D2H transfer-byte accounting)."""
    total = 0
    for a in arrays or ():
        n = getattr(a, "nbytes", None)
        if n is not None:
            total += int(n)
    return total
