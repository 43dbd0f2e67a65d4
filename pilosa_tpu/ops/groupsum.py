"""The sums of a GroupBy's groups: `groupby_sum`'s body.

A group's mask is `pre[pi] ∧ bank[si] ∧ not-null`; its sum needs
|mask ∧ plane_j| for every bit plane j of the summed field and |mask|.
The kernel walks the column space a word tile at a time. For a tile it
holds the field's planes in VMEM once, brings each group's two operand
tiles straight from their resident arrays (the index vectors are scalar
prefetched: the block index maps read `si`, `pi` and the plane slots, so
no row is copied and no mask is written to HBM), forms the mask on chip
and takes every plane's count of it before the tile is dropped. Counts
are kept as (sublane, lane) vectors — adds only inside the kernel — and
reduced across lanes once a launch, by the caller's program.

What it fetches (`rows_fetched`): the planes' tiles once a block of
groups, a group's row tile and prefix tile once a RUN of groups that
name the same row — the pipeline skips a fetch whose block index is the
step before's, and that is all the reuse of rows there is: a row that
comes back later in the block is fetched again. The level loop hands
groups over prefix by prefix, so a prefix is one run and the last
child's rows change with every group.

One body: on a CPU the same kernel runs interpreted (`interpret=True`,
the tests'), which is a check of results and never a speed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128         # words a vector register holds along the word axis
SUBLANES = 8        # ... and rows of them
# VMEM for the count vectors of a block of groups, the block's two
# pipeline buffers together: the planes are read again once per block,
# so the block is as many groups as fit (64 at 25 planes; 32 and 128
# read the same on the chip: PERF.md §6, PR 46).
COUNTS_VMEM_BYTES = 16 << 20
# ... for the operand tiles a grid step holds, a buffer of each: the
# planes' tiles and one group's two — 4,096 words of 16 shards at 25
# planes. A grid step costs ~0.87 µs whatever it holds and ~0.28 µs a
# 1,024 words of 16 shards and 25 planes, so wide tiles pay: 512 lanes
# read 19.1 / 11.6 / 8.2 ms at 1,024 / 2,048 / 4,096 words (PERF.md §6).
TILE_VMEM_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 48 << 20
# Groups one call takes at most, whatever their rows' width: the two
# index vectors live in scalar memory and the count vectors
# [lanes, planes, 8, 128] u32 in HBM (400 MB at 25 planes).
MAX_LANES = 4096


def group_block(lanes: int, planes: int) -> int:
    """Groups whose count vectors [planes, 8, 128] u32 stay in
    VMEM while the tiles go by: the largest power of two that fits
    COUNTS_VMEM_BYTES twice over, and no more than the launch has."""
    most = COUNTS_VMEM_BYTES // (2 * planes * SUBLANES * LANES * 4)
    return min(lanes, 1 << max(0, most.bit_length() - 1))


def tile_words(w: int, shards: int, operands: int) -> int:
    """Words of the word axis a grid step takes: the widest multiple of
    128 that divides `w` (itself one: a bank's width is) and keeps
    `operands` tiles of [shards (padded to 8), tile] u32 inside
    TILE_VMEM_BYTES."""
    rows = -(-shards // SUBLANES) * SUBLANES
    most = max(1, TILE_VMEM_BYTES // (operands * rows * LANES * 4))
    n = w // LANES
    return LANES * max(k for k in range(1, n + 1)
                       if n % k == 0 and k <= most)


def rows_fetched(pi, si, planes: int, tiles: int) -> int:
    """Operand rows' worth of tiles one call brings from HBM, by the
    host's copy of its index vectors (`pi` None: no prefix): per block
    of groups every plane once and a row once a run of equal consecutive
    indices. With one tile a call the grid's steps are the groups in
    order, so a run goes on across blocks and the planes come once."""
    lanes = len(si)
    gb = lanes if tiles == 1 else group_block(lanes, planes)

    def runs(idx):
        idx = np.asarray(idx).reshape(-1, gb)
        return idx.shape[0] + int(np.count_nonzero(idx[:, 1:] != idx[:, :-1]))
    return planes * (lanes // gb) + runs(si) + (
        0 if pi is None else runs(pi))


def _fold_words(counts):
    """[S, tw] counts added down to [S, 128]: whole-register adds,
    never a cross-lane reduction. One register column after another
    onto one sum, which stays in registers; adding by halves traces a
    fifth of the operations (every start traces each lane shape's
    program again, ~0.35 s each at 4,096 words) and reads 21 % slower
    on the chip, each level's half-size sum stored and read back
    (PERF.md §6, PR 46)."""
    return functools.reduce(jnp.add, [
        counts[:, k:k + LANES] for k in range(0, counts.shape[-1], LANES)])


def group_plane_counts(pre, pi, bank, si, plane_bank, sel, w: int, *,
                       interpret: bool = False, mesh=None,
                       axis: str = "shards"):
    """u32 [planes, lanes]: row j < planes - 1 is
    |pre[pi[g]] ∧ bank[si[g]] ∧ plane_bank[sel[-1]] ∧ plane_bank[sel[j]]|
    over the first `w` words of every shard, the last row the same
    without a plane (`|mask|`). `pre` (and `pi`) may be None: no prefix.
    All arrays [n, S, W >= w] u32; `pi`, `si` int32 [lanes], `sel` int32
    [planes]. With a `mesh` (a `jax.sharding.Mesh` whose `axis` splits
    the arrays' S) every device runs the kernel over its own shards and
    the counts are summed by one `psum`."""
    kernel = functools.partial(_tile_counts, w=w, interpret=interpret)
    if mesh is None:
        return kernel(pre, pi, bank, si, plane_bank, sel)
    from jax.sharding import PartitionSpec as P
    split, whole = P(None, axis, None), P()

    def local(bank, si, plane_bank, sel, *prefix):
        pre, pi = prefix or (None, None)
        return jax.lax.psum(kernel(pre, pi, bank, si, plane_bank, sel), axis)
    return jax.shard_map(
        local, mesh=mesh, out_specs=whole, check_vma=False,
        in_specs=(split, whole, split, whole)
        + ((split, whole) if pre is not None else ()),
    )(bank, si, plane_bank, sel, *(() if pre is None else (pre, pi)))


def _distinct_rows(bank, si, w: int):
    """(rows [n, S, w], index [lanes]): the DISTINCT rows `si` names of
    `bank`, copied once each to the front of `rows` (the rest is never
    read), and per lane its row's place there; n is as many as there
    can be, the lanes or the bank's rows."""
    n = min(si.shape[0], bank.shape[0])
    slots, place = jnp.unique(si, size=n, fill_value=si[0],
                              return_inverse=True)
    s = bank.shape[-2]

    def copy(i, rows):
        row = jax.lax.dynamic_slice(bank, (slots[i], 0, 0), (1, s, w))
        return jax.lax.dynamic_update_slice(rows, row, (i, 0, 0))
    rows = jax.lax.fori_loop(0, jnp.max(place) + 1, copy,
                             jnp.zeros((n, s, w), bank.dtype))
    return rows, place.astype(jnp.int32)


def _tile_counts(pre, pi, bank, si, plane_bank, sel, *, w: int,
                 interpret: bool, tile: int | None = None,
                 compact: bool | None = None):
    """`group_plane_counts` on one device."""
    lanes, planes = int(si.shape[0]), int(sel.shape[0])
    shards = int(bank.shape[-2])
    if lanes > MAX_LANES:
        raise ValueError(f"{lanes} groups a call, over {MAX_LANES}")
    # The kernel reads its operands row-major, eight shards to a
    # register. Where a device's shards are no multiple of eight XLA
    # keeps a bank with eight ROWS to a tile instead (compiled for a
    # described v5e at [1024, 15, 32768]: layout {2,0,1:T(8,128)}) and
    # re-lays every operand out before the call — the whole bank, 1.9 GiB
    # a launch. There the bank is cut to the launch's distinct rows
    # first (never more bytes than the whole copy: the fewer of lanes
    # and rows are written, a launch's few distinct rows copied); the
    # smaller operands are left to XLA's copy.
    if compact is None:
        compact = shards % SUBLANES != 0
    if compact:
        bank, si = _distinct_rows(bank, si, w)
    has_pre = pre is not None
    tw = tile or tile_words(w, shards, planes + 1 + has_pre)
    gb = group_block(lanes, planes)
    if lanes % gb or w % tw or tw % LANES:
        raise ValueError(f"{lanes} lanes in blocks of {gb}, {w} words in "
                         f"tiles of {tw}")
    # A (group, plane)'s count vector: the shards' eights added onto
    # each other, a last ragged eight onto its first rows.
    a_rows = min(shards, SUBLANES)
    eights = range(0, shards - shards % SUBLANES, SUBLANES)
    ragged = shards % SUBLANES if shards > SUBLANES else 0

    def kernel(pi_ref, si_ref, sel_ref, *refs):
        del pi_ref, si_ref, sel_ref     # read by the index maps
        out_ref = refs[-1]
        plane_refs = refs[1 + has_pre:-1]
        t, g = pl.program_id(1), pl.program_id(2)
        mask = refs[0][...] & plane_refs[-1][...]
        if has_pre:
            mask = mask & refs[1][...]

        @pl.when(t == 0)
        def _():
            out_ref[g] = jnp.zeros((planes, a_rows, LANES), jnp.uint32)

        for j in range(planes):
            counts = _fold_words(jax.lax.population_count(
                mask if j == planes - 1 else mask & plane_refs[j][...]))
            if not eights:
                out_ref[g, j] += counts
                continue
            out_ref[g, j] += functools.reduce(
                jnp.add, [counts[r:r + SUBLANES] for r in eights])
            if ragged:
                out_ref[g, j, :ragged] += counts[shards - ragged:]

    def row(index):
        return pl.BlockSpec((None, shards, tw), index)

    in_specs = [row(lambda b, t, g, pi, si, sel: (si[b * gb + g], 0, t))]
    if has_pre:
        in_specs.append(
            row(lambda b, t, g, pi, si, sel: (pi[b * gb + g], 0, t)))
    in_specs += [row(lambda b, t, g, pi, si, sel, j=j: (sel[j], 0, t))
                 for j in range(planes)]
    counts = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes // gb, w // tw, gb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (gb, planes, a_rows, LANES),
                lambda b, t, g, pi, si, sel: (b, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((lanes, planes, a_rows, LANES),
                                       jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="groupby_sum_tiles",
    )(si if pi is None else pi, si, sel,
      bank, *((pre,) if has_pre else ()), *[plane_bank] * planes)
    # The one reduction across lanes of a launch.
    return jnp.sum(counts, axis=(-2, -1), dtype=jnp.uint32).T
