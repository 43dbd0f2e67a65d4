"""Pallas TPU kernels for the bitmap hot loops.

The reference's hottest code is the per-container popcount/AND loops
(/root/reference/roaring/roaring.go:2438 intersectionCountBitmapBitmap,
:2630 intersectBitmapBitmap) driven by the TopN scan
(/root/reference/fragment.go:1067-1188). Here the equivalent unit of work is
a *bank sweep*: popcount every row of a [rows, shards, words] HBM-resident
view bank, optionally AND-ed with a broadcast filter row — one pass that is
purely HBM-bandwidth-bound.

XLA already compiles `sum(popcount(bank & filt))` well; the Pallas kernels
below exist to (a) pin the tiling — one (row, shard) block of 128 KiB per
grid step, double-buffered HBM→VMEM by the pipeline — and (b) fuse the
masked and unmasked counts into a single data pass: TopN-with-filter needs
BOTH |row ∧ filter| and |row| (for the tanimoto denominator,
/root/reference/fragment.go:1087-1093).

Mosaic requires output blocks to be lane-shaped (…, 8k, 128), so each
kernel accumulates an (8, 128)-shaped partial per row across the shard grid
axis (the shard axis is the minor, sequential grid dimension) and a tiny
fused jnp reduction collapses it afterwards.

`available()` is False off-TPU and the executor takes the fused-jnp path;
a TPU that cannot initialise is an error, not "off". Tier-1 runs the
kernels in interpret mode on CPU against the jnp reference;
tools/pallas_chip_check.py (`chip_smoke.py --pallas`) is what compiles
them through Mosaic, non-interpret, at the served shapes
([1024, 16, 32768] banks). Every kernel in this file passed it on a
TPU v5e with jax 0.9.0; a kernel that stops passing is deleted, not
kept as an opt-in nobody can test.

XLA's own fusion of popcount(b∧f)+popcount(b) already reads the bank
once, so the executor defaults to the jnp path and uses these kernels
only when PILOSA_TPU_PALLAS=1 (`enabled()`); which side wins on the chip
is for the benchmark to say.
"""
# graftlint: disable-file=GL006 — module-level jitted entry points,
# compiled once per static shape bucket; executor call sites reach
# them only from inside _note_jit_compile-tracked programs
# (_counts_fn), so the retrace counter still sees every real
# signature miss.


from __future__ import annotations

import functools
import os
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from pilosa_tpu.ops.bitset import WORDS_PER_SHARD

# Words per (row, shard) block reshaped to VPU-friendly (sublane, lane) tiles:
# 32768 u32 words = 256 sublanes x 128 lanes = 128 KiB VMEM per block.
_LANES = 128
_SUBLANES = WORDS_PER_SHARD // _LANES
# Partial-sum tile kept per row: the minimal 32-bit VMEM tile (8, 128).
_ACC_SUB = 8
_ACC_GROUPS = _SUBLANES // _ACC_SUB


def available() -> bool:
    """True when a TPU backend is attached and Pallas is not disabled."""
    if os.environ.get("PILOSA_TPU_NO_PALLAS"):
        return False
    return jax.devices()[0].platform == "tpu"


def enabled() -> bool:
    """True when the executor should route sweeps through Pallas: opt-in
    via PILOSA_TPU_PALLAS=1 (XLA's fused path measured faster on current
    hardware — see module docstring)."""
    flag = os.environ.get("PILOSA_TPU_PALLAS", "").strip().lower()
    return flag in ("1", "true", "yes", "on") and available()


def _popcount32(x: jax.Array) -> jax.Array:
    """SWAR popcount over uint32 lanes (kept to VPU-native shift/and/add/mul
    so it lowers on every Mosaic version; equivalent to
    jax.lax.population_count)."""
    x = x - ((x >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> jnp.uint32(2))
                                        & jnp.uint32(0x33333333))
    x = (x + (x >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def _block_partial(blk: jax.Array) -> jax.Array:
    """[SUBLANES, LANES] uint32 words -> (ACC_SUB, LANES) popcount partial.

    Accumulates in int32 (Mosaic has no unsigned reductions); per-lane
    partials stay far below 2^31 — ≤32 bits/word × 32 groups × shards."""
    return jnp.sum(
        _popcount32(blk).astype(jnp.int32).reshape(
            _ACC_GROUPS, _ACC_SUB, _LANES),
        axis=0, dtype=jnp.int32)


def _counts_kernel(bank_ref: Any, out_ref: Any) -> None:
    """Grid step (r, s): accumulate one block's popcount into out[r]."""
    from jax.experimental import pallas as pl

    partial = _block_partial(bank_ref[0, 0])
    first = pl.program_id(1) == 0

    @pl.when(first)
    def _init() -> None:
        out_ref[0] = partial

    @pl.when(jnp.logical_not(first))
    def _acc() -> None:
        out_ref[0] += partial


def _masked_counts_kernel(bank_ref: Any, filt_ref: Any,
                          inter_ref: Any, raw_ref: Any) -> None:
    """Grid step (r, s): one data pass accumulates BOTH |row ∧ filt| and
    |row| partials."""
    from jax.experimental import pallas as pl

    blk = bank_ref[0, 0]
    p_inter = _block_partial(blk & filt_ref[0])
    p_raw = _block_partial(blk)
    first = pl.program_id(1) == 0

    @pl.when(first)
    def _init() -> None:
        inter_ref[0] = p_inter
        raw_ref[0] = p_raw

    @pl.when(jnp.logical_not(first))
    def _acc() -> None:
        inter_ref[0] += p_inter
        raw_ref[0] += p_raw


@functools.partial(jax.jit, static_argnames=("interpret",))
def bank_row_counts(bank: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Per-row popcounts of a [R, S, W] uint32 bank -> uint32[R].

    The TopN sweep (reference fragment.top, fragment.go:1067 — there a
    heap scan over cached counts; here an exact full sweep).
    """
    from jax.experimental import pallas as pl

    R, S, W = bank.shape
    assert W == WORDS_PER_SHARD, bank.shape
    tiled = bank.reshape(R, S, _SUBLANES, _LANES)
    partials = pl.pallas_call(
        _counts_kernel,
        grid=(R, S),
        in_specs=[pl.BlockSpec((1, 1, _SUBLANES, _LANES),
                               lambda r, s: (r, s, 0, 0))],
        out_specs=pl.BlockSpec((1, _ACC_SUB, _LANES), lambda r, s: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, _ACC_SUB, _LANES), jnp.int32),
        interpret=interpret,
    )(tiled)
    return jnp.sum(partials, axis=(1, 2), dtype=jnp.int32).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bank_row_counts_masked(
        bank: jax.Array, filt: jax.Array, *,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """([R,S,W] bank, [S,W] filter) -> (|row ∧ filt| uint32[R], |row|
    uint32[R]) in ONE pass over the bank (tanimoto needs both,
    fragment.go:1087-1093)."""
    from jax.experimental import pallas as pl

    R, S, W = bank.shape
    assert W == WORDS_PER_SHARD, bank.shape
    assert filt.shape == (S, W), (filt.shape, bank.shape)
    tiled = bank.reshape(R, S, _SUBLANES, _LANES)
    filt_t = filt.reshape(S, _SUBLANES, _LANES)
    inter, raw = pl.pallas_call(
        _masked_counts_kernel,
        grid=(R, S),
        in_specs=[
            pl.BlockSpec((1, 1, _SUBLANES, _LANES),
                         lambda r, s: (r, s, 0, 0)),
            pl.BlockSpec((1, _SUBLANES, _LANES), lambda r, s: (s, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _ACC_SUB, _LANES), lambda r, s: (r, 0, 0)),
            pl.BlockSpec((1, _ACC_SUB, _LANES), lambda r, s: (r, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, _ACC_SUB, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((R, _ACC_SUB, _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(tiled, filt_t)
    return (jnp.sum(inter, axis=(1, 2), dtype=jnp.int32).astype(jnp.uint32),
            jnp.sum(raw, axis=(1, 2), dtype=jnp.int32).astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsi_plane_counts(planes: jax.Array, mask: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """([D, S, W] bit-planes, [S, W] column mask) -> uint32[D] masked
    popcounts per plane — the O(bitDepth) loop of BSI Sum/Range
    (reference fragment.sum, fragment.go:767: per-bit IntersectionCount).
    The caller weights plane d by 2^d and handles sign/offset. Identical
    sweep shape to bank_row_counts_masked with planes as rows."""
    inter, _ = bank_row_counts_masked(planes, mask, interpret=interpret)
    return inter
