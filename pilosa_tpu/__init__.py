"""pilosa_tpu — a TPU-native distributed bitmap index.

A from-scratch rebuild of the capabilities of Pilosa (reference:
Crixalis2013/pilosa, a distributed in-memory roaring-bitmap index) designed
TPU-first:

- Shard-resident rows are packed uint32 bitsets in HBM (2^20 bits/shard).
- The reference's per-container Go kernels (roaring/roaring.go:2313-3607)
  collapse into fused XLA bitwise + popcount ops over dense words.
- Per-shard query evaluation is batched per chip (shards as a leading array
  axis) instead of goroutine-per-shard (executor.go:2377).
- Cross-shard reduction rides ICI collectives under jax.shard_map instead of
  HTTP scatter-gather (executor.go:2277).
- Sparse/run encodings (roaring containers) remain a host/storage concern:
  durability uses the reference's roaring file format (cookie 12348).
"""

__version__ = "0.1.0"

_BITSET_EXPORTS = ("SHARD_WIDTH", "WORDS_PER_SHARD", "WORD_BITS")


def __getattr__(name):
    # Resolved on first use, not at import: ops.bitset imports jax, and
    # the storage codec (pilosa_tpu.storage) must stay importable by a
    # process that is not allowed to load it (chip_smoke.py's parent).
    if name in _BITSET_EXPORTS:
        from pilosa_tpu.ops import bitset
        return getattr(bitset, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
