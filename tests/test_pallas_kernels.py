"""Pallas kernel correctness vs the jnp reference (interpret mode on CPU).

Mirrors the reference's container-kernel matrices
(/root/reference/roaring/roaring_internal_test.go) at the bank-sweep level:
same counts out of the Pallas path as out of the fused-jnp path for dense,
sparse, empty, and full operands.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pilosa_tpu.ops import pallas_kernels as pk  # noqa: E402
from pilosa_tpu.ops.bitset import WORDS_PER_SHARD, popcount  # noqa: E402


def _bank(rng, r, s, density):
    if density == 0:
        return np.zeros((r, s, WORDS_PER_SHARD), np.uint32)
    if density == 1:
        return np.full((r, s, WORDS_PER_SHARD), 0xFFFFFFFF, np.uint32)
    b = rng.integers(0, 2**32, (r, s, WORDS_PER_SHARD), dtype=np.uint32)
    if density < 0.5:
        b &= rng.integers(0, 2**32, b.shape, dtype=np.uint32)
    return b


@pytest.mark.parametrize("density", [0, 0.25, 0.5, 1])
def test_bank_row_counts_matches_jnp(density):
    rng = np.random.default_rng(3)
    bank = _bank(rng, 4, 2, density)
    got = np.asarray(pk.bank_row_counts(jnp.asarray(bank), interpret=True))
    want = np.asarray(popcount(jnp.asarray(bank), axis=(-2, -1)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("density", [0, 0.5, 1])
def test_bank_row_counts_masked_matches_jnp(density):
    rng = np.random.default_rng(4)
    bank = _bank(rng, 3, 2, 0.5)
    filt = _bank(rng, 1, 2, density)[0]
    gi, gr = pk.bank_row_counts_masked(jnp.asarray(bank), jnp.asarray(filt),
                                       interpret=True)
    wi = np.asarray(popcount(jnp.asarray(bank & filt), axis=(-2, -1)))
    wr = np.asarray(popcount(jnp.asarray(bank), axis=(-2, -1)))
    np.testing.assert_array_equal(np.asarray(gi), wi)
    np.testing.assert_array_equal(np.asarray(gr), wr)


def test_bsi_plane_counts_matches_jnp():
    rng = np.random.default_rng(5)
    planes = _bank(rng, 5, 2, 0.5)
    mask = _bank(rng, 1, 2, 0.5)[0]
    got = np.asarray(pk.bsi_plane_counts(jnp.asarray(planes),
                                         jnp.asarray(mask), interpret=True))
    want = np.asarray(popcount(jnp.asarray(planes & mask), axis=(-2, -1)))
    np.testing.assert_array_equal(got, want)


def test_swar_popcount_exhaustive_words():
    words = np.array([0, 1, 0xFFFFFFFF, 0x80000000, 0xAAAAAAAA, 0x55555555,
                      0x12345678, 0xDEADBEEF], np.uint32)
    tile = np.zeros((8, 128), np.uint32)
    tile[: len(words), 0] = words
    got = np.asarray(pk._popcount32(jnp.asarray(tile)))[: len(words), 0]
    want = np.array([bin(int(w)).count("1") for w in words], np.uint32)
    np.testing.assert_array_equal(got, want)


def test_executor_pallas_path_topn(tmp_path, monkeypatch):
    """End-to-end: TopN through the executor with the Pallas sweep forced
    on (interpret lowering is exercised separately; here we only verify the
    dispatch plumbing keeps results identical)."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    holder = Holder(str(tmp_path))
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    cols = np.arange(0, 5000, 7, dtype=np.uint64)
    f.import_bits(np.arange(len(cols), dtype=np.uint64) % 5, cols)
    (want,) = Executor(holder).execute("i", "TopN(f, n=3)")

    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    if pk.available():
        (got,) = Executor(holder).execute("i", "TopN(f, n=3)")
        assert got.pairs == want.pairs
    holder.close()


def test_pbank_search_membership_matches_compare(tmp_path, monkeypatch):
    """The searchsorted membership form answers identically to the
    compare form through the full executor tanimoto path."""
    import os
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import executor as executor_mod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    def build(d):
        h = Holder(d)
        h.open()
        idx = h.create_index("m")
        f = idx.create_field("fp", FieldOptions(max_columns=512))
        view = f.create_view_if_not_exists("standard")
        frag = view.create_fragment_if_not_exists(0)
        rng = np.random.default_rng(9)
        cpr = SHARD_WIDTH // 65536
        for i in range(3000):
            frag.storage.containers[i * cpr] = np.unique(
                rng.integers(0, 512, 24, dtype=np.uint16))
            frag._touch_row(i)
        return h

    monkeypatch.setattr(executor_mod, "TOPN_MAX_BANK_BYTES", 1)
    q = ("TopN(fp, Row(fp=7), n=20, tanimotoThreshold=30)")
    # Pin the baseline to "compare": the module default is "auto",
    # which resolves to "search" on the CPU test mesh — without the
    # pin this test would compare search against itself.
    monkeypatch.setattr(executor_mod, "PBANK_MEMBERSHIP", "compare")
    h1 = build(str(tmp_path / "a"))
    (want,) = Executor(h1).execute("m", q)
    h1.close()
    monkeypatch.setattr(executor_mod, "PBANK_MEMBERSHIP", "search")
    h2 = build(str(tmp_path / "b"))
    (got,) = Executor(h2).execute("m", q)
    h2.close()
    assert got.pairs == want.pairs and want.pairs


def test_pbank_membership_auto_resolves_per_backend(tmp_path,
                                                    monkeypatch):
    """'auto' (the default) must resolve to 'search' on the XLA CPU
    backend and be cached under the RESOLVED name, so
    an explicit-'search' run shares the same compiled kernel."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.executor import executor as executor_mod
    from pilosa_tpu.ops.bitset import SHARD_WIDTH

    assert jax.devices()[0].platform == "cpu"  # test mesh is CPU-forced
    monkeypatch.setattr(executor_mod, "PBANK_MEMBERSHIP", "auto")
    monkeypatch.setattr(executor_mod, "TOPN_MAX_BANK_BYTES", 1)
    monkeypatch.setattr(executor_mod.Executor, "_PBANK_KERNELS", {})
    h = Holder(str(tmp_path / "auto"))
    h.open()
    idx = h.create_index("m")
    f = idx.create_field("fp", FieldOptions(max_columns=512))
    view = f.create_view_if_not_exists("standard")
    frag = view.create_fragment_if_not_exists(0)
    rng = np.random.default_rng(11)
    cpr = SHARD_WIDTH // 65536
    for i in range(512):
        frag.storage.containers[i * cpr] = np.unique(
            rng.integers(0, 512, 24, dtype=np.uint16))
        frag._touch_row(i)
    (res,) = Executor(h).execute(
        "m", "TopN(fp, Row(fp=3), n=5, tanimotoThreshold=20)")
    h.close()
    assert res.pairs
    forms = {key[3] for key in executor_mod.Executor._PBANK_KERNELS}
    assert "search" in forms
    assert "auto" not in forms
