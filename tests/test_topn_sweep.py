"""The TopN bank sweep: two one-filter programs selected by what the call
carries (`topn_sweep`, `topn_sweep_unfiltered`; a tanimoto call launches
the first, and the second once a bank version for the rows' own
popcounts), answers against a plain numpy recomputation on every path that reaches
`Executor._dispatch_counts`, and structural guards on what each program
computes and fetches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.view import ViewBank
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.ops.bitset import (SHARD_WIDTH, SWEEP_MAX_PIECES,
                                   WORDS_PER_SHARD, masked_row_counts,
                                   masked_row_counts_multi, popcount,
                                   sweep_filter_pieces)
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.server.api import API
from pilosa_tpu.utils.stats import MemStatsClient
from pilosa_tpu.utils.timeline import TIMELINE

N_SHARDS = 4
N_ROWS = 40
FILTER_ROW = 0


def _rows(width_cols: int) -> dict:
    """row id -> sorted unique columns over N_SHARDS shards, every column
    offset inside a shard below `width_cols`. Rows 1..9 are noisy copies
    of the filter row (so a tanimoto threshold keeps some and drops
    others); the rest are independent draws."""
    rng = np.random.default_rng(25)

    def draw(n):
        shard = rng.integers(0, N_SHARDS, n).astype(np.uint64)
        return np.unique(shard * np.uint64(SHARD_WIDTH)
                         + rng.integers(0, width_cols, n).astype(np.uint64))

    base = draw(400)
    rows = {FILTER_ROW: base}
    for r in range(1, 10):
        keep = base[rng.random(base.size) < 1.0 - 0.07 * r]
        rows[r] = np.unique(np.concatenate([keep, draw(12 * r)]))
    for r in range(10, N_ROWS):
        rows[r] = draw(int(rng.integers(20, 500)))
    return rows


def _reference(rows: dict, n: int, tanimoto: int) -> list:
    """What TopN(f, Row(f=FILTER_ROW), n, tanimotoThreshold) must return."""
    filt = rows[FILTER_ROW]
    pairs = []
    for r, cols in rows.items():
        inter = np.intersect1d(cols, filt).size
        if tanimoto:
            denom = cols.size + filt.size - inter
            if inter * 100 <= tanimoto * denom:    # upstream: == T is out
                continue
        if inter > 0:
            pairs.append((r, inter))
    pairs.sort(key=lambda p: (-p[1], p[0]))
    return pairs[:n]


@pytest.fixture(scope="module", params=["full_width", "trimmed"])
def sweep_holder(request, tmp_path_factory):
    h = Holder(str(tmp_path_factory.mktemp(f"sweep_{request.param}")))
    h.open()
    f = h.create_index("i").create_field("f")
    # Column offsets up to the whole shard, or inside its first container:
    # the bank is then trimmed to 2048 of the shard's 32768 words.
    rows = _rows(SHARD_WIDTH if request.param == "full_width" else 50_000)
    f.import_bits(
        np.concatenate([np.full(c.size, r, np.uint64)
                        for r, c in rows.items()]),
        np.concatenate(list(rows.values())))
    width = f.view().trimmed_words()
    assert width == (WORDS_PER_SHARD if request.param == "full_width"
                     else 2048)
    yield h, rows, width
    h.close()


@pytest.fixture(scope="module")
def mesh4():
    return MeshContext(jax.devices()[:4])


@pytest.mark.parametrize("stream", ["resident_bank", "chunked_stream"])
@pytest.mark.parametrize("placement", ["single_device", "mesh"])
@pytest.mark.parametrize("tanimoto", [0, 60])
def test_filtered_sweep_matches_numpy(sweep_holder, mesh4, monkeypatch,
                                      tanimoto, placement, stream):
    h, rows, width = sweep_holder
    if stream == "chunked_stream":
        monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
        monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", 16)
    mesh = mesh4 if placement == "mesh" else None
    ex = Executor(h, mesh=mesh)
    q = f"TopN(f, Row(f={FILTER_ROW}), n=12" + (
        f", tanimotoThreshold={tanimoto})" if tanimoto else ")")
    if mesh is not None:
        with mesh.mesh:
            (res,) = ex.execute("i", q)
    else:
        (res,) = ex.execute("i", q)
    want = _reference(rows, 12, tanimoto)
    assert res.pairs == want
    assert 3 <= len(want) and (not tanimoto or len(want) < 12)
    # The programs that ran are the ones the call's arguments select, at
    # the bank's (trimmed) width, once per chunk shape: `topn_sweep`, and
    # under tanimoto the unfiltered sweep that computes a bank's |row|.
    programs = {"topn_sweep"} | (
        {"topn_sweep_unfiltered"} if tanimoto else set())
    with ex._jit_cache_lock:
        keys = [k for k, fn in ex._jit_cache.items()
                if k.startswith("topn:")]
        names = {ex._jit_cache[k].__name__ for k in keys}
    assert names == programs, keys
    assert all(f", {width})" in k for k in keys), keys
    assert len(keys) == len(programs) * (
        1 if stream == "resident_bank" else 2), keys


def _popcnts(fn, *args) -> int:
    return fn.lower(*args).as_text().count("stablehlo.popcnt")


def _n_results(fn, *args) -> int:
    return len(jax.tree_util.tree_leaves(jax.eval_shape(fn, *args)))


def test_each_sweep_program_computes_only_what_its_query_reads(tmp_holder):
    """The rows' own popcounts (tanimoto's denominator) are a second
    popcount + reduction over the whole bank: no filtered sweep carries
    them. A tanimoto call launches `topn_sweep`, and
    `topn_sweep_unfiltered` once a bank version."""
    ex = Executor(tmp_holder)
    bank = jnp.zeros((8, 2, 64), jnp.uint32)
    filt = jnp.zeros((2, 64), jnp.uint32)
    assert sweep_filter_pieces(64) == 1
    sweep = ex._counts_fn(True, bank.shape)
    assert (_popcnts(sweep, bank, filt), _n_results(sweep, bank, filt)) \
        == (1, 1)
    launched = []
    ex._call_program = lambda fn, *args: launched.append(fn.__name__) \
        or fn(*args)
    vbank = ViewBank(bank, {}, 7, {})
    for _ in range(3):                       # three tanimoto calls' worth
        ex._dispatch_counts(vbank.array, filt)
        ex._bank_popcounts(vbank)
    assert launched == ["topn_sweep", "topn_sweep_unfiltered",
                        "topn_sweep", "topn_sweep"]
    unf = ex._counts_fn(False, bank.shape)
    assert (_popcnts(unf, bank, None), _n_results(unf, bank, None)) \
        == (1, 1)
    # A wide filter is cut into word-axis pieces: one popcount per piece
    # and output, still one pass and the same number of results.
    wide_bank = jnp.zeros((8, 16, 1024), jnp.uint32)
    wide_filt = jnp.zeros((16, 1024), jnp.uint32)
    k = sweep_filter_pieces(1024)
    assert k == 2
    wide = ex._counts_fn(True, wide_bank.shape)
    assert (_popcnts(wide, wide_bank, wide_filt),
            _n_results(wide, wide_bank, wide_filt)) == (k, 1)
    wide_u = ex._counts_fn(False, wide_bank.shape)
    assert (_popcnts(wide_u, wide_bank, None),
            _n_results(wide_u, wide_bank, None)) == (1, 1)


@pytest.mark.parametrize("n_words,pieces,left_over_lanes", [
    (32768, 4, 0),  # a whole shard, the benchmark cell's: 4 x 8192 words
    (16384, 2, 0),
    (12288, 2, 0),  # under two whole pieces: halves
    (8192, 2, 0), (2048, 2, 0), (256, 2, 0),
    (384, 3, 0),    # three lanes do not halve into whole lanes
    (32640, 3, 0),  # 255 lanes (max_columns): thirds, not quarters
    (9472, 2, 0),   # 74 lanes = 2 x 37: halves, not 37 pieces
    (4480, 2, 1),   # 35 lanes = 5 x 7: halves of 17 and the odd lane
    (896, 2, 1),    # 7 lanes, not 7 pieces
    (32128, 2, 1),  # 251 lanes, a prime: halves of 125 + one lane
    (4736, 2, 1),   # 37 lanes (max_columns=150000)
    (128, 1, 0),    # one lane: nothing to cut
    (200, 1, 0),    # not whole lanes: left whole
])
def test_sweep_filter_pieces(n_words, pieces, left_over_lanes):
    assert sweep_filter_pieces(n_words) == pieces
    assert n_words // 128 % pieces == left_over_lanes


def test_sweep_filter_pieces_bounds_the_pieces_at_every_width():
    """Any multiple of 128 words can be a bank's width under max_columns:
    none may be cut into more than SWEEP_MAX_PIECES and one short piece
    (never a piece per lane), and only a single lane is left uncut."""
    for n_words in range(128, WORDS_PER_SHARD + 1, 128):
        k = sweep_filter_pieces(n_words)
        assert k <= SWEEP_MAX_PIECES
        assert (k == 1) == (n_words == 128)
        left_over = n_words // 128 % k
        assert left_over == 0 or (k, left_over) == (2, 1), n_words


def _words(rng, shape, density) -> np.ndarray:
    """uint32 words with about `density` of their bits set; 0 and 1 are
    the empty and the full operand."""
    if density in (0, 1):
        return np.full(shape, 0xFFFFFFFF * density, np.uint32)
    w = rng.integers(0, 2**32, shape, dtype=np.uint32)
    if density < 0.5:
        w &= rng.integers(0, 2**32, shape, dtype=np.uint32)
    return w


_SHAPES = [(5, 3, 256), (7, 16, 1024), (4, 1, 128), (3, 2, 200),
           (2, 4, 384), (3, 2, 896), (2, 1, 1408), (2, 3, 640)]
# Every shape at a uniform draw, and the edge densities (both operands
# empty, sparse, full) at one wide (cut in pieces) and one narrow shape.
_DRAWS = [(shape, 0.5) for shape in _SHAPES] + \
    [(shape, d) for shape in [(7, 16, 1024), (4, 1, 128)]
     for d in (0, 0.25, 1)]


@pytest.mark.parametrize("shape,density", _DRAWS)
@pytest.mark.parametrize("body", ["filtered", "rows_own"])
def test_masked_row_counts_matches_numpy(shape, density, body):
    """The two vectors a tanimoto answer reads, each from its own
    program's body: |row ∧ filter| (`masked_row_counts`) and |row|
    (`popcount` over the shard and word axes, what the bank keeps)."""
    rng = np.random.default_rng(sum(shape))
    bank = _words(rng, shape, density)
    filt = _words(rng, shape[1:], density)
    if body == "filtered":
        got = masked_row_counts(jnp.asarray(bank), jnp.asarray(filt))
        want = np.bitwise_count(bank & filt).sum(axis=(1, 2))
    else:
        got = popcount(jnp.asarray(bank), axis=(-2, -1))
        want = np.bitwise_count(bank).sum(axis=(1, 2))
    assert got.dtype == jnp.uint32 and got.shape == shape[:1]
    assert np.asarray(got).tolist() == want.tolist()


@pytest.mark.parametrize("n_words,by_filters", [
    (32768, {1: 4, 2: 2, 3: 2, 4: 2, 8: 2}),  # the cell's: outputs <= 4..
    (16384, {1: 2, 2: 2, 4: 2}),              # ..and never under 2 pieces
    (32640, {1: 3, 2: 2, 4: 2}),  # 255 lanes: thirds alone, else halves
    (384, {1: 3, 2: 2, 4: 2}),    # of 127 (of 1) and the odd lane
    (4480, {1: 2, 2: 2, 4: 2}),               # halves + the odd lane
    (128, {1: 1, 2: 1, 4: 1}), (200, {1: 1, 4: 1}),
])
def test_sweep_filter_pieces_of_a_multi_filter_pass(n_words, by_filters):
    """A pass for K filters has K outputs a piece, all reduced in every
    window step: it takes fewer pieces, the same rule otherwise."""
    for filters, pieces in by_filters.items():
        assert sweep_filter_pieces(n_words, filters) == pieces, filters
        assert pieces <= SWEEP_MAX_PIECES


# Wide (cut in pieces), one lane (uncut), an odd lane left over (896: 7
# lanes, 1408: 11), three lanes, and not whole lanes at all.
_MULTI_SHAPES = [(7, 16, 1024), (4, 1, 128), (3, 2, 896), (2, 1, 1408),
                 (2, 4, 384), (3, 2, 200)]


@pytest.mark.parametrize("shape", _MULTI_SHAPES)
@pytest.mark.parametrize("k", [2, 3, 4, 8])
def test_masked_row_counts_multi_lanes_match_the_one_filter_body(shape, k):
    """Lane j of the K-filter pass is `masked_row_counts` of filter j bit
    for bit, and numpy's, with an empty, a sparse and a full filter
    among the K."""
    rng = np.random.default_rng(sum(shape) + k)
    bank = _words(rng, shape, 0.5)
    densities = ([0, 0.25, 1] + [0.5] * k)[:k]
    filts = [_words(rng, shape[1:], d) for d in densities]
    got = masked_row_counts_multi(jnp.asarray(bank),
                                  *map(jnp.asarray, filts))
    assert got.dtype == jnp.uint32 and got.shape == (k, shape[0])
    for j, f in enumerate(filts):
        one = masked_row_counts(jnp.asarray(bank), jnp.asarray(f))
        assert np.asarray(got[j]).tolist() == np.asarray(one).tolist()
        assert np.asarray(got[j]).tolist() == \
            np.bitwise_count(bank & f).sum(axis=(1, 2)).tolist()


def test_multi_sweep_program_reads_each_piece_once_per_filter(tmp_holder):
    """`topn_sweep_multi` as the executor builds it: K x pieces popcounts
    over one bank operand, one [K, R] result."""
    ex = Executor(tmp_holder)
    bank = jnp.zeros((8, 16, 1024), jnp.uint32)
    filt = jnp.zeros((16, 1024), jnp.uint32)
    for k in (2, 4):
        fn = ex._counts_multi_fn(bank, filt, k)
        assert fn.__name__ == "topn_sweep_multi"
        args = (bank,) + (filt,) * k
        assert _popcnts(fn, *args) == k * sweep_filter_pieces(1024, k)
        assert _n_results(fn, *args) == 1
        assert jax.eval_shape(fn, *args).shape == (k, 8)


@pytest.mark.parametrize("density", [0, 0.25, 0.5, 1])
def test_unfiltered_sweep_matches_numpy(tmp_holder, density):
    """`topn_sweep_unfiltered` as the executor builds it, against numpy,
    on empty, sparse, uniform and full banks."""
    shape = (7, 16, 1024)
    bank = _words(np.random.default_rng(3), shape, density)
    got = Executor(tmp_holder)._counts_fn(False, shape)(
        jnp.asarray(bank), None)
    assert got.dtype == jnp.uint32
    assert np.asarray(got).tolist() == \
        np.bitwise_count(bank).sum(axis=(1, 2)).tolist()


@pytest.mark.parametrize("tanimoto,answers", [
    # (sweep programs launched, [R] vectors fetched) per answer in turn
    (0, [(["topn_sweep"], 1), (["topn_sweep"], 1)]),
    # The first tanimoto answer of a bank version also sweeps and
    # fetches the rows' own popcounts; the second finds them kept.
    (30, [(["topn_sweep", "topn_sweep_unfiltered"], 2),
          (["topn_sweep"], 1)]),
])
def test_sweep_fetches_one_vector_and_a_bank_versions_popcounts_once(
        tmp_holder, tanimoto, answers):
    """`dispatch program=` names which sweeps ran and the answer's `d2h`
    is `slots x 4` bytes per vector the finalize reads: counts; under
    tanimotoThreshold the filter's own popcount (one uint32) and, on the
    first answer of a bank version only, the bank's popcounts."""
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=64, sample_every=1)
    try:
        idx = tmp_holder.create_index("tl")
        cols = np.array([1, 2, SHARD_WIDTH + 3, SHARD_WIDTH + 9], np.uint64)
        f = idx.create_field("f")
        f.import_bits(np.array([1, 1, 1, 2], np.uint64), cols)
        api = API(tmp_holder, stats=MemStatsClient())
        api.executor.result_cache.enabled = False
        q = "TopN(f, Row(f=1), n=2" + (
            f", tanimotoThreshold={tanimoto})" if tanimoto else ")")
        slots = f.view().device_bank((0, 1), trim=True).array.shape[0]
        for programs, vectors in answers:
            assert api.query("tl", q)["results"][0] == [
                {"id": 1, "count": 3}]
            spans = list(TIMELINE.requests()[-1].root.walk())
            assert [s.attrs["program"] for s in spans
                    if s.name == "dispatch"
                    and s.attrs["program"].startswith("topn_sweep")] \
                == programs
            (d2h,) = [s for s in spans if s.name == "d2h"]
            assert d2h.attrs["bytes"] == vectors * slots * 4 + (
                4 if tanimoto else 0)
            assert d2h.attrs["transfers"] == vectors + (
                1 if tanimoto else 0)
    finally:
        TIMELINE.reset()
        TIMELINE.configure(enabled=True, ring=256, sample_every=1)


def test_a_dropped_sweepers_vector_is_a_d2h_of_the_call_that_fetches_it(
        tmp_holder):
    """The call that swept a bank's popcounts never reached its finalize:
    the next answer's own `d2h` is its counts and the filter's popcount,
    and the pending vector crosses as a second `d2h`, inside `finish`."""
    TIMELINE.reset()
    TIMELINE.configure(enabled=True, ring=64, sample_every=1)
    try:
        idx = tmp_holder.create_index("tl")
        f = idx.create_field("f")
        f.import_bits(np.array([1, 1, 1, 2], np.uint64),
                      np.array([1, 2, 3, 9], np.uint64))
        api = API(tmp_holder, stats=MemStatsClient())
        api.executor.result_cache.enabled = False
        bank = f.view().device_bank((0,), trim=True)
        slots = bank.array.shape[0]
        assert api.executor._bank_popcounts(bank)[1]
        for vectors in ([slots * 4 + 4, slots * 4], [slots * 4 + 4]):
            assert api.query("tl", "TopN(f, Row(f=1), n=2, "
                             "tanimotoThreshold=30)")["results"][0] == [
                {"id": 1, "count": 3}]
            spans = list(TIMELINE.requests()[-1].root.walk())
            assert [s.attrs["bytes"] for s in spans
                    if s.name == "d2h"] == vectors
    finally:
        TIMELINE.reset()
        TIMELINE.configure(enabled=True, ring=256, sample_every=1)


def test_pbank_search_membership_matches_compare(tmp_path, monkeypatch):
    """The searchsorted membership form answers identically to the
    compare form through the full executor tanimoto path."""
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

    monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
    q = ("TopN(fp, Row(fp=7), n=20, tanimotoThreshold=30)")
    # Pin the baseline to "compare": the module default is "auto",
    # which resolves to "search" on the CPU test mesh — without the
    # pin this test would compare search against itself.
    monkeypatch.setattr(ex_mod, "PBANK_MEMBERSHIP", "compare")
    h1 = build(str(tmp_path / "a"))
    (want,) = Executor(h1).execute("m", q)
    h1.close()
    monkeypatch.setattr(ex_mod, "PBANK_MEMBERSHIP", "search")
    h2 = build(str(tmp_path / "b"))
    (got,) = Executor(h2).execute("m", q)
    h2.close()
    assert got.pairs == want.pairs and want.pairs


def test_pbank_membership_auto_resolves_per_backend(tmp_path,
                                                    monkeypatch):
    """'auto' (the default) must resolve to 'search' on the XLA CPU
    backend and be cached under the RESOLVED name, so
    an explicit-'search' run shares the same compiled kernel."""
    assert jax.devices()[0].platform == "cpu"  # test mesh is CPU-forced
    monkeypatch.setattr(ex_mod, "PBANK_MEMBERSHIP", "auto")
    monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
    monkeypatch.setattr(ex_mod.Executor, "_PBANK_KERNELS", {})
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
    forms = {key[3] for key in ex_mod.Executor._PBANK_KERNELS}
    assert "search" in forms
    assert "auto" not in forms
