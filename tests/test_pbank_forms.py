"""How a positions segment counts a row's matches (PR 41): the
membership compare is as wide as the bank's widest row (a compile key,
in fan-outs of at most `PBANK_COMPARE_CHUNK` slots), a filter with
more on-bits than that takes the table gather, and a segment is laid
out fixed (`[L, rows]` slot-major, an add over L planes) at a library's density
unless the budget refuses the padded bank — every answer equal, pair
for pair and in order, to a plain numpy recomputation. The compare is
forced where it is what is tested: on the CPU backend `auto` resolves
to `search`."""

import numpy as np
import pytest

from pilosa_tpu.core import view as view_mod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.utils.stats import MemStatsClient

COLUMNS = 4096
FORM = "executor.pbank_form{form:%s}"


class Library:
    """`lengths[i]` distinct on-bits in row i of field `fp`, drawn with
    a few popular columns so that rows resemble each other, behind an
    executor that counts; `bits` is the same as a bool matrix."""

    def __init__(self, path, lengths, seed=41):
        rng = np.random.default_rng(seed)
        popular = 1.0 / (np.arange(COLUMNS) + 12.0)
        popular /= popular.sum()
        self.bits = np.zeros((len(lengths), COLUMNS), bool)
        for r, n in enumerate(lengths):
            self.bits[r, rng.choice(COLUMNS, int(n), replace=False,
                                    p=popular)] = True
        self.holder = Holder(str(path))
        self.holder.open()
        self.index = self.holder.create_index("lib")
        self.field = self.index.create_field(
            "fp", FieldOptions(max_columns=COLUMNS, cache_type="none"))
        rows, cols = np.nonzero(self.bits)
        self.field.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))
        self.ex = Executor(self.holder)
        self.ex.stats = MemStatsClient()

    def close(self):
        self.holder.close()

    def bank(self):
        view = self.field.view()
        return view.positions_bank(0, view.trimmed_words())

    def counters(self):
        return dict(self.ex.stats.snapshot()["counters"])

    def ask(self, pql):
        (res,) = self.ex.execute("lib", pql)
        return [(int(r), int(c)) for r, c in res.pairs]

    def want(self, filt, n, tanimoto=0):
        """TopN over every row against the bool vector `filt`, by the
        dense rule: count >= 1, `count * 100 > T * |row ∪ filter|`,
        ties by ascending row."""
        c = (self.bits & filt).sum(1)
        keep = c >= 1
        if tanimoto:
            keep &= c * 100 > tanimoto * (self.bits.sum(1) + filt.sum() - c)
        rows = sorted(np.flatnonzero(keep), key=lambda r: (-c[r], r))
        return [(int(r), int(c[r])) for r in rows][:n or None]


def _lengths(widest, rows=260, seed=5):
    """A library's shape in small: on-bit counts around 48, two rows of
    exactly `widest` among them (rows 3 and 200) and, where it is wider
    than that, rows of 64 and 65 (5 and 6): where the compare is two
    fan-outs of 64, the last filter the first holds alone and the first
    that spills into the second."""
    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.normal(48, 12, rows).astype(int), 8,
                      min(widest, 100))
    lengths[[3, 200]] = widest
    if widest > 65:
        lengths[[5, 6]] = 64, 65
    return lengths


@pytest.fixture
def forced(monkeypatch):
    """Past the resident limit, the compare membership, fresh kernels,
    and segments small enough that every bank has a few."""
    monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
    monkeypatch.setattr(ex_mod, "PBANK_MEMBERSHIP", "compare")
    monkeypatch.setattr(ex_mod.Executor, "_PBANK_KERNELS", {})
    monkeypatch.setattr(view_mod, "PBANK_SEGMENT_POSITIONS", 4096)
    return monkeypatch


def _layouts(pb):
    return {"fixed" if pos.ndim == 2 else "flat"
            for _, _, pos, _, _ in pb.segments}


def _flat(monkeypatch):
    """A budget whose half no padded bank fits: every segment flat."""
    monkeypatch.setattr(view_mod.BANK_BUDGET, "budget", 1 << 10)


QUERIES = ((7, 0), (7, 10), (0, 10), (0, 5))    # (n, tanimotoThreshold)


def _pql(filter_pql, n, tanimoto):
    args = [a for a in (f"n={n}" if n else "",
                        f"tanimotoThreshold={tanimoto}" if tanimoto else "")
            if a]
    return f"TopN(fp, {filter_pql}, {', '.join(args)})"


@pytest.mark.parametrize("widest, qslots", [(39, 40), (103, 104), (113, 120),
                                            (128, 128), (200, 128)])
def test_the_compare_is_as_wide_as_the_banks_widest_row(
        tmp_path, forced, widest, qslots):
    """The width follows what the build observed — the longest row over
    all segments, in steps of 8, capped — and is a compile key: a write
    that lengthens the longest row past a step compiles another
    program, one inside the step compiles none."""
    lengths = np.full(90, 20)
    lengths[17] = widest
    lib = Library(tmp_path / "h", lengths)
    pb = lib.bank()
    assert max(pb.row_widths) == widest
    assert len(pb.row_widths) == len(pb.segments)
    # 89 rows of 20 on-bits and one of `widest`: the one sets the width.
    assert pb.qslots == -(-widest // 8) * 8
    assert min(pb.qslots, ex_mod.PBANK_SPARSE_FILTER_BITS) == qslots
    assert lib.ask("TopN(fp, Row(fp=17), n=5)") \
        == lib.want(lib.bits[17], 5)
    keys = set(ex_mod.Executor._PBANK_KERNELS)
    assert {key[6] for key in keys} == {qslots}
    if widest == 39:
        # One more bit: still 40 slots, no new program.
        free = np.flatnonzero(~lib.bits[17])
        lib.field.set_bit(17, int(free[0]))
        lib.bits[17, free[0]] = True
        assert lib.ask("TopN(fp, Row(fp=17), n=5)") \
            == lib.want(lib.bits[17], 5)
        assert {key[6] for key in ex_mod.Executor._PBANK_KERNELS} \
            == {40}
        # Eight more: the bank's width is 48 now.
        for col in free[1:9].tolist():
            lib.field.set_bit(17, col)
            lib.bits[17, col] = True
        assert lib.bank().qslots == 48
        assert lib.ask("TopN(fp, Row(fp=17), n=5)") \
            == lib.want(lib.bits[17], 5)
        assert {key[6] for key in ex_mod.Executor._PBANK_KERNELS} \
            == {40, 48}
    lib.close()


@pytest.mark.parametrize("filt", ["widest_row", "common_row", "row_of_64",
                                  "row_of_65", "union"])
@pytest.mark.parametrize("layout", ["fixed", "flat"])
@pytest.mark.parametrize("widest", [103, 120, 128])
def test_every_width_layout_and_filter_answers_as_numpy(
        tmp_path, forced, widest, layout, filt):
    """A bank whose widest row needs one fan-out (103 -> 104 slots) or
    two (113-128), in both layouts: its widest row, a common row and
    the rows on either side of a 64-slot fan-out's edge as the filter
    take the compare, the Union of its two widest rows has
    more on-bits than the bank's width and takes the gather — with `n`,
    with a tanimoto threshold, and without `n`."""
    if layout == "flat":
        _flat(forced)
    lib = Library(tmp_path / "h", _lengths(widest))
    pb = lib.bank()
    assert _layouts(pb) == {layout} and len(pb.segments) >= 3
    assert pb.qslots == -(-widest // 8) * 8
    pql, vec = {
        "widest_row": ("Row(fp=3)", lib.bits[3]),
        "common_row": ("Row(fp=11)", lib.bits[11]),
        "row_of_64": ("Row(fp=5)", lib.bits[5]),
        "row_of_65": ("Row(fp=6)", lib.bits[6]),
        "union": ("Union(Row(fp=3), Row(fp=200))",
                  lib.bits[3] | lib.bits[200]),
    }[filt]
    before = lib.counters()
    launches = 0
    for n, tanimoto in QUERIES:
        got = lib.ask(_pql(pql, n, tanimoto))
        assert got == lib.want(vec, n, tanimoto), (n, tanimoto)
        assert got
        launches += len(pb.segments)
    after = lib.counters()
    # One form a launch: the cond's own word, fetched with the rows.
    took = "gather" if filt == "union" else "compare"
    other = "compare" if filt == "union" else "gather"
    assert vec.sum() > pb.qslots if filt == "union" \
        else vec.sum() <= pb.qslots
    assert after.get(FORM % took, 0) - before.get(FORM % took, 0) \
        == launches
    assert after.get(FORM % other, 0) == before.get(FORM % other, 0)
    assert after["executor.pbank_launches"] \
        - before.get("executor.pbank_launches", 0) == launches
    lib.close()


def test_wide_banks_compare_in_fan_outs_under_the_cliff(tmp_path, forced):
    """No fan-out is wider than PBANK_COMPARE_CHUNK slots, whatever the
    bank's widest row: 128 slots are two of 64."""
    import jax

    lib = Library(tmp_path / "h", _lengths(128))
    pb = lib.bank()
    _, _, pos, aux, _ = pb.segments[0]
    assert pb.qslots == 128
    kern = ex_mod.Executor._pbank_kernel(
        5, True, fixed=pos.ndim == 2, width=128, qslots=pb.qslots)
    fw = np.zeros((1, 128), np.uint32)
    text = str(jax.make_jaxpr(kern)(
        fw, pos, aux, np.zeros(2, np.uint32), None))
    fans = {int(dim) for shape in _shapes_of(text, "eq") for dim in shape[-1:]}
    assert fans == {64}
    lib.close()


def _shapes_of(jaxpr_text, primitive):
    """Output shapes of every `primitive` equation in a jaxpr's text."""
    import re
    for m in re.finditer(r":bool\[([0-9,]+)\] = %s " % primitive,
                         jaxpr_text):
        yield tuple(m.group(1).split(","))


@pytest.mark.parametrize("membership", ["compare", "search"])
@pytest.mark.parametrize("layout", ["fixed", "flat"])
def test_a_filter_bit_at_the_last_word_matches_no_pad(
        tmp_path, forced, layout, membership):
    """A filter row from a wider field sets bit 65535, the value of the
    fixed layout's 0xFFFF row pads and of the flat buffer's tail: the
    filter is cut to the bank's width first, so it matches nothing."""
    forced.setattr(ex_mod, "PBANK_MEMBERSHIP", membership)
    if layout == "flat":
        _flat(forced)
    lib = Library(tmp_path / "h", _lengths(60, rows=220))
    wide = lib.index.create_field("wide", FieldOptions(cache_type="none"))
    cols = np.concatenate([np.flatnonzero(lib.bits[5])[:9], [65535]])
    wide.import_bits(np.zeros(len(cols), np.uint64), cols.astype(np.uint64))
    assert _layouts(lib.bank()) == {layout}
    vec = np.zeros(COLUMNS, bool)
    vec[cols[:-1]] = True
    assert lib.ask("TopN(fp, Row(wide=0), n=10)") == lib.want(vec, 10)
    assert lib.ask("TopN(fp, Row(wide=0))")[:3] == lib.want(vec, 3)
    lib.close()


def test_a_library_dense_segment_is_fixed_unless_the_budget_refuses(
        tmp_path, forced):
    """Rows of 48 +- 12 on-bits under a longest of 103 fill 0.4-0.47 of
    the padded matrix: laid out fixed, priced at the tiles the
    device stores; the same rows under a budget whose half the
    padded bank does not fit stay flat, 2 B a position; both give the
    same pairs in the same order."""
    lengths = _lengths(103, rows=400, seed=9)
    lengths[::40] = 103     # a longest row in every segment, as at scale
    lib = Library(tmp_path / "a", lengths)
    pb = lib.bank()
    assert _layouts(pb) == {"fixed"}
    for (_, n, pos, lens, p), longest in zip(pb.segments, pb.row_widths):
        assert pos.shape == (-(-longest // 8) * 8, lens.shape[0])
        assert 0.35 <= p / (n * pos.shape[0]) <= 0.5
        assert int(np.asarray(lens).sum()) == p
    # 16 slots x 128 rows of u16 a tile: 104 slots are stored as 112.
    assert pb.nbytes == sum(112 * pos.shape[1] * 2 + aux.shape[0] * 4
                            for _, _, pos, aux, _ in pb.segments)
    assert all(pos.shape[1] % 128 == 0 for _, _, pos, _, _ in pb.segments)
    queries = [_pql(f"Row(fp={m})", n, t)
               for m in (3, 57, 311) for n, t in QUERIES]
    fixed = [lib.ask(q) for q in queries]
    lib.close()

    assert view_mod.pbank_fixed_fits(len(lengths))
    _flat(forced)
    assert not view_mod.pbank_fixed_fits(len(lengths))
    lib = Library(tmp_path / "b", lengths)
    assert _layouts(lib.bank()) == {"flat"}
    assert [lib.ask(q) for q in queries] == fixed
    for q, got in zip(queries[:4], fixed):
        assert got == lib.want(lib.bits[3], *QUERIES[queries.index(q)])
    lib.close()


def test_the_padded_bank_is_priced_against_half_the_budget():
    """What the build asks before it pads: rows x 128 slots x 2 B (+ a
    length word) against half of one device's bank budget — the cell's
    library (2.2 GB) and its first size (4.4 GB) fit 12 GiB's half, a
    library of 2^25 molecules does not."""
    assert view_mod.pbank_fixed_bytes(104, 1 << 20) == 112 * (1 << 20) * 2
    assert view_mod.pbank_fixed_bytes(129, 10) == 144 * 128 * 2
    assert view_mod.BANK_BUDGET.budget == 12 << 30
    assert view_mod.pbank_fixed_fits((1 << 23) - 1)
    assert view_mod.pbank_fixed_fits((1 << 24) - 1)
    assert not view_mod.pbank_fixed_fits(1 << 25)


@pytest.mark.parametrize("short, layout", [(6, "flat"), (30, "flat"),
                                           (38, "fixed"), (45, "fixed")])
def test_the_density_floor_sits_just_under_a_librarys(
        tmp_path, forced, short, layout):
    """Rows of `short` on-bits under a longest of 100 (104 slots): a
    library's own fill (0.38-0.47, the only densities read on the chip)
    is laid out fixed, anything sparser stays flat — the padded form
    would compare mostly pads."""
    lengths = np.full(300, short)
    lengths[::25] = 100     # a longest row in every segment
    lib = Library(tmp_path / "h", lengths)
    pb = lib.bank()
    assert _layouts(pb) == {layout}
    for _, n, _, _, p in pb.segments:
        assert (p / (n * 104) >= view_mod.PBANK_FIXED_MIN_DENSITY) \
            == (layout == "fixed")
    assert lib.ask("TopN(fp, Row(fp=50), n=9)") == lib.want(lib.bits[50], 9)
    lib.close()


@pytest.mark.parametrize("n", [6, 0])
def test_the_form_is_counted_once_a_launch_and_is_no_row(
        tmp_path, forced, n):
    """`executor.pbank_form{form:…}` moves by one a filtered launch —
    a rerun of a call without `n` included — an unfiltered TopN moves
    neither, and `executor.topn_rows_fetched` counts rows, not the
    form's word."""
    forced.setattr(ex_mod.Executor, "PBANK_EVERY_K", 2)
    lib = Library(tmp_path / "h", _lengths(103))
    segs = len(lib.bank().segments)
    before = lib.counters()
    assert lib.ask("TopN(fp, n=4)") == lib.want(np.ones(COLUMNS, bool), 4)
    mid = lib.counters()
    assert mid["executor.pbank_launches"] \
        - before.get("executor.pbank_launches", 0) == segs
    assert FORM % "compare" not in mid and FORM % "gather" not in mid
    got = lib.ask(_pql("Row(fp=3)", n, 10))
    assert got == lib.want(lib.bits[3], n, 10)
    after = lib.counters()
    launches = after["executor.pbank_launches"] \
        - mid["executor.pbank_launches"]
    reruns = after.get("executor.pbank_overflow_reruns", 0)
    assert launches == segs + reruns and (reruns > 0) == (n == 0)
    assert after[FORM % "compare"] == launches
    assert FORM % "gather" not in after
    if n:
        assert after["executor.topn_rows_fetched"] \
            - mid["executor.topn_rows_fetched"] \
            == sum(2 * min(n, rows) for _, rows, *_ in lib.bank().segments)
    lib.close()
