"""`groupby_sum`'s program (PR 46: `ops/groupsum.py`, launched by
`Executor._GroupSums`) against a plain numpy reference — bits unpacked,
boolean masks, Python-int sums; nothing of `ops/bitset` — on one device
and under four forced host devices. Off a TPU the kernel runs
interpreted: the results are what is held here, never a time.

A case hands `_GroupSums.launch` what the level loop would: the last
level's prefixes (or None), the last child's bank and slots, the summed
field's plane bank with its planes at scattered slots, and the picked
(prefix, row) pairs; `finalize` weighs the counts into each group's sum.
"""

import dataclasses
import itertools
import math
import types

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import upload
from pilosa_tpu.executor.results import GroupCount
from pilosa_tpu.utils.stats import MemStatsClient

DEVICES = 4


@dataclasses.dataclass(frozen=True)
class Case:
    groups: int             # picked (prefix, row) pairs
    depth: int              # bit planes of the summed field
    shards: int             # on one device; under the mesh see `mesh_shards`
    words: int = 128        # the banks' width
    cut: int = 0            # the launch's width where narrower (else `words`)
    prefixes: int = 6       # 0: a one-level GroupBy, `pre` is None
    rows: int = 10          # rows of the last child
    minimum: int = 0        # bsiGroup.min (a signed field's is negative)
    distinct: bool = False  # every group its own prefix and its own row
    zeros: bool = False     # prefix 0 and the bank's slot 0 are all zero
    mesh_shards: int = 0    # shards under four devices (else 4 x `shards`)
    absent_last: bool = False   # ... the last of them a padded, absent one

    def shards_on(self, mesh) -> int:
        return self.shards if mesh is None else \
            self.mesh_shards or DEVICES * self.shards


CASES = {
    "one_group_one_shard": Case(1, 4, 1),
    "eight_distinct_no_prefix": Case(8, 24, 16, prefixes=0, rows=8,
                                     distinct=True),
    "nine_pad_to_sixteen_signed_zero_rows": Case(
        9, 4, 2, minimum=-9, zeros=True),
    "lanes_128_width_cut": Case(128, 4, 8, words=256, cut=128,
                                prefixes=16, rows=12, mesh_shards=32),
    "lanes_130_depth_33": Case(130, 33, 2, prefixes=20, rows=9,
                               minimum=-(1 << 31)),
    # Twelve prefixes, so that no OPERAND has the masks' shape [16, 16, w].
    "all_distinct_with_prefixes": Case(16, 5, 16, prefixes=12, rows=16,
                                       distinct=True, mesh_shards=16),
    # A device's shards no multiple of eight: the bank is cut to the
    # launch's distinct rows first, here fewer than its 43 rows. Seven real
    # shards and an absent one under the mesh, as a padded shard list.
    "more_rows_than_lanes_odd_shards": Case(8, 4, 3, rows=40,
                                            mesh_shards=8,
                                            absent_last=True),
    # `ssb-host4`'s share, 15 shards a device (60 under the mesh): a
    # whole eight and a ragged seven added onto its first rows, on the
    # bank cut to the launch's distinct rows.
    "fifteen_shards_a_device": Case(9, 5, 15, rows=40, prefixes=4,
                                    mesh_shards=60),
}


def _bits(words: np.ndarray) -> np.ndarray:
    """bool [..., S * w * 32]: the columns of u32 [..., S, w] rows."""
    flat = np.ascontiguousarray(words).reshape(*words.shape[:-2], -1)
    return np.unpackbits(flat.view(np.uint8), axis=-1,
                         bitorder="little").astype(bool)


def _operands(case: Case, shards: int, rng):
    """Host operands of a launch: (pre or None, bank, slots of the last
    child's rows, plane bank, slots of its planes, picked pairs)."""
    def draw(n):
        a = rng.integers(0, 1 << 32, (n, shards, case.words),
                         dtype=np.uint32)
        return a & rng.integers(0, 1 << 32, a.shape, dtype=np.uint32)
    bank = draw(case.rows + 3)          # more slots than the child's rows
    slots = rng.permutation(case.rows + 3)[:case.rows].astype(np.int32)
    # The planes at scattered slots of their bank, not in order; the
    # not-null plane dense.
    plane_bank = draw(case.depth + 4)
    sel = rng.permutation(case.depth + 4)[:case.depth + 1].astype(np.int32)
    plane_bank[sel[-1]] |= rng.integers(0, 1 << 32, plane_bank[0].shape,
                                        dtype=np.uint32)
    w = case.cut or case.words
    pre = draw(case.prefixes)[..., :w] if case.prefixes else None
    if case.zeros:
        pre[0] = 0
        bank[slots[0]] = 0
    if case.absent_last and shards == case.mesh_shards:
        # All zero, as padding the shard list leaves it.
        for a in (bank, plane_bank) + (() if pre is None else (pre,)):
            a[:, -1] = 0
    p = max(case.prefixes, 1)
    if case.distinct:
        picked = [(g % p, g) for g in range(case.groups)]
    else:       # few distinct rows, many groups, prefix by prefix
        pairs = np.sort(rng.choice(p * case.rows, size=min(
            case.groups, p * case.rows), replace=False))
        picked = [(int(k) // case.rows, int(k) % case.rows) for k in pairs]
        picked = (picked * math.ceil(case.groups / len(picked)))[
            :case.groups]
    if case.zeros:      # a group of the zero prefix, one of the zero row
        picked[:2] = [(0, 1), (1, 0)]
    return pre, bank, slots, plane_bank, sel, picked


def _reference(case: Case, pre, bank, slots, plane_bank, sel, picked):
    """[(count of valued columns, signed sum)] a group, by the bits."""
    w = case.cut or case.words
    rows = _bits(bank[slots][..., :w])
    planes = _bits(plane_bank[sel][..., :w])
    prefixes = None if pre is None else _bits(pre)
    out = []
    for pi, ri in picked:
        mask = rows[ri] & planes[-1]
        if prefixes is not None:
            mask = mask & prefixes[pi]
        base = sum(int((mask & planes[j]).sum()) << j
                   for j in range(case.depth))
        n = int(mask.sum())
        out.append((n, base + case.minimum * n))
    return out


class _Launcher:
    """`_GroupSums` over hand-made operands: the object `launch` and
    `finalize` expect, without an index behind it."""

    def __init__(self, tmp_holder, mesh, case: Case, seed=46):
        import jax
        self.ex = Executor(tmp_holder, mesh=mesh)
        self.ex.stats = MemStatsClient()
        self.case = case
        shards = case.shards_on(mesh)
        self.host = _operands(case, shards, np.random.default_rng(seed))
        pre, bank, slots, plane_bank, sel, self.picked = self.host

        def put(a):
            if a is None:
                return None
            return jax.device_put(a) if mesh is None else mesh.put_bank(a)
        self.pre = put(pre)
        self.programs = {}      # jit key -> (builder, the last call's args)
        sums = object.__new__(Executor._GroupSums)
        sums.ex, sums.jit = self.ex, self._jit
        sums.bank, sums.slots = put(bank), slots
        sums.bsig = types.SimpleNamespace(min=case.minimum,
                                          bit_depth=case.depth)
        sums.depth = case.depth
        sums.planes, sums.sel = put(plane_bank), upload(sel)
        sums.width = case.cut or case.words
        sums.pending = []
        self.sums = sums

    def _jit(self, key, builder, span="groupby", **cut):
        import jax
        fn = jax.jit(builder)

        def call(*args):
            self.programs[key] = (builder, args, cut)
            return fn(*args)
        return call

    def run(self):
        groups = [GroupCount([], 0) for _ in self.picked]
        self.sums.launch(self.pre, self.picked, groups)
        self.sums.finalize()
        return groups

    def counters(self) -> dict:
        return self.ex.stats.snapshot()["counters"]


@pytest.fixture(scope="module")
def mesh4():
    import jax
    from pilosa_tpu.parallel.mesh import MeshContext
    return MeshContext(jax.devices()[:DEVICES])


@pytest.mark.parametrize("devices", [1, DEVICES])
@pytest.mark.parametrize("name", list(CASES))
def test_sums_equal_the_bits(tmp_holder, mesh4, name, devices):
    case = CASES[name]
    mesh = mesh4 if devices > 1 else None
    lch = _Launcher(tmp_holder, mesh, case)
    groups = lch.run()
    want = _reference(case, *lch.host)
    assert [g.sum for g in groups] == [s for _, s in want]
    assert any(n for n, _ in want), "a case of empty groups holds nothing"
    if case.zeros:
        assert want[0] == want[1] == (0, 0) and want[2] != (0, 0)
    c = lch.counters()
    assert c["executor.groupsum_launches"] == 1
    assert c["executor.groupsum_plane_rows"] == \
        case.groups * (case.depth + 1)
    # Lanes are padded as ever: powers of two to 128, multiples of 128
    # past it.
    (key,) = lch.programs
    lanes = int(key.split(":")[1])
    assert lanes == {1: 8, 8: 8, 9: 16, 16: 16, 128: 128, 130: 256}[
        case.groups]


def _runs(idx) -> int:
    return len([k for k, _ in itertools.groupby(idx)])


@pytest.mark.parametrize("name", [
    # 9 groups padded to 16 lanes, pairs of 6 prefixes x 10 rows in the
    # level loop's order: a prefix is a run, the rows change with every
    # group, the seven pad lanes repeat the first group.
    "nine_pad_to_sixteen_signed_zero_rows",
    # 8 groups, no prefix, a row each.
    "eight_distinct_no_prefix",
])
def test_operand_rows_are_the_fetched_rows(tmp_holder, name):
    """One word tile a launch at these widths: the grid's steps are the
    lanes in order, the planes come once, a row and a prefix once a run
    of lanes that name the same one."""
    case = CASES[name]
    lch = _Launcher(tmp_holder, None, case)
    lch.run()
    (key,) = lch.programs
    lanes = int(key.split(":")[1])
    padded = lch.picked + lch.picked[:1] * (lanes - len(lch.picked))
    want = case.depth + 1 + _runs(ri for _, ri in padded)
    if case.prefixes:
        want += _runs(pi for pi, _ in padded)
    if name == "eight_distinct_no_prefix":
        assert want == 25 + 8
    assert lch.counters()["executor.groupsum_operand_rows"] == want


def test_rows_fetched_by_blocks_of_groups():
    """Two word tiles or more: every block of groups (64 at 25 planes)
    walks the tiles on its own, so the planes come once a block and a
    run ends with its block."""
    from pilosa_tpu.ops.groupsum import group_block, rows_fetched
    assert group_block(128, 25) == 64
    pi = np.repeat(np.arange(4), 32)            # a run crosses no block
    si = np.tile(np.arange(32), 4)              # a row a group
    assert rows_fetched(pi, si, 25, tiles=8) == 2 * 25 + 128 + 4
    pi = np.repeat(np.arange(2), [40, 88])      # ... and one that does
    assert rows_fetched(pi, si, 25, tiles=8) == 2 * 25 + 128 + 3
    same = np.zeros(128, np.int32)              # one row, one prefix
    assert rows_fetched(same, same, 25, tiles=8) == 2 * 25 + 2 + 2
    assert rows_fetched(None, same, 25, tiles=8) == 2 * 25 + 2
    # One tile: the steps are the groups in order across the blocks.
    assert rows_fetched(same, same, 25, tiles=1) == 25 + 1 + 1


def test_no_mask_array_in_the_program(tmp_holder):
    """The lowered program has no [lanes, S, w] u32 value: the group
    masks are never an array (the parent wrote them: 1 GiB a launch)."""
    import jax
    case = CASES["all_distinct_with_prefixes"]
    lch = _Launcher(tmp_holder, None, case)
    lch.run()
    ((builder, args, cut),) = lch.programs.values()
    text = jax.jit(builder).lower(*args).as_text()
    assert cut["lanes"] == 16
    masks = f"tensor<{cut['lanes']}x{case.shards}x{case.words}xui32>"
    assert masks not in text
    # ... while the operands are there under that spelling.
    assert f"tensor<{case.rows + 3}x{case.shards}x{case.words}xui32>" in text


@pytest.mark.parametrize("devices", [1, DEVICES])
def test_a_shrunk_bound_cuts_the_launches(tmp_holder, mesh4, monkeypatch,
                                          devices):
    """GROUPSUM_CHUNK_BYTES bounds a launch's groups by a device's share
    of a row [S, w] each: 130 groups at eight a launch are seventeen
    launches, on a device of the mesh as on a lone one."""
    case = CASES["lanes_130_depth_33"]
    mesh = mesh4 if devices > 1 else None
    lch = _Launcher(tmp_holder, mesh, case)
    monkeypatch.setattr(Executor, "GROUPSUM_CHUNK_BYTES",
                        8 * case.shards * case.words * 4)
    groups = lch.run()
    assert [g.sum for g in groups] == [s for _, s in
                                       _reference(case, *lch.host)]
    assert lch.counters()["executor.groupsum_launches"] == \
        math.ceil(130 / 8) == 17
    assert {int(k.split(":")[1]) for k in lch.programs} == {8}
