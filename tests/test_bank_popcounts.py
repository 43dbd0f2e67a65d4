"""The rows' own popcounts (tanimoto's |row|) belong to the bank version:
the first tanimoto TopN to meet a `ViewBank` sweeps for them once
(`topn_sweep_unfiltered`), every later one — a flush's other members
included — finds the pending or the fetched vector, a write makes a new
bank that is swept for its own, and every answer is the set-arithmetic
rule's, on the resident sweep, the streamed chunks and a 4-device mesh."""

import sys
import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.parallel import MeshContext
from pilosa_tpu.utils.stats import MemStatsClient

BITS = 4096
QUERY_ROW = 0
ALL_ONES = 4
EMPTIED = 6
CHUNK_ROWS = 8
PATHS = ["resident", "streamed", "mesh"]


def _fingerprints() -> dict:
    """row id -> set of on-bits. Rows 1 and 2 share 7 of row 0's 10 bits
    (exactly 70 %), 3 shares 8 of its own 9, 4 is all ones, 5 shares
    nothing; the rest are noisy copies of row 0 and independent draws,
    enough rows for three chunks."""
    rng = np.random.default_rng(31)
    fp = {QUERY_ROW: set(range(10)), 1: set(range(7)), 2: set(range(3, 10)),
          3: {*range(8), 40}, ALL_ONES: set(range(BITS)), 5: {100, 101}}
    for r in range(7, 15):
        keep = {b for b in range(10) if rng.random() < 0.8}
        fp[r] = keep | set(rng.integers(10, BITS, r).tolist())
    for r in range(15, 20):
        fp[r] = set(rng.integers(0, BITS, 48).tolist())
    return fp


def _reference(fp: dict, filt_row: int, n: int, tanimoto: int,
               candidates=None) -> list:
    """TopN(fp, Row(fp=filt_row), n, tanimotoThreshold) by set arithmetic:
    upstream's rule, a ratio of exactly T is out."""
    filt = fp[filt_row]
    pairs = []
    for r, bits in fp.items():
        if candidates is not None and r not in candidates:
            continue
        inter = len(bits & filt)
        if inter * 100 <= tanimoto * len(bits | filt):
            continue
        if inter:
            pairs.append((r, inter))
    pairs.sort(key=lambda p: (-p[1], p[0]))
    return pairs[:n] if n else pairs


def _q(tanimoto: int, n: int = 10, row: int = QUERY_ROW, extra: str = ""):
    return (f"TopN(fp, Row(fp={row}), n={n}{extra}, "
            f"tanimotoThreshold={tanimoto})")


@pytest.fixture(scope="module")
def mesh4():
    return MeshContext(jax.devices()[:4])


@pytest.fixture
def served(request, tmp_holder, mesh4, monkeypatch):
    """(executor with fresh counters, the fingerprints it holds, run):
    `run(queries)` executes them in turn on the path of the test's
    `path` parameter and returns their pairs."""
    path = request.param
    fp = _fingerprints()
    idx = tmp_holder.create_index("mole")
    f = idx.create_field("fp", FieldOptions(max_columns=BITS))
    rows = np.concatenate([[r] * len(b) for r, b in fp.items()])
    cols = np.concatenate([sorted(b) for b in fp.values()])
    f.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))
    if path == "streamed":
        monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1)
        monkeypatch.setattr(ex_mod, "TOPN_CHUNK_ROWS", CHUNK_ROWS)
        monkeypatch.setattr(ex_mod, "PBANK_ENABLED", False)
    mesh = mesh4 if path == "mesh" else None
    ex = Executor(tmp_holder, mesh=mesh)
    ex.stats = MemStatsClient()
    ex.result_cache.enabled = False

    def run(queries, batch=False):
        if isinstance(queries, str):
            queries = [queries]
        if batch:
            out = ex.execute_batch([("mole", q, None) for q in queries])
            return [results[0].pairs for results, _ in out]
        return [ex.execute("mole", q)[0].pairs for q in queries]

    if mesh is not None:
        with mesh.mesh:
            yield ex, fp, run, path
    else:
        yield ex, fp, run, path


def _counters(ex) -> dict:
    c = ex.stats.snapshot()["counters"]
    return {"launches": c.get("executor.sweep_launches", 0),
            "swept": c.get("executor.bank_popcounts{path:swept}", 0),
            "kept": c.get("executor.bank_popcounts{path:kept}", 0),
            "tanimoto": c.get("executor.tanimoto_sweeps", 0)}


def _banks(fp: dict, path: str) -> int:
    """The ViewBanks one TopN of the whole field sweeps: the resident
    bank, or a chunk bank per CHUNK_ROWS rows."""
    return -(-len(fp) // CHUNK_ROWS) if path == "streamed" else 1


def _view_bank(ex):
    idx = ex.holder.index("mole")
    return idx.field("fp").view().device_bank(
        tuple(ex._shards(idx, [0])), mesh=ex.mesh, trim=True)


# -------------------------------- (i) swept once a bank version, kept after


@pytest.mark.parametrize("served", PATHS, indirect=True)
@pytest.mark.parametrize("n_queries", [1, 5])
def test_n_answers_cost_n_sweeps_and_one_for_the_popcounts(served,
                                                           n_queries):
    ex, fp, run, path = served
    thresholds = [50, 69, 70, 30, 90][:n_queries]
    got = run([_q(t) for t in thresholds])
    assert got == [_reference(fp, QUERY_ROW, 10, t) for t in thresholds]
    banks = _banks(fp, path)
    assert _counters(ex) == {"launches": (n_queries + 1) * banks,
                             "swept": banks,
                             "kept": (n_queries - 1) * banks,
                             "tanimoto": n_queries}


@pytest.mark.parametrize("served", ["resident", "mesh"], indirect=True)
def test_a_flush_of_eight_members_shares_one_pending_vector(served):
    """Every member is staged before any is finalised: the first sweeps,
    seven find its vector still on the device, one fetch keeps it."""
    ex, fp, run, path = served
    rows = [0, 1, 2, 3, 7, 8, 9, 10]
    got = run([_q(40, row=r) for r in rows], batch=True)
    assert got == [_reference(fp, r, 10, 40) for r in rows]
    assert _counters(ex) == {"launches": 8 + 1, "swept": 1, "kept": 7,
                             "tanimoto": 8}
    bank = _view_bank(ex)
    assert isinstance(bank.popcounts, np.ndarray)
    slots = bank.array.shape[0]
    c = ex.stats.snapshot()["counters"]
    assert c["executor.topn_rows_fetched"] == (8 + 1) * slots


@pytest.mark.parametrize("served", ["resident", "mesh"], indirect=True)
def test_a_pending_vector_whose_sweeper_was_dropped_is_fetched_once(served):
    """The call that swept never reaches its finalize: the next one finds
    the vector pending (`kept`), fetches it inside its own finalize and
    keeps it; the one after reads the host's copy."""
    ex, fp, run, path = served
    run(f"TopN(fp, Row(fp={QUERY_ROW}), n=1)")      # builds the bank
    bank = _view_bank(ex)
    pending, swept = ex._bank_popcounts(bank)       # ... and is dropped
    assert swept and bank.popcounts is pending
    assert not isinstance(pending, np.ndarray)
    got = run([_q(50), _q(30)])
    assert got == [_reference(fp, QUERY_ROW, 10, t) for t in (50, 30)]
    c = _counters(ex)
    assert (c["swept"], c["kept"]) == (1, 2)
    assert isinstance(bank.popcounts, np.ndarray)
    slots = bank.array.shape[0]
    c = ex.stats.snapshot()["counters"]
    # The plain answer's counts, two tanimoto answers' and the popcounts.
    assert c["executor.topn_rows_fetched"] == (1 + 2 + 1) * slots
    assert c["executor.topn_rows_swept"] == (1 + 2 + 1) * slots


@pytest.mark.parametrize("served", ["resident"], indirect=True)
def test_concurrent_first_answers_sweep_a_bank_once(served):
    """More threads than cores meet a bank nobody has asked yet, under a
    short switch interval: a lost update would sweep it twice."""
    ex, fp, run, _ = served
    n_threads = 24
    start = threading.Barrier(n_threads)
    got, errors = [None] * n_threads, []

    def ask(i):
        try:
            start.wait(timeout=30)
            got[i] = ex.execute("mole", _q(40, row=i % 4))[0].pairs
        except Exception as e:          # surfaced below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads)
    assert got == [_reference(fp, i % 4, 10, 40) for i in range(n_threads)]
    c = _counters(ex)
    assert (c["swept"], c["kept"]) == (1, n_threads - 1)
    assert c["launches"] == n_threads + 1


# --------------------------------------- (ii) a write is read back


@pytest.mark.parametrize("served", PATHS, indirect=True)
@pytest.mark.parametrize("write", ["set", "clear"])
def test_a_write_between_two_answers_is_read_back(served, write):
    """`|row|` is computed from the bank version's own array: after a
    Set / Clear the second answer is the written data's, from a new
    bank that was swept again."""
    ex, fp, run, path = served
    (first,) = run(_q(69))
    assert first == _reference(fp, QUERY_ROW, 10, 69)
    assert 1 in {r for r, _ in first}
    before = _counters(ex)
    if write == "set":
        # Row 1 grows from 7 bits to 9: 7 of 12 is under 69 % now.
        ex.execute("mole", "Set(2000, fp=1) Set(2001, fp=1)")
        fp[1] |= {2000, 2001}
    else:
        # Row 3 loses its one bit outside the query: 8 of 10 stays in,
        # and row 2 drops from 7 of 10 to 6 of 10.
        ex.execute("mole", "Clear(40, fp=3) Clear(3, fp=2)")
        fp[3] -= {40}
        fp[2] -= {3}
    (second,) = run(_q(69))
    assert second == _reference(fp, QUERY_ROW, 10, 69) != first
    after = _counters(ex)
    # Every bank the write reached is a new version, swept again; a
    # chunk bank it did not reach keeps its vector.
    again = after["swept"] - before["swept"]
    assert 1 <= again <= _banks(fp, path)
    assert after["kept"] - before["kept"] == _banks(fp, path) - again
    if path != "streamed":
        bank = _view_bank(ex)
        want = [len(fp.get(int(r), ())) for r in bank.slot_rows()]
        assert bank.popcounts[:len(want)].tolist() == want
        assert not bank.popcounts[len(want):].any()


# ------------------------------------------- (iii) the rule at its edges


@pytest.mark.parametrize("served", PATHS, indirect=True)
def test_the_rule_at_its_edges(served):
    ex, fp, run, path = served
    # A row that was set and emptied again: no bits, no answer, no fault.
    ex.execute("mole", f"Set(9, fp={EMPTIED}) Clear(9, fp={EMPTIED})")
    # A ratio of exactly T is out: rows 1 and 2 at 7 of 10.
    at, under = run([_q(70), _q(69)])
    assert {1, 2} & {r for r, _ in at} == set()
    assert {1, 2} <= {r for r, _ in under}
    assert at == _reference(fp, QUERY_ROW, 10, 70)
    assert under == _reference(fp, QUERY_ROW, 10, 69)
    # The all-ones row: 4,096 bits, every filter bit shared.
    (ones,) = run(_q(99, row=ALL_ONES))
    assert ones == [(ALL_ONES, BITS)]
    (low,) = run(_q(1, n=0, row=ALL_ONES))
    assert low == _reference(fp, ALL_ONES, 0, 1)
    assert low[0] == (ALL_ONES, BITS) and len(low) > 3
    # 10 of 4,096 is 0.24 %: row 4 is out at T = 1 from the other side.
    (n0,) = run(_q(1, n=0))
    assert n0 == _reference(fp, QUERY_ROW, 0, 1)
    assert ALL_ONES not in {r for r, _ in n0} and len(n0) > 10
    # Restricted candidates read the same kept vector by their slots.
    (by_ids,) = run(_q(1, extra=", ids=[3, 1, 5, 555]"))
    assert by_ids == _reference(fp, QUERY_ROW, 10, 1, {3, 1, 5})
    ex.execute("mole", 'SetRowAttrs(fp, 2, series="a") '
                       'SetRowAttrs(fp, 8, series="a") '
                       'SetRowAttrs(fp, 3, series="b")')
    (by_attr,) = run(_q(1, extra=', attrName=series, attrValues=["a"]'))
    assert by_attr == _reference(fp, QUERY_ROW, 10, 1, {2, 8})
    assert EMPTIED not in {r for pairs in (at, under, n0) for r, _ in pairs}


# ------------------------- (iv) a filtered TopN without a threshold


@pytest.mark.parametrize("served", PATHS, indirect=True)
def test_a_filtered_topn_without_a_threshold_asks_for_no_popcounts(served):
    ex, fp, run, path = served
    (got,) = run(f"TopN(fp, Row(fp={QUERY_ROW}), n=10)")
    assert got == _reference(fp, QUERY_ROW, 10, 0)
    # No filter: the threshold is ignored, and so are the popcounts.
    run("TopN(fp, n=3, tanimotoThreshold=9)")
    c = _counters(ex)
    assert (c["swept"], c["kept"], c["tanimoto"]) == (0, 0, 0)
    assert c["launches"] == 2 * _banks(fp, path)
    if path != "streamed":
        bank = _view_bank(ex)
        assert bank.popcounts is None
