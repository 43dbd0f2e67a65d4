"""The `chem-chip` deployment at a size a test can hold — 4,096 molecules
x 4,096-bit fingerprints, one shard — through the normal path: `python
-m pilosa_tpu.cli server`, the library loaded over HTTP by
`benchmark/datasets/chem.py`, restarted, and every threshold of the
`tanimoto-sweep` traffic answered equal, pair for pair, to that module's
plain reference (upstream's own threshold rule, a ratio exactly at T and
a tie at the n-th place among the cases). Then, in process, what a sweep
of a whole bank keeps with the bank: the slot-ordered row ids, the rows'
own popcounts per bank version, and the counters that say what a TopN
swept and fetched."""

import json
import os
import sys

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.utils.stats import MemStatsClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
MOLECULES = 4096
THRESHOLDS = (90, 80, 70, 50)


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own server child, loader, generator and
    reference (its modules import each other from `benchmark/`)."""
    added = [p for p in (BENCH, REPO) if p not in sys.path]
    sys.path[:0] = added
    from datasets import chem
    from harness import loadgen, server
    yield chem, loadgen, server
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "tanimoto-sweep.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def library(bench):
    chem, _, _ = bench
    with open(os.path.join(BENCH, "configs", "chem-chip.json")) as f:
        seed = json.load(f)["data_seed"]
    return chem.Library(seed, MOLECULES)


@pytest.fixture(scope="module")
def served(bench, library, tmp_path_factory):
    chem, _, server = bench
    state = tmp_path_factory.mktemp("chem")
    toml = state / "server.toml"
    toml.write_text("mesh_devices = 1\n")

    def start():
        srv = server.Server(REPO, str(state / "data"), "cpu", str(toml),
                            str(state / "server.log"),
                            server.server_env(str(state / "jax_cache")))
        srv.wait_ready()
        return srv

    srv = start()
    try:
        chem.load(srv, library)     # refuses another rule or path itself
        assert srv.stop() == 0, srv.log_tail()
        srv = start()               # serve from a re-opened directory
        yield srv
        assert srv.stop() == 0, srv.log_tail()
    finally:
        srv.kill()


def _boundary_cases(chem, lib, threshold, n):
    """(a molecule with a neighbour whose similarity is exactly the
    threshold, a molecule whose n-th and (n+1)-th neighbours past the
    threshold tie on the count), found with the reference's own
    arrays."""
    exact = tie = None
    for m in range(lib.n):
        src = int(lib.popcount[m])
        every = chem.similar(lib, m, 0, 0)      # all with a shared bit
        ids = np.array([p["id"] for p in every])
        c = np.array([p["count"] for p in every])
        denom = lib.popcount[ids] + src - c
        if exact is None and np.any(c * 100 == threshold * denom):
            exact = m
        past = chem.similar(lib, m, 0, threshold)
        if tie is None and len(past) > n \
                and past[n - 1]["count"] == past[n]["count"]:
            tie = m
        if exact is not None and tie is not None:
            break
    return exact, tie


@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_each_threshold_of_the_traffic_equals_the_reference(
        bench, traffic, library, served, threshold):
    chem, loadgen, _ = bench
    assert {e["threshold"] for e in traffic["cycle"]} == set(THRESHOLDS)
    before = served.get("/debug/vars")["counters"]
    one = dict(traffic, cycle=[e for e in traffic["cycle"]
                               if e["threshold"] == threshold])
    stream = loadgen.client_stream(chem, library, one, 2**31 + 30, 0)
    sizes = []
    for (_, pql, ref), _ in zip(stream, range(48)):
        want = ref()
        assert chem.equal(served.query(chem.INDEX, pql), want), pql
        assert f"n=50, tanimotoThreshold={threshold})" in pql
        sizes.append(len(want))
    # The query molecule itself always passes; neighbours do for some.
    assert min(sizes) >= 1 and max(sizes) > 1
    # A neighbour at exactly T is out (upstream: ceil(ratio) <= T skips);
    # a tie at the n-th place is cut by the smaller row id.
    exact, tie = _boundary_cases(chem, library, threshold, 5)
    assert exact is not None and tie is not None
    for m, n in ((exact, 0), (exact, 50), (tie, 5)):
        want = chem.similar(library, m, n, threshold)
        assert served.query(chem.INDEX, chem.pql(m, n, threshold)) == want
    keeps_equal = chem.similar(library, exact, 0, threshold - 1)
    assert len(keeps_equal) > len(chem.similar(library, exact, 0, threshold))
    after = served.get("/debug/vars")["counters"]
    asked = 48 + 3
    assert after["executor.topn_sweeps{path:resident}"] \
        - before.get("executor.topn_sweeps{path:resident}", 0) == asked
    assert after["executor.tanimoto_sweeps"] \
        - before.get("executor.tanimoto_sweeps", 0) == asked
    assert after.get("executor.topn_sweeps{path:streamed}", 0) == 0


def test_the_sources_query_and_the_span_of_its_sweep(bench, library, served):
    chem, _, _ = bench
    (pql, want), = chem.family_queries(library)
    assert pql == "TopN(fingerprint, Row(fingerprint=6), tanimotoThreshold=90)"
    assert served.query(chem.INDEX, pql) == want and want[0]["id"] == 6
    spans = [e for e in served.get("/debug/timeline?last=4")["traceEvents"]
             if e.get("ph") == "X"]
    sweep = [e["args"] for e in spans if e["name"] == "dispatch"
             and e["args"].get("program") == "topn_sweep"]
    # 4,096 molecules + the zero slot pad to 8,192 slots of 128 words.
    assert sweep and sweep[-1]["rows"] == 8192 and sweep[-1]["words"] == 128
    names = {e["name"] for e in spans}
    assert {"finish.slot_map", "finish.select"} <= names
    select = [e["args"] for e in spans if e["name"] == "finish.select"]
    assert select[-1]["rows"] == MOLECULES


def test_a_server_with_another_rule_is_refused_before_the_load(bench):
    """`chem.load` asks a scratch index for a similarity of exactly T
    first; a server that keeps it never sees the library."""
    chem, _, server = bench

    class KeepsEqual:
        posted = []

        def post_json(self, path, obj):
            self.posted.append(path)

        def request(self, method, path, *a):
            self.posted.append(path)

        def query(self, index, pql):
            return [{"id": 0, "count": 10}, {"id": 1, "count": 7}]

    srv = KeepsEqual()
    with pytest.raises(server.BenchFailure, match="exactly T"):
        chem.load(srv, None)
    assert all(chem.PROBE in p for p in srv.posted)


# ------------------------------------------------------- in process


ROWS = (3, 9, 4000, 70001, 70002)      # row ids with holes


@pytest.fixture
def ex(tmp_holder):
    """A fingerprint field of a few molecules: rows 9 and 4000 share 7
    of row 3's 10 bits (exactly 70 %), 70001 shares 8, 70002 none."""
    idx = tmp_holder.create_index("mole")
    f = idx.create_field("fingerprint", FieldOptions(max_columns=4096))
    bits = {3: range(10), 9: range(7), 4000: range(3, 10),
            70001: [*range(8), 40], 70002: [100, 101]}
    rows = np.concatenate([[r] * len(b) for r, b in bits.items()])
    cols = np.concatenate([list(b) for b in bits.values()])
    f.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))
    ex = Executor(tmp_holder)
    ex.stats = MemStatsClient()
    ex.result_cache.enabled = False
    return ex


def _topn(ex, q):
    (res,) = ex.execute("mole", q)
    return res.pairs


def _bank(ex):
    view = ex.holder.index("mole").field("fingerprint").view()
    return view.device_bank((0,), trim=True)


def _counters(ex):
    return ex.stats.snapshot()["counters"]


@pytest.mark.parametrize("path", ["resident", "streamed", "positions"])
def test_a_ratio_of_exactly_t_is_out_on_every_path(ex, monkeypatch, path):
    """7 of 10 bits is exactly 70: out at T = 70 (upstream's
    `tanimoto <= threshold` skips it), in at 69 — on the resident sweep,
    the streamed chunks and the positions bank alike."""
    if path != "resident":
        monkeypatch.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 0)
    monkeypatch.setattr(ex_mod, "PBANK_ENABLED", path == "positions")
    q = "TopN(fingerprint, Row(fingerprint=3), n=10, tanimotoThreshold={})"
    assert _topn(ex, q.format(70)) == [(3, 10), (70001, 8)]
    assert _topn(ex, q.format(69)) == [(3, 10), (70001, 8), (9, 7),
                                       (4000, 7)]
    assert _counters(ex)[f"executor.topn_sweeps{{path:{path}}}"] == 2


def test_slot_rows_follow_the_banks_slots_through_holes_and_a_new_row(ex):
    def agrees(bank):
        rows = bank.slot_rows()
        assert rows.dtype == np.uint64 and len(rows) == len(bank.slots)
        assert {int(r): i for i, r in enumerate(rows)} == bank.slots
        return rows

    first = _bank(ex)
    assert agrees(first).tolist() == sorted(ROWS)
    assert first.slot_rows() is first.slot_rows()       # kept
    ex.execute("mole", "Set(5, fingerprint=12)")        # a new row
    second = _bank(ex)
    assert second is not first and 12 in second.slots
    assert sorted(agrees(second).tolist()) == sorted(ROWS + (12,))
    q = "TopN(fingerprint, Row(fingerprint=3), n=10, tanimotoThreshold=1)"
    assert _topn(ex, q) == [(3, 10), (70001, 8), (9, 7), (4000, 7), (12, 1)]


def test_restricted_candidates_map_through_the_same_array(ex):
    q = ("TopN(fingerprint, Row(fingerprint=3), n=10, ids=[9, 70001, 555], "
         "tanimotoThreshold=1)")
    assert _topn(ex, q) == [(70001, 8), (9, 7)]


def test_popcounts_live_one_bank_version(ex):
    """The rows' own popcounts are swept for, and ride to the host with,
    the first tanimoto answer of a bank version and stay with the bank;
    a write to the field makes a new bank, which sweeps for its own."""
    q = "TopN(fingerprint, Row(fingerprint=3), n=10, tanimotoThreshold=50)"
    slots = _bank(ex).array.shape[0]
    assert _bank(ex).popcounts is None
    _topn(ex, q)
    kept = _bank(ex).popcounts
    assert kept[_bank(ex).slot(70001)] == 9 and len(kept) == slots
    assert _counters(ex)["executor.topn_rows_fetched"] == 2 * slots
    _topn(ex, q)
    assert _bank(ex).popcounts is kept
    assert _counters(ex)["executor.topn_rows_fetched"] == 3 * slots
    assert _counters(ex)["executor.bank_popcounts{path:kept}"] == 1
    ex.execute("mole", "Set(41, fingerprint=70001)")
    assert _bank(ex).popcounts is None
    assert _topn(ex, q) == [(3, 10), (70001, 8), (9, 7), (4000, 7)]
    assert _bank(ex).popcounts[_bank(ex).slot(70001)] == 10
    assert _counters(ex)["executor.topn_rows_fetched"] == 5 * slots
    assert _counters(ex)["executor.bank_popcounts{path:swept}"] == 2
    # A tanimoto answer is one `topn_sweep`; each bank version one
    # unfiltered sweep more.
    assert _counters(ex)["executor.sweep_launches"] == 3 + 2


def test_the_three_counters_add_up(ex):
    """Per TopN call: `topn_rows_swept` the slots of each bank it swept
    (the one for the rows' popcounts included), `topn_rows_fetched` the vector elements its finalize read,
    `tanimoto_sweeps` the calls that read the rows' popcounts — which
    the batch's two share: one sweep, one fetch."""
    slots = _bank(ex).array.shape[0]
    tani = "TopN(fingerprint, Row(fingerprint=3), n=3, tanimotoThreshold=60)"
    plain = "TopN(fingerprint, Row(fingerprint=3), n=3)"
    ex.execute_batch([("mole", tani, None), ("mole", plain, None),
                      ("mole", plain, None), ("mole", tani, None),
                      ("mole", "TopN(fingerprint, n=3, tanimotoThreshold=9)",
                       None)])       # no filter: the threshold is ignored
    c = _counters(ex)
    assert c["executor.tanimoto_sweeps"] == 2
    swept = c["executor.topn_sweeps{path:resident}"]
    assert swept == 5
    assert c["executor.topn_rows_swept"] == (swept + 1) * slots
    # A count vector a call, and the popcounts once: both tanimoto calls
    # of the batch were staged before either finished, and the second
    # found the first's vector pending.
    assert c["executor.topn_rows_fetched"] == 6 * slots
    assert c["executor.bank_popcounts{path:swept}"] == 1
    assert c["executor.bank_popcounts{path:kept}"] == 1


def test_tanimoto_before_and_after_a_write_in_one_query(ex):
    q = "TopN(fingerprint, Row(fingerprint=3), n=10, tanimotoThreshold=70)"
    before, changed, after = ex.execute(
        "mole", f"{q} Set(7, fingerprint=9) {q}")
    assert changed is True
    assert before.pairs == [(3, 10), (70001, 8)]
    assert after.pairs == [(3, 10), (9, 8), (70001, 8)]     # 8 of 10 now
