"""The `chem-lib-chip` deployment at a size a test can hold (PR 40): a
seeded library of a few thousand molecules behind one in-process server
whose resident limit and segment size are patched small, so that the
fingerprint field is served from the positions bank in five segments or
more, with a wave sync a call. Every threshold of the traffic, with
`n` = 50 and without `n` (the source's own query), ties at the n-th
place, a ratio of exactly T, a query row wider than the compare's cap
(the gather form) and a segment that overflows its first bound — each equal
to `benchmark/datasets/chem.py`'s plain reference; the path counter says
`positions` and never `streamed`; the spans and counters the cell's
metrics read are in the record. Then what an import-roaring body costs:
N bodies into one fragment make O(log N) snapshots, a fragment opened
again without a clean stop holds every acknowledged bit, and
`clear=True` still clears."""

import os
import sys
import time

import numpy as np
import pytest

from pilosa_tpu.core import fragment as frag_mod
from pilosa_tpu.core import view as view_mod
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.executor import executor as ex_mod
from pilosa_tpu.storage.roaring import Bitmap
from pilosa_tpu.utils.timeline import TIMELINE

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from datasets import chem, chem_lib  # noqa: E402
from harness.server import BenchFailure, Client, Server  # noqa: E402

MOLECULES = 6000
THRESHOLDS = chem_lib.THRESHOLDS
SEGMENT = 1 << 15           # positions a segment: ~290,000 in all
CONFIG = {"data_seed": 20240229, "grid_rows": MOLECULES}


class Served:
    """One in-process server (coalescer on) over a holder whose
    fingerprint field is past the resident limit."""

    def __init__(self, path: str):
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.server import API, serve
        from pilosa_tpu.server.coalescer import QueryCoalescer
        from pilosa_tpu.utils.stats import MemStatsClient

        self.lib = chem_lib.make(CONFIG, chem.SHARD_WIDTH)
        self.holder = Holder(path)
        self.holder.open()
        self.api = API(self.holder, stats=MemStatsClient())
        self.api.coalescer = QueryCoalescer(
            self.api.executor, window_s=0.0005, stats=self.api.stats)
        self.api.coalescer.start()
        self.http = serve(self.api, "localhost", 0, background=True)
        self.srv = Server.__new__(Server)
        self.srv.port = self.http.server_address[1]
        self.srv.client = Client(self.srv.port)

    def close(self) -> None:
        self.srv.client.close()
        self.http.shutdown()
        self.http.server_close()
        self.api.coalescer.stop()
        self.holder.close()

    def counters(self, until=None) -> dict:
        """The counters; with `until`, once it holds of them: a record
        hands its counts over when it finishes, after its reply is
        written, so a client that has read the reply may be ahead."""
        deadline = time.monotonic() + 10
        while True:
            now = dict(self.api.stats.snapshot()["counters"])
            if until is None or until(now) or time.monotonic() > deadline:
                return now
            time.sleep(0.01)

    def stage_count(self, stage: str, at_least: int = 0) -> int:
        """Records that fed `request.stage_seconds{stage:…}`, waited for
        as `counters(until)` waits."""
        name = f"request.stage_seconds{{stage:{stage}}}"
        deadline = time.monotonic() + 10
        while True:
            n = self.api.stats.snapshot()["histograms"].get(
                name, {"count": 0})["count"]
            if n >= at_least or time.monotonic() > deadline:
                return n
            time.sleep(0.01)

    def spans(self, name: str) -> list:
        """Spans of that name in the newest records, once one is there."""
        deadline = time.monotonic() + 10
        while True:
            found = [sp for rec in TIMELINE.requests(last=8)
                     for sp in rec.root.walk() if sp.name == name]
            if found or time.monotonic() > deadline:
                return found
            time.sleep(0.01)

    def ask(self, m: int, n: int, t: int) -> list:
        return self.srv.query(chem.INDEX, chem.pql(m, n, t))

    def view(self):
        return self.holder.index(chem.INDEX).field(chem.FIELD).view()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # 6,000 molecules + the zero slot pad to 8,192 slots of 512 B = 4 MiB
    # dense: past a 1 MiB limit, as 8 GiB is past 2 GiB.
    mp.setattr(ex_mod, "TOPN_MAX_BANK_BYTES", 1 << 20)
    mp.setattr(ex_mod.Executor, "BANK_MAX_BYTES", 1 << 20)
    mp.setattr(view_mod, "PBANK_SEGMENT_POSITIONS", SEGMENT)
    mp.setattr(ex_mod.Executor, "_PBANK_KERNELS", {})
    s = Served(str(tmp_path_factory.mktemp("chem_lib")))
    try:
        chem_lib.load(s.srv, s.lib)     # refuses another rule or path itself
        yield s
    finally:
        s.close()
        mp.undo()


def _moved(before: dict, after: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


def test_the_bank_is_in_segments_and_every_call_waits_for_a_wave(served):
    pb = served.view().positions_bank(0, served.view().trimmed_words())
    assert len(pb.segments) >= 5
    # Both layouts occur at this size (a segment whose longest row is
    # short is `fixed`); at the cell's every segment is `flat`.
    assert {pos.ndim for _, _, pos, _, _ in pb.segments} <= {1, 2}
    assert sum(n for _, n, _, _, _ in pb.segments) == MOLECULES
    before = served.counters()
    waits = served.stage_count("pbank.wave_wait")
    assert served.ask(11, 50, 70) == chem.similar(served.lib, 11, 50, 70)
    after = served.counters()
    assert _moved(before, after, "executor.pbank_launches") \
        == len(pb.segments)
    # A record's waits are one observation.
    assert served.stage_count("pbank.wave_wait", waits + 1) == waits + 1


@pytest.mark.parametrize("n", [50, 0])
@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_every_threshold_with_and_without_n_equals_the_reference(
        served, threshold, n):
    lib = served.lib
    before = served.counters()
    rng = np.random.default_rng([40, threshold, n])
    sizes = []
    asked = [int(m) for m in rng.integers(0, MOLECULES, 12)]
    for m in asked:
        want = chem.similar(lib, m, n, threshold)
        assert served.ask(m, n, threshold) == want, (m, n, threshold)
        sizes.append(len(want))
    assert min(sizes) >= 1 and max(sizes) > 1
    after = served.counters()
    assert _moved(before, after,
                  "executor.topn_sweeps{path:positions}") == len(asked)
    assert _moved(before, after, "executor.tanimoto_sweeps") == len(asked)
    assert after["executor.topn_sweeps{path:streamed}"] == 0
    assert _moved(before, after, "executor.topn_rows_swept") \
        >= len(asked) * MOLECULES
    assert _moved(before, after, "executor.topn_rows_fetched") > 0


def _boundary_cases(lib, threshold, n):
    """(a molecule with a neighbour whose similarity is exactly the
    threshold, a molecule whose n-th and (n+1)-th neighbours past the
    threshold tie on the count), by the reference's own arrays."""
    exact = tie = None
    for m in range(lib.n):
        src = int(lib.popcount[m])
        every = chem.similar(lib, m, 0, 0)
        ids = np.array([p["id"] for p in every])
        c = np.array([p["count"] for p in every])
        if exact is None and np.any(
                c * 100 == threshold * (lib.popcount[ids] + src - c)):
            exact = m
        past = chem.similar(lib, m, 0, threshold)
        if tie is None and len(past) > n \
                and past[n - 1]["count"] == past[n]["count"]:
            tie = m
        if exact is not None and tie is not None:
            break
    return exact, tie


@pytest.mark.parametrize("threshold", THRESHOLDS)
def test_a_ratio_of_exactly_t_and_a_tie_at_the_nth_place(served, threshold):
    lib = served.lib
    exact, tie = _boundary_cases(lib, threshold, 5)
    assert exact is not None and tie is not None
    for m, n in ((exact, 0), (exact, 50), (tie, 5), (tie, 0)):
        assert served.ask(m, n, threshold) \
            == chem.similar(lib, m, n, threshold), (m, n)
    # The neighbour at exactly T is in at T - 1: it was the rule that
    # dropped it.
    assert len(chem.similar(lib, exact, 0, threshold - 1)) \
        > len(chem.similar(lib, exact, 0, threshold))
    assert served.ask(exact, 0, threshold - 1) \
        == chem.similar(lib, exact, 0, threshold - 1)


def test_a_query_row_wider_than_the_compare_bound_takes_the_gather_form(
        served, monkeypatch):
    """The compare is as wide as the bank's widest row, so every
    fingerprint takes it; its cap (128) set to 64 for the test, so that
    the library's widest molecules pass it: the program's other branch,
    the table gather, answers them, and is counted."""
    lib = served.lib
    monkeypatch.setattr(ex_mod, "PBANK_SPARSE_FILTER_BITS", 64)
    monkeypatch.setattr(ex_mod.Executor, "_PBANK_KERNELS", {})
    wide = np.flatnonzero(lib.popcount > 64)
    assert len(wide) >= 3
    gather = "executor.pbank_form{form:gather}"
    before = served.counters()
    asked = 0
    for m in wide[:3].tolist():
        for n, t in ((50, 50), (0, 50), (50, 90), (0, 70)):
            assert served.ask(m, n, t) == chem.similar(lib, m, n, t), (m, t)
            asked += 1
    after = served.counters(
        lambda c: _moved(before, c, gather)
        >= _moved(before, c, "executor.pbank_launches") > 0)
    assert _moved(before, after, gather) \
        == _moved(before, after, "executor.pbank_launches") \
        >= asked * len(served.view().positions_bank(
            0, served.view().trimmed_words()).segments)


def test_a_segment_past_its_first_bound_runs_again_wider(served,
                                                         monkeypatch):
    """A call without `n` whose survivors in some segment pass the
    first top_k bound: that segment runs again at the power of two
    that holds them, the answer is whole, and the rerun is counted."""
    lib = served.lib
    monkeypatch.setattr(ex_mod.Executor, "PBANK_EVERY_K", 2)
    sizes = {m: len(chem.similar(lib, m, 0, 50)) for m in range(200)}
    m = max(sizes, key=sizes.get)
    assert sizes[m] > 2 * len(served.view().positions_bank(
        0, served.view().trimmed_words()).segments)    # some segment > 2
    before = served.counters()
    assert served.ask(m, 0, 50) == chem.similar(lib, m, 0, 50)
    after = served.counters()
    reruns = _moved(before, after, "executor.pbank_overflow_reruns")
    assert reruns >= 1
    assert _moved(before, after, "executor.pbank_launches") \
        == reruns + len(served.view().positions_bank(
            0, served.view().trimmed_words()).segments)
    # With `n` the bound is `n` itself: no survivors are counted, no
    # segment runs twice.
    before = served.counters()
    assert served.ask(m, 50, 50) == chem.similar(lib, m, 50, 50)
    assert _moved(before, served.counters(),
                  "executor.pbank_overflow_reruns") == 0


def test_the_spans_and_counters_are_in_the_record(served):
    TIMELINE.reset()
    pb = served.view().positions_bank(0, served.view().trimmed_words())
    assert served.ask(17, 50, 80) == chem.similar(served.lib, 17, 50, 80)
    launches = [sp.attrs for sp in served.spans("dispatch")
                if sp.attrs.get("program") == "topn_positions"]
    assert [a["segment"] for a in launches] == list(range(len(pb.segments)))
    for a, (_, n_rows, pos, aux, p_real) in zip(launches, pb.segments):
        fixed = pos.ndim == 2
        assert a["layout"] == ("fixed" if fixed else "flat")
        assert a["k"] == min(50, n_rows) and a["positions"] == p_real
        assert a["rows"] == aux.shape[0] - (0 if fixed else 1)
        assert a["qslots"] == pb.qslots \
            == -(-max(pb.row_widths) // 8) * 8
        assert a["jit"] in ("hit", "miss")
    waits = served.spans("pbank.wave_wait")
    assert waits and waits[0].attrs["segments"] \
        == ex_mod.PBANK_INFLIGHT_SEGMENTS
    # Published at 0 from the start, read or not.
    counters = served.counters()
    for name in ("executor.pbank_launches", "executor.pbank_builds",
                 "executor.pbank_builds{kind:full}",
                 "executor.pbank_builds{kind:patch}",
                 "executor.pbank_overflow_reruns",
                 "executor.pbank_form{form:compare}",
                 "executor.pbank_form{form:gather}",
                 "executor.topn_sweeps{path:positions}",
                 "executor.topn_sweeps{path:streamed}"):
        assert name in counters, name
    assert counters["executor.pbank_builds{kind:full}"] >= 1
    # Every fingerprint fits the bank's own width: the compare, always.
    assert counters["executor.pbank_form{form:compare}"] >= len(pb.segments)


def test_the_query_rows_subset_bank_is_a_counted_upload(served):
    """The filter `Row(fingerprint=m)` reads a view truly past the leaf
    limit: a one-row subset bank a distinct query molecule, built from
    the row's positions — a `plan.bank_upload` all the same, which
    `bank_upload_mb_in_window.lib` reads; a repeat finds it cached."""
    m = 4242
    before = served.counters()
    assert served.ask(m, 50, 70) == chem.similar(served.lib, m, 50, 70)
    first = served.counters(
        lambda c: c["executor.bank_upload_bytes"]
        > before["executor.bank_upload_bytes"])
    moved = _moved(before, first, "executor.bank_upload_bytes")
    assert moved > 0 and moved % 512 == 0 and moved <= 64 * 512
    assert served.ask(m, 50, 90) == chem.similar(served.lib, m, 50, 90)
    assert _moved(first, served.counters(),
                  "executor.bank_upload_bytes") == 0


def test_a_build_is_a_span_of_the_request_that_met_no_bank(served):
    """The first TopN after a start, and after a write, builds the bank
    inside its own `plan`: span `plan.pbank_build`, counted by kind."""
    view = served.view()
    for key in [k for k in view._bank_cache if k[0] == "pbank"]:
        view._bank_cache.pop(key)
    TIMELINE.reset()
    before = served.counters()
    assert served.ask(5, 50, 70) == chem.similar(served.lib, 5, 50, 70)
    after = served.counters(
        lambda c: c["executor.pbank_builds"]
        > before["executor.pbank_builds"])
    assert _moved(before, after, "executor.pbank_builds") == 1
    assert _moved(before, after, "executor.pbank_builds{kind:full}") == 1
    (build,) = served.spans("plan.pbank_build")
    pb = view.positions_bank(0, view.trimmed_words())
    assert build.attrs["kind"] == "full"
    assert build.attrs["rows"] == MOLECULES
    assert build.attrs["positions"] == int(served.lib.popcount.sum())
    assert build.attrs["segments"] == len(pb.segments)
    assert build.attrs["bytes"] == pb.nbytes
    assert 0 <= build.attrs["pad_bytes"] < pb.nbytes
    assert served.stage_count("plan.pbank_build", 1) >= 1
    # A second call finds it: no build.
    assert served.ask(5, 50, 70) == chem.similar(served.lib, 5, 50, 70)
    assert _moved(after, served.counters(), "executor.pbank_builds") == 0


def test_the_parents_server_is_refused_before_the_load():
    asked = []

    class Parent:
        def get(self, path):
            asked.append(path)
            return {"counters": {"executor.topn_sweeps{path:positions}": 0}}

        def post_json(self, path, obj):
            assert chem.PROBE in path, "data sent to a refused server"

        def request(self, method, path, *a):
            assert chem.PROBE in path, "data sent to a refused server"

        def query(self, index, pql):
            return [{"id": 0, "count": 10}]     # upstream's rule

    with pytest.raises(BenchFailure, match="executor.pbank_launches"):
        chem_lib.load(Parent(), None)
    assert asked == ["/debug/vars"]


def test_least_bytes_prices_the_rows_the_rule_lets_through(served):
    lib = served.lib
    whole = chem_lib.bank_bytes(CONFIG)
    assert whole == int(lib.popcount.sum()) * 2 + (MOLECULES + 1) * 4
    for m in (0, 17, 4321):
        src = int(lib.popcount[m])
        by_t = [chem_lib.least_bytes("tanimoto", (m, t), CONFIG)
                for t in THRESHOLDS]
        assert by_t == sorted(by_t)             # a looser T reads more
        assert 512 <= by_t[0] and by_t[-1] <= whole + 512
        inside = (lib.popcount * 100 > src * 50) \
            & (lib.popcount * 50 < src * 100)
        assert by_t[-1] == int(lib.popcount[inside].sum()) * 2 \
            + int(inside.sum()) * 4 + 512


# ------------------------------------------------- what a body costs


def _body(rows, cols) -> bytes:
    return chem.roaring_bytes(np.asarray(rows, np.uint64),
                              np.asarray(cols, np.uint64), chem.SHARD_WIDTH)


def _open(path) -> Fragment:
    f = Fragment(path, "mole", "fingerprint", "standard", 0)
    f.open()
    return f


def test_n_bodies_make_log_n_snapshots_not_n(tmp_path, monkeypatch):
    """Bodies append to the op log as OP_ADD_ROARING records and fold by
    the byte rule: the file is rewritten when the log has grown to half
    the last snapshot (the floor patched down to a body's size here)."""
    monkeypatch.setattr(frag_mod, "OPLOG_FOLD_MIN_BYTES", 1 << 12)
    f = _open(str(tmp_path / "frag"))
    snapshots = []
    real = Fragment._snapshot
    monkeypatch.setattr(Fragment, "_snapshot", lambda self: (
        snapshots.append(self.storage.count()), real(self))[-1])
    rng = np.random.default_rng(40)
    n_bodies, rows_a_body = 128, 64
    want = set()
    for b in range(n_bodies):
        rows = np.repeat(np.arange(b * rows_a_body, (b + 1) * rows_a_body), 8)
        cols = rng.integers(0, 4096, len(rows))
        f.import_roaring(_body(rows, cols))
        want |= set(zip(rows.tolist(), cols.tolist()))
    assert 2 <= len(snapshots) <= 4 * int(np.log2(n_bodies))
    # Geometric: each fold holds at least a third more than the last.
    later = snapshots[2:]
    assert all(b >= a * 4 // 3 for a, b in zip(later, later[1:]))
    assert f.storage.count() == len(want)
    assert f.storage.op_n > 0           # the tail holds unfolded bodies
    f.close()


def test_a_reopen_without_a_clean_stop_holds_every_acknowledged_bit(
        tmp_path):
    path = str(tmp_path / "frag")
    f = _open(path)
    rng = np.random.default_rng(41)
    want = set()
    for b in range(6):
        rows = np.repeat(np.arange(b * 50, (b + 1) * 50), 10)
        cols = rng.integers(0, 4096, len(rows))
        f.import_roaring(_body(rows, cols))
        want |= set(zip(rows.tolist(), cols.tolist()))
    size = os.path.getsize(path)
    assert f.storage.op_n > 0 and f.storage.oplog_bytes > 0
    # No close(), no flush: the process is gone, the file is what the
    # kernel holds. Another fragment object opens it.
    again = _open(path)
    assert os.path.getsize(path) >= size
    assert again.storage.count() == len(want)
    for r, c in list(want)[::37]:
        assert again.bit(r, c)
    assert again.row_ids() == tuple(range(300))
    again.close()
    f.close()


def test_an_acknowledged_body_is_synced_to_the_disk(tmp_path, monkeypatch):
    """The snapshot every body used to end in fsynced its file; the
    record a body is now is fsynced too, once, before the call returns
    (a fold syncs its own file besides)."""
    f = _open(str(tmp_path / "frag"))
    synced = []
    real = os.fsync
    monkeypatch.setattr(frag_mod.os, "fsync", lambda fd: (
        synced.append((fd, os.fstat(fd).st_size)), real(fd))[-1])
    for b in range(3):
        before = len(synced)
        f.import_roaring(_body([b, b], [1, 2 + b]))
        assert len(synced) == before + 1
        fd, size = synced[-1]
        # The op log's own handle, with the record already in the file.
        assert fd == f._file.fileno() and size == os.path.getsize(f.path)
    f.close()


def test_a_body_that_came_with_an_op_tail_is_logged_flat(tmp_path):
    path = str(tmp_path / "frag")
    f = _open(path)
    donor = Bitmap()
    donor.add_batch(np.asarray([5, 70000, (3 << 20) + 9], np.uint64))
    data = donor.write_bytes()
    import io
    tail = io.BytesIO()
    donor.op_writer = tail
    donor.add((7 << 20) + 1)
    donor.remove(70000)
    f.import_roaring(data + tail.getvalue())
    again = _open(path)
    assert sorted(again.storage.slice().tolist()) \
        == [5, (3 << 20) + 9, (7 << 20) + 1]
    again.close()
    f.close()


def test_clear_still_clears_and_snapshots(tmp_path, monkeypatch):
    path = str(tmp_path / "frag")
    f = _open(path)
    f.import_roaring(_body([1, 1, 2, 2], [10, 11, 10, 12]))
    snapshots = []
    real = Fragment._snapshot
    monkeypatch.setattr(Fragment, "_snapshot", lambda self: (
        snapshots.append(1), real(self))[-1])
    f.import_roaring(_body([1, 2], [11, 10]), clear=True)
    assert snapshots == [1]
    assert sorted(f.storage.slice().tolist()) \
        == [(1 << 20) + 10, (2 << 20) + 12]
    again = _open(path)
    assert sorted(again.storage.slice().tolist()) \
        == [(1 << 20) + 10, (2 << 20) + 12]
    again.close()
    f.close()


def _tailed_file(n_rows: int, tail_rows: int):
    """A fragment file as a crash leaves it: a snapshot of `n_rows`
    one-container rows and an op tail of every record kind — bodies as
    OP_ADD_ROARING, a batch add, a batch remove, single adds and a
    single remove."""
    import io
    rng = np.random.default_rng(42)
    rows = np.repeat(np.arange(n_rows), 6)
    snap = Bitmap.from_bytes(_body(rows, rng.integers(0, 4096, len(rows))))
    data = snap.write_bytes()
    tail = io.BytesIO()
    snap.op_writer = tail
    for lo in range(n_rows, n_rows + tail_rows, 500):
        body = _body(np.repeat(np.arange(lo, lo + 500), 5),
                     rng.integers(0, 4096, 2500))
        snap._append_roaring_record(body, 0)
        snap.union_in_place(Bitmap.from_bytes(body))
    snap.add_batch(np.asarray([(3 << 20) + 77, (n_rows << 20) + 5,
                               ((n_rows + 10**6) << 20) + 1], np.uint64))
    snap.remove_batch(snap.slice()[:40])
    snap.add((5 << 20) + 4000, ((n_rows + 2 * 10**6) << 20) + 9)
    snap.remove(int(snap.slice()[100]))
    return data + tail.getvalue(), snap, len(data)


@pytest.mark.parametrize("containers, torn", [(20000, 0), (20000, 7),
                                              (300, 0)])
def test_an_op_tailed_file_loads_its_sections_apart_and_the_same(
        monkeypatch, containers, torn):
    """A file with an op tail has its snapshot section loaded
    compactly and the tail replayed record by record, whatever its
    size; the result — bits, op accounting, torn tail — is the
    whole-file parse's."""
    from pilosa_tpu import native
    from pilosa_tpu.storage import roaring
    if not native.available():
        pytest.skip("no native library: one loader only")
    data, want, snapshot_bytes = _tailed_file(containers, 3000)
    if torn:
        data = data[:-torn]     # the last single-bit record, torn
    calls = []
    real = native.roaring_load_ex
    monkeypatch.setattr(native, "roaring_load_ex", lambda d, **kw: (
        calls.append(len(d)), real(d, **kw))[-1])
    split = Bitmap.from_bytes(data, tolerate_torn_tail=True)
    # Never the whole file (a roaring record's payload is parsed too).
    assert calls[0] == snapshot_bytes and len(data) not in calls
    monkeypatch.setattr(roaring, "_split_load_at", lambda d: None)
    whole = Bitmap.from_bytes(data, tolerate_torn_tail=True)
    assert calls[-1] == len(data)
    monkeypatch.setattr(native, "available", lambda: False)
    plain = Bitmap.from_bytes(data, tolerate_torn_tail=True)
    for got in (split, whole):
        assert np.array_equal(got.slice(), plain.slice())
        assert (got.op_n, got.op_n_small, got.oplog_bytes,
                got.snapshot_bytes, got.tail_dropped) \
            == (plain.op_n, plain.op_n_small, plain.oplog_bytes,
                plain.snapshot_bytes, plain.tail_dropped)
    assert plain.snapshot_bytes == snapshot_bytes
    assert plain.tail_dropped == (13 - torn if torn else 0)   # what is left
    if not torn:
        assert np.array_equal(plain.slice(), want.slice())
    # A torn tail is an error for bytes that came over the wire.
    if torn:
        monkeypatch.undo()
        with pytest.raises(ValueError):
            Bitmap.from_bytes(data)


def test_a_replayed_record_moves_its_containers():
    """A file that ends in roaring records opens into views of the
    records' load blocks, as a snapshot opens into views of its own:
    a new container is the payload's array itself with its count, one
    the snapshot already has is OR-ed in."""
    from pilosa_tpu import native
    from pilosa_tpu.storage.roaring import encode_op_roaring
    if not native.available():
        pytest.skip("no native library: one loader only")
    base, more = Bitmap(), Bitmap()
    base.add_batch(np.asarray([1, 9, (1 << 16) + 4], np.uint64))
    more.add_batch(np.asarray([(1 << 16) + 5, (1 << 16) + 4,
                               (2 << 16) + 7, (2 << 16) + 8], np.uint64))
    for data in (Bitmap().write_bytes(), base.write_bytes()):
        got = Bitmap.from_bytes(data + encode_op_roaring(more.write_bytes()))
        want = (base.copy() if len(data) > 8 else Bitmap())
        want.union_in_place(more)
        assert got.slice().tolist() == want.slice().tolist()
        assert got.count() == want.count() and got.op_n == more.count()
        moved = got.containers[2]
        assert moved.base is not None and moved.tolist() == [7, 8]
        assert got._counts[2] == 2
    # The container both hold is OR-ed in, not replaced.
    assert got.contains((1 << 16) + 4) and got.contains((1 << 16) + 5)


def test_a_crashed_library_fragment_opens_without_a_dense_parse(
        tmp_path, monkeypatch):
    """A fragment of many one-container rows whose file ends in an op
    tail (no clean stop after the last bodies) opens through the split
    load: no parse of the whole file, which would hold every container
    dense."""
    from pilosa_tpu import native
    if not native.available():
        pytest.skip("no native library: one loader only")
    path = str(tmp_path / "frag")
    f = _open(path)
    rng = np.random.default_rng(43)
    total = 0
    for b in range(10):
        rows = np.repeat(np.arange(b * 2000, (b + 1) * 2000), 8)
        f.import_roaring(_body(rows, rng.integers(0, 4096, len(rows))))
        total = f.storage.count()
    assert f.storage.oplog_bytes > 0
    size = os.path.getsize(path)
    seen = []
    real = native.roaring_load_ex
    monkeypatch.setattr(native, "roaring_load_ex", lambda d, **kw: (
        seen.append(len(d)), real(d, **kw))[-1])
    again = _open(path)
    assert size not in seen and seen[0] == again.storage.snapshot_bytes
    assert again.storage.count() == total
    assert again.row_ids() == tuple(range(20000))
    again.close()
    f.close()
