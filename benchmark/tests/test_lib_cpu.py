"""The cell PR 40 adds, off the chip: `chem-lib-chip.tanimoto-library`
at 8,192 molecules against a real server on the CPU whose resident
limit, leaf-bank limit and segment size are set under the library's
(the server child inherits the environment: at the cell's own size the
defaults do it), so that every TopN is answered from the positions bank
in several segments. `correct`; altered answers come out not correct;
`least_bytes` never prices an answer over the whole bank; the parent's
server is refused before the load; the manifest's rules with the new
entries."""

import json
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT
from datasets import chem, chem_lib
from harness import cell, loadgen
from harness.manifest import Manifest
from harness.server import BenchFailure

LIB = "chem-lib-chip.tanimoto-library"
TINY = {"grid_rows": 8192}
NEW = {"topn_positions_share.lib", "lib_answer_roofline",
       "pbank_launches_per_op.lib", "pbank_wave_wait_mean_ms.lib",
       "pbank_builds_in_window.lib", "bank_upload_mb_in_window.lib",
       "rows_fetched_per_op.lib"}
# 8,192 molecules + the zero slot pad to 16,384 slots of 512 B = 8 MiB.
SMALL_LIMITS = {"PILOSA_TPU_TOPN_BANK_BYTES": str(1 << 20),
                "PILOSA_TPU_BANK_BYTES": str(1 << 20),
                "PILOSA_TPU_PBANK_SEGMENT": str(1 << 16),
                "JAX_PLATFORMS": "cpu"}


@pytest.fixture
def small_limits(monkeypatch):
    for k, v in SMALL_LIMITS.items():
        monkeypatch.setenv(k, v)


def _run(seed):
    return cell.run_cell(CHECKOUT, LIB, seed, 3.0, False, time.monotonic(),
                         platform="cpu", sizes=TINY)


def test_lib_rehearsal_equals_the_reference(small_limits, capfd):
    res = _run(2**31 + 40)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 20 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
    check = json.loads(out.strip().splitlines()[-1])
    assert check["check"]["answers_differing"] == 0
    assert check["check"]["answers_compared"] \
        == check["check"]["answers_in_window"]      # every answer
    assert check["generator"]["clients"] == 16
    assert check["window"]["compiles"] == 0


def test_altered_lib_answers_come_out_not_correct(small_limits):
    out = subprocess.run(
        [sys.executable, f"{BENCH}/control.py", "--workload", LIB,
         "--seconds", "3", "--seeds", "5", "--platform", "cpu",
         "--grid-rows", "8192"], capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": false' in out.stdout


def test_a_rehearsal_under_the_resident_limit_is_refused_after_the_load(
        monkeypatch):
    """Without the small limits 8,192 molecules are a resident bank:
    the source's query is answered by a sweep, not from positions, and
    the loader says so instead of measuring another mechanism."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for k in SMALL_LIMITS:
        if k.startswith("PILOSA_"):
            monkeypatch.delenv(k, raising=False)
    with pytest.raises(BenchFailure, match="positions bank"):
        cell.run_cell(CHECKOUT, LIB, 7, 1.0, False, time.monotonic(),
                      platform="cpu", sizes={"grid_rows": 2048})


def test_the_parents_server_is_refused_before_the_load():
    asked = []

    class Parent:
        def get(self, path):
            asked.append(path)
            return {"counters": {"executor.sweep_launches": 0}}

        def post_json(self, path, obj):
            assert chem.PROBE in path, "data sent to a refused server"

        def request(self, method, path, *a):
            assert chem.PROBE in path, "data sent to a refused server"

        def query(self, index, pql):
            return [{"id": 0, "count": 10}]

    with pytest.raises(BenchFailure, match="executor.pbank_launches"):
        chem_lib.load(Parent(), None)
    assert asked == ["/debug/vars"]


def test_least_bytes_is_at_most_the_whole_bank_for_every_request():
    man = Manifest(CHECKOUT)
    cfg = dict(man.config("chem-lib-chip"), **TINY)
    traffic = man.load_json("traffic", "tanimoto-library")
    lib = chem_lib.make(cfg, cfg["shard_width"])
    positions = int(lib.popcount.sum())     # waits for the library
    whole = chem_lib.bank_bytes(cfg)
    assert whole == positions * 2 + (lib.n + 1) * 4
    seen = set()
    for c in range(traffic["clients"]):
        stream = loadgen.client_stream(chem_lib, lib, traffic, 2**31 + 40, c)
        for (family, pql, ref), _ in zip(stream, range(40)):
            m, t = ref.constants
            assert ref.family == family == "tanimoto"
            assert pql == chem.pql(m, 50, t)
            b = chem_lib.least_bytes(family, ref.constants, cfg)
            assert 512 <= b <= whole + 512
            seen.add(t)
    assert seen == {90, 80, 70, 50}
    # By hand, one request: the rows whose on-bit count the rule lets
    # through, 2 B a position and 4 B a row start, and the query row.
    m, t = 77, 80
    src = int(lib.popcount[m])
    inside = (lib.popcount * 100 > src * t) & (lib.popcount * t < src * 100)
    assert chem_lib.least_bytes("tanimoto", (m, t), cfg) \
        == int(lib.popcount[inside].sum()) * 2 + int(inside.sum()) * 4 + 512
    # The reference skips exactly the rows outside it.
    past = {p["id"] for p in chem.similar(lib, m, 0, t)}
    assert past <= set(np.flatnonzero(inside).tolist())


def test_the_family_queries_are_the_sources_and_a_page_a_threshold():
    lib = chem_lib.make({"data_seed": 20240229, "grid_rows": 2048}, 1 << 20)
    texts = [q for q, _ in chem_lib.family_queries(lib)]
    assert texts[0] \
        == "TopN(fingerprint, Row(fingerprint=6), tanimotoThreshold=90)"
    assert texts[1:] == [chem.pql(6, 50, t) for t in (90, 80, 70, 50)]
    # The expected answers are computed when `equal` compares them (the
    # first query is posted while the library is still in the making).
    _, want = chem_lib.family_queries(lib)[0]
    assert isinstance(want, chem_lib.Reference)
    assert chem_lib.equal(chem.similar(lib, 6, 0, 90), want)
    assert not chem_lib.equal([], want) and want()[0]["id"] == 6


def test_the_manifest_lists_as_this_pr_leaves_them():
    man = Manifest(CHECKOUT)
    doc = man.doc
    cells = {w["name"]: w for w in doc["workloads"]}
    assert list(cells)[-1] == LIB and len(cells) == 7
    assert cells[LIB] == dict(cells[LIB], config="chem-lib-chip",
                              traffic="tanimoto-library", chips=1)
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    e2e = [m["name"] for m in man.metrics_for("end_to_end", LIB)]
    assert e2e == ["sweep_qps", "setup_s"]
    sweep = next(m for m in doc["end_to_end"] if m["name"] == "sweep_qps")
    assert sweep["workloads"][-1] == LIB and sweep["bound"] == 0.1
    mine = {m["name"] for m in man.metrics_for("per_layer", LIB)}
    assert NEW <= mine and "device_idle_share.sweep" in mine
    assert not {"topn_sweep_roofline", "tanimoto_sweep_roofline",
                "topn_resident_share.chem", "ssb_answer_roofline"} & mine
    # No new reader: every metric of the cell reads through a reader
    # an earlier PR wrote.
    old_readers = {"answer_roofline", "counter_share", "counter_per_op",
                   "histogram_mean", "counter_delta", "counter_scaled"}
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [LIB] and m["moves"] == "sweep_qps"
            assert man.metric_spec(m["name"])["reader"] in old_readers
    for name in mine:
        man.load_module("readers", man.metric_spec(name)["reader"])
    # The contract's form for what this PR adds.
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for entry in (doc["configs"][-1], cells[LIB]):
        assert name_re.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    conf = doc["configs"][-1]
    assert conf["name"] == "chem-lib-chip" and conf["reduced"] == ["molecules"]
    assert 1 <= len(conf["source"]) <= 200
    cfg = man.config("chem-lib-chip")
    assert cfg["source"] == conf["source"]
    assert cfg["dataset"] == "chem_lib" and cfg["reduced"] == ["molecules"]
    assert cfg["grid_rows"] == cfg["molecules"] == 2**23 - 1
    assert cfg["shards"] == 1 and cfg["n_days"] == 0
    assert cfg["server_config"] == {"mesh_devices": 1}
    assert set(cfg["guarantees"]) == {"exact", "server_defaults", "read_only"}
    chem_cfg = man.config("chem-chip")
    assert cfg["guarantees"] == chem_cfg["guarantees"]
    assert cfg["data_seed"] == chem_cfg["data_seed"]
    assert set(chem_cfg["assumed"]) <= set(cfg["assumed"])
    # Dense, the field is twice the resident limit.
    assert chem.bank_bytes(cfg) == 4 << 30
    traffic = man.load_json("traffic", "tanimoto-library")
    assert traffic["clients"] == 16 and traffic["row_skew"] == 0.0
    assert [e["threshold"] for e in traffic["cycle"]] == [90, 80, 70, 50]
    assert all(e["n"] == 50 for e in traffic["cycle"])
    assert traffic["warmup"]["pinned"] == traffic["cycle"]
    assert traffic["trace"] == {"at_s": 5.0, "seconds": 6.0}
    assert traffic["verify_sample"] == 512
    assert traffic["prune_cache_from"] == "window"


def test_the_reference_a_block_at_a_time_is_chem_similar(monkeypatch):
    """`Reference` runs `chem.similar` over blocks of the library and
    merges their pairs: pair for pair what `chem.similar` gives for the
    whole, at every threshold, with and without `n`, ties at the cut
    among them."""
    monkeypatch.setattr(chem_lib, "BLOCK_ROWS", 700)
    lib = chem_lib.make({"data_seed": 20240229, "grid_rows": 5000}, 1 << 20)
    assert [b.r0 for b in lib.blocks] == list(range(0, 5000, 700))
    assert sum(b.n for b in lib.blocks) == 5000
    whole = chem.make({"data_seed": 20240229, "grid_rows": 5000}, 1 << 20)
    rng = np.random.default_rng(40)
    for m in [6, 4999] + rng.integers(0, 5000, 30).tolist():
        for n in (0, 50, 3):
            for t in (90, 80, 70, 50, 1):
                got = chem_lib.Reference(lib, "tanimoto", m, n, t)()
                assert got == chem.similar(whole, m, n, t), (m, n, t)
