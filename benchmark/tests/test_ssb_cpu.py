"""The cell PR 32 adds, off the chip: `ssb-chip.flights` on one shard
and a few thousand orders against a real server on the CPU, equal to
its plain reference; altered answers come out not correct; a server
without the operator is refused before the load; `least_bytes` by hand;
the manifest's lists as the PR leaves them."""

import json
import subprocess
import sys
import time

import pytest

from conftest import BENCH, CHECKOUT
from datasets import ssb
from harness import cell, tamper
from harness.manifest import Manifest
from harness.server import BenchFailure

SSB = "ssb-chip.flights"
TINY = {"shards": 1, "grid_rows": 3000}
ROW = 16 * (1 << 20) // 8       # an operand row at 16 shards: 2 MiB
NEW = {"groupby_aggregate_mean_ms.ssb", "groupby_groups_per_op.ssb",
       "groupsum_launches_per_op.ssb", "bank_upload_mb_in_window.ssb",
       "ssb_answer_roofline"}


def _run(seed):
    return cell.run_cell(CHECKOUT, SSB, seed, 2.0, False, time.monotonic(),
                         platform="cpu", sizes=TINY)


def test_ssb_rehearsal_equals_the_reference(capfd):
    res = _run(2**31 + 32)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 16 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
    check = json.loads(out.strip().splitlines()[-1])["check"]
    assert check["answers_differing"] == 0 == check["answers_differing_limit"]
    assert check["answers_compared"] == check["answers_in_window"]
    assert set(check["families_compared"]) <= set(ssb.FAMILIES)


def test_altered_ssb_answers_come_out_not_correct(monkeypatch):
    monkeypatch.setattr(cell, "Server", tamper.TamperedServer)
    res = _run(32)
    assert res["correct"] is False and res["failed"] >= 3
    assert tamper.TamperedServer.altered >= res["failed"]


def test_control_tool_on_the_ssb_cell():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/control.py", "--workload", SSB,
         "--seconds", "1.5", "--seeds", "5", "--platform", "cpu",
         "--shards", "1", "--grid-rows", "3000"], capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": false' in out.stdout


def test_a_server_without_the_operator_is_refused_before_the_load():
    """What the parent commit does with the probe: 200, counts alone."""
    posted = []

    class Parent:
        def post_json(self, path, obj):
            posted.append(path)

        def request(self, method, path, *a):
            posted.append(path)

        def query(self, index, pql):
            return [{"group": [{"field": "g", "rowID": 1}], "count": 2},
                    {"group": [{"field": "g", "rowID": 2}], "count": 1}]

    lo = ssb.Lineorder(1, 1, 10, 1 << 20)
    with pytest.raises(BenchFailure, match="no GroupBy aggregate"):
        ssb.load(Parent(), lo)
    assert posted and all(p.split("/")[2] == ssb.PROBE for p in posted)


def test_least_bytes_by_hand():
    cfg = Manifest(CHECKOUT).config("ssb-chip")
    fixed = {f: fam.fixed for f, fam in ssb.FAMILIES.items()}
    # Q2.1: Rows(d_year) 7 + Rows(p_brand1) 1,000, the filter's category
    # and region rows, lo_revenue's 24 planes and its not-null plane.
    assert ssb.least_bytes("q2.1", fixed["q2.1"], cfg) == 1034 * ROW
    # Q3.2: two city fields of 250, d_year's 7 (the filter's six years
    # are among them), two nation rows, the same 25 planes.
    assert ssb.least_bytes("q3.2", fixed["q3.2"], cfg) == 534 * ROW
    # Q1.1: one year row; discount 4 + 1, quantity 6 + 1, and
    # lo_revenue_computed's 27 + 1 planes.
    assert ssb.least_bytes("q1.1", fixed["q1.1"], cfg) == 41 * ROW
    # Q4.3: 7 + 250 + 1,000, region, nation and category rows beside
    # the years, lo_profit's 24 + 1.
    assert ssb.least_bytes("q4.3", fixed["q4.3"], cfg) == 1285 * ROW
    # Q2.2's eight brand rows are among Rows(p_brand1)'s thousand.
    assert ssb.operand_rows("q2.2", fixed["q2.2"]) == 7 + 1000 + 1 + 25


def test_the_reference_by_hand():
    """Six rows written out: two groups, a signed profit."""
    import numpy as np
    lo = ssb.Lineorder.__new__(ssb.Lineorder)
    lo.n = 6
    lo.p_category = np.array([1, 1, 1, 2, 1, 1], np.uint8)
    lo.s_region = np.array([1, 1, 1, 1, 0, 1], np.uint8)
    lo.d_year = np.array([1993, 1993, 1992, 1993, 1993, 1993], np.uint16)
    lo.p_brand1 = np.array([41, 41, 40, 41, 41, 79], np.uint16)
    lo.lo_revenue = np.array([10, 20, 5, 99, 99, 7], np.int32)
    got = ssb.answer(lo, "q2.1", {"category": 1, "region": 1})
    assert got == [
        {"group": [{"field": "d_year", "rowID": 1992},
                   {"field": "p_brand1", "rowID": 40}], "count": 1, "sum": 5},
        {"group": [{"field": "d_year", "rowID": 1993},
                   {"field": "p_brand1", "rowID": 41}], "count": 2, "sum": 30},
        {"group": [{"field": "d_year", "rowID": 1993},
                   {"field": "p_brand1", "rowID": 79}], "count": 1, "sum": 7}]


def test_the_manifest_lists_as_this_pr_leaves_them():
    man = Manifest(CHECKOUT)
    cells = {w["name"]: w for w in man.doc["workloads"]}
    assert cells[SSB]["chips"] == 1 and cells[SSB]["config"] == "ssb-chip"
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    e2e = [m["name"] for m in man.metrics_for("end_to_end", SSB)]
    assert e2e == ["sweep_qps", "setup_s"]
    mine = {m["name"] for m in man.metrics_for("per_layer", SSB)}
    assert NEW <= mine
    # A GroupBy or a Sum never asks the result cache: the reader finds
    # nothing to read there, so the cell lists no hit share.
    assert not {"topn_sweep_roofline", "tanimoto_sweep_roofline",
                "topn_resident_share.sweep", "result_cache_hit_share.ssb",
                "result_cache_hit_share.point"} & mine
    for name in mine:
        man.load_module("readers", man.metric_spec(name)["reader"])
    for m in man.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [SSB] and m["moves"] == "sweep_qps"
    cfg = man.config("ssb-chip")
    assert cfg["reduced"] == ["lineorder_rows"]
    assert cfg["lineorder_rows"] == cfg["shards"] * cfg["shard_width"]
    assert cfg["server_config"] == {"mesh_devices": 1}
    assert set(cfg["source_queries"]) - {"how_used"} == set(ssb.FAMILIES)
