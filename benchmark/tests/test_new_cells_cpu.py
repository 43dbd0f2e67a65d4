"""The cell PR 30 adds, off the chip: `chem-chip.tanimoto-sweep` at 4,096
molecules on the CPU against a real server, equal to its plain
reference; altered answers come out not correct; the reference's own
rule; and the manifest's lists as the PR leaves them."""

import json
import subprocess
import sys
import time

import numpy as np

from conftest import BENCH, CHECKOUT
from datasets import chem
from harness import cell, tamper
from harness.manifest import Manifest

CHEM = "chem-chip.tanimoto-sweep"


def _run(workload, seed):
    return cell.run_cell(CHECKOUT, workload, seed, 2.0, False,
                         time.monotonic(), platform="cpu",
                         sizes={"grid_rows": 4096})


def test_chem_rehearsal_equals_the_reference(capfd):
    res = _run(CHEM, 2**31 + 30)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 20 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
    check = json.loads(out.strip().splitlines()[-1])["check"]
    assert check["answers_differing"] == 0 == check["answers_differing_limit"]
    assert check["families_compared"] == ["tanimoto"]


def test_altered_chem_answers_come_out_not_correct(monkeypatch):
    monkeypatch.setattr(cell, "Server", tamper.TamperedServer)
    res = _run(CHEM, 30)
    assert res["correct"] is False and res["failed"] >= 3
    assert tamper.TamperedServer.altered >= res["failed"]


def test_control_tool_on_the_chem_cell():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/control.py", "--workload", CHEM,
         "--seconds", "1.5", "--seeds", "5", "--platform", "cpu",
         "--grid-rows", "4096"], capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": false' in out.stdout


def test_the_reference_applies_upstreams_rule():
    """Ten molecules by hand: 7 of 10 shared bits is exactly 70 % and is
    out at T = 70, in at 69; counts outside (src*T/100, src*100/T) are
    pruned; equal counts go by the smaller id."""
    lib = chem.Library.__new__(chem.Library)
    prints = {0: range(10), 1: range(7), 2: range(3, 10), 3: range(8),
              4: [*range(10), *range(20, 40)], 5: [50, 51]}
    lib.n = lib.grid_rows = len(prints)
    lib.popcount = np.array([len(p) for p in prints.values()], np.int64)
    lib.offsets = np.concatenate([[0], np.cumsum(lib.popcount)])
    lib.bits = np.concatenate([list(p) for p in prints.values()]) \
        .astype(np.uint16)
    mol = np.repeat(np.arange(lib.n, dtype=np.uint32), lib.popcount)
    order = np.argsort(lib.bits, kind="stable")
    lib.post = mol[order]
    lib.post_offsets = np.concatenate(
        [[0], np.cumsum(np.bincount(lib.bits, minlength=chem.BITS))])

    def ids(t, n=0):
        return [(p["id"], p["count"]) for p in chem.similar(lib, 0, n, t)]

    assert ids(70) == [(0, 10), (3, 8)]
    assert ids(69) == [(0, 10), (3, 8), (1, 7), (2, 7)]
    assert ids(69, n=3) == [(0, 10), (3, 8), (1, 7)]
    # Molecule 4 holds all ten bits among its 30: a third, which rounds
    # up to 34 — past T = 33, not past 34 (where its 30 bits are also
    # outside the pruning's 10 * 100 / 34).
    assert ids(34) == [(0, 10), (3, 8), (1, 7), (2, 7)]
    assert ids(33)[:2] == [(0, 10), (4, 10)]


def test_the_manifest_lists_as_this_pr_leaves_them():
    man = Manifest(CHECKOUT)
    cells = {w["name"]: w for w in man.doc["workloads"]}
    assert cells[CHEM]["chips"] == 1
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    per_layer = {m["name"]: m for m in man.doc["per_layer"]}
    assert per_layer["topn_sweep_roofline"]["workloads"] == [
        "taxi-chip.topn-sweep", "taxi-host4.topn-sweep"]
    mine = [m["name"] for m in man.metrics_for("per_layer", CHEM)]
    assert {"tanimoto_sweep_roofline", "topn_resident_share.chem",
            "finish_select_mean_ms.chem", "rows_fetched_per_op.chem"} \
        <= set(mine)
    assert "topn_sweep_roofline" not in mine
    for name in mine:
        man.load_module("readers", man.metric_spec(name)["reader"])
    cfg = man.config("chem-chip")
    assert cfg["reduced"] == [] and cfg["grid_rows"] == 2097151
    assert chem.bank_bytes(cfg) == 1 << 30
