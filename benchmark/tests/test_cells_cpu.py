"""A tiny run of each traffic mix against a real server on the CPU
(one shard, 15 grid rows): the reference answers equal the server's;
with the answers altered on their way out of the server `correct`
comes out false; and without a TPU the command exits non-zero with no
result."""

import json
import subprocess
import sys
import time

import pytest

from conftest import BENCH, CHECKOUT
from harness import cell, tamper

TINY = {"shards": 1, "grid_rows": 15}
CELLS = ["taxi-chip.topn-sweep", "taxi-chip.point-serial"]


def _run(workload, seed, **kw):
    return cell.run_cell(CHECKOUT, workload, seed, 2.0, False,
                         time.monotonic(), platform="cpu", sizes=TINY, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_reference_equals_server_on_cpu(workload, capfd):
    res = _run(workload, 2**31 + 7)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 20
    assert res["device"]["platform"] == "cpu"   # never mistaken for a chip
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # Each number compared is printed beside its limit.
    check = json.loads(out.strip().splitlines()[-1])["check"]
    assert check["answers_differing"] == 0 == check["answers_differing_limit"]
    assert check["answers_compared"] >= 20
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answers_come_out_not_correct(workload, monkeypatch):
    """The control, at a size a test can hold: the harness's look for
    a chip is skipped (`platform="cpu"`), the rest of a run is driven,
    and every 5th answer is altered on its way to the load generator."""
    monkeypatch.setattr(cell, "Server", tamper.TamperedServer)
    res = _run(workload, 11)
    assert res["correct"] is False
    assert res["failed"] >= 3
    assert res["attempted"] > 20
    assert tamper.TamperedServer.altered >= res["failed"] >= 3


def test_control_tool_reports_not_correct():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/control.py", "--workload",
         "taxi-chip.point-serial", "--seconds", "1.5", "--seeds", "5", "6",
         "--platform", "cpu", "--shards", "1", "--grid-rows", "15"],
        cwd=CHECKOUT, capture_output=True, timeout=600)
    lines = [ln for ln in out.stdout.decode().splitlines()
             if ln.startswith("CONTROL ")]
    assert out.returncode == 0 and len(lines) == 2
    assert all('"correct": false' in ln for ln in lines)


def test_no_tpu_exits_nonzero_with_no_result():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload",
         "taxi-chip.topn-sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, capture_output=True, timeout=300)
    assert out.returncode != 0
    assert b'"metrics"' not in out.stdout and b'"correct"' not in out.stdout
