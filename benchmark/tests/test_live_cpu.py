"""The cell PR 38 adds, off the chip: `taxi-live-chip.report-ingest` at
2 shards / 15 grid rows against a real server on the CPU — `correct`
twice in ONE state directory (the second run re-opens what the first
wrote and sends its arrivals in another order); altered answers come
out not correct; the parent's server is refused before the load; the
new reader's arithmetic; the manifest's lists as the PR leaves them."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, CHECKOUT
from datasets import taxi_live
from harness import cell
from harness.manifest import Manifest
from harness.server import BenchFailure
from readers import bank_patch_roofline

LIVE = "taxi-live-chip.report-ingest"
TINY = {"shards": 2, "grid_rows": 15}
NEW = {"topn_sweep_roofline.live", "sweep_launches_per_op.live",
       "filter_launches_per_op.live", "topn_resident_share.live",
       "range_fold_share.live", "write_apply_mean_ms.live",
       "bank_patch_mean_ms.live", "bank_patches_per_write.live",
       "bank_rebuilds_in_window.live", "batch_flush_share.live",
       "bank_patch_roofline.live"}


def _run(seed):
    return cell.run_cell(CHECKOUT, LIVE, seed, 3.0, False, time.monotonic(),
                         platform="cpu", sizes=TINY)


def test_live_rehearsal_is_correct_twice_in_one_state_directory(capfd):
    man = Manifest(CHECKOUT)
    state = cell.state_dir(man.roots[0], dict(man.config("taxi-live-chip"),
                                              **TINY), "cpu")
    seen = []
    for seed in (2**31 + 38, 2**31 + 38):       # a seed that repeats
        res = _run(seed)
        out = capfd.readouterr().out
        assert res["correct"] is True and res["failed"] == 0
        assert res["attempted"] > 200 and res["device"]["platform"] == "cpu"
        assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
        check = json.loads(out.strip().splitlines()[-1])
        assert check["check"]["answers_differing"] == 0
        assert {"ride_set", "report_period", "report_tod"} \
            <= set(check["check"]["families_compared"])
        seen.append(check["families"]["ride_set"][0])
    assert min(seen) >= 10
    # Nothing beside the data directory carries state between runs.
    assert set(os.listdir(state)) <= {"data", "jax_cache", "loaded.json",
                                      "server.log", "server.toml",
                                      "trace_ctl"}


def test_control_tool_on_the_live_cell():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/control.py", "--workload", LIVE,
         "--seconds", "3", "--seeds", "5", "--platform", "cpu",
         "--shards", "2", "--grid-rows", "15"], capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": false' in out.stdout


def test_the_parents_server_is_refused_before_the_load():
    asked = []

    class Parent:
        def get(self, path):
            asked.append(path)
            return {"counters": {"executor.sweep_launches": 0}}

        def post_json(self, path, obj):
            raise AssertionError("data sent to a refused server")

    with pytest.raises(BenchFailure, match="executor.bank_patches"):
        taxi_live.load(Parent(), None)
    assert asked == ["/debug/vars"]


def test_module_ops_takes_the_ops_inside_the_modules_intervals():
    planes = [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["jit_bank_patch(7)", 100, 50],
                        ["jit_topn_sweep(9)", 200, 900],
                        ["jit_bank_patch(7)", 2000, 40]],
        "XLA Ops": [["%copy.3 = u32[4]", 100, 30], ["%fusion.1 = x", 135, 10],
                    ["%popcnt_reduce_fusion = x", 200, 900],
                    ["%copy.3 = u32[4]", 2000, 25],
                    ["%dynamic-update-slice.1 = x", 2020, 15]]}}]
    launches, seconds = bank_patch_roofline.module_ops(planes,
                                                       "jit_bank_patch")
    assert launches == 2 and abs(seconds - 75e-9) < 1e-15
    assert bank_patch_roofline.module_ops(planes, "jit_absent") == (0, 0.0)


def test_the_reader_reads_nothing_without_the_counters():
    ctx = {"trace": {"busy_s": 1.0}, "before": {"vars": {"counters": {}}},
           "after": {"vars": {"counters": {}}}}
    assert bank_patch_roofline.read(ctx, "jit_bank_patch",
                                    "patch_cell_bytes") is None
    assert bank_patch_roofline.read(dict(ctx, trace=None), "jit_bank_patch",
                                    "patch_cell_bytes") is None


def test_the_cycle_is_ycsb_ds_mix():
    traffic = Manifest(CHECKOUT).load_json("traffic", "report-ingest")
    names = [e if isinstance(e, str) else e["family"]
             for e in traffic["cycle"]]
    assert len(names) == 200 and traffic["clients"] == 64
    assert names.count("ride_set") == 10 and names.count("ride_readback") == 1
    assert all(names[i] == "ride_set" for i in range(0, 200, 20))
    reports = [e for e in traffic["cycle"] if not isinstance(e, str)]
    assert len(reports) == 189
    for fam in ("report_period", "report_dist_lt", "report_amount_gt",
                "report_miles_dollars", "report_tod"):
        assert sum(e["family"] == fam for e in reports) in (37, 38)
    assert {e["span"] for e in reports} == {1, 7}
    assert {e["field"] for e in reports} == set(taxi_live.GRID_FIELDS)


def test_the_manifest_lists_as_this_pr_leaves_them():
    man = Manifest(CHECKOUT)
    cells = {w["name"]: w for w in man.doc["workloads"]}
    assert cells[LIVE]["chips"] == 1
    assert cells[LIVE]["config"] == "taxi-live-chip"
    assert cells[LIVE]["traffic"] == "report-ingest"
    e2e = [m["name"] for m in man.metrics_for("end_to_end", LIVE)]
    assert e2e == ["sweep_qps", "setup_s"]
    mine = {m["name"] for m in man.metrics_for("per_layer", LIVE)}
    assert NEW <= mine and "device_idle_share.sweep" in mine
    assert not {"topn_sweep_roofline", "tanimoto_sweep_roofline",
                "ssb_answer_roofline", "topn_resident_share.sweep"} & mine
    for name in mine:
        man.load_module("readers", man.metric_spec(name)["reader"])
    for m in man.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [LIVE] and m["moves"] == "sweep_qps"
    cfg = man.config("taxi-live-chip")
    assert cfg["reduced"] == [] and cfg["dataset"] == "taxi_live"
    assert cfg["server_config"] == {"mesh_devices": 1}
    assert set(cfg["guarantees"]) == {"exact", "read_your_writes", "durable",
                                      "server_defaults"}
    assert cfg["rides_loaded"] == taxi_live.n_loaded(cfg)
    assert cfg["rides_loaded"] + cfg["rides_arriving"] \
        == cfg["shards"] * cfg["shard_width"]
    assert taxi_live.patch_cell_bytes(cfg) == 2 * (1 << 20) // 8
