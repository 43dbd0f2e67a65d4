"""`taxi-host4.topn-sweep` off the chip: a tiny rehearsal of the cell
against a real server whose mesh is four forced host devices (4 shards,
15 grid rows) equals the numpy reference; altered answers come out not
correct; the new readers' arithmetic; the manifest's rules with the new
entries; and a program that publishes no per-device limits is refused
before a byte is loaded."""

import json
import os
import time

import pytest

from conftest import BENCH, CHECKOUT
from datasets import taxi, taxi_mesh
from harness import cell, tamper, trace_reduce
from harness.manifest import Manifest
from harness.server import BenchFailure
from readers import counter_share, op_time_share

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "taxi-host4.topn-sweep"
TINY = {"shards": 4, "grid_rows": 15}
COLLECTIVES = Manifest(CHECKOUT).metric_spec(
    "collective_share.host4")["args"]["op_regex"]


@pytest.fixture
def four_host_devices(monkeypatch):
    """The server child inherits the environment: four CPU devices for
    its `mesh_devices = 4`."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def _run(seed, **kw):
    return cell.run_cell(CHECKOUT, CELL, seed, 2.0, False, time.monotonic(),
                         platform="cpu", sizes=TINY, **kw)


def test_rehearsal_on_four_host_devices_equals_the_reference(
        four_host_devices, capfd):
    res = _run(2**31 + 26)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 20
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    check = json.loads(out.strip().splitlines()[-1])["check"]
    assert check["answers_differing"] == 0 == check["answers_differing_limit"]
    assert len(check["families_compared"]) == 6


def test_altered_answers_come_out_not_correct(four_host_devices,
                                              monkeypatch):
    monkeypatch.setattr(cell, "Server", tamper.TamperedServer)
    res = _run(26)
    assert res["correct"] is False and res["failed"] >= 3
    assert tamper.TamperedServer.altered >= res["failed"]


# ----------------------------------------------------------- the readers


def test_collective_share_on_a_two_device_trace():
    """Per device 400 ns of sweep, a 50 ns all-reduce after it and 50 ns
    of its async halves: 100 of 500 busy ns are collectives."""
    def device(n):
        return {"name": f"/device:TPU:{n}", "lines": {
            "XLA Ops": [["%popcnt_reduce_fusion.2 = ...", 0, 400],
                        ["%all-reduce.6 = u32[1024] ...", 400, 50],
                        ["%all-gather-start.1 = ...", 1000, 20],
                        ["%all-gather-done.1 = ...", 1020, 30]],
            "XLA Modules": [["jit_topn_sweep(1)", 0, 1050]]}}
    s = trace_reduce.reduce([device(0), device(1)], 2e-6)
    assert s["n_devices"] == 2 and s["busy_s"] == pytest.approx(500e-9)
    assert op_time_share.read({"trace": s}, COLLECTIVES) \
        == pytest.approx(20.0)
    # One device of the two ran no collective: the mean over devices.
    lone = device(1)
    lone["lines"]["XLA Ops"] = lone["lines"]["XLA Ops"][:1]
    s = trace_reduce.reduce([device(0), lone], 2e-6)
    assert op_time_share.read({"trace": s}, COLLECTIVES) \
        == pytest.approx(100 * 50e-9 / 450e-9)
    assert op_time_share.read({"trace": None}, COLLECTIVES) is None
    assert op_time_share.read(
        {"trace": {"busy_s": 0.0, "ops": {}}}, COLLECTIVES) is None


def test_collective_share_of_the_recorded_one_chip_trace_is_zero():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    s = trace_reduce.reduce(rec["planes"], rec["window_s"])
    assert s["busy_s"] > 0
    assert op_time_share.read({"trace": s}, COLLECTIVES) == 0.0
    assert op_time_share.read({"trace": s}, "^popcnt_reduce_fusion$") > 50


def test_resident_share_reads_the_two_path_counters():
    spec = Manifest(CHECKOUT).metric_spec("topn_resident_share.sweep")
    assert spec["reader"] == "counter_share"

    def ctx(before, after):
        def snap(c):
            return {"vars": {"counters": {
                f"executor.topn_sweeps{{path:{p}}}": n
                for p, n in c.items()}}}
        return {"before": snap(before), "after": snap(after)}

    read = counter_share.read
    assert read(ctx({"resident": 40, "streamed": 0},
                    {"resident": 3040, "streamed": 0}),
                **spec["args"]) == 100.0
    assert read(ctx({"resident": 40, "streamed": 2},
                    {"resident": 70, "streamed": 12}),
                **spec["args"]) == 75.0
    # A program without the counters (the parent): nothing, no error.
    assert read(ctx({}, {}), **spec["args"]) is None
    with open(os.path.join(HERE, "recorded_vars.json")) as f:
        parent = json.load(f)["cells"]["taxi-chip.topn-sweep"]
    assert read(parent, **spec["args"]) is None


# ---------------------------------------------------------- the manifest


def test_manifest_rules_hold_with_the_new_entries():
    man = Manifest(CHECKOUT)
    doc = man.doc
    assert [w["name"] for w in doc["workloads"]] == [
        "taxi-chip.topn-sweep", "taxi-chip.point-serial", CELL]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for w in doc["workloads"]:
        cfg = man.config(w["config"])
        assert cfg["chips"] == w["chips"] \
            == cfg["server_config"]["mesh_devices"]
        man.find("datasets", cfg["dataset"], ".py")
        assert len(w["why"]) <= 200
    used = {man.metric_spec(m["name"])["reader"]
            for m in doc["end_to_end"] + doc["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "readers"))
               if f.endswith(".py") and not f.startswith("_")}
    assert on_disk == used and "op_time_share" in used
    # The new cell reports sweep_qps and setup_s, and every per-layer
    # metric that moves them and lists no cells, plus the two new ones.
    assert [m["name"] for m in man.metrics_for("end_to_end", CELL)] \
        == ["sweep_qps", "setup_s"]
    mine = {m["name"] for m in man.metrics_for("per_layer", CELL)}
    chip = {m["name"] for m in man.metrics_for("per_layer",
                                               "taxi-chip.topn-sweep")}
    assert mine - chip == {"collective_share.host4"}
    assert chip - mine == set()
    assert "topn_resident_share.sweep" in chip
    assert {"topn_sweep_roofline", "device_idle_share.sweep",
            "hbm_peak_gb.sweep", "warmup_s"} <= mine
    assert not {m for m in mine if m.endswith(".point")}
    for name in ("collective_share.host4", "topn_resident_share.sweep"):
        assert set(man.metric_spec(name)) == {"what", "reader", "args"}
    # Appended: nothing the benchmark had moved.
    assert [m["name"] for m in doc["per_layer"][-2:]] == [
        "collective_share.host4", "topn_resident_share.sweep"]


def test_the_deployment_keeps_the_sources_shapes():
    man = Manifest(CHECKOUT)
    chip, host = man.config("taxi-chip"), man.config("taxi-host4")
    for key in ("shard_width", "grid_rows", "n_days", "data_seed",
                "source_queries", "guarantees"):
        assert host[key] == chip[key], key
    assert set(host["schema"]) == set(chip["schema"])
    assert set(host["assumed"]) == set(chip["assumed"])
    assert host["reduced"] == [] and host["shards"] == 64
    assert host["rides"] == 64 * host["shard_width"] == 67_108_864
    (entry,) = [c for c in man.doc["configs"] if c["name"] == "taxi-host4"]
    assert entry["source"] == host["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == []
    # One device's share of a grid bank is taxi-chip's whole bank.
    assert taxi_mesh.bank_bytes(host) // host["chips"] \
        == taxi.bank_bytes(chip) == 2**31
    # The same module's rides, queries and reference.
    assert taxi_mesh.make is taxi.make and taxi_mesh.equal is taxi.equal
    assert taxi_mesh.family_queries is taxi.family_queries


def test_sweep_references_by_shard_equal_taxis_whole_array_ones():
    """Every sweep family, both grid fields, pinned and free draws: the
    request text is `taxi.query`'s and the reference recomputed a shard
    at a time equals `taxi.py`'s over all the rides at once."""
    import numpy as np
    rides = taxi.Rides(7, 5, 15, 1 << 12)
    traffic = Manifest(CHECKOUT).load_json("traffic", "topn-sweep")
    families = {e["family"] for e in traffic["cycle"]}
    assert families == set(taxi_mesh.FILTERS)

    def draws(seed):
        return taxi.Draws({"grid_rows": 15, "n_days": 28},
                          np.random.default_rng(seed))
    compared = 0
    for seed in range(40):
        for entry in traffic["cycle"] + traffic["warmup"]["pinned"]:
            pinned = {k: v for k, v in entry.items() if k != "family"}
            pql, ref = taxi_mesh.query(rides, entry["family"], draws(seed),
                                       **pinned)
            want_pql, want = taxi.query(rides, entry["family"], draws(seed),
                                        **pinned)
            assert pql == want_pql and entry["field"] in pql
            got = ref()
            assert got == want() and got == ref()
            compared += bool(got)
    assert compared > 400           # not a comparison of empty answers
    # No temporary is larger than a shard: the filter sees slices only.
    seen = []
    real = taxi_mesh.FILTERS["topn_tod"]
    taxi_mesh.FILTERS["topn_tod"] = lambda r, g, sl: (
        seen.append(sl.stop - sl.start) or real(r, g, sl))
    try:
        taxi_mesh.query(rides, "topn_tod", draws(1))[1]()
    finally:
        taxi_mesh.FILTERS["topn_tod"] = real
    assert seen == [1 << 12] * 5


def test_other_families_keep_taxis_reference_unchanged():
    """A family without an entry in `FILTERS` gets `taxi.query`'s own
    thunk back, not a copy or a wrapper of it."""
    import numpy as np
    rides = taxi.Rides(3, 1, 15, 1 << 12)
    handed = []

    def recorded(r, family, draws, **pinned):
        handed.append(real(r, family, draws, **pinned))
        return handed[-1]
    real, taxi.query = taxi.query, recorded
    try:
        for family in sorted(set(taxi.FAMILIES) - set(taxi_mesh.FILTERS)):
            d = taxi.Draws({"grid_rows": 15, "n_days": 28},
                           np.random.default_rng(5))
            pql, ref = taxi_mesh.query(rides, family, d)
            assert (pql, ref) == handed[-1] and ref is handed[-1][1]
    finally:
        taxi.query = real
    assert len(handed) == 12


# ------------------------------------------------- the parent is refused


class _StubServer:
    def __init__(self, info):
        self.info = info
        self.posted = []

    def get(self, path):
        assert path == "/info"
        return self.info

    def post_json(self, path, obj):
        self.posted.append(path)
        raise RuntimeError("stop here: the load has begun")


def test_a_program_without_per_device_limits_is_refused_before_the_load():
    parent = _StubServer({"shardWidth": 1 << 20, "meshDevices": 4})
    with pytest.raises(BenchFailure, match="residentLimits"):
        taxi_mesh.load(parent, None)
    assert parent.posted == []
    half = _StubServer({"residentLimits": {"topnBankBytesPerDevice": 1}})
    with pytest.raises(BenchFailure, match="bankBudgetBytesPerDevice"):
        taxi_mesh.load(half, None)
    change = _StubServer({"residentLimits": {
        "topnBankBytesPerDevice": 2 << 30,
        "bankBudgetBytesPerDevice": 12 << 30}})
    with pytest.raises(RuntimeError, match="the load has begun"):
        taxi_mesh.load(change, None)
    assert change.posted == ["/index/taxi"]
