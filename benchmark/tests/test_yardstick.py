"""The yardstick's own arithmetic, without a server: the generator is
deterministic in --seed, the trace reduction on a small recorded trace,
the peaks table, the manifest and its files, and a cell added as files."""

import json
import os
import shutil

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT
from datasets import taxi
from harness import cell, loadgen, peaks, trace_reduce
from harness.manifest import Manifest
from readers import kernel_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = ["topn-sweep", "point-serial"]


@pytest.fixture(scope="module")
def rides():
    return taxi.Rides(3, 1, 15, 1 << 14)


def _config():
    with open(os.path.join(BENCH, "configs", "taxi-chip.json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _take(stream, n):
    return [(fam, pql) for (fam, pql, _), _ in zip(stream, range(n))]


@pytest.mark.parametrize("name", TRAFFIC)
def test_generator_is_deterministic_in_seed(rides, name):
    t = _traffic(name)
    big = 2**31 + 12345   # the driver's seeds pass 32 signed bits
    a = _take(loadgen.client_stream(taxi, rides, t, big, 5), 200)
    b = _take(loadgen.client_stream(taxi, rides, t, big, 5), 200)
    c = _take(loadgen.client_stream(taxi, rides, t, big + 1, 5), 200)
    d = _take(loadgen.client_stream(taxi, rides, t, big, 6), 200)
    assert a == b
    assert [p for _, p in a] != [p for _, p in c]
    assert [p for _, p in a] != [p for _, p in d]
    # Every seed and client walks the same cycle, in fixed proportion.
    cycle = [loadgen._entry(e)[0] for e in t["cycle"]]
    for seq in (a, c, d):
        fams = [f for f, _ in seq[:len(cycle) * (200 // len(cycle))]]
        for fam in set(cycle):
            assert fams.count(fam) == len(fams) * cycle.count(fam) \
                // len(cycle)


@pytest.mark.parametrize("name", TRAFFIC)
def test_warmup_pins_every_shape_changing_draw(rides, name):
    t = _traffic(name)
    pinned = list(loadgen.pinned_stream(taxi, rides, t, t["warmup"]["seed"]))
    fams = {f for f, _, _ in pinned}
    assert fams == {loadgen._entry(e)[0] for e in t["cycle"]}
    # ... and every (family, pinned draw) of the cycle, the swept field
    # among them.
    for e in t["cycle"]:
        fam, pins = loadgen._entry(e)
        assert any(loadgen._entry(p)[0] == fam and
                   pins.items() <= loadgen._entry(p)[1].items()
                   for p in t["warmup"]["pinned"])
    # A time range's view count is the program's shape: 1..8 static
    # views, then the literal path.
    spans = sorted(e["span"] for e in t["warmup"]["pinned"] if "span" in e)
    assert spans[:9] == list(range(1, 10)) and spans[-1] > 9
    for _, pql, ref in pinned:
        assert pql and ref() is not None
    # Two skewed draws from one field meet now and then: one row
    # gathered instead of two is another program.
    if "count_xor" in fams:
        assert any(p.split("=")[1].split(")")[0] == p.split("=")[2]
                   .split(")")[0] for f, p, _ in pinned if f == "count_xor")


def test_reference_thunks_are_plain_numpy(rides):
    d = taxi.Draws({"grid_rows": 15, "n_days": 28},
                   np.random.default_rng(1), 0.99)
    pql, ref = taxi.query(rides, "topn_cab_dist", d)
    assert pql.startswith("TopN(pickup_grid_id, Intersect(Row(cab_type=")
    got = ref()
    assert len(got) <= 10 and got == sorted(
        got, key=lambda p: (-p["count"], p["id"]))
    pql, ref = taxi.query(rides, "time_range", d, span=3)
    a, d0, d1 = int(pql.split("pickup=")[1][0]), *[
        (np.datetime64(s) - np.datetime64("2019-01-01")).astype(int)
        for s in (pql.split("from='")[1][:10], pql.split("to='")[1][:10])]
    assert d1 - d0 == 3
    assert ref() == int(((rides.cab_id == a) & (rides.day >= d0)
                         & (rides.day < d1)).sum())


def test_source_schema_and_queries(rides):
    """The source's seven set fields are loaded under its names, its
    buckets are whole miles and dollars, and its two typical queries
    are families with a plain recomputation."""
    made = []

    class Srv:
        def post_json(self, path, obj):
            made.append(path)

        def request(self, *a):
            made.append(a[1])
    taxi.load(Srv(), rides)
    fields = {p.split("/field/")[1] for p in made
              if "/field/" in p and "/import" not in p}
    assert fields >= {"cab_type", "dist_miles", "total_amount_dollars",
                      "passenger_count", "drop_grid_id", "pickup_grid_id",
                      "pickup_elapsed_time_of_day"}
    assert fields - {"dist", "amount", "pickup"} == fields - set(
        k for k in _config()["schema"] if _config()["schema"][k]
        .startswith("EXTRA"))
    assert set(_config()["schema"]) == fields
    assert (rides.miles == rides.dist // 10).all()
    assert (rides.dollars == rides.amount // 10).all()
    assert rides.drop.max() < 15 and (rides.drop != rides.grid).any()
    d = taxi.Draws({"grid_rows": 15, "n_days": 28},
                   np.random.default_rng(4), 0.0)
    pql, ref = taxi.query(rides, "topn_cab_miles_dollars", d)
    a, b = (int(x.split(")")[0]) for x in pql.split("=")[1:])
    assert pql == (f"TopN(cab_type, Intersect(Row(dist_miles={a}), "
                   f"Row(total_amount_dollars={b})))")
    m = (rides.dist // 10 == a) & (rides.amount // 10 == b)
    assert m.any()      # the drawn dollars are ones such rides pay
    assert sum(p["count"] for p in ref()) == int(m.sum())
    pql, ref = taxi.query(rides, "groupby_pax_cab", d)
    h = int(pql.split("time_of_day=")[1].split(")")[0])
    assert pql.startswith("GroupBy(Rows(passenger_count), Rows(cab_type), ")
    assert sum(g["count"] for g in ref()) == int((rides.tod == h).sum())
    pql, ref = taxi.query(rides, "topn_tod", d, field="drop_grid_id")
    h = int(pql.split("time_of_day=")[1].split(")")[0])
    assert pql.startswith("TopN(drop_grid_id, Row(pickup_elapsed_time_")
    assert ref() == taxi.topn(np.bincount(
        rides.drop[rides.tod == h], minlength=15), 10)
    fams = {p.split("(")[0] for p, _ in taxi.family_queries(rides)}
    assert {"TopN", "GroupBy", "Count", "Sum"} <= fams


def test_sample_takes_runs_of_neighbouring_replies():
    reqs = []
    for i in range(3000):
        r = loadgen.Request(i % 64, f"f{i % 6}", "q", None)
        r.t_recv = float(i)
        reqs.append(r)
    a = cell.draw_sample(reqs, 1024, 2**31 + 5, 64)
    b = cell.draw_sample(reqs, 1024, 2**31 + 5, 64)
    assert [id(r) for r in a] == [id(r) for r in b] and len(a) == 1024
    assert len({id(r) for r in a}) == 1024
    runs = a[:512]
    for k in range(0, 512, 64):
        ts = [r.t_recv for r in runs[k:k + 64]]
        assert ts == list(np.arange(ts[0], ts[0] + 64)) and ts[0] % 64 == 0
    assert {r.family for r in a[512:]} == {f"f{i}" for i in range(6)}
    assert cell.draw_sample(reqs, 10**6, 1, 64) != [] and \
        len(cell.draw_sample(reqs, 10**6, 1, 0)) == 3000
    assert [id(r) for r in cell.draw_sample(reqs, 1024, 6, 64)] \
        != [id(r) for r in a]


def test_zipf_rows_are_skewed_and_uniform_rows_are_not():
    shape = {"grid_rows": 1023, "n_days": 28}
    z = taxi.Draws(shape, np.random.default_rng(2), 0.99)
    u = taxi.Draws(shape, np.random.default_rng(2), 0.0)
    zs = [z.grid_row() for _ in range(4000)]
    us = [u.grid_row() for _ in range(4000)]
    assert zs.count(0) > 300 and max(zs) <= 1022
    assert us.count(0) < 30


# ------------------------------------------------------------ trace


def _recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_reduce_small_synthetic_trace():
    # Two devices; ops nest (a `while` around its body) and overlap.
    planes = [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [["jit_run(1)", 0, 600], ["jit_sum(2)", 2000, 500]],
            "XLA Ops": [["%while.3 = ...", 0, 500], ["fusion.1", 100, 300],
                        ["copy-done.2", 550, 50], ["fusion.7", 2000, 500]]}},
        {"name": "/device:TPU:1", "lines": {
            "XLA Modules": [["jit_run(1)", 0, 600]],
            "XLA Ops": [["fusion.1", 0, 200], ["all-reduce.4", 200, 100]]}},
    ]
    s = trace_reduce.reduce(planes, window_s=4e-6)
    d0, d1 = s["devices"]
    assert d0["busy_s"] == pytest.approx(1050e-9)   # 500 + 50 + 500
    assert d1["busy_s"] == pytest.approx(300e-9)
    assert s["busy_s"] == pytest.approx(675e-9)
    assert s["ops"]["fusion"] == [3, pytest.approx((300 + 500 + 200) / 2e9)]
    assert s["ops"]["while"][0] == 1
    assert s["ops"]["all-reduce"] == [1, pytest.approx(50e-9)]
    # 500..550 lies inside jit_run; 600..2000 is before jit_sum.
    assert d0["gaps"] == {"inside_jit_run": pytest.approx(50e-9),
                          "before_jit_sum": pytest.approx(1400e-9)}
    assert trace_reduce.top(s["gaps"])[0][0] == "before_jit_sum"
    # The profiler collects past the asked stop: never shorter than the
    # device's own span of ops.
    assert trace_reduce.reduce(planes, 1e-6)["window_s"] \
        == pytest.approx(2500e-9)
    ctx = {"trace": s, "ops_in_trace": 3}
    from readers import trace_busy_per_op, trace_idle_share
    assert trace_idle_share.read(ctx) == pytest.approx(
        100 * (1 - 675e-9 / 4e-6))
    assert trace_busy_per_op.read(ctx) == pytest.approx(1e3 * 675e-9 / 3)


def test_names_are_normalised():
    assert trace_reduce.op_name("%popcnt_reduce_fusion.3 = u32[] ...") \
        == "popcnt_reduce_fusion"
    assert trace_reduce.op_name("copy-done.12") == "copy-done"
    assert trace_reduce.module_name("jit_run(5723945)") == "jit_run"


def test_roofline_counts_the_bank_once_per_launch():
    # 2 GiB bank, 8 ms a launch on a v5e: 2^31 / 819e9 / 0.008 = 32.8 %.
    peak = peaks.hbm_bytes_per_s("TPU v5 lite")
    one = kernel_roofline.share(1, 2**31, 0.008, peak)
    assert one == pytest.approx(100 * (2**31 / 819e9) / 0.008)
    assert 32 < one < 34
    # Ten launches in ten times the time: the same share. A launch that
    # serves eight coalesced filters still read the bank once.
    assert kernel_roofline.share(10, 2**31, 0.08, peak) \
        == pytest.approx(one)
    cfg = {"grid_rows": 1023, "shards": 16}
    assert taxi.bank_bytes(cfg) == 2**31
    assert taxi.bank_bytes({"grid_rows": 1023, "shards": 64}) == 2**33
    trace = {"n_devices": 4, "ops": {"popcnt_reduce_fusion": [40, 0.08],
                                     "fusion": [99, 1.0]}}
    ctx = {"trace": trace, "dataset": taxi, "device_kind": "TPU v5 lite",
           "config": {"grid_rows": 1023, "shards": 64}}
    # 40 events over 4 devices = 10 launches each, a quarter of 8 GiB each.
    assert kernel_roofline.read(ctx, "^popcnt_reduce_fusion$",
                                "bank_bytes") == pytest.approx(one)
    assert kernel_roofline.read(dict(ctx, trace=None), "x", "bank_bytes") \
        is None


def test_recorded_chip_trace_reduces():
    """Events kept from a traced run of taxi-chip.topn-sweep on the
    v5e (PR 23), trimmed: the sweep fusion dominates the device."""
    planes = _recorded()
    s = trace_reduce.reduce(planes["planes"], planes["window_s"])
    assert s["n_devices"] == 1 and 0 < s["busy_s"] <= s["window_s"]
    name, secs = trace_reduce.top(s["ops"])[0]
    assert name == "popcnt_reduce_fusion" and secs > 0.5 * s["busy_s"]
    ctx = {"trace": s, "dataset": taxi, "device_kind": "TPU v5 lite",
           "config": {"grid_rows": 1023, "shards": 16}}
    share = kernel_roofline.read(ctx, "^popcnt_reduce_fusion$", "bank_bytes")
    assert 5 < share < 100


def test_unknown_device_kind_is_an_error():
    assert peaks.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="no published HBM peak"):
        peaks.hbm_bytes_per_s("TPU v9 imaginary")


# --------------------------------------------------------- manifest


def test_manifest_names_files_that_agree():
    man = Manifest(CHECKOUT)
    doc = man.doc
    assert doc["command"] == ["python3", "benchmark/run.py"]
    for w in doc["workloads"]:
        cfg = man.config(w["config"])
        assert cfg["chips"] == w["chips"]
        assert cfg["server_config"]["mesh_devices"] == w["chips"]
        t = man.load_json("traffic", w["traffic"])
        assert t["loop"] == "closed"    # no judged metric from an open loop
        man.find("datasets", cfg["dataset"], ".py")
        assert {m["name"] for m in man.metrics_for("end_to_end", w["name"])} \
            > {"setup_s"}
        assert man.metrics_for("per_layer", w["name"])
    e2e = {m["name"] for m in doc["end_to_end"]}
    used = set()
    for m in doc["end_to_end"] + doc["per_layer"]:
        spec = man.metric_spec(m["name"])
        used.add(spec["reader"])
        man.find("readers", spec["reader"], ".py")
        # BENCHMARK.json alone states unit, layer and `moves`.
        assert set(spec) == {"what", "reader", "args"}
        assert m.get("moves", next(iter(e2e))) in e2e
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "readers"))
               if f.endswith(".py") and not f.startswith("_")}
    assert on_disk == used


def test_a_cell_and_a_metric_are_added_as_files(tmp_path):
    """A later PR brings a directory of its own with one traffic file,
    one metric file and one reader, and two manifest entries: nothing
    that is there is edited."""
    co = tmp_path / "checkout"
    co.mkdir()
    os.symlink(BENCH, co / "benchmark")
    extra = co / "extra"
    for sub in ("traffic", "metrics", "readers"):
        (extra / sub).mkdir(parents=True)
    t = _traffic("point-serial")
    t.update(name="bsi-only", cycle=["bsi_lt", "bsi_gt"], clients=2)
    (extra / "traffic" / "bsi-only.json").write_text(json.dumps(t))
    (extra / "metrics" / "bsi_sent.point.json").write_text(json.dumps(
        {"what": "bsi_lt requests sent", "reader": "count_sent",
         "args": {"family": "bsi_lt"}}))
    (extra / "readers" / "count_sent.py").write_text(
        "def read(ctx, family):\n"
        "    return sum(r.family == family for r in ctx['requests'])\n")
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"].append("extra")
    doc["workloads"].append({"name": "taxi-chip.bsi-only",
                             "config": "taxi-chip", "traffic": "bsi-only",
                             "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] in ("point_p50_ms", "point_p95_ms"):
            m["workloads"].append("taxi-chip.bsi-only")
    doc["per_layer"].append({"name": "bsi_sent.point", "unit": "ops",
                             "better": "higher", "source": "program_counter",
                             "layer": "Plan / fuse", "moves": "point_p50_ms",
                             "workloads": ["taxi-chip.bsi-only"]})
    (co / "BENCHMARK.json").write_text(json.dumps(doc))

    man = Manifest(str(co))
    wl = man.workload("taxi-chip.bsi-only")
    traffic = man.load_json("traffic", wl["traffic"])
    assert traffic["cycle"] == ["bsi_lt", "bsi_gt"]
    names = [m["name"] for m in man.metrics_for("per_layer", wl["name"])]
    assert "bsi_sent.point" in names
    assert "server_start_s" in names and "compiles_in_window.point" \
        in names       # no list: every cell that reports `moves`
    assert [m["name"] for m in man.metrics_for("end_to_end", wl["name"])] \
        == ["point_p50_ms", "point_p95_ms", "setup_s"]
    rides = taxi.Rides(1, 1, 15, 1 << 12)
    stream = loadgen.client_stream(taxi, rides, traffic, 9, 0)
    reqs = [loadgen.Request(0, fam, pql, ref)
            for (fam, pql, ref), _ in zip(stream, range(10))]
    spec = man.metric_spec("bsi_sent.point")
    reader = man.load_module("readers", spec["reader"])
    assert reader.read({"requests": reqs}, **spec["args"]) == 5
