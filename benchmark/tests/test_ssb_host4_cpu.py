"""`ssb-host4.flights` off the chip (PR 44): a tiny rehearsal of the
cell against a real server whose mesh is four forced host devices equals
`ssb.py`'s reference; altered answers come out not correct; a program
that publishes no per-device limits is refused before a byte is loaded;
the loader's pool keeps a shard's bodies in order and hides no failure;
and every entry the PR adds resolves to a file (presence, never
position: a later PR appends too)."""

import http.server
import json
import threading
import time

import pytest

from conftest import CHECKOUT
from datasets import ssb, ssb_mesh
from harness import cell, tamper
from harness.manifest import Manifest
from harness.server import BenchFailure

CELL = "ssb-host4.flights"
TINY = {"shards": 6, "grid_rows": 1500}
NEW = {"groupby_levels_per_op.ssb4", "groupsum_launches_per_op.ssb4",
       "groupby_groups_per_op.ssb4", "groupby_aggregate_mean_ms.ssb4",
       "bank_upload_mb_in_window.ssb4", "collective_share.ssb4",
       "ssb_answer_roofline.host4", "groupby_spills_in_window.ssb4"}


@pytest.fixture
def four_host_devices(monkeypatch):
    """The server child inherits the environment: four CPU devices for
    its `mesh_devices = 4`."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def _run(seed):
    return cell.run_cell(CHECKOUT, CELL, seed, 2.0, False, time.monotonic(),
                         platform="cpu", sizes=TINY)


def test_rehearsal_on_four_host_devices_equals_the_reference(
        four_host_devices, capfd):
    res = _run(2**31 + 44)
    out = capfd.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 16
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"sweep_qps", "setup_s"}
    check = json.loads(out.strip().splitlines()[-1])["check"]
    assert check["answers_differing"] == 0 == check["answers_differing_limit"]
    assert check["answers_compared"] == check["answers_in_window"]
    assert set(check["families_compared"]) <= set(ssb.FAMILIES)


def test_altered_answers_come_out_not_correct(four_host_devices,
                                              monkeypatch):
    monkeypatch.setattr(cell, "Server", tamper.TamperedServer)
    res = _run(44)
    assert res["correct"] is False and res["failed"] >= 3
    assert tamper.TamperedServer.altered >= res["failed"]


# ------------------------------------------------- the parent is refused


class _StubServer:
    port = 1

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
    whole = _StubServer({"shardWidth": 1 << 20, "meshDevices": 4})
    with pytest.raises(BenchFailure, match="residentLimits"):
        ssb_mesh.load(whole, None)
    assert whole.posted == []
    change = _StubServer({"residentLimits": {
        "topnBankBytesPerDevice": 2 << 30,
        "bankBudgetBytesPerDevice": 12 << 30}})
    with pytest.raises(RuntimeError, match="the load has begun"):
        ssb_mesh.load(change, None)
    # The first thing posted is `ssb.load`'s own first step: the probe
    # of `refuse_no_aggregate`, before any data.
    assert change.posted == [f"/index/{ssb.PROBE}"]


# ------------------------------------------------------- the loader's pool


class _Recorder(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    seen, refuse = [], None

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(0.002)
        type(self).seen.append((self.client_address[1], self.path))
        bad = self.path == type(self).refuse
        body = b'{"error": "no"}' if bad else b"{}"
        self.send_response(500 if bad else 200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


class _Direct:
    """The harness server's surface over the recorder."""

    def __init__(self, port):
        self.port, self.direct = port, []

    def request(self, method, path, body=None, ctype=None):
        self.direct.append((path, len(_Recorder.seen)))
        return {}

    def get(self, path):
        return self.request("GET", path)


@pytest.fixture
def recorder():
    _Recorder.seen, _Recorder.refuse = [], None
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Recorder)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield _Direct(httpd.server_address[1])
    httpd.shutdown()
    httpd.server_close()


def test_the_pool_keeps_a_shards_bodies_in_order_on_one_connection(
        recorder):
    pool = ssb_mesh._Pool(recorder)
    posted = [f"/index/ssb/field/f{k}/import-roaring/{s}"
              + ("?view=bsig_f" if k % 2 else "")
              for s in range(9) for k in range(5)]
    try:
        for path in posted:
            assert pool.request("POST", path, b"x" * 64,
                                "application/octet-stream") == {}
        pool.get("/debug/vars")     # anything else waits for them all
    finally:
        pool.close()
    assert recorder.direct == [("/debug/vars", len(posted))]
    assert sorted(p for _, p in _Recorder.seen) == sorted(posted)
    by_shard = {}
    for conn, path in _Recorder.seen:
        by_shard.setdefault(path.split("/import-roaring/")[1].split("?")[0],
                            []).append((conn, path))
    for s, got in by_shard.items():
        assert len({conn for conn, _ in got}) == 1
        assert [p for _, p in got] == [p for p in posted
                                       if p.split("/")[-1].split("?")[0] == s]
    assert len({conn for conn, _ in _Recorder.seen}) == ssb_mesh.POOL


def test_a_body_the_server_refuses_fails_the_load(recorder):
    _Recorder.refuse = "/index/ssb/field/a/import-roaring/2"
    pool = ssb_mesh._Pool(recorder)
    try:
        for s in range(4):
            pool.request("POST", f"/index/ssb/field/a/import-roaring/{s}",
                         b"x", "application/octet-stream")
        with pytest.raises(BenchFailure, match="import-roaring/2 -> 500"):
            pool.get("/debug/vars")
    finally:
        pool.close()
    assert recorder.direct == []


# ---------------------------------------------------------- the manifest


def test_every_new_entry_resolves_to_a_file():
    man = Manifest(CHECKOUT)
    cells = {w["name"]: w for w in man.doc["workloads"]}
    assert cells[CELL]["chips"] == 4 and cells[CELL]["config"] == "ssb-host4"
    assert cells[CELL]["traffic"] == cells["ssb-chip.flights"]["traffic"]
    man.load_json("traffic", cells[CELL]["traffic"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    assert len(cells[CELL]["why"]) <= 200
    e2e = {m["name"] for m in man.metrics_for("end_to_end", CELL)}
    assert e2e == {"sweep_qps", "setup_s"}
    mine = {m["name"] for m in man.metrics_for("per_layer", CELL)}
    assert NEW <= mine
    assert {"device_idle_share.sweep", "d2h_wait_mean_ms.sweep",
            "dispatch_mean_ms.sweep", "hbm_in_use_gb.sweep",
            "xla_compiles_in_window.sweep", "warmup_s"} <= mine
    # Other cells' own entries stay theirs.
    assert not {m for m in mine if m.endswith((".ssb", ".point", ".lib",
                                               ".live", ".chem"))}
    assert not {"ssb_answer_roofline", "collective_share.host4",
                "topn_sweep_roofline"} & mine
    for name in mine:
        man.load_module("readers", man.metric_spec(name)["reader"])
    for m in man.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "sweep_qps"
    spills = man.metric_spec("groupby_spills_in_window.ssb4")
    assert spills["reader"] == "counter_delta"
    assert spills["args"]["path"][-1] == "executor.groupby_spills"
    roof = man.metric_spec("ssb_answer_roofline.host4")
    assert roof == man.metric_spec("ssb_answer_roofline")


def test_the_spill_reader_finds_nothing_on_a_program_without_the_counter():
    from readers import counter_delta
    path = Manifest(CHECKOUT).metric_spec(
        "groupby_spills_in_window.ssb4")["args"]["path"]

    def ctx(before, after):
        return {"before": {"vars": {"counters": before}},
                "after": {"vars": {"counters": after}}}
    assert counter_delta.read(ctx({}, {}), path) is None    # the parent
    name = path[-1]
    assert counter_delta.read(ctx({name: 0}, {name: 0}), path) == 0
    assert counter_delta.read(ctx({name: 1}, {name: 4}), path) == 3


def test_the_deployment_keeps_the_sources_shapes():
    man = Manifest(CHECKOUT)
    chip, host = man.config("ssb-chip"), man.config("ssb-host4")
    for key in ("shard_width", "n_days", "data_seed", "dimensions",
                "source_queries"):
        assert host[key] == chip[key], key
    assert set(host["schema"]) == set(chip["schema"])
    assert set(chip["assumed"]) <= set(host["assumed"])
    assert set(host["guarantees"]) == set(chip["guarantees"])
    for key in ("exact", "read_only"):
        assert host["guarantees"][key] == chip["guarantees"][key]
    assert host["reduced"] == [] and host["shards"] == 58
    assert host["grid_rows"] == 15_000_000      # SF = 10's orders
    assert host["chips"] == 4 == host["server_config"]["mesh_devices"]
    assert 57 * host["shard_width"] < host["lineorder_rows"] \
        < 58 * host["shard_width"]
    (entry,) = [c for c in man.doc["configs"] if c["name"] == "ssb-host4"]
    assert entry["source"] == host["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry["file"].endswith("ssb-host4.json")
    for word in ("rev. 3", "SF=10", "Q1.1-Q4.3", "pilosa/demo-ssb"):
        assert word in entry["source"]
    # The same module's rows, queries, reference and price.
    assert ssb_mesh.make is ssb.make and ssb_mesh.equal is ssb.equal
    assert ssb_mesh.family_queries is ssb.family_queries
    assert ssb_mesh.query is ssb.query and ssb_mesh.answer is ssb.answer
    assert ssb_mesh.least_bytes is ssb.least_bytes
    # A row of the host is 58 shards of bits; the reader divides by four.
    fixed = ssb.FAMILIES["q2.1"].fixed
    assert ssb_mesh.least_bytes("q2.1", fixed, host) \
        == 1034 * 58 * (1 << 20) // 8
