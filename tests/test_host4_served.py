"""The `taxi-host4` deployment at a size a test can hold, through the
normal path: `python -m pilosa_tpu.cli server` with `mesh_devices = 4` on
four of the conftest's virtual CPU devices, 4 shards of NYC-taxi rides
(15 grid rows) loaded over HTTP, restarted, and the six TopN families of
the `topn-sweep` traffic answered equal to `benchmark/datasets/taxi.py`'s
numpy recomputation — and what the server then says about how it
answered (`/info`, `/debug/vars`, `/debug/timeline`)."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
N_SHARDS, GRID_ROWS = 4, 15


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own server child, loader, generator and
    reference (its modules import each other from `benchmark/`)."""
    added = [p for p in (BENCH, REPO) if p not in sys.path]
    sys.path[:0] = added
    from datasets import taxi
    from harness import loadgen, server
    yield taxi, loadgen, server
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    taxi, _, server = bench
    state = tmp_path_factory.mktemp("host4")
    toml = state / "server.toml"
    toml.write_text("mesh_devices = 4\n")
    rides = taxi.Rides(20190101, N_SHARDS, GRID_ROWS, 1 << 20)

    def start():
        # JAX_PLATFORMS=cpu and 8 host devices come from the conftest.
        srv = server.Server(REPO, str(state / "data"), "cpu", str(toml),
                            str(state / "server.log"),
                            server.server_env(str(state / "jax_cache")))
        srv.wait_ready()
        return srv

    srv = start()
    try:
        taxi.load(srv, rides)
        assert srv.stop() == 0, srv.log_tail()
        srv = start()       # serve from a re-opened directory
        yield srv, rides
        assert srv.stop() == 0, srv.log_tail()
    finally:
        srv.kill()


def test_six_topn_families_under_a_four_device_mesh_equal_numpy(bench,
                                                                served):
    taxi, loadgen, _ = bench
    srv, rides = served
    info = srv.get("/info")
    assert info["meshDevices"] == 4 and info["deviceCount"] >= 4
    assert info["residentLimits"] == {
        "topnBankBytesPerDevice": 2 << 30,
        "bankBudgetBytesPerDevice": 12 << 30}
    with open(os.path.join(BENCH, "traffic", "topn-sweep.json")) as f:
        traffic = json.load(f)
    for pql, want in taxi.family_queries(rides):
        assert taxi.equal(srv.query(taxi.INDEX, pql), want), pql
    before = srv.get("/debug/vars")["counters"]
    seen = {}
    for fam, pql, ref in loadgen.pinned_stream(taxi, rides, traffic, 11):
        assert taxi.equal(srv.query(taxi.INDEX, pql), ref()), pql
        seen[fam] = seen.get(fam, 0) + 1
    stream = loadgen.client_stream(taxi, rides, traffic, 2**31 + 26, 0)
    for (fam, pql, ref), _ in zip(stream, range(24)):
        assert taxi.equal(srv.query(taxi.INDEX, pql), ref()), pql
        seen[fam] = seen.get(fam, 0) + 1
    assert set(seen) == {"topn_dist_lt", "topn_miles_dollars",
                         "topn_cab_dist", "topn_amount_gt",
                         "topn_pickup_range", "topn_tod"}
    assert min(seen.values()) >= 4
    # Every one of them swept a resident bank; none streamed.
    after = srv.get("/debug/vars")["counters"]

    def grew(name):
        return after[name] - before[name]

    assert grew("executor.topn_sweeps{path:resident}") == sum(seen.values())
    assert grew("executor.topn_sweeps{path:streamed}") == 0
    # Both grid banks, each [16, 4, 32768] u32 over four devices, went
    # up once, in blocks (the family queries built pickup_grid_id's).
    bank = 16 * N_SHARDS * 32768 * 4
    assert grew("executor.bank_upload_bytes") >= bank
    assert after["executor.bank_upload_bytes"] >= 2 * bank
    tl = srv.get("/debug/timeline?last=256")
    spans = [e for e in tl["traceEvents"] if e.get("ph") == "X"]
    uploads = [e["args"] for e in spans if e["name"] == "plan.bank_upload"]
    assert {"bytes": bank, "devices": 4, "blocks": 4} in [
        {k: a[k] for k in ("bytes", "devices", "blocks")} for a in uploads]
    sweeps = [e["args"] for e in spans if e["name"] == "dispatch"
              and e["args"].get("program") == "topn_sweep"]
    assert sweeps and all(a["mesh_devices"] == 4 for a in sweeps)
    # Each device holds its block of every bank: the same bytes or none
    # (the CPU backend keeps no allocator counters).
    used = [d["bytesInUse"] for d in info["devices"][:4]]
    assert len(set(used)) == 1 or np.ptp(used) <= 0.05 * max(used)
