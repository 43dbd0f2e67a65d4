"""chip_smoke.py, on the CPU: the identical script at a tiny size must
pass and say `cpu`; with the default platform and no chip it must fail
fast and print no record; and its own checks must catch what they are
there to catch."""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rides(smoke):
    return smoke.Rides(seed=3, n_shards=1, grid_rows=15,
                       shard_width=1 << 12)


def _run(args, cwd=REPO, timeout=600, **env):
    return subprocess.run([sys.executable, *args], cwd=cwd,
                          env=dict(os.environ, **env),
                          capture_output=True, text=True, timeout=timeout)


# --------------------------------------------------------------- end to end


@pytest.mark.timeout(600)
def test_tiny_cpu_run_passes_and_its_record_says_cpu(tmp_path):
    out_dir, cache = tmp_path / "out", tmp_path / "cache"
    r = _run([SCRIPT, "--platform", "cpu", "--shards", "1",
              "--grid-rows", "15", "--out", str(out_dir)],
             JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stderr[-3000:]
    # The last stdout line is the result: exactly these keys, the
    # device as JAX reports it. The full record is the line before.
    full_line, last_line = r.stdout.strip().splitlines()[-2:]
    assert json.loads(last_line) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    rec = json.loads(full_line)
    assert rec["ok"] is True
    assert rec["device"] == json.loads(last_line)["device"]
    assert rec["reduced"] == ["shards: 16 -> 1",
                              "pickup_grid rows: 1023 -> 15"]
    assert rec["rides"] == 1 << 20 and rec["chips_used"] == 4
    # Every compiled program of the server went where the environment
    # said, and the restart found them all again.
    cc = rec["compile_cache"]
    assert cc["dir"] == str(cache)
    assert cc["files_after_warm"] == cc["files_after_cold"] > 0
    assert rec["warm"]["queries_run"] == 12   # 9 families + 3 Set checks
    assert rec["cold"]["queries_run"] > 64
    # The mesh leg ran too: tests see 8 virtual devices.
    assert rec["mesh"]["mesh_devices"] == 4
    assert json.loads((out_dir / "record.json").read_text()) == rec
    for leg in ("cold", "warm", "mesh"):
        log = (out_dir / f"server_{leg}.log").read_text()
        assert " devices: platform=cpu " in log


@pytest.mark.timeout(120)
def test_default_platform_without_a_chip_fails_and_prints_no_record(
        tmp_path):
    """The default is tpu and there is no other fallback: on this
    chipless machine the run dies at server start, in seconds."""
    r = _run([SCRIPT, "--out", str(tmp_path)], timeout=100)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "Unable to initialize backend 'tpu'" in r.stderr


@pytest.mark.timeout(120)
def test_server_platform_tpu_without_a_chip_dies_at_start(tmp_path):
    r = _run(["-m", "pilosa_tpu.cli", "server", "-d", str(tmp_path),
              "-b", "127.0.0.1:10199", "--platform", "tpu"], timeout=100)
    assert r.returncode != 0
    assert "Unable to initialize backend 'tpu'" in r.stderr


@pytest.mark.timeout(120)
def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the
    repo: no server to start, no record, non-zero — on any platform."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py", "--platform", "cpu", "--shards", "1",
              "--grid-rows", "15"], cwd=str(tmp_path), timeout=100)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_the_parent_never_imports_jax():
    src = f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)
r = m.Rides(0, 1, 15, 1 << 12)
m.roaring_bytes(r.grid, __import__("numpy").arange(r.n), r.shard_width)
m.family_queries(r); m.burst_queries(r, 0, 20); m.versions()
print("jax" in sys.modules)
"""
    r = _run(["-c", src], timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


# ------------------------------------------------- the reference and checks


class _FakeServer:
    def __init__(self, answers):
        self.answers = answers

    def query(self, pql):
        return self.answers[pql]


def test_burst_queries_are_distinct_mixed_and_no_family_repeat(smoke,
                                                               rides):
    burst = smoke.burst_queries(rides, seed=1, n=80)
    pqls = [q for q, _ in burst]
    assert len(set(pqls)) == 80
    assert not set(pqls) & {q for q, _ in smoke.family_queries(rides)}
    shapes = {re.sub(r"\d+", "N", q) for q in pqls}
    assert len(shapes) == smoke.BURST_SHAPES


def test_reference_answers_agree_with_a_direct_recount(smoke, rides):
    fam = dict(smoke.family_queries(rides))
    cab0 = np.flatnonzero(rides.cab[0])
    want = np.bincount(rides.grid[cab0], minlength=15)
    top = fam["TopN(pickup_grid, Row(cab_type=0), n=10)"]
    assert [p["count"] for p in top] == sorted(want, reverse=True)[:10]
    assert sum(g["count"] for g in fam[
        "GroupBy(Rows(cab_type), Rows(passenger_count))"]) == rides.n


def test_a_wrong_answer_fails_the_run(smoke, rides):
    fam = smoke.family_queries(rides)
    answers = dict(fam)
    smoke.run_queries(_FakeServer(answers), fam, "ok")   # all equal
    answers[fam[2][0]] += 1
    with pytest.raises(smoke.SmokeFailure, match="reference"):
        smoke.run_queries(_FakeServer(answers), fam, "cold")


@pytest.mark.parametrize("line", [
    "Traceback (most recent call last):\n  File x\nValueError: boom",
    "pilosa_tpu.utils.locks.LockOrderError: A -> B"])
def test_a_traceback_in_the_server_log_fails_the_run(smoke, tmp_path,
                                                     line):
    log = tmp_path / "server.log"
    log.write_text("2026 INFO devices: platform=cpu\n" + line + "\n")
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_server_log(str(log))


def test_a_clean_server_log_passes(smoke, tmp_path):
    log = tmp_path / "server.log"
    log.write_text("2026 INFO devices: platform=cpu kind=cpu count=1\n")
    smoke.check_server_log(str(log))


def _info(n=1, platform="tpu", used=None, native=True):
    used = used if used is not None else [3 << 30] * n
    return {"devices": [{"id": i, "platform": platform, "kind": "k",
                         "bytesInUse": b, "peakBytesInUse": b,
                         "bytesLimit": 16 << 30}
                        for i, b in enumerate(used)],
            "native": {"loaded": native, "error": "" if native else
                       "make: g++: not found"}}


def test_check_devices(smoke):
    full = smoke.Rides(0, 16, 1023, 64)   # bank_bytes needs no data
    full.shard_width = 1 << 20
    assert smoke.bank_bytes(full) == 2 << 30
    assert len(smoke.check_devices(_info(), "tpu", 1, full)) == 1
    # A CPU server cannot pass for a chip run.
    with pytest.raises(smoke.SmokeFailure, match="wanted tpu"):
        smoke.check_devices(_info(platform="cpu"), "tpu", 1, full)
    with pytest.raises(smoke.SmokeFailure, match="native library"):
        smoke.check_devices(_info(native=False), "tpu", 1, full)
    # Less resident than the pickup_grid bank alone.
    with pytest.raises(smoke.SmokeFailure, match="holds"):
        smoke.check_devices(_info(used=[1 << 30]), "tpu", 1, full)
    # Four devices: a quarter each passes, everything on device 0 is
    # caught either as a starved device or as an unbalanced mesh.
    quarter = [600 << 20] * 4
    assert len(smoke.check_devices(_info(4, used=quarter), "tpu", 4,
                                   full)) == 4
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_devices(
            _info(4, used=[2400 << 20, 1 << 20, 1 << 20, 1 << 20]),
            "tpu", 4, full)
    with pytest.raises(smoke.SmokeFailure, match="not spread"):
        smoke.check_devices(
            _info(4, used=[4000 << 20] + [600 << 20] * 3), "tpu", 4,
            full)
