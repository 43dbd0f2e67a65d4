"""tools/trace_gaps.py on a small synthetic xplane-shaped input: device
idle gaps attributed to the `pilosa:` stage open on the thread that
enqueued the next program."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_gaps  # noqa: E402

MS = 1_000_000


def _planes():
    """One device, two host threads. Device ops: [0,10] [30,40] [70,80]
    [100,110] ms -> gaps 10-30, 40-70, 80-100.

    Thread A (the dispatcher): plan 5-28 with plan.stage 12-20 inside,
    dispatch 28-31 (launches the op at 30), then nothing until
    finish 60-66, dispatch 66-71 (launches the op at 70).
    Thread B (a request thread): http.read 41-43 — never launches.
    Thread C: an eager helper launched at 99 with no stage open."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%fusion.1", 0, 10 * MS], ["%fusion.2", 30 * MS, 10 * MS],
                ["%copy", 70 * MS, 10 * MS], ["%x", 100 * MS, 10 * MS]]},
            {"name": "XLA Modules", "events": [
                ["jit_tree_count(1)", 0, 10 * MS]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["pilosa:plan", 5 * MS, 23 * MS],
                ["pilosa:plan.stage", 12 * MS, 8 * MS],
                ["pilosa:dispatch", 28 * MS, 3 * MS],
                ["PjitFunction(tree_count)", 29 * MS, 1 * MS],
                ["pilosa:finish", 60 * MS, 6 * MS],
                ["pilosa:dispatch", 66 * MS, 5 * MS],
                ["PjitFunction(topn_sweep)", 67 * MS, 2 * MS],
                ["np.asarray", 61 * MS, 1 * MS]]},
            {"name": "python3", "events": [
                ["pilosa:http.read", 41 * MS, 2 * MS]]},
            {"name": "python3", "events": [
                ["PjitFunction(concatenate)", 99 * MS, 1 * MS]]},
            {"name": "tf_worker", "events": [["Execute", 0, 5 * MS]]}]},
        {"name": "/host:metadata", "lines": []},
    ]


def test_innermost_segments_flatten_nesting():
    segs = trace_gaps.innermost_segments([
        ["pilosa:plan", 0, 100], ["pilosa:plan.stage", 20, 30],
        ["pilosa:zero", 60, 0], ["pilosa:dispatch", 100, 10]])
    assert segs == [(0, 20, "pilosa:plan"), (20, 50, "pilosa:plan.stage"),
                    (50, 100, "pilosa:plan"),
                    (100, 110, "pilosa:dispatch")]
    assert trace_gaps.busy_union([["a", 0, 5], ["b", 3, 5], ["c", 20, 1],
                                  ["z", 9, 0]]) == [[0, 8], [20, 21]]


def test_gaps_go_to_the_stage_on_the_launching_thread():
    r = trace_gaps.attribute(_planes())
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["idle_s"] == pytest.approx(0.070)
    by = r["by_stage"]
    # Gap 10-30: thread A launched the op at 30 (PjitFunction at 29):
    # plan 10-12, plan.stage 12-20, plan 20-28, dispatch 28-30.
    # Gap 40-70: thread A again (launch at 67): nothing open 40-60,
    # finish 60-66, dispatch 66-70. Thread B's http.read is ignored.
    # Gap 80-100: thread C launched at 99 with no stage: no_request.
    assert by["plan"] == pytest.approx(0.010)
    assert by["plan.stage"] == pytest.approx(0.008)
    assert by["dispatch"] == pytest.approx(0.002 + 0.004)
    assert by["finish"] == pytest.approx(0.006)
    assert by["idle.no_request"] == pytest.approx(0.020 + 0.020)
    assert "http.read" not in by
    assert sum(by.values()) == pytest.approx(r["idle_s"])
    assert list(by)[0] == "idle.no_request"        # largest first
    assert r["host_threads"] == 3 and r["launch_events"] == 5


def test_trace_start_and_no_launch():
    # A device busy before any launch event: no thread to blame.
    planes = _planes()
    planes[0]["lines"][0]["events"] = [["%a", 0, 1 * MS],
                                       ["%b", 2 * MS, 1 * MS]]
    r = trace_gaps.attribute(planes)
    assert r["by_stage"] == {"idle.no_launch": pytest.approx(0.001)}
    # Before the launching thread's first recorded stage: whatever it
    # was in when the profiler started left no event.
    planes = _planes()
    planes[1]["lines"][0]["events"] = [
        ["pilosa:dispatch", 28 * MS, 3 * MS]]
    r = trace_gaps.attribute(planes)
    assert r["launch_events"] == 2       # the stage, thread C's helper
    assert r["by_stage"]["idle.trace_start"] == pytest.approx(0.018)
    assert r["by_stage"]["dispatch"] == pytest.approx(0.002)


def test_command_line_reads_the_json_form(tmp_path):
    path = tmp_path / "events.json"
    path.write_text(json.dumps({"planes": _planes()}))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_gaps.py"),
         str(path)], capture_output=True, text=True, check=True).stdout
    lines = out.splitlines()
    assert lines[0].startswith("devices 1  busy 0.0400 s")
    assert lines[2].split()[0] == "idle.no_request"
    assert "57.1%" in lines[2]
    js = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_gaps.py"),
         str(path), "--json"],
        capture_output=True, text=True, check=True).stdout
    assert json.loads(js)["launch_events"] == 5
    empty = tmp_path / "none.json"
    empty.write_text(json.dumps({"planes": []}))
    assert subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_gaps.py"),
         str(empty)], capture_output=True).returncode == 1
