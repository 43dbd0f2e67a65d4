"""tools/doctor.py: the bundle of a live server's debug surfaces, its
structural diff and the consistency verdicts of `baseline`."""

import importlib.util
import json
import pathlib


def _load_doctor():
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "doctor.py"
    spec = importlib.util.spec_from_file_location("_doctor", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_doctor_bundle_diff_and_baseline(live_server, tmp_path,
                                         capsys):
    """tools/doctor.py against a live server: the bundle captures
    every surface, self-diff is empty (exit 0), the judge passes on a
    healthy unmodified tree, and an inconsistent memory document flips
    the verdict to failing."""
    doctor = _load_doctor()
    base, _api, _h = live_server
    bundle = doctor.snapshot_bundle(base)
    assert [k for k, _ in doctor.SURFACES] == list(bundle["surfaces"])
    errs = {k: s["error"] for k, s in bundle["surfaces"].items()
            if "error" in s}
    assert errs == {}, errs
    p1 = tmp_path / "a.json"
    p1.write_text(json.dumps(bundle))
    assert doctor.main(["diff", str(p1), str(p1)]) == 0
    out = capsys.readouterr().out
    assert "0 difference(s)" in out
    # Structural diff pins: changed leaf, added key, volatile ignored.
    lines = doctor.diff_docs(
        doctor._normalize({"a": 1, "t": 5, "x": {"y": 2}}),
        doctor._normalize({"a": 2, "t": 9, "x": {"y": 2, "z": 3}}))
    assert any(l.startswith("~ a:") for l in lines)
    assert any(l.startswith("+ x.z") for l in lines)
    assert not any(" t" in l.split(":")[0] for l in lines)
    # The judge on the healthy bundle: zero failing checks.
    verdicts = doctor.judge_bundle(bundle)
    assert [(c, s, d) for c, s, d in verdicts if s == "FAIL"] == []
    assert ("memory.totals-consistent", "PASS") in \
        [(c, s) for c, s, _ in verdicts]
    assert doctor.main(["baseline", str(p1)]) == 0
    # A memory document whose categories do not add up fails it.
    bundle["surfaces"]["memory"]["doc"]["totalBytes"] += 1
    p2 = tmp_path / "b.json"
    p2.write_text(json.dumps(bundle))
    assert any(c == "memory.totals-consistent" and s == "FAIL"
               for c, s, _ in doctor.judge_bundle(bundle))
    assert doctor.main(["baseline", str(p2)]) == 1
    assert doctor.main(["diff", str(p1), str(p2)]) == 1


def test_doctor_records_unreachable_surface():
    doctor = _load_doctor()
    bundle = doctor.snapshot_bundle("http://localhost:1")  # refused
    assert all("error" in s for s in bundle["surfaces"].values())
    verdicts = doctor.judge_bundle(bundle)
    assert any(c == "surface:memory" and s == "FAIL"
               for c, s, _ in verdicts)
