"""From a profiler trace to what the per-layer readers take.

Two stages, so that the arithmetic can be checked on a small recorded
trace without the profiler:

1. `extract(xplane_path)` reads the `.xplane.pb` with
   `jax.profiler.ProfileData` and keeps, for every device plane, the
   events of its op line and its module line as plain
   `[name, start_ns, duration_ns]` lists.
2. `reduce(planes, window_s)` is pure Python: per device the union of
   the op intervals (busy), the per-op sums and counts, and every idle
   gap named by the XLA module that ran next (`before_<module>`) or
   that it lay inside (`inside_<module>`); then the mean over devices.

Run as a program after the traced server has ended (this process may
import jax; it pins it to the CPU and never touches the chip):

    python benchmark/harness/trace_reduce.py <control dir> <out.json> \
        [<events.json>: also keep the extracted events]
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ARGS = re.compile(r"\(.*$")


def op_name(raw: str) -> str:
    """`%popcnt_reduce_fusion.3 = ...` -> `popcnt_reduce_fusion`."""
    name = raw.strip().lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return _SUFFIX.sub("", name) or raw


def module_name(raw: str) -> str:
    """`jit_run(123456789)` -> `jit_run`."""
    return _SUFFIX.sub("", _MODULE_ARGS.sub("", raw.strip())) or raw


def extract(xplane_path: str, index: dict = None) -> list:
    """Device planes' op and module events; `index`, when given, is
    filled with every plane's line names and event counts."""
    index = {} if index is None else index
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        index[plane.name] = {ln.name: sum(1 for _ in ln.events)
                             for ln in plane.lines}
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_device(plane: dict) -> dict:
    ops = plane["lines"].get(OPS_LINE, [])
    mods = sorted(([s, s + d, module_name(n)] for n, s, d in
                   plane["lines"].get(MODULES_LINE, [])))
    per_op = {}
    for raw, _, dur in ops:
        rec = per_op.setdefault(op_name(raw), [0, 0.0])
        rec[0] += 1
        rec[1] += dur / 1e9
    busy = union([[s, s + d] for _, s, d in ops if d > 0])
    gaps = {}
    mi = 0
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        # The module this gap lies inside, else the next to start.
        while mi < len(mods) and mods[mi][1] <= gap_start:
            mi += 1
        if mi < len(mods) and mods[mi][0] <= gap_start:
            name = "inside_" + mods[mi][2]
        elif mi < len(mods):
            name = "before_" + mods[mi][2]
        else:
            name = "before_unknown"
        gaps[name] = gaps.get(name, 0.0) + (gap_end - gap_start) / 1e9
    return {"plane": plane["name"],
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "first_ns": busy[0][0] if busy else None,
            "last_ns": busy[-1][1] if busy else None,
            "ops": per_op, "gaps": gaps}


def reduce(planes: list, window_s: float) -> dict:
    """The summary the readers take. `window_s` is the traced window's
    length by the tracing process's own clock (profiler started ->
    stop asked for); the profiler collects a little past that moment,
    so the window is no shorter than a device's own span of ops.
    Per-op and gap seconds are means over devices."""
    devices = [reduce_device(p) for p in planes]
    n = len(devices)
    window_s = max([window_s] + [(d["last_ns"] - d["first_ns"]) / 1e9
                                 for d in devices if d["busy_s"] > 0])
    ops, gaps = {}, {}
    for d in devices:
        for name, (count, secs) in d["ops"].items():
            rec = ops.setdefault(name, [0, 0.0])
            rec[0] += count
            rec[1] += secs / n
        for name, secs in d["gaps"].items():
            gaps[name] = gaps.get(name, 0.0) + secs / n
    return {"window_s": window_s, "n_devices": n,
            "busy_s": sum(d["busy_s"] for d in devices) / n if n else 0.0,
            "ops": ops, "gaps": gaps, "devices": devices}


def top(table: dict, k: int = 10) -> list:
    """[[name, seconds], ...], longest first."""
    rows = [[name, v[1] if isinstance(v, list) else v]
            for name, v in table.items()]
    return sorted(rows, key=lambda r: -r[1])[:k]


def main(argv: list) -> int:
    ctl, out = argv[:2]
    with open(os.path.join(ctl, "started")) as f:
        t0 = json.load(f)["time"]
    with open(os.path.join(ctl, "stopped")) as f:
        t1 = json.load(f)["time"]
    found = glob.glob(os.path.join(ctl, "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not found:
        print("trace_reduce: no .xplane.pb under", ctl, file=sys.stderr)
        return 1
    index = {}
    planes = extract(found[0], index)
    summary = reduce(planes, t1 - t0)
    summary["plane_lines"] = index
    summary["started"], summary["stopped"] = t0, t1
    summary["xplane_bytes"] = os.path.getsize(found[0])
    with open(out, "w") as f:
        json.dump(summary, f)
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            json.dump(planes, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
