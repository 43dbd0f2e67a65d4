"""A bank patch's share of the HBM roofline, in percent, by the XLA
MODULE that ran it.

`harness/trace_reduce.py` keeps op names and no module, and the ops a
scatter compiles to carry XLA's generic names (`copy`, `fusion`,
`dynamic-update-slice`), which other programs' ops share. So this
reader goes back to the profiler's trace: in a child (this file run as
a program; the parent never imports jax) it takes every op that lies
inside an interval of the named module on a device's `XLA Modules`
line. Launches are the module's events, seconds the union of those
ops, both per device. Bytes a launch are the window's mean real cells a
patch (two counters of the program) times a cell's bytes (`bytes_fn`
of the configuration's dataset module); the peak comes from
`harness/peaks.py`. Nothing where the program publishes no such
counters, no patch ran in the window, or the module is not in the
trace.
"""

import bisect
import glob
import json
import os
import subprocess
import sys

CELLS = ["vars", "counters", "executor.bank_patch_cells"]
PATCHES = ["vars", "counters", "executor.bank_patches"]


def read(ctx, module, bytes_fn):
    from harness.peaks import hbm_bytes_per_s
    from readers._paths import delta

    t = ctx["trace"]
    cells, patches = delta(ctx, CELLS), delta(ctx, PATCHES)
    if not t or not cells or not patches:
        return None
    state = os.path.dirname(ctx["after"]["info"]["compileCacheDir"])
    found = glob.glob(os.path.join(state, "trace_ctl", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), found[0], module],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, check=True).stdout
    launches, seconds = json.loads(out.splitlines()[-1])
    if not launches or not seconds:
        return None
    cell = getattr(ctx["dataset"], bytes_fn)(ctx["config"])
    least = launches * (cells / patches) * cell \
        / hbm_bytes_per_s(ctx["device_kind"])
    return 100.0 * least / seconds


def module_ops(planes: list, module: str) -> tuple:
    """(launches, seconds) of `module`, means over the device planes:
    its events, and the union of the ops that start inside them."""
    from harness.trace_reduce import (MODULES_LINE, OPS_LINE, module_name,
                                      union)
    launches, seconds = 0, 0.0
    for plane in planes:
        mods = sorted([s, s + d] for n, s, d in
                      plane["lines"].get(MODULES_LINE, [])
                      if module_name(n) == module)
        starts = [m[0] for m in mods]
        inside = []
        for _, s, d in plane["lines"].get(OPS_LINE, []):
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mods[k][1] and d > 0:
                inside.append([s, s + d])
        launches += len(mods)
        seconds += sum(e - s for s, e in union(inside)) / 1e9
    n = max(1, len(planes))
    return launches / n, seconds / n


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from harness.trace_reduce import extract
    print(json.dumps(module_ops(extract(sys.argv[1]), sys.argv[2])))
