"""The answers' share of the HBM roofline, in percent, over all device
time.

Every reply read while the window was traced is priced at the bytes its
answer cannot be computed without reading - `bytes_fn(family,
constants, config)` of the configuration's dataset module, from the
query's own text - divided over the mesh's devices; the sum over the
device's published peak is the least time those answers could have
taken, and the share is that over the device's busy seconds in the
stretch (mean over devices). Whatever ran on the device is charged:
filter programs, level expansions, sums and eager helpers alike. A
request's reference thunk carries its family and constants.
"""

import time

from harness.peaks import hbm_bytes_per_s


def read(ctx, bytes_fn):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    price = getattr(ctx["dataset"], bytes_fn)
    # The window ran on the monotonic clock, the profiler's marks are
    # wall-clock: the offset between the two is read here as
    # harness/cell.py reads it.
    wall_ahead = time.time() - time.monotonic()
    done = [r for r in ctx["requests"] if r.status == 200
            and t["started"] <= r.t_recv + wall_ahead <= t["stopped"]]
    if not done:
        return None
    least = sum(price(r.family, r.ref.constants, ctx["config"])
                for r in done) / t["n_devices"]
    return 100.0 * least / hbm_bytes_per_s(ctx["device_kind"]) / t["busy_s"]
