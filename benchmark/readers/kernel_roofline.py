"""A memory-bound kernel's share of the HBM roofline, in percent.

The least time one launch could take is the bytes it must read over the
device's published peak; the share is that over the launch's measured
device time. The bytes are the resident bank's, from its shape
(`bytes_fn` of the configuration's dataset module), divided over the
mesh's devices and counted ONCE per launch — a launch that sweeps the
bank once for eight coalesced filters is one bank read, not eight.
Launches and seconds are those of the ops whose name matches, per
device; the peak comes from `harness/peaks.py` by `device_kind` and an
unknown kind is an error.
"""

import re

from harness.peaks import hbm_bytes_per_s


def read(ctx, op_regex, bytes_fn):
    t = ctx["trace"]
    if not t:
        return None
    pat = re.compile(op_regex)
    hits = [v for name, v in t["ops"].items() if pat.search(name)]
    launches = sum(v[0] for v in hits) / t["n_devices"]   # per device
    seconds = sum(v[1] for v in hits)                     # per device
    if not launches or not seconds:
        return None
    bank = getattr(ctx["dataset"], bytes_fn)(ctx["config"])
    return share(launches, bank / t["n_devices"], seconds,
                 hbm_bytes_per_s(ctx["device_kind"]))


def share(launches, bytes_per_launch, seconds, peak_bytes_per_s):
    return 100.0 * (launches * bytes_per_launch / peak_bytes_per_s) / seconds
