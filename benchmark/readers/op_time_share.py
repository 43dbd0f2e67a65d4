"""Device seconds in the ops whose name matches, over the device's busy
seconds, in percent (both means over the devices traced): what share of
the chip's working time a kind of op took — the collectives of a mesh,
say. 0 where the trace holds ops and none matches; nothing without a
trace or without a busy device."""

import re


def read(ctx, op_regex):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    pat = re.compile(op_regex)
    seconds = sum(v[1] for name, v in t["ops"].items() if pat.search(name))
    return 100.0 * seconds / t["busy_s"]
