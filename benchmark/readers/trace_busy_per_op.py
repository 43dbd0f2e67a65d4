"""Device-busy milliseconds per answer: the traced window's busy time
(mean over devices) over the replies read while it was traced."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["ops_in_trace"]:
        return None
    return 1e3 * t["busy_s"] / ctx["ops_in_trace"]
