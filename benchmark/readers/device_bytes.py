"""The device allocator's own byte counter after the window (`/info`
`devices[]`, `memory_stats()`), the fullest device, in GB. Nothing on
a backend that keeps no counters (the CPU)."""


def read(ctx, key):
    vals = [d.get(key) for d in ctx["after"]["info"]["devices"]]
    vals = [v for v in vals if v is not None]
    return max(vals) / 1e9 if vals else None
