"""1 - (union of device-op intervals / traced window), in percent,
mean over the devices used."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
