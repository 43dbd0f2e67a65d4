"""One part of the set-up, by the harness's clock, in seconds."""


def read(ctx, key):
    return ctx["setup"].get(key)
