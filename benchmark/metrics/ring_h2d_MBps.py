"""Bytes the input ring sent to the device over the window, per second of
the window (not per second of transfer)."""


def read(ctx):
    io = ctx["io"]
    if not io.get("bytes"):
        return None
    return io["bytes"] / ctx["window_s"] / 1e6
