"""Share of the window the fit loop spent waiting on an empty input ring:
`io_plane.stats()["stall_s"]` over the window."""


def read(ctx):
    io = ctx["io"]
    if "stall_s" not in io or not io.get("batches"):
        return None
    return 100.0 * io["stall_s"] / ctx["window_s"]
