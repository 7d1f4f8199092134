"""The feeder thread's time per batch in its source, the inner iterator's
`next()`: `io_plane.stats()["source_s"]` over the batches of the window.
With its two siblings it is the feeder's whole work on a batch; against the
batch's interval it says how busy the feeder is."""


def read(ctx):
    io = ctx["io"]
    if "source_s" not in io or not io.get("batches"):
        return None
    return 1e3 * io["source_s"] / io["batches"]
