"""The feeder thread's time per batch staging: the cast and the copy into the
ring's slot: `io_plane.stats()["stage_s"]` over the batches of the window.
With its two siblings it is the feeder's whole work on a batch; against the
batch's interval it says how busy the feeder is."""


def read(ctx):
    io = ctx["io"]
    if "stage_s" not in io or not io.get("batches"):
        return None
    return 1e3 * io["stage_s"] / io["batches"]
