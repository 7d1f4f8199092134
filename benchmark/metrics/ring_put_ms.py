"""The feeder thread's time per batch in `device_put` and the wait for the
transfer: `io_plane.stats()["put_s"]` over the batches of the window.  With
its two siblings it is the feeder's whole work on a batch; against the
batch's interval it says how busy the feeder is.  The three against `h2d_s`
(stage + put, as one `H2DRing.put` times them) and against the window go on
standard error."""
import sys


def read(ctx):
    io = ctx["io"]
    if "put_s" not in io or not io.get("batches"):
        return None
    n = io["batches"]
    stages = io["source_s"] + io["stage_s"] + io["put_s"]
    print("[bench] ring: source + stage + put %.3f ms a batch; h2d_s + "
          "source_s %.3f ms a batch; the feeder worked %.1f%% of the window"
          % (1e3 * stages / n, 1e3 * (io["h2d_s"] + io["source_s"]) / n,
             100 * stages / ctx["window_s"]), file=sys.stderr)
    return 1e3 * io["put_s"] / n
