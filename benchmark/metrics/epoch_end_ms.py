"""Milliseconds of the window's `fit.epoch_end` span: the metric read (its
`wait_us`), every parameter to the host (`fit.get_params`) and back
(`fit.set_params`), and the counters (`fit.op_counters`).  That split, and
the bytes each way, go to standard error."""
import sys


def read(ctx):
    spans = ctx["spans"]
    ends = [s for s in spans if s["name"] == "fit.epoch_end"]
    if not ends:
        return None
    parts = {"wait": sum(s["args"].get("wait_us", 0) for s in ends)}
    moved = {}
    for s in spans:
        if s["name"] in ("fit.get_params", "fit.set_params",
                         "fit.op_counters"):
            parts[s["name"]] = parts.get(s["name"], 0) + s["dur"]
            if s["args"].get("bytes") is not None:
                moved[s["name"]] = moved.get(s["name"], 0) + \
                    s["args"]["bytes"]
    total = sum(s["dur"] for s in ends)
    print("[bench] epoch_end_ms over %d: %s, other %.3f ms%s" % (
        len(ends), ", ".join("%s %.3f ms" % (n, us / 1e3)
                             for n, us in parts.items()),
        (total - sum(parts.values())) / 1e3, "".join(
            "; %s %d bytes" % kv for kv in sorted(moved.items()))),
        file=sys.stderr)
    return total / 1e3
