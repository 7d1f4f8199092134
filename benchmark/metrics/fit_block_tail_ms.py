"""Host WORK of the fit loop between two blocks, per block: the summed
durations of the program's `fit.callbacks` and `fit.guardian` spans of the
window, less the part of each that was a wait for the device (`wait_us`),
over the blocks (`fit.step_block` spans).  The split, and what the epoch's
end cost, go on standard error."""
import sys


def read(ctx):
    spans = ctx["spans"]
    blocks = sum(1 for s in spans if s["name"] == "fit.step_block")
    work = {}
    for s in spans:
        if s["name"] in ("fit.callbacks", "fit.guardian"):
            work[s["name"]] = work.get(s["name"], 0.0) + \
                s["dur"] - s["args"].get("wait_us", 0)
    if not blocks or not work:
        return None
    print("[bench] fit_block_tail_ms over %d blocks: %s" % (
        blocks, ", ".join(f"{n} {us / blocks / 1e3:.3f} ms a block"
                          for n, us in sorted(work.items()))),
        file=sys.stderr)
    for s in spans:
        if s["name"] == "fit.epoch_end":
            print("[bench] fit.epoch_end: %.3f ms, of which %.3f waiting "
                  "for the device" % (s["dur"] / 1e3, s["args"].get(
                      "wait_us", 0) / 1e3), file=sys.stderr)
    return sum(work.values()) / blocks / 1e3
