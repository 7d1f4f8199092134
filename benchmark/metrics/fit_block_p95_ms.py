"""95th percentile of the wall time of a K-step block over ALL blocks of the
window: the start-to-start intervals of the `fit.step_block` spans, the last
block closed by the end of the window.  The count goes on an earlier line
of standard error."""
import statistics
import sys


def read(ctx):
    starts = sorted(s["ts"] for s in ctx["spans"]
                    if s["name"] == "fit.step_block")
    if len(starts) < 2:
        return None
    walls = [b - a for a, b in zip(starts, starts[1:])]
    walls.append(ctx["window_end_us"] - starts[-1])
    print(f"[bench] fit_block_p95_ms over {len(walls)} blocks",
          file=sys.stderr)
    if len(walls) < 20:
        return max(walls) / 1e3
    return statistics.quantiles(walls, n=20)[-1] / 1e3
