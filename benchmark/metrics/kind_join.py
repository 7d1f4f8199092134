"""Device time of one MXNet operator kind, forward and backward, per step of
the window: the `by_kind` sums of scope_join.py (the trace's
per-instruction times of the busiest chip joined with
`mx.compile.op_scopes()`).  Not a metric: the helper the `*_device_ms` and
`*_roofline_pct` readers of single operator kinds share, loaded by path.
On a program whose map knows no such kind there is nothing to read."""
import os

from benchmark.harness import cells

_HERE = os.path.dirname(os.path.abspath(__file__))
_join = cells.load_module(os.path.join(_HERE, "scope_join.py"))


def kind_ms(ctx, kind):
    s = _join.split(ctx)
    if s is None:
        return None
    ns = sum(t for (op, _phase), t in s["by_kind"].items() if op == kind)
    return ns / ctx["steps"] / 1e6 if ns else None


def roofline_pct(ctx, kind):
    """The least time the chip could take for the kind's work in a step
    (the larger of operations over the bf16 peak and bytes over the HBM
    peak, `kernel_work` of the configuration's adapter) over the measured
    device time of the kind, in percent."""
    ms = kind_ms(ctx, kind)
    path = os.path.join(cells.BENCH_DIR, "configs",
                        str(ctx["cfg"].get("name")) + "_program.py")
    if ms is None or ctx.get("peaks") is None or not os.path.exists(path):
        return None
    work = getattr(cells.load_module(path), "kernel_work", None)
    if work is None:
        return None
    tokens = ctx["traffic"]["batch_per_chip"] * ctx["cfg"]["rate"]["per_row"]
    ops, nbytes = work(ctx["cfg"], tokens)[kind]
    least_s = max(ops / ctx["peaks"]["flops_bf16"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / ms
