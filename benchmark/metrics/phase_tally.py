"""What the set-up readers share (PR 38): the program's phase tally,
`obs.trace.phases()`, read in the run's own process after the window, less
the window's own spans of the same names, which `ctx["spans"]` holds because
tracing is on in the window.  What is left is set-up alone: process start to
the window's start.  A program that keeps no tally (before PR 38) reads
nothing."""
import sys


def tally():
    """`{phase: {"n", "s", "jax": {event: {"n", "s"}}}}`, or None."""
    try:
        from incubator_mxnet_tpu.obs import trace
    except ImportError:
        return None
    phases = getattr(trace, "phases", None)
    return (phases() or None) if phases is not None else None


def setup_s(ctx, names):
    """Seconds of the named phases before the window, or None."""
    t = tally()
    if t is None:
        return None
    window = sum(s["dur"] for s in ctx["spans"] if s["name"] in names)
    return max(sum(t[n]["s"] for n in names if n in t) - window / 1e6, 0.0)


def jax_events(ctx, *keys):
    """`{phase: {key: (n, s)}}` of JAX's compile events in set-up, for the
    phases that saw any of `keys`; None without a tally or where the window
    compiled (that run is not `correct`, and its events are not set-up's)."""
    t = tally()
    if t is None or ctx.get("compiles_in_window"):
        return None
    return {name: {k: (p["jax"][k]["n"], p["jax"][k]["s"])
                   for k in keys if k in p["jax"]}
            for name, p in t.items() if any(k in p["jax"] for k in keys)}


def split(metric, by_phase, unit="s"):
    """The split by phase to standard error; `""` is the harness's share,
    and the fit loop's own programs outside the phases."""
    print("[bench] %s by phase: %s" % (metric, ", ".join(
        "%s %s%s" % (name or '""', "%g" % v if unit == "count" else
                     "%.3f" % v, "" if unit == "count" else " s")
        for name, v in sorted(by_phase.items(), key=lambda kv: -kv[1]))),
        file=sys.stderr)
    return sum(by_phase.values())
