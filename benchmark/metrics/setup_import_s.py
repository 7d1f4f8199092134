"""Seconds of the package's own import (`mx.import`: the first line of
`incubator_mxnet_tpu/__init__.py` to its last), which set-up pays once.  The
whole phase tally goes to standard error."""
import os
import sys

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    t = phase_tally.tally()
    if t is None or "mx.import" not in t:
        return None
    for name, p in sorted(t.items()):
        print("[bench] phase %s: n %d, %.3f s, jax %s" % (
            name or '""', p["n"], p["s"], " ".join(
                "%s %d/%.3f s" % (k, e["n"], e["s"])
                for k, e in sorted(p["jax"].items())) or "-"),
            file=sys.stderr)
    return t["mx.import"]["s"]
