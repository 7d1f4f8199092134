"""Seconds JAX spent tracing jaxprs and lowering them to MLIR in set-up, in
every phase (an event inside another of its kind counts once, in the outer
one).  The split by phase goes to standard error."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    events = phase_tally.jax_events(ctx, "trace", "lower")
    if events is None:
        return None
    return phase_tally.split("setup_jax_trace_s", {
        name: sum(s for _, s in ev.values()) for name, ev in events.items()})
