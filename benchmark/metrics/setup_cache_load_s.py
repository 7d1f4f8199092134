"""Seconds set-up spent loading compiled programs: JAX's persistent-cache
retrievals in every phase, plus the program cache's own `compile.load`."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    events = phase_tally.jax_events(ctx, "cache_load")
    load = phase_tally.setup_s(ctx, ("compile.load",))
    if events is None or load is None:
        return None
    return load + sum(ev["cache_load"][1] for ev in events.values())
