"""Seconds of XLA's backend compiles in set-up, in every phase.  A hit in
JAX's persistent cache is a backend-compile event of JAX's too; its retrieval
is `setup_cache_load_s`'s and is taken off here.  The split by phase goes to
standard error; `""` is the harness's share."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    events = phase_tally.jax_events(ctx, "compile", "cache_load")
    if events is None:
        return None
    return phase_tally.split("setup_jax_compile_s", {
        name: max(ev.get("compile", (0, 0.0))[1] -
                  ev.get("cache_load", (0, 0.0))[1], 0.0)
        for name, ev in events.items()})
