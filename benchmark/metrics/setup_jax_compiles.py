"""XLA backend compiles in set-up that JAX's persistent cache did not answer:
the work a warm run does again (programs that compile in less than
`jax_persistent_cache_min_compile_time_secs` are never stored).  The split
by phase goes to standard error."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    events = phase_tally.jax_events(ctx, "compile", "cache_hit")
    if events is None:
        return None
    return float(phase_tally.split("setup_jax_compiles", {
        name: ev.get("compile", (0, 0.0))[0] - ev.get("cache_hit", (0, 0.0))[0]
        for name, ev in events.items()}, unit="count"))
