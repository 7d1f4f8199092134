"""Seconds of set-up's `fused.reown`: the cold dispatch's re-own of the
carry, whole or leaf by leaf in place (`fused.REOWN_IN_PLACE_BYTES`)."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    return phase_tally.setup_s(ctx, ("fused.reown",))
