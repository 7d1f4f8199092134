"""Seconds of set-up's `fit.epoch_end`s (its two `fit` calls: the first
block and the warm-up block)."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    return phase_tally.setup_s(ctx, ("fit.epoch_end",))
