"""Seconds of set-up's `fit.bind` + `fit.init_params` + `fit.init_optimizer`
phases (set-up makes two `fit` calls; the window's own are taken off)."""
import os

from benchmark.harness import cells

phase_tally = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "phase_tally.py"))


def read(ctx):
    return phase_tally.setup_s(
        ctx, ("fit.bind", "fit.init_params", "fit.init_optimizer"))
