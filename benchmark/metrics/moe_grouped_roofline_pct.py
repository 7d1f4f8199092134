"""The routed experts' share of their roofline: the grouped product at the
expected local assignments, one read of rows and weights and one write of
outputs a pass, over the measured device time of the kind (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.roofline_pct(ctx, "RoutedExperts")
