"""Device time per step in the gated short convolution (operator kind
GatedShortConv), forward, backward and recomputation (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.kind_ms(ctx, "GatedShortConv")
