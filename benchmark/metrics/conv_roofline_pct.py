"""The gated short convolution's share of its roofline: no product, one
read of the fused (tokens, 3c) projection and one write of (tokens, c)
forward, the same with the gradients backward, over the measured device
time of the kind (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.roofline_pct(ctx, "GatedShortConv")
