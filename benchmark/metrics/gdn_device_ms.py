"""Device time per step in the gated delta rule (operator kind GatedDeltaRule),
forward and backward, recomputation included (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.kind_ms(ctx, "GatedDeltaRule")
