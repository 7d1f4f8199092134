"""The gated delta rule's share of its roofline: the published recurrence's
operations, and one read of each pass's inputs and one write of its outputs,
over the measured device time of the kind (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.roofline_pct(ctx, "GatedDeltaRule")
