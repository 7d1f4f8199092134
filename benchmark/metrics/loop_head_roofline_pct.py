"""The heads' and exit objective's share of their roofline: T steps of the
head's product forward and its two products backward (the logits made again
in the backward pass are in the measured time, not in the work), one read of
the states, the head's weight and the labels and one write of each result
(`kernel_work["LoopExitOutput"]` of the configuration's adapter), over the
measured device time of the kind (kind_join.py).  A configuration whose
adapter counts no such work has nothing to read."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    try:
        return _kinds.roofline_pct(ctx, "LoopExitOutput")
    except KeyError:        # an adapter whose kernel_work has no such kind
        return None
