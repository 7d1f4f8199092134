"""The attention's share of its roofline: the mask's score entries x 2
products forward and 4 backward (what the algorithm needs: the backward
kernel's second computation of a tile's scores and the re-materialised
forward pass are in the measured time, not in the work), one read of q, k,
v and the output's gradient and one write of each result
(`kernel_work["BlockwiseAttention"]` of the configuration's adapter), over
the measured device time of the kind (kind_join.py).  A configuration
whose adapter counts no such work has nothing to read."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    try:
        return _kinds.roofline_pct(ctx, "BlockwiseAttention")
    except KeyError:        # an adapter whose kernel_work has no such kind
        return None
