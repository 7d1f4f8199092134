"""Device time per step in the optimizer's update: the trace's per-instruction
sums of the busiest chip whose instruction `mx.compile.op_scopes()` puts
under the phase `optimizer` (scope_join.py), over the steps of the window."""
import os

from benchmark.harness import cells

_join = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "scope_join.py"))


def read(ctx):
    return _join.phase_ms(ctx, "optimizer")
