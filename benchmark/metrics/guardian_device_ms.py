"""Device time per step in the guardian: the finite reduction, the displacement
signal and the keeps.  Where XLA fuses the optimizer's update into them the
update's time counts here too (the `mixed` share on standard error says how
much): the trace's per-instruction sums of the busiest chip whose
instruction `mx.compile.op_scopes()` puts under the phase `guardian`
(scope_join.py), over the steps of the window."""
import os

from benchmark.harness import cells

_join = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "scope_join.py"))


def read(ctx):
    return _join.phase_ms(ctx, "guardian")
