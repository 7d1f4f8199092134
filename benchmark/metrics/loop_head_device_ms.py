"""Device time per step in a looped LM's heads and exit objective (operator
kind LoopExitOutput: every recurrent step's head, loss and exit gate, the
objective, and the logits made again in the backward pass), forward and
backward (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.kind_ms(ctx, "LoopExitOutput")
