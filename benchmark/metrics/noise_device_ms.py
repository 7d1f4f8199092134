"""Device time per step in the block-diffusion corruption (operator kind
BlockDiffusionNoise: the rows' checksums, the draws, the mask and the
weights), forward, backward and recomputation (kind_join.py)."""
import os

from benchmark.harness import cells

_kinds = cells.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "kind_join.py"))


def read(ctx):
    return _kinds.kind_ms(ctx, "BlockDiffusionNoise")
