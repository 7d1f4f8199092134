"""Expert parallelism's first fact: which experts of a layer a chip holds.

An `ExpertShare` says it as the layer is told it: `count` consecutive
experts from `offset`, out of `num_experts` routed over.  A routed-expert
layer (`ops/experts.RoutedExperts`) routes over all of them and computes the
part of the result its own experts give; a layout over several chips gives
every chip its own share (`ExpertShare.of_chip`), and the partial results of
all shares add up to the whole layer's.  On one chip the layer runs without
its exchange, and nothing stands in for the absent chips.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..base import MXNetError


@dataclass(frozen=True)
class ExpertShare:
    num_experts: int
    offset: int = 0
    count: int = None

    def __post_init__(self):
        if self.count is None:
            object.__setattr__(self, "count", self.num_experts - self.offset)
        if not (0 <= self.offset and 0 < self.count and
                self.offset + self.count <= self.num_experts):
            raise MXNetError(
                "ExpertShare: experts [%d, %d) do not lie within %d"
                % (self.offset, self.offset + self.count, self.num_experts))

    @classmethod
    def of_chip(cls, num_experts, chips, index):
        """The share of chip `index` when `chips` chips divide a layer."""
        if num_experts % chips or not 0 <= index < chips:
            raise MXNetError(
                "ExpertShare: %d experts do not divide over %d chips, or "
                "there is no chip %d" % (num_experts, chips, index))
        per = num_experts // chips
        return cls(num_experts, index * per, per)

    @property
    def chips(self):
        """How many such shares make up the layer."""
        return self.num_experts // self.count

    def op_params(self):
        """The share as `RoutedExperts` takes it."""
        return {"num_experts": self.num_experts,
                "experts_offset": self.offset, "experts_count": self.count}
