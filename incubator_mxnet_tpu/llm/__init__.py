"""Transformer language model: the flagship attention workload.

`model.py` defines the gluon `TransformerLM` (embedding, N identical
pre-norm blocks over the registered `BlockwiseAttention` op, tied
output head) and `lm_symbol`, its `Module.fit`-ready training graph.
`decode_core.py` holds the pure-function decode plane: stacked
per-layer parameters scanned by one fixed-shape decode-step program
and per-bucket prefill programs, with the KV cache as a donated carry
— what `serving/decode.py`'s continuous-batching `DecodeEngine` runs.
`qwen3_next.py` is the hybrid linear-attention LM of the Qwen3-Next family
(`Qwen3NextLM`, `qwen3_next_symbol`): gated delta-rule and gated
grouped-query attention layers in periods, routed experts in every layer;
training path only.  `lfm2.py` is the gated-short-convolution / attention
hybrid of the LFM2 mixture-of-experts family (`Lfm2MoeLM`,
`lfm2_moe_symbol`): a dense layer in front of routed ones, mixers by a
published list, a sigmoid router with a selection bias, a tied head; it
reuses `qwen3_next.py`'s attention mixer and sparse layer; training path
only.  `sdar.py` is the block-diffusion LM of the SDAR mixture-of-experts
family (`SdarMoeLM`, `sdar_moe_symbol`): the graph corrupts the clean
sequence (`BlockDiffusionNoise`), runs [noisy | clean] rows through
attention layers under the block-diffusion mask with routed experts, and
weighs the noisy copy's loss by m / t; the same mixer and sparse layer;
training path only.
"""
from .model import (LMConfig, TransformerBlock, TransformerLM, lm_symbol,
                    lm_block_op_count)
from .qwen3_next import (Qwen3NextConfig, Qwen3NextLM, Qwen3NextBlock,
                         qwen3_next_symbol)
from .lfm2 import (Lfm2MoeConfig, Lfm2MoeLM, Lfm2MoeBlock, lfm2_moe_symbol)
from .sdar import SdarMoeConfig, SdarMoeLM, SdarMoeBlock, sdar_moe_symbol
from .decode_core import (DecodePrograms, stack_lm_params, init_kv_cache)

__all__ = ["LMConfig", "TransformerBlock", "TransformerLM", "lm_symbol",
           "lm_block_op_count", "Qwen3NextConfig", "Qwen3NextLM",
           "Qwen3NextBlock", "qwen3_next_symbol", "Lfm2MoeConfig", "Lfm2MoeLM",
           "Lfm2MoeBlock", "lfm2_moe_symbol", "SdarMoeConfig", "SdarMoeLM",
           "SdarMoeBlock", "sdar_moe_symbol", "DecodePrograms",
           "stack_lm_params", "init_kv_cache"]
