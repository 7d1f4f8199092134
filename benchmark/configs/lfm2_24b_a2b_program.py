"""How the `lfm2_24b_a2b` configuration is composed in the program under
test (`llm.lfm2_moe_symbol`: the gluon `Lfm2MoeLM` under `SoftmaxOutput`),
how its parameter names map onto the plain reference's leaves, and the
operations and bytes of its step, of its routed experts and of its gated
short convolution.
"""
import re

import numpy as np

PREFIX = "lm_"


def build_symbol(mx, cfg):
    from incubator_mxnet_tpu.llm import Lfm2MoeConfig, lfm2_moe_symbol
    return lfm2_moe_symbol(Lfm2MoeConfig.from_dict(cfg), prefix=PREFIX)


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


_LAYER = (("norm1.w", "norm1_gamma"), ("norm2.w", "norm2_gamma"),
          ("conv.in.w", "conv_in_proj_weight"),
          ("conv.conv.w", "conv_conv_weight"),
          ("conv.out.w", "conv_out_proj_weight"),
          ("attn.q.w", "attn_q_proj_weight"),
          ("attn.k.w", "attn_k_proj_weight"),
          ("attn.v.w", "attn_v_proj_weight"),
          ("attn.qnorm.w", "attn_q_norm_gamma"),
          ("attn.knorm.w", "attn_k_norm_gamma"),
          ("attn.out.w", "attn_out_proj_weight"),
          ("ffn.w1.w", "ffn_w1_weight"), ("ffn.w3.w", "ffn_w3_weight"),
          ("ffn.w2.w", "ffn_w2_weight"),
          ("moe.router.w", "moe_router_weight"),
          ("moe.gate.w", "moe_experts_gate_weight"),
          ("moe.up.w", "moe_experts_up_weight"),
          ("moe.down.w", "moe_experts_down_weight"),
          ("moe.bias", "moe_select_bias"), ("moe.load", "moe_load"))
# the head is the embedding's matrix again: one leaf on either side
_TOP = {"embed_weight": "embed.w", "final_norm_gamma": "norm.w"}
_OF_LAYER = {prog: ref for ref, prog in _LAYER}
_NAME = re.compile(re.escape(PREFIX) + r"(?:layer(\d+)_)?(.+)$")


def _leaf(name):
    """The reference leaf of a program name, or None (the inputs, and
    `moe_dropped`, which has no counterpart: the reference drops nothing
    by construction)."""
    m = _NAME.match(name)
    if m is None:
        return None
    if m.group(1) is None:
        return _TOP.get(m.group(2))
    ref = _OF_LAYER.get(m.group(2))
    return ref and f"l{m.group(1)}.{ref}"


def to_program(leaves, cfg, names):
    """Reference leaves -> {program name: array} for the names given (the
    selection bias, a parameter that no gradient moves in the reference, is
    an auxiliary state of the program: the same array); the program's own
    counter starts at zero."""
    out = {n: leaves[_leaf(n)] for n in names if _leaf(n) in leaves}
    out.update({n: np.zeros((2,), np.float32) for n in names
                if n.endswith("moe_dropped")})
    return out


def from_program(arrays, cfg):
    """{program name: array} -> {reference leaf: array}.  The loads the
    experts held received are compared with the reference's counts (the
    `aux` numbers).  Left out: `moe_dropped`, held to 0 by the run, and the
    selection bias, which no step moves on either side (the reference keeps
    it among its parameters, where a leaf that does not move is passed
    over)."""
    return {_leaf(n): a for n, a in arrays.items()
            if _leaf(n) and not n.endswith("moe_select_bias")}


def _work(total, macs, params=0):
    total.forward_macs += macs
    total.train_flops += 6 * macs
    total.param_bytes_f32 += 4 * params


def _kinds(cfg):
    """(conv layers, attention layers, dense layers, routed layers)."""
    types = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    return (types.count("conv"), types.count("full_attention"), dense,
            len(types) - dense)


def local_assignments(cfg):
    """Expected assignments a token makes to the experts held here."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * held["count"] / held["of"]


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one token: the matrix products
    of the layers held and of the tied head (its matrix counted once among
    the parameters), the attention scores at the mean causal length, the
    convolution's taps and gates, and the routed experts at the expected
    local assignments a token; recomputation not counted."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = c // heads
    inter, held = cfg["moe_intermediate_size"], cfg["experts_held"]
    conv, attn, dense, routed = _kinds(cfg)
    total = flops.Count()
    for _ in range(conv):
        total.dense(c, 3 * c)
        total.dense(c, c)
        _work(total, c * (cfg["conv_L_cache"] + 2),
              c * cfg["conv_L_cache"])
    for _ in range(attn):
        total.dense(c, heads * d)
        total.dense(c, kv * d)
        total.dense(c, kv * d)
        total.dense(heads * d, c)
        _work(total, 2 * heads * d * (cfg["seq_len"] + 1) / 2)
    for _ in range(dense):
        for _ in range(3):
            total.dense(c, cfg["intermediate_size"])
    for _ in range(routed):
        total.dense(c, held["of"])
        _work(total, local_assignments(cfg) * 3 * c * inter,
              held["count"] * 3 * c * inter)
    total.dense(c, v)                  # the head; the embedding: no product
    return total


def kernel_work(cfg, tokens):
    """{operator kind: (operations, bytes)} of one training step of `tokens`
    tokens, forward and backward, over all layers: what the published
    algorithm needs, whatever implements it -- its multiply-adds, and ONE
    read of each pass's inputs and ONE write of its outputs in the
    configuration's types (bfloat16 activations and weights).  The divisors
    of `moe_grouped_roofline_pct` and `conv_roofline_pct`."""
    conv, _, _, routed = _kinds(cfg)
    c, inter, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["experts_held"]
    rows = tokens * local_assignments(cfg)
    weights = 2 * held["count"] * 3 * c * inter
    # forward reads the routed rows and the weights held, writes a row per
    # assignment; backward reads rows, weights and the rows' gradients,
    # writes the rows' and the weights' gradients
    moe_bytes = routed * (5 * rows * 2 * c + 3 * weights)
    moe_ops = routed * 6 * rows * 3 * c * inter
    # the gated short convolution has no product: forward reads the fused
    # (tokens, 3c) projection and writes (tokens, c); backward reads the
    # projection and the output's gradient and writes the projection's
    taps = cfg["conv_L_cache"]
    conv_bytes = conv * tokens * 2 * c * ((3 + 1) + (3 + 1 + 3))
    conv_ops = conv * tokens * 3 * 2 * c * (taps + 2)
    return {"RoutedExperts": (moe_ops, moe_bytes),
            "GatedShortConv": (conv_ops, conv_bytes)}
