"""How the `qwen3_next_80b_a3b` configuration is composed in the program
under test (`llm.qwen3_next_symbol`: the gluon `Qwen3NextLM` under
`SoftmaxOutput`), how its parameter names map onto the plain reference's
leaves, and the operations and bytes of its step and of its two new kernels.
"""
import re

import numpy as np

PREFIX = "lm_"


def build_symbol(mx, cfg):
    from incubator_mxnet_tpu.llm import Qwen3NextConfig, qwen3_next_symbol
    return qwen3_next_symbol(Qwen3NextConfig.from_dict(cfg), prefix=PREFIX)


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


_LAYER = (("norm1.w", "norm1_gamma"), ("norm2.w", "norm2_gamma"),
          ("gdn.qkvz.w", "gdn_qkvz_weight"), ("gdn.ba.w", "gdn_ba_weight"),
          ("gdn.conv.w", "gdn_conv_weight"), ("gdn.a_log", "gdn_a_log"),
          ("gdn.dt_bias", "gdn_dt_bias"), ("gdn.norm.w", "gdn_norm_gamma"),
          ("gdn.out.w", "gdn_out_proj_weight"),
          ("attn.q.w", "attn_q_proj_weight"),
          ("attn.k.w", "attn_k_proj_weight"),
          ("attn.v.w", "attn_v_proj_weight"),
          ("attn.qnorm.w", "attn_q_norm_gamma"),
          ("attn.knorm.w", "attn_k_norm_gamma"),
          ("attn.out.w", "attn_out_proj_weight"),
          ("moe.router.w", "moe_router_weight"),
          ("moe.gate.w", "moe_experts_gate_weight"),
          ("moe.up.w", "moe_experts_up_weight"),
          ("moe.down.w", "moe_experts_down_weight"),
          ("moe.shared_gate.w", "moe_shared_gate_weight"),
          ("moe.shared_up.w", "moe_shared_up_weight"),
          ("moe.shared_down.w", "moe_shared_down_weight"),
          ("moe.shared_sigmoid.w", "moe_shared_sigmoid_weight"),
          ("moe.load", "moe_load"))
_TOP = {"embed_weight": "embed.w", "head_weight": "head.w",
        "final_norm_gamma": "norm.w"}
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
    """Reference leaves -> {program name: array} for the names given; the
    program's own counter starts at zero."""
    out = {n: leaves[_leaf(n)] for n in names if _leaf(n) in leaves}
    out.update({n: np.zeros((2,), np.float32) for n in names
                if n.endswith("moe_dropped")})
    return out


def from_program(arrays, cfg):
    """{program name: array} -> {reference leaf: array}.  The loads the
    experts held received are compared with the reference's counts (the
    `aux` numbers); `moe_dropped` is left out and held to 0 by the run."""
    return {_leaf(n): a for n, a in arrays.items() if _leaf(n)}


def _work(total, macs, params=0):
    total.forward_macs += macs
    total.train_flops += 6 * macs
    total.param_bytes_f32 += 4 * params


def _kinds(cfg):
    n = cfg["num_hidden_layers"]
    attn = sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(n))
    return n - attn, attn


def delta_rule_macs(cfg):
    """The published recurrence, per token and layer: S'^T k, k u^T and
    S^T q, one key-size x value-size product each per value head."""
    return 3 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * \
        cfg["linear_num_value_heads"]


def local_assignments(cfg):
    """Expected assignments a token makes to the experts held here."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * held["count"] / held["of"]


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one token: the matrix products
    of the layers held and of the head, the attention scores at the mean
    causal length, the delta rule's state work, and the routed experts at
    the expected local assignments a token; recomputation not counted."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    inter, held = cfg["moe_intermediate_size"], cfg["experts_held"]
    gdn, attn = _kinds(cfg)
    total = flops.Count()
    for _ in range(gdn):
        total.dense(c, 2 * kd + 2 * vd)
        total.dense(c, 2 * cfg["linear_num_value_heads"])
        total.dense(vd, c)
        conv = (2 * kd + vd) * cfg["linear_conv_kernel_dim"]
        _work(total, conv, conv)
        _work(total, delta_rule_macs(cfg))
    for _ in range(attn):
        total.dense(c, 2 * heads * d)
        total.dense(c, kv * d)
        total.dense(c, kv * d)
        total.dense(heads * d, c)
        _work(total, 2 * heads * d * (cfg["seq_len"] + 1) / 2)
    for _ in range(gdn + attn):
        total.dense(c, held["of"])
        for _ in range(2):
            total.dense(c, cfg["shared_expert_intermediate_size"])
        total.dense(cfg["shared_expert_intermediate_size"], c)
        total.dense(c, 1)
        _work(total, local_assignments(cfg) * 3 * c * inter,
              held["count"] * 3 * c * inter)
    total.dense(c, v)
    total.param_bytes_f32 += 4 * c * v       # the embedding: no product
    return total


def kernel_work(cfg, tokens):
    """{operator kind: (operations, bytes)} of one training step of `tokens`
    tokens, forward and backward, over all layers: what the published
    algorithm needs, whatever implements it -- its multiply-adds, and ONE
    read of each pass's inputs and ONE write of its outputs in the
    configuration's types (bfloat16 activations and weights, float32 g and
    beta).  The divisors of `gdn_roofline_pct` and
    `moe_grouped_roofline_pct`."""
    gdn, attn = _kinds(cfg)
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    hv = cfg["linear_num_value_heads"]
    # forward reads q, k, v, g, beta and writes o; backward reads them and
    # do, writes dq, dk, dv, dg, dbeta
    ins = 2 * (2 * kd + vd) + 4 * 2 * hv
    delta_bytes = tokens * gdn * (ins + 2 * vd + ins + 2 * vd + ins)
    delta_ops = tokens * gdn * 6 * delta_rule_macs(cfg)
    c, inter, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["experts_held"]
    rows = tokens * local_assignments(cfg)
    weights = 2 * held["count"] * 3 * c * inter
    # forward reads the routed rows and the weights held, writes a row per
    # assignment; backward reads rows, weights and the rows' gradients,
    # writes the rows' and the weights' gradients
    moe_bytes = (gdn + attn) * (5 * rows * 2 * c + 3 * weights)
    moe_ops = (gdn + attn) * 6 * rows * 3 * c * inter
    return {"GatedDeltaRule": (delta_ops, delta_bytes),
            "RoutedExperts": (moe_ops, moe_bytes)}
