"""How the `ouro_2_6b` configuration is composed in the program under test
(`llm.ouro_symbol`: the gluon `OuroLM`, one `contrib.foreach` over the
recurrent steps under `LoopExitOutput`), how its parameter names map onto
the plain reference's leaves, and the operations and bytes of its step, of
its attention and of its heads and exit objective.

The harness binds `data` (ids) and `softmax_label` (the same stream one
token later) and reads the loss as the `ce` metric of output 0, the last
recurrent step's probabilities, against `softmax_label`; the objective's
label is `softmax_label` too.
"""
import re

import numpy as np

PREFIX = "lm_"


def build_symbol(mx, cfg):
    """The training graph.  Also sets the process's `MXNET_FUSED_STEP_BLOCK`
    to the configuration's `fused_step_block`: `Module.fit` takes its block
    from that knob alone, the harness passes it nothing, and this
    configuration's block is 2, not the knob's default 8.

    The setting is process-wide and outlives this call, because `fit`
    reads the knob at every block: a caller that goes on to build another
    cell in the same process inherits K = 2 (its module is then reported
    unfused) unless it puts the knob back, as the tests that build this
    symbol do.  A stopgap until the harness hands a configuration's block
    to `Module.fit`; then this line goes."""
    import os
    from incubator_mxnet_tpu.llm import OuroConfig, ouro_symbol
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(int(cfg["fused_step_block"]))
    return ouro_symbol(OuroConfig.from_dict(cfg), prefix=PREFIX)


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


_LAYER = (("norm1a.w", "norm1a_gamma"), ("norm1b.w", "norm1b_gamma"),
          ("norm2a.w", "norm2a_gamma"), ("norm2b.w", "norm2b_gamma"),
          ("attn.q.w", "attn_q_proj_weight"),
          ("attn.k.w", "attn_k_proj_weight"),
          ("attn.v.w", "attn_v_proj_weight"),
          ("attn.out.w", "attn_out_proj_weight"),
          ("mlp.gate.w", "mlp_w1_weight"), ("mlp.up.w", "mlp_w3_weight"),
          ("mlp.down.w", "mlp_w2_weight"))
_TOP = {"embed_weight": "embed.w", "head_weight": "head.w",
        "final_norm_gamma": "norm.w", "exit_gate_weight": "gate.w",
        "exit_gate_bias": "gate.b", "exit_stats": "exit.stats"}
_OF_LAYER = {prog: ref for ref, prog in _LAYER}
_NAME = re.compile(re.escape(PREFIX) + r"(?:layer(\d+)_)?(.+)$")


def _leaf(name):
    """The reference leaf of a program name, or None (the inputs)."""
    m = _NAME.match(name)
    if m is None:
        return None
    if m.group(1) is None:
        return _TOP.get(m.group(2))
    ref = _OF_LAYER.get(m.group(2))
    return ref and f"l{m.group(1)}.{ref}"


def to_program(leaves, cfg, names):
    """Reference leaves -> {program name: array} for the names given."""
    return {n: leaves[_leaf(n)] for n in names if _leaf(n) in leaves}


def from_program(arrays, cfg):
    """{program name: array} -> {reference leaf: array}; the exit
    distribution's counts are compared with the reference's (`aux_*`)."""
    return {_leaf(n): a for n, a in arrays.items() if _leaf(n)}


def _work(total, macs, params=0):
    total.forward_macs += macs
    total.train_flops += 6 * macs
    total.param_bytes_f32 += 4 * params


def layer_macs(cfg):
    """Multiply-adds of one layer application a token: the four
    projections and the SwiGLU's three products (the weights' count)."""
    c, inter = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    return c * (heads * d + 2 * kv * d) + heads * d * c + 3 * c * inter


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one token: T steps of every
    held layer (its products, and the causal scores and values, d
    multiply-adds an entry a head over (L + 1) / 2 keys on average), and T
    heads with their exit gates; recomputation not counted."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    steps, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    total = flops.Count()
    for _ in range(steps):
        for _ in range(layers):
            _work(total, layer_macs(cfg))
            _work(total, 2 * heads * d * (cfg["seq_len"] + 1) / 2)
        _work(total, v * c + c)           # the head and the exit gate
    total.param_bytes_f32 += 4 * (layers * layer_macs(cfg) + 2 * v * c + c)
    return total


def kernel_work(cfg, tokens):
    """{operator kind: (operations, bytes)} of one training step of `tokens`
    tokens, forward and backward: what the published algorithm needs,
    whatever implements it -- its multiply-adds, and ONE read of each
    pass's inputs and ONE write of its outputs in the configuration's types
    (bfloat16 activations and weights).  The divisors of
    `attn_roofline_pct` and `loop_head_roofline_pct`."""
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    length, c, v = cfg["seq_len"], cfg["hidden_size"], cfg["vocab_size"]
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    # attention: the causal square's L (L + 1) / 2 entries a sequence x 2
    # products forward and 4 backward, a head d deep; one read of q, k, v
    # and the output's gradient, one write of the output and of the three
    # gradients
    entries = tokens / length * length * (length + 1) / 2
    attn_ops = applications * entries * heads * d * 2 * 6
    attn_bytes = applications * tokens * 2 * (4 * heads * d + 4 * kv * d)
    # the heads and the exit objective: T steps of the head's product
    # forward and its two products backward (the logits made again in the
    # backward pass are the operator's, not the algorithm's); one read of
    # the T states, the head's weight twice (forward, backward) and of the
    # labels, one write of the last step's probabilities, of the states'
    # gradients and of the weight's gradient
    steps = cfg["total_ut_steps"]
    head_ops = steps * tokens * v * c * 2 * 3
    head_bytes = 2 * (2 * steps * tokens * c + tokens * v + 3 * v * c) + \
        4 * tokens
    return {"BlockwiseAttention": (attn_ops, attn_bytes),
            "LoopExitOutput": (head_ops, head_bytes)}
