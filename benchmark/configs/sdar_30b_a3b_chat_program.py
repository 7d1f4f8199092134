"""How the `sdar_30b_a3b_chat` configuration is composed in the program
under test (`llm.sdar_moe_symbol`: the gluon `SdarMoeLM` under a weighted
`SoftmaxOutput`), how its parameter names map onto the plain reference's
leaves, and the operations and bytes of its step, of its routed experts and
of its attention under the block-diffusion mask.

Two things the harness fixes, which the symbol lives with (the harness is
not edited).  (1) It binds `data` and `softmax_label`, both (batch, L), the
second the same stream one token later, and reads the loss as the `ce`
metric of output 0 against `softmax_label`.  (2) It hands the reference's
`outputs`, which decides `out0_gap`, `data` ALONE.  So the clean sequence x
is `data` on both sides: the graph corrupts `data`, runs [noisy | clean]
and reads its in-place targets from `data`; the weighted objective is held
by the gradient readings (`dw_*`, `mom_*`) and the first step's
probabilities (`out0_*`).  `softmax_label` stays an argument whose value
reaches no gradient: the `loss` reading, on both sides, is the unweighted
mean over all L noisy rows of -log p_i[softmax_label_i] -- with this
traffic the stream's NEXT token, which on a stream drawn token by token has
the distribution of the token in place.  (ISSUE 36 asked for x =
`softmax_label` and `data` unused; `outputs` cannot be computed from `data`
then: the last column of x, on which every row's noise draw depends, is not
in it.)  One input array a cell is for a `benchmark` issue to offer.
"""
import re

import numpy as np

PREFIX = "lm_"


def build_symbol(mx, cfg):
    from incubator_mxnet_tpu.llm import SdarMoeConfig, sdar_moe_symbol
    return sdar_moe_symbol(SdarMoeConfig.from_dict(cfg), prefix=PREFIX)


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


_LAYER = (("norm1.w", "norm1_gamma"), ("norm2.w", "norm2_gamma"),
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
          ("moe.load", "moe_load"))
_TOP = {"embed_weight": "embed.w", "head_weight": "head.w",
        "final_norm_gamma": "norm.w", "noise_stats": "noise.stats"}
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
    experts held received and the noise's counts (rows, rows masked, the
    weights' sum) are compared with the reference's (the `aux` numbers);
    `moe_dropped`, held to 0 by the run, is left out."""
    return {_leaf(n): a for n, a in arrays.items() if _leaf(n)}


def _work(total, macs, params=0):
    total.forward_macs += macs
    total.train_flops += 6 * macs
    total.param_bytes_f32 += 4 * params


def local_assignments(cfg):
    """Expected assignments a row makes to the experts held here."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * held["count"] / held["of"]


def mask_entries(cfg):
    """Score entries the block-diffusion mask leaves, a sequence: L^2 + L B
    of the (2L)^2 (L B on the noisy diagonal, L (L - B) / 2 noisy against
    clean, L (L + B) / 2 clean against clean)."""
    return cfg["seq_len"] * (cfg["seq_len"] + cfg["block_length"])


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one CLEAN token (the 2L rows
    are the model's business: a clean token costs its noisy row and its
    clean row in every layer): the layers' matrix products for two rows,
    the attention scores at the mask's L^2 + L B entries a sequence, the
    routed experts at the expected local assignments of two rows, and the
    head on the noisy row alone; recomputation not counted."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    inter, held = cfg["moe_intermediate_size"], cfg["experts_held"]
    total = flops.Count()
    for _ in range(cfg["num_hidden_layers"]):
        for n_in, n_out in ((c, heads * d), (c, kv * d), (c, kv * d),
                            (heads * d, c), (c, held["of"])):
            _work(total, 2 * n_in * n_out, n_in * n_out)
        # the score and the value product: d multiply-adds an entry a head
        _work(total, 2 * heads * d * mask_entries(cfg) / cfg["seq_len"])
        _work(total, 2 * local_assignments(cfg) * 3 * c * inter,
              held["count"] * 3 * c * inter)
    total.dense(c, v)                  # the head; the embedding: no product
    total.param_bytes_f32 += 4 * (v + 1) * c
    return total


def kernel_work(cfg, tokens):
    """{operator kind: (operations, bytes)} of one training step of `tokens`
    CLEAN tokens, forward and backward, over all layers: what the published
    algorithm needs, whatever implements it -- its multiply-adds, and ONE
    read of each pass's inputs and ONE write of its outputs in the
    configuration's types (bfloat16 activations and weights).  The divisors
    of `moe_grouped_roofline_pct` and `attn_roofline_pct`."""
    layers = cfg["num_hidden_layers"]
    c, inter, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["experts_held"]
    rows = 2 * tokens * local_assignments(cfg)
    weights = 2 * held["count"] * 3 * c * inter
    # forward reads the routed rows and the weights held, writes a row per
    # assignment; backward reads rows, weights and the rows' gradients,
    # writes the rows' and the weights' gradients
    moe_bytes = layers * (5 * rows * 2 * c + 3 * weights)
    moe_ops = layers * 6 * rows * 3 * c * inter
    # attention: the mask's entries x 2 products forward and 4 backward
    # (the score's recomputation in the backward pass is the kernel's, not
    # the algorithm's); one read of q, k, v and the output's gradient, one
    # write of the output and of the three gradients
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    sequences = tokens / cfg["seq_len"]
    attn_ops = layers * sequences * mask_entries(cfg) * heads * d * 2 * 6
    attn_bytes = layers * 2 * tokens * 2 * (4 * heads * d + 4 * kv * d)
    return {"RoutedExperts": (moe_ops, moe_bytes),
            "BlockwiseAttention": (attn_ops, attn_bytes)}
