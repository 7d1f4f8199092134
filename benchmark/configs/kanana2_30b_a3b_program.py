"""How the `kanana2_30b_a3b` configuration is composed in the program under
test (`llm.kanana2_symbol`: the gluon `Kanana2LM` under `SoftmaxOutput`),
how its parameter names map onto the plain reference's leaves, and the
operations and bytes of its step, of its attention and of its routed
experts.

The reference holds the family's layout: a head of q is [nope | pe], the
k_pe rows of W_kva are pe wide, and with `rope_interleave` the rotary
embedding pairs entries 2i and 2i + 1; two shared experts.  The program's
head is [pe | nope] and its rotary embedding pairs entry i with i + pe / 2;
one shared SwiGLU of twice the width.  `to_program` permutes the rows of
W_q and of W_kva's k_pe part (evens, then odds) and puts the shared experts
side by side; `from_program` undoes both, so that every reading compares
the same leaves.
"""
import re

import numpy as np

PREFIX = "lm_"


def build_symbol(mx, cfg):
    """The training graph.  Also sets the process's `MXNET_FUSED_STEP_BLOCK`
    to the configuration's `fused_step_block` (4, `assumed`): `Module.fit`
    takes its block from that knob alone and the harness passes it nothing.
    The setting outlives this call: a caller that builds another cell in
    the same process puts the knob back, as the tests that build this
    symbol do (ouro_2_6b_program.py's stopgap, for the same reason)."""
    import os
    from incubator_mxnet_tpu.llm import Kanana2Config, kanana2_symbol
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(int(cfg["fused_step_block"]))
    return kanana2_symbol(Kanana2Config.from_dict(cfg), prefix=PREFIX)


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


_LAYER = (("norm1.w", "norm1_gamma"), ("norm2.w", "norm2_gamma"),
          ("attn.q.w", "mla_q_proj_weight"),
          ("attn.kva.w", "mla_kv_a_proj_weight"),
          ("attn.kvnorm.w", "mla_kv_norm_gamma"),
          ("attn.kvb.w", "mla_kv_b_proj_weight"),
          ("attn.o.w", "mla_o_proj_weight"),
          ("mlp.gate.w", "ffn_w1_weight"), ("mlp.up.w", "ffn_w3_weight"),
          ("mlp.down.w", "ffn_w2_weight"),
          ("moe.router.w", "moe_router_weight"),
          ("moe.gate.w", "moe_experts_gate_weight"),
          ("moe.up.w", "moe_experts_up_weight"),
          ("moe.down.w", "moe_experts_down_weight"),
          ("moe.bias", "moe_select_bias"), ("moe.load", "moe_load"))
# the shared experts: one program matrix, a reference leaf an expert, side
# by side along the intermediate width (rows of gate and up, columns of
# down)
_SHARED = {"moe_shared_gate_weight": ("gate.w", 0),
           "moe_shared_up_weight": ("up.w", 0),
           "moe_shared_down_weight": ("down.w", 1)}
_TOP = {"embed_weight": "embed.w", "head_weight": "head.w",
        "final_norm_gamma": "norm.w"}
_OF_LAYER = {prog: ref for ref, prog in _LAYER}
_NAME = re.compile(re.escape(PREFIX) + r"(?:layer(\d+)_)?(.+)$")


def _split(name):
    """(layer or None, the name's rest) of a program name, or None."""
    m = _NAME.match(name)
    return None if m is None else (m.group(1), m.group(2))


def _leaf(name):
    """The reference leaf of a program name, or None (the inputs, the
    shared experts, and `moe_dropped`, which has no counterpart: the
    reference drops nothing by construction)."""
    parts = _split(name)
    if parts is None:
        return None
    layer, rest = parts
    if layer is None:
        return _TOP.get(rest)
    ref = _OF_LAYER.get(rest)
    return ref and f"l{layer}.{ref}"


def rope_rows(cfg):
    """The order of a head's pe rows in the program: evens, then odds,
    where the family interleaves its pairs; as they are otherwise."""
    pe = cfg["qk_rope_head_dim"]
    if not cfg["rope_interleave"]:
        return np.arange(pe)
    return np.concatenate([np.arange(0, pe, 2), np.arange(1, pe, 2)])


def row_orders(cfg):
    """{reference leaf suffix: the rows of the reference's matrix in the
    program's order}: each head of W_q as [pe | nope], and W_kva's k_pe
    rows as the rotary embedding pairs them."""
    h, nope, pe = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    head = np.concatenate([nope + rope_rows(cfg), np.arange(nope)])
    q = (np.arange(h)[:, None] * (nope + pe) + head[None, :]).reshape(-1)
    kva = np.concatenate([np.arange(rank), rank + rope_rows(cfg)])
    return {"attn.q.w": q, "attn.kva.w": kva}


def to_program(leaves, cfg, names):
    """Reference leaves -> {program name: array} for the names given (the
    selection bias, a parameter that no gradient moves in the reference, is
    an auxiliary state of the program: the same array); the program's own
    counter starts at zero."""
    import jax.numpy as jnp
    orders = row_orders(cfg)
    out = {}
    for n in names:
        leaf, parts = _leaf(n), _split(n)
        if leaf in leaves:
            suffix = leaf.split(".", 1)[1]
            out[n] = leaves[leaf][orders[suffix]] if suffix in orders \
                else leaves[leaf]
        elif parts and parts[1] in _SHARED:
            which, axis = _SHARED[parts[1]]
            out[n] = jnp.concatenate(
                [leaves[f"l{parts[0]}.moe.shared{j}.{which}"]
                 for j in range(cfg["n_shared_experts"])], axis=axis)
        elif n.endswith("moe_dropped"):
            out[n] = np.zeros((2,), np.float32)
    return out


def from_program(arrays, cfg):
    """{program name: array} -> {reference leaf: array}, the rows put back
    in the family's order and the shared experts apart.  The loads the
    experts held received are compared with the reference's counts (the
    `aux` numbers).  Left out: `moe_dropped`, held to 0 by the run, and the
    selection bias, which no step moves on either side (the reference keeps
    it among its parameters, where a leaf that does not move is passed
    over)."""
    back = {s: np.argsort(order) for s, order in row_orders(cfg).items()}
    out = {}
    for n, a in arrays.items():
        leaf, parts = _leaf(n), _split(n)
        if leaf and not n.endswith("moe_select_bias"):
            suffix = leaf.split(".", 1)[1]
            out[leaf] = a[back[suffix]] if suffix in back else a
        elif parts and parts[1] in _SHARED:
            which, axis = _SHARED[parts[1]]
            width = a.shape[axis] // cfg["n_shared_experts"]
            for j in range(cfg["n_shared_experts"]):
                part = slice(j * width, (j + 1) * width)
                out[f"l{parts[0]}.moe.shared{j}.{which}"] = \
                    a[part] if axis == 0 else a[:, part]
    return out


def local_assignments(cfg):
    """Expected assignments a token makes to the experts held here."""
    held = cfg["experts_held"]
    return cfg["num_experts_per_tok"] * held["count"] / held["of"]


def mla_projections(cfg):
    """(in, out) of the MLA's four products: q, kv_a, kv_b, o."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return ((c, h * (nope + pe)), (c, rank + pe), (rank, h * (nope + dv)),
            (h * dv, c))


def _work(total, macs, params=0):
    total.forward_macs += macs
    total.train_flops += 6 * macs
    total.param_bytes_f32 += 4 * params


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one token: the matrix products
    of the layers held and of the untied head, the attention's scores
    (nope + pe deep) and values (dv deep) at the mean causal length (L + 1)
    / 2 a head, the routers, the shared experts, and the routed experts at
    the expected local assignments a token; recomputation not counted."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    depth = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + \
        cfg["v_head_dim"]
    inter, held = cfg["moe_intermediate_size"], cfg["experts_held"]
    total = flops.Count()
    for i in range(cfg["num_hidden_layers"]):
        for n_in, n_out in mla_projections(cfg):
            total.dense(n_in, n_out)
        _work(total, h * depth * (cfg["seq_len"] + 1) / 2)
        if i < cfg["first_k_dense_replace"]:
            for _ in range(3):
                total.dense(c, cfg["intermediate_size"])
            continue
        total.dense(c, held["of"])
        for _ in range(3):
            total.dense(c, inter * cfg["n_shared_experts"])
        _work(total, local_assignments(cfg) * 3 * c * inter,
              held["count"] * 3 * c * inter)
    total.dense(c, v)                  # the head
    total.param_bytes_f32 += 4 * v * c  # the embedding: no product
    return total


def kernel_work(cfg, tokens):
    """{operator kind: (operations, bytes)} of one training step of `tokens`
    tokens, forward and backward, over all layers: what the published
    algorithm needs, whatever implements it -- its multiply-adds, and ONE
    read of each pass's inputs and ONE write of its outputs in the
    configuration's types (bfloat16 activations and weights).  The divisors
    of `attn_roofline_pct` and `moe_grouped_roofline_pct`."""
    layers = cfg["num_hidden_layers"]
    routed = layers - cfg["first_k_dense_replace"]
    c, inter, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["experts_held"]
    rows = tokens * local_assignments(cfg)
    weights = 2 * held["count"] * 3 * c * inter
    # forward reads the routed rows and the weights held, writes a row per
    # assignment; backward reads rows, weights and the rows' gradients,
    # writes the rows' and the weights' gradients
    moe_bytes = routed * (5 * rows * 2 * c + 3 * weights)
    moe_ops = routed * 6 * rows * 3 * c * inter
    # attention: the causal square's L (L + 1) / 2 entries a sequence, the
    # score product nope + pe deep and the value product dv deep, once
    # forward and twice backward (the score's recomputation in the backward
    # pass is the kernel's, not the algorithm's), 2 operations a
    # multiply-add, at the UNPADDED widths; one read of q, k, v and the
    # output's gradient, one write of the output and of the three gradients
    h, length = cfg["num_attention_heads"], cfg["seq_len"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    entries = tokens / length * length * (length + 1) / 2
    attn_ops = layers * entries * h * (d + dv) * 2 * 3
    attn_bytes = layers * tokens * 2 * (2 * h * (d + dv) + 2 * h * (d + dv))
    return {"RoutedExperts": (moe_ops, moe_bytes),
            "BlockwiseAttention": (attn_ops, attn_bytes)}
