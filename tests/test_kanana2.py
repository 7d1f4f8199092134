"""The Kanana-2 family (DeepSeek-V3's architecture: multi-head latent
attention, a dense layer in front of sigmoid-routed experts with a scale and
ungated shared experts) on the training path, at a small size on the CPU,
against the plain float32 reference the benchmark keeps
(benchmark/configs/kanana2_30b_a3b_reference.py, loaded by path: it imports
nothing of the program and holds the family's own layout).  Seeded random
weights throughout.

Tolerances.  Everything here runs in float32 on the CPU, where a matrix
product is a true float32 product; program and reference differ in the ORDER
of their sums only (a blocked softmax against a whole one, grouped rows
against a dense mask, one shared SwiGLU of twice the width against two, a
rotate-half rotary embedding on permuted rows against the interleaved one).
The helpers and their tolerance are tests/test_qwen3_next.py's: 2e-5
relative to the largest entry covers a few hundred float32 roundings (6e-8
each); gradients get five times that, having passed through both passes.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.llm import (Kanana2Config, MultiHeadLatentAttention,
                                     kanana2_symbol)
from incubator_mxnet_tpu.llm.qwen3_next import SparseMoE
from incubator_mxnet_tpu.ops import attention, experts as experts_ops
from incubator_mxnet_tpu.parallel import ExpertShare
from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn

from test_qwen3_next import cells, _close, _rand, _same_with_grads

CELL = "kanana2_mla_train_hostfed"

REF = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "kanana2_30b_a3b_reference.py"))
ADAPTER = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "kanana2_30b_a3b_program.py"))


def _tiny_cell():
    return cells.Cell(cells.benchmark_json(), CELL, tiny=True)


def _block_fn(block):
    """A gluon block on one input as a JAX function of (x, {parameter name
    without the block's prefix: array}), through the graph the symbol
    makes."""
    out = block(mx.sym.Variable("data"))
    fn, arg_nodes, aux_nodes, _ = graph_eval_fn(out, False)
    prefix = block.prefix

    def run(x, params, aux=()):
        args = tuple(x if n.name == "data" else params[n.name[len(prefix):]]
                     for n in arg_nodes)
        return fn(args, tuple(aux), jax.random.PRNGKey(0))[0][0]
    return run, [n.name[len(prefix):] for n in arg_nodes if
                 n.name != "data"], [n.name[len(prefix):] for n in aux_nodes]


# -- multi-head latent attention ----------------------------------------------

# heads, nope, pe, dv, latent, hidden: the tiny size, the head deeper than
# its value as the published one is
H, NOPE, PE, DV, RANK, C, T = 4, 16, 8, 16, 32, 48, 40


def _mla_cfg(interleave=True):
    return {"num_attention_heads": H, "qk_nope_head_dim": NOPE,
            "qk_rope_head_dim": PE, "v_head_dim": DV, "kv_lora_rank": RANK,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
            "rope_interleave": interleave}


def _mla_leaves(seed=10):
    return {"attn.q.w": _rand(seed, H * (NOPE + PE), C, scale=0.3),
            "attn.kva.w": _rand(seed + 1, RANK + PE, C, scale=0.3),
            "attn.kvnorm.w": 1.0 + _rand(seed + 2, RANK, scale=0.1),
            "attn.kvb.w": _rand(seed + 3, H * (NOPE + DV), RANK, scale=0.3),
            "attn.o.w": _rand(seed + 4, C, H * DV, scale=0.3)}


def _program_mla(cfg, orders=None):
    """The program's mixer on the family's weights: the rows put in the
    program's order by the benchmark's adapter (or by `orders`)."""
    block = MultiHeadLatentAttention(Kanana2Config(
        hidden_size=C, num_attention_heads=H, num_key_value_heads=H,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=PE, v_head_dim=DV,
        kv_lora_rank=RANK, rope_theta=cfg["rope_theta"]), prefix="mla_")
    run, names, _ = _block_fn(block)
    assert sorted(names) == ["kv_a_proj_weight", "kv_b_proj_weight",
                             "kv_norm_gamma", "o_proj_weight",
                             "q_proj_weight"]
    orders = orders or ADAPTER.row_orders(cfg)

    def program(x, q, kva, kvnorm, kvb, o):
        return run(x, {"q_proj_weight": q[orders["attn.q.w"]],
                       "kv_a_proj_weight": kva[orders["attn.kva.w"]],
                       "kv_norm_gamma": kvnorm, "kv_b_proj_weight": kvb,
                       "o_proj_weight": o})
    return program


def _reference_mla(cfg):
    names = ("attn.q.w", "attn.kva.w", "attn.kvnorm.w", "attn.kvb.w",
             "attn.o.w")

    def reference(x, *ws):
        return REF.mla(dict(zip(names, ws)), x, cfg, "float32")
    return reference, names


@pytest.mark.parametrize("interleave", [True, False])
def test_mla_against_the_family_layout(interleave):
    """The mixer ([pe | nope] heads, rotate-half pairs, the shared key
    broadcast into every head) on the family's weights ([nope | pe] heads,
    interleaved pairs where `rope_interleave` says so) put in its order:
    the output and the gradients of x and of all five weights, in the
    family's order."""
    cfg = _mla_cfg(interleave)
    reference, names = _reference_mla(cfg)
    leaves = _mla_leaves()
    args = (_rand(1, 2, T, C),) + tuple(leaves[n] for n in names)
    _same_with_grads(_program_mla(cfg), reference, args)


def test_mla_layout_is_not_free():
    """The permutation is what makes them agree: the family's weights taken
    in their own order, or interleaved pairs taken as rotate-half ones,
    give another result."""
    cfg = _mla_cfg()
    reference, names = _reference_mla(cfg)
    leaves = _mla_leaves()
    args = (_rand(1, 2, T, C),) + tuple(leaves[n] for n in names)
    want = np.asarray(reference(*args))
    as_given = {n: np.arange(len(o))
                for n, o in ADAPTER.row_orders(cfg).items()}
    for orders in (as_given, ADAPTER.row_orders(dict(cfg,
                                                     rope_interleave=False))):
        got = np.asarray(_program_mla(cfg, orders)(*args))
        assert np.abs(got - want).max() > 1e-3 * np.abs(want).max()


def test_interleaved_pairs_are_rotate_half_on_permuted_entries():
    """The identity the layout rests on: turning pairs (2i, 2i + 1) and
    then taking evens before odds is turning the permuted entries by
    rotate-half."""
    from incubator_mxnet_tpu.ops.lm_ops import rotary
    x = _rand(2, 2, 9, 3, PE)
    order = ADAPTER.rope_rows(_mla_cfg())
    _close(REF.rotary_interleaved(x, 10000.0)[..., order],
           rotary(x[..., order], PE, 10000.0), 1e-6)


def test_mla_nodes_are_named_and_the_key_is_shared():
    """Every node between the mixer's input and its output is named
    ``mla_*``; one rotary key a token, broadcast into the heads."""
    cfg = Kanana2Config(hidden_size=C, num_attention_heads=H,
                        num_key_value_heads=H, qk_nope_head_dim=NOPE,
                        qk_rope_head_dim=PE, v_head_dim=DV, kv_lora_rank=RANK)
    out = MultiHeadLatentAttention(cfg, prefix="mla_")(
        mx.sym.Variable("data"))
    nodes = [n for n in out._topo() if not n.is_variable]
    assert all(n.name.startswith("mla_") for n in nodes), \
        [n.name for n in nodes]
    kinds = [n.op.name for n in nodes]
    assert kinds.count("FullyConnected") == 4
    assert kinds.count("RotaryEmbedding") == 2
    assert kinds.count("BlockwiseAttention") == 1
    (bcast,) = [n for n in nodes if n.op.name == "broadcast_axis"]
    assert int(bcast.attrs["size"]) == H
    arg_shapes, _, _ = out.infer_shape(data=(2, T, C))
    shapes = dict(zip(out.list_arguments(), arg_shapes))
    assert shapes["mla_kv_a_proj_weight"] == (RANK + PE, C)
    assert shapes["mla_q_proj_weight"] == (H * (NOPE + PE), C)


# -- the flash path at scores deeper than values ------------------------------

def test_flash_kernels_at_192_over_128_interpreted(monkeypatch):
    """The published head, 128 + 64 deep scores over 128-wide values, one
    key-value head a query head: the kernels, interpreted, against XLA's
    block form -- output and the three gradients; the call pads 64 lanes
    (192 -> 256 in VMEM) and none of the value's."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (1, 256, 2, 192), jnp.float32)
    k = jax.random.normal(keys[1], (1, 256, 2, 192), jnp.float32)
    v = jax.random.normal(keys[2], (1, 256, 2, 128), jnp.float32)
    assert attention._driver(1, 256, 256, 192, 128, 4) == "interpret"
    lanes = mx.obs.counter("ops.attention.padded_lanes")
    before = lanes.value
    _same_with_grads(
        lambda q, k, v: attention.grouped_query_attention(q, k, v),
        lambda q, k, v: attention._xla_blocks(q, k, v, block_size=64),
        (q, k, v))
    assert lanes.value > before and (lanes.value - before) % 64 == 0
    # a call at whole lane tiles pads nothing; XLA's form counts nothing
    before = lanes.value
    jax.jit(attention.grouped_query_attention).lower(v, v, v)
    monkeypatch.delenv("MXNET_FLASH_INTERPRET")
    jax.jit(attention.grouped_query_attention).lower(q, k, v)
    assert lanes.value == before


# -- the router's scale ------------------------------------------------------

E, TOPK, EC, EI, N = 16, 4, 24, 12, 40
_ROUTER = {"num_experts_per_tok": TOPK, "norm_topk_prob": True,
           "routed_scaling_factor": 2.448}


def _moe_leaves(seed=20, e=E, c=EC, i=EI):
    return {"moe.router.w": _rand(seed, e, c, scale=0.5),
            "moe.gate.w": _rand(seed + 1, e, i, c, scale=0.3),
            "moe.up.w": _rand(seed + 2, e, i, c, scale=0.3),
            "moe.down.w": _rand(seed + 3, e, c, i, scale=0.3),
            "moe.bias": _rand(seed + 4, e, scale=0.05),
            "moe.shared0.gate.w": _rand(seed + 5, i, c, scale=0.3),
            "moe.shared0.up.w": _rand(seed + 6, i, c, scale=0.3),
            "moe.shared0.down.w": _rand(seed + 7, c, i, scale=0.3),
            "moe.shared1.gate.w": _rand(seed + 8, i, c, scale=0.3),
            "moe.shared1.up.w": _rand(seed + 9, i, c, scale=0.3),
            "moe.shared1.down.w": _rand(seed + 10, c, i, scale=0.3)}


def test_scaled_routed_experts_against_the_reference_router():
    """`RoutedExperts(scale=2.448)` with the family's epsilon: output and
    the gradients of x and of the four matrices against the reference's
    dense mask; the choice and the weights against `route`."""
    p, x = _moe_leaves(), _rand(30, 2, N // 2, EC)
    names = ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")

    def program(x, router, gate, up, down):
        return experts_ops.routed_experts(
            x, router, gate, up, down, E, TOPK, 0, bias=p["moe.bias"],
            scoring="sigmoid", norm_eps=REF.ROUTER_EPS, scale=2.448)[0]

    def reference(x, *ws):
        leaves = dict(p, **dict(zip(names, ws)))
        return REF.moe(leaves, x, _ROUTER, "float32", held=(0, E))[0]
    _same_with_grads(program, reference,
                     (x,) + tuple(p[n] for n in names))
    _, _, weights, chosen = experts_ops._routing(
        x.reshape(-1, EC), p["moe.router.w"], p["moe.bias"], TOPK,
        experts_ops.Router("sigmoid", True, REF.ROUTER_EPS, 2.448))
    want_w, want_e = REF.route(p, x.reshape(-1, EC), _ROUTER, "float32")
    assert np.array_equal(np.asarray(chosen), np.asarray(want_e))
    _close(weights, want_w)
    # the weights of a token sum to the scale
    _close(weights.sum(-1), jnp.full((N,), 2.448), 1e-6)


def _parent_sigmoid_routing(x2, router_weight, bias, top_k, eps):
    """`ops/experts._routing` with a sigmoid, a bias and `norm_topk`, as it
    stood before the scale."""
    logits = jnp.dot(x2, router_weight.astype(x2.dtype).T,
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)[1]
    top_s = experts_ops._chosen(scores, experts)
    total = jnp.sum(top_s, axis=-1, keepdims=True) + jnp.float32(eps)
    return scores, top_s, top_s / total, experts


def _parent_sigmoid_routing_bwd(x2, router_weight, scores, top_s, top_e,
                                eps, dw):
    """`ops/experts._routing_bwd` likewise."""
    total = jnp.sum(top_s, axis=-1, keepdims=True) + jnp.float32(eps)
    dw = (dw - jnp.sum(dw * top_s, axis=-1, keepdims=True) / total) / total
    experts = jnp.arange(scores.shape[1], dtype=top_e.dtype)
    dscores = jnp.sum(jnp.where(top_e[:, :, None] == experts,
                                dw[:, :, None], jnp.float32(0)), axis=1)
    dlogits = (dscores * scores * (jnp.float32(1) - scores)).astype(x2.dtype)
    dx2 = jnp.dot(dlogits, router_weight.astype(x2.dtype),
                  preferred_element_type=jnp.float32)
    drouter = lax.dot_general(dlogits, x2, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return dx2, drouter.astype(router_weight.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scale_one_is_bit_equal_to_the_unscaled_router(dtype):
    """`lfm2_24b_a2b`'s router (sigmoid, bias, epsilon 1e-6) at scale 1:
    outputs and both gradients bit for bit what the code before the scale
    gives, and the same jaxpr; a scale multiplies the weights and the
    weights' gradient, nothing else."""
    p = _moe_leaves(70)
    x = _rand(71, 64, EC).astype(dtype)
    router = p["moe.router.w"].astype(dtype)
    dw = _rand(72, 64, TOPK)
    now = experts_ops.Router("sigmoid", True, 1e-6, 1.0)
    assert now == experts_ops.Router("sigmoid", True, 1e-6)

    def new(x, router, dw, router_cfg=now):
        out = experts_ops._routing(x, router, p["moe.bias"], TOPK,
                                   router_cfg)
        return out + experts_ops._routing_bwd(x, router, out[0], out[1],
                                              out[3], router_cfg, dw)

    def old(x, router, dw):
        out = _parent_sigmoid_routing(x, router, p["moe.bias"], TOPK, 1e-6)
        return out + _parent_sigmoid_routing_bwd(x, router, out[0], out[1],
                                                 out[3], 1e-6, dw)
    for got, want in zip(jax.jit(new)(x, router, dw),
                         jax.jit(old)(x, router, dw)):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                              np.asarray(want.astype(jnp.float32)))
    assert str(jax.make_jaxpr(new)(x, router, dw)) == \
        str(jax.make_jaxpr(old)(x, router, dw))
    scaled = jax.jit(lambda x, r, dw: new(
        x, r, dw, experts_ops.Router("sigmoid", True, 1e-6, 2.448)))(
            x, router, dw)
    base = jax.jit(new)(x, router, dw)
    _close(scaled[2], 2.448 * base[2].astype(jnp.float32), 1e-6)
    for i in (0, 1, 3):
        assert np.array_equal(np.asarray(scaled[i]), np.asarray(base[i]))
    # the router's gradient at dw is the unscaled one's at 2.448 dw
    at = jax.jit(new)(x, router, 2.448 * dw)
    for got, want in zip(scaled[4:], at[4:]):
        _close(got.astype(jnp.float32), want.astype(jnp.float32),
               2e-2 if dtype == "bfloat16" else 1e-6)


# -- the layer of a chip's share ----------------------------------------------

def _moe_cfg(held):
    return Kanana2Config(hidden_size=EC, moe_intermediate_size=EI,
                         n_routed_experts=E, num_experts_per_tok=TOPK,
                         experts_held=held)


def _program_moe(p, x, held):
    """The program's `SparseMoE` of `kanana2` (routed experts of the share,
    the shared ones as one ungated SwiGLU) on the reference's leaves."""
    cfg = _moe_cfg(held)
    run, names, aux = _block_fn(SparseMoE(cfg, router=cfg.router(),
                                          shared_gate=False, prefix="moe_"))
    assert "shared_sigmoid_weight" not in names
    assert aux == ["select_bias", "load", "dropped"]
    leaves = {f"l1.{n}": a for n, a in p.items()}
    given = ADAPTER.to_program(
        leaves, {"n_shared_experts": 2, **_mla_cfg()},
        ["lm_layer1_moe_" + n for n in names])
    share = slice(held.offset, held.offset + held.count)
    params = {n[len("lm_layer1_moe_"):]: a for n, a in given.items()}
    for n in ("experts_gate_weight", "experts_up_weight",
              "experts_down_weight"):
        params[n] = params[n][share]
    return run(x, params, (p["moe.bias"], jnp.zeros((held.count,)),
                           jnp.zeros((2,))))


@pytest.mark.parametrize("count", [2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(count):
    """Over all disjoint shares of `count` experts, the program's layer
    outputs, the shared experts (which every chip computes) counted once,
    add up to the reference's layer that holds every expert."""
    p, x = _moe_leaves(40), _rand(41, 2, N // 2, EC)
    cfg = dict(_ROUTER, n_shared_experts=2)
    routed, load = REF.moe(p, x, cfg, "float32", held=(0, E))
    shared = REF.shared_experts(p, x, cfg, "float32")
    assert float(load.sum()) == N * TOPK
    shares = [ExpertShare.of_chip(E, E // count, i)
              for i in range(E // count)]
    parts = [_program_moe(p, x, s) for s in shares]
    _close(sum(parts) - (len(parts) - 1) * shared, routed + shared)
    for part, s in zip(parts, shares):
        held = dict(p, **{n: p[n][s.offset:s.offset + s.count]
                          for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
        _close(part, REF.moe(held, x, cfg, "float32",
                             held=(s.offset, s.count))[0] + shared)


def test_one_shared_swiglu_is_the_two():
    """Two SwiGLUs of width I side by side (the adapter's layout) are one
    of width 2I; and the split back gives the two leaves."""
    p, x = _moe_leaves(50), _rand(51, 3, EC)
    names = ["lm_layer1_moe_shared_" + n + "_weight"
             for n in ("gate", "up", "down")]
    one = ADAPTER.to_program({f"l1.{n}": a for n, a in p.items()},
                             {"n_shared_experts": 2, **_mla_cfg()}, names)
    g, u, d = (one[n] for n in names)
    assert g.shape == (2 * EI, EC) and d.shape == (EC, 2 * EI)
    _close(REF._swiglu(x, g, u, d, "float32"),
           REF.shared_experts(p, x, {"n_shared_experts": 2}, "float32"))
    back = ADAPTER.from_program(one, {"n_shared_experts": 2, **_mla_cfg()})
    for j in range(2):
        for part in ("gate", "up", "down"):
            leaf = f"l1.moe.shared{j}.{part}.w"
            assert np.array_equal(np.asarray(back[leaf]),
                                  np.asarray(p[leaf[3:]]))


# -- the model through Module.fit ---------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """The benchmark's own set-up at the `tiny` size: ONE module driven
    through `Module.fit` for a block of K = 4 fused steps with the guardian
    on, and the plain reference's 4 steps from the same seed.  The
    adapter sets the process's `MXNET_FUSED_STEP_BLOCK`; it is put back
    after this file."""
    from benchmark.harness import compare, runner
    from incubator_mxnet_tpu.obs import trace as obs_trace
    was = os.environ.get("MXNET_FUSED_STEP_BLOCK")
    cell = _tiny_cell()
    obs_trace.enable()
    obs_trace.reset()
    program = runner.Program(cell, 2147483791)
    spans = obs_trace.buffered()
    reference = compare.run_reference(cell.reference, cell.cfg, program.key,
                                      program.pool, program.k)
    yield cell, program, reference, spans
    if was is None:
        os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)
    else:
        os.environ["MXNET_FUSED_STEP_BLOCK"] = was


def test_fit_block_matches_the_reference(fitted):
    from benchmark.harness import compare
    cell, program, reference, _ = fitted
    assert program.unfused == 0 and program.k == 4
    fs = program.mod._fused_step
    assert fs is not None and not fs.broken
    # the four routed layers, and nothing else, as one scanned run
    assert [run[1] for run in fs.scan_runs] == [4]
    assert program.mod._guardian is not None
    nums = compare.numbers(program.prog, reference)
    # float32 on both sides: the gaps are roundings, 4 steps deep; out0 is
    # the first step's probabilities (the logits), dw and mom every leaf's
    # change and gradient
    for name in ("loss_gap", "loss0_gap", "out0_gap", "dw_gap", "mom_gap",
                 "aux_gap"):
        assert nums[name][0] < 1e-5, (name, nums[name])
    assert reference["loss"][-1] < reference["loss"][0]     # it trains
    # every leaf of the reference is compared, the shared experts apart and
    # the rows in the family's order; the bias moved on neither side
    compared = set(program.prog["dw"])
    assert compared == {n for n in reference["dw"]
                        if not n.endswith("moe.bias")}
    assert {"l1.moe.shared0.gate.w", "l4.moe.shared1.down.w",
            "l0.attn.kva.w", "head.w"} <= compared
    assert all(v == 0.0 for n, v in reference["dw"].items()
               if n.endswith("moe.bias"))


def test_moe_load_span_says_which_router(fitted):
    cell, program, reference, spans = fitted
    (load,) = [s for s in spans if s["name"] == "moe.load"]
    args = load["args"]
    tokens = 4 * cell.traffic["batch_per_chip"] * cell.cfg["seq_len"]
    assert args["scoring"] == "sigmoid"
    assert args["tokens"] == tokens and args["dropped"] == 0
    assert args["layers"] == 4          # the dense layer routes nothing
    assert 0 < args["assigned"] <= tokens * 4 * \
        cell.cfg["num_experts_per_tok"]


def test_scan_plan_folds_the_four_routed_layers():
    from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
    symbol = kanana2_symbol(Kanana2Config())
    plan = scan_plan(symbol)
    (run,) = plan["runs"]
    assert run["length"] == 4 and not plan["rejected"]
    kinds = {n.op.name for n in run["segments"][0]}
    assert {"BlockwiseAttention", "RotaryEmbedding", "broadcast_axis",
            "RoutedExperts", "RMSNorm", "FullyConnected"} <= kinds
    assert any(n.op.scan_remat for n in run["segments"][0])
    # every slot stacks one variable of four layers in a row
    for slot in run["params"] + run["aux"]:
        layers = [int(v.name.split("_")[1][len("layer"):]) for v in slot]
        assert layers == [1, 2, 3, 4]
    # the dense layer stays inlined in front
    inlined = [n for n in symbol._topo()
               if not n.is_variable and id(n) not in run["covered"]]
    read = {src.name for n in inlined for src, _ in n.inputs
            if src.is_variable}
    assert {"lm_layer0_ffn_w1_weight", "lm_layer0_mla_q_proj_weight",
            "lm_embed_weight", "lm_head_weight"} <= read


def test_config_from_the_published_keys():
    cell = cells.Cell(cells.benchmark_json(), CELL)
    cfg = Kanana2Config.from_dict(cell.cfg)
    assert cfg.experts_held == ExpertShare(128, 0, 8)
    assert cfg.num_experts == 128 and cfg.n_routed_experts == 128
    assert cfg.qk_head_dim == 192 and cfg.v_head_dim == 128
    assert cfg.shared_expert_intermediate_size == 1536
    assert cfg.router() == {"scoring": "sigmoid", "norm_eps": 1e-20,
                            "select_bias": True, "scale": 2.448}
    for bad in ({"q_lora_rank": 1536}, {"n_group": 8}, {"topk_group": 4},
                {"scoring_func": "softmax"}, {"num_key_value_heads": 8}):
        with pytest.raises(mx.MXNetError, match="Kanana2Config"):
            Kanana2Config(**bad)


def test_declared_bfloat16_parameters_and_fresh_initialisation():
    """Parameters declared bfloat16 bind so; the bias and the counters are
    float32 auxiliary states; a fresh `Module.fit` initialises the norms,
    the latent's among them, to 1 and the bias and the counters to 0."""
    cfg = Kanana2Config(param_dtype="bfloat16", vocab_size=32)
    mod = mx.mod.Module(kanana2_symbol(cfg), context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    exe = mod._exec_group.execs[0]
    for name in ("lm_embed_weight", "lm_head_weight",
                 "lm_layer0_mla_kv_b_proj_weight", "lm_layer0_ffn_w2_weight",
                 "lm_layer2_moe_shared_down_weight"):
        assert str(exe.arg_dict[name].dtype) == "bfloat16", name
    for name in ("lm_layer1_moe_select_bias", "lm_layer4_moe_load"):
        assert str(exe.aux_dict[name].dtype) == "float32", name
    mod.init_params(mx.init.Normal(0.02))
    args, aux = mod.get_params()
    for name in ("lm_layer0_norm1_gamma", "lm_layer1_mla_kv_norm_gamma",
                 "lm_final_norm_gamma"):
        assert float(args[name].asnumpy().min()) == 1.0, name
    assert float(np.abs(aux["lm_layer2_moe_select_bias"].asnumpy()).max()) \
        == 0.0
    assert 0 < float(np.abs(args["lm_embed_weight"].asnumpy()).max()) < 0.2
