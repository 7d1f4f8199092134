"""The LFM2 mixture-of-experts family on the training path, at a small size
on the CPU, against the plain float32 reference the benchmark keeps
(benchmark/configs/lfm2_24b_a2b_reference.py, loaded by path: it imports
nothing of the program).  Seeded random weights throughout.

Tolerances.  Everything here runs in float32 on the CPU, where a matrix
product is a true float32 product; program and reference differ in the ORDER
of their sums only (a blocked softmax against a whole one, grouped rows
against a dense mask, tiles of the intermediate width against the whole).
The helpers and their tolerance are tests/test_qwen3_next.py's: 2e-5
relative to the largest entry covers a few hundred float32 roundings (6e-8
each); gradients get five times that, having passed through both passes.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.llm import Lfm2MoeConfig, lfm2_moe_symbol
from incubator_mxnet_tpu.ops import registry, experts as experts_ops
from incubator_mxnet_tpu.parallel import ExpertShare

from test_qwen3_next import (ROOT, cells, _close, _op, _rand,
                             _same_with_grads)

CELL = "lfm2_moe_train_hostfed"

REF = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "lfm2_24b_a2b_reference.py"))


# -- the gated short convolution ---------------------------------------------------

@pytest.mark.parametrize("length", [9, 2, 1])
def test_gated_short_conv(length):
    """Kernel 3, on sequences longer and SHORTER than the kernel: values and
    both gradients against the reference's [B | C | x] form."""
    bcx, w = _rand(1, 2, length, 3 * 12), _rand(2, 12, 3)
    _same_with_grads(_op("GatedShortConv", kernel=3), REF.short_conv,
                     (bcx, w))


def test_gated_short_conv_is_causal_and_gated():
    bcx, w = _rand(3, 2, 9, 3 * 12), _rand(4, 12, 3)
    op = _op("GatedShortConv", kernel=3)
    out = op(bcx, w)
    # what comes later changes nothing before it
    later = bcx.at[:, 6:].add(1.0)
    _close(op(later, w)[:, :6], out[:, :6])
    # by hand at t = 0: only the last tap sees a token, the others zeros
    b, gate, x = bcx[..., :12], bcx[..., 12:24], bcx[..., 24:]
    _close(out[:, 0], gate[:, 0] * w[:, 2] * b[:, 0] * x[:, 0])
    _close(out[:, 2], gate[:, 2] * sum(
        w[:, j] * b[:, j] * x[:, j] for j in range(3)))
    # it shares its taps with CausalConv1D
    _close(out, gate * _op("CausalConv1D", kernel=3)(b * x, w))
    # one form, counted where it is traced
    lowered = mx.obs.counter("ops.short_conv.lowered.xla")
    before = lowered.value
    jax.jit(op).lower(bcx, w)
    assert lowered.value == before + 1
    with pytest.raises(mx.MXNetError, match="GatedShortConv"):
        op(bcx, _rand(5, 12, 4))


# -- the sigmoid router ---------------------------------------------------------

E, TOPK, C, I, N = 16, 4, 24, 12, 40
_ROUTER = {"num_experts_per_tok": TOPK, "norm_topk_prob": True,
           "use_expert_bias": True, "routed_scaling_factor": 1.0}


def _moe_leaves(seed=20, e=E, c=C, i=I, scales=(0.5, 0.3), bias=0.05):
    return {"moe.router.w": _rand(seed, e, c, scale=scales[0]),
            "moe.gate.w": _rand(seed + 1, e, i, c, scale=scales[1]),
            "moe.up.w": _rand(seed + 2, e, i, c, scale=scales[1]),
            "moe.down.w": _rand(seed + 3, e, c, i, scale=scales[1]),
            "moe.bias": _rand(seed + 4, e, scale=bias)}


def _route(x2, router_weight, bias, top_k=TOPK, **router):
    return experts_ops._routing(
        x2, router_weight, bias, top_k,
        experts_ops.Router("sigmoid", **router))


@pytest.mark.parametrize("bias,norm", [(0.05, True), (0.5, True),
                                       (0.05, False)])
def test_sigmoid_router_against_the_reference(bias, norm):
    """Choice by score + bias (a small one, and one that moves most
    choices), weights by the score alone over the chosen scores' sum plus
    epsilon."""
    p, x = _moe_leaves(bias=bias), _rand(30, N, C)
    cfg = dict(_ROUTER, norm_topk_prob=norm)
    want_w, want_e = REF.route(p, x, cfg, "float32")
    scores, top_s, weights, experts = _route(
        x, p["moe.router.w"], p["moe.bias"], norm_topk=norm,
        eps=REF.ROUTER_EPS)
    assert np.array_equal(np.asarray(experts), np.asarray(want_e))
    _close(weights, want_w)
    s = np.asarray(jax.nn.sigmoid(x @ p["moe.router.w"].T))
    _close(scores, s)
    chosen = np.take_along_axis(s, np.asarray(experts), axis=1)
    _close(top_s, chosen)
    if norm:
        # the epsilon is there: the weights sum to a little under 1
        total = np.asarray(weights).sum(axis=1)
        assert np.all(total < 1.0) and np.all(total > 1.0 - 1e-5)
        _close(weights, chosen / (chosen.sum(1, keepdims=True) + 1e-6),
               1e-6)


def test_a_bias_flips_a_choice_and_leaves_the_weights_the_scores():
    p, x = _moe_leaves(bias=0.0), _rand(31, N, C)
    _, _, w0, e0 = _route(x, p["moe.router.w"], p["moe.bias"], eps=1e-6)
    # against no bias at all: the same choice and weights
    _, _, w_none, e_none = _route(x, p["moe.router.w"], None, eps=1e-6)
    assert np.array_equal(np.asarray(e0), np.asarray(e_none))
    _close(w0, w_none)
    # expert 7 lifted past every score: chosen by every token, first
    bias = p["moe.bias"].at[7].set(2.0)
    scores, top_s, w1, e1 = _route(x, p["moe.router.w"], bias, eps=1e-6)
    assert np.all(np.asarray(e1)[:, 0] == 7)
    assert not np.array_equal(np.asarray(e0), np.asarray(e1))
    # its weight is made of its SCORE, under 1, not of score + bias
    _close(top_s[:, 0], scores[:, 7])
    assert float(top_s.max()) < 1.0


def test_router_gradients_and_none_for_the_bias():
    """The written transpose of the routing against `jax.grad` of the
    reference, through the operator: the router's weight and x; the bias
    receives zeros."""
    p, x = _moe_leaves(60), _rand(61, 2, N // 2, C)
    names = ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")

    def program(x, router, gate, up, down, bias):
        return experts_ops.routed_experts(
            x, router, gate, up, down, E, TOPK, 0, bias=bias,
            scoring="sigmoid", norm_eps=1e-6)[0]

    def reference(x, router, gate, up, down, bias):
        leaves = dict(zip(names + ("moe.bias",),
                          (router, gate, up, down, bias)))
        return REF.moe(leaves, x, _ROUTER, "float32", held=(0, E))[0]
    args = (x,) + tuple(p[n] for n in names) + (p["moe.bias"],)
    _same_with_grads(program, reference, args)
    ct = _rand(62, 2, N // 2, C)
    dbias = jax.grad(lambda *a: jnp.sum(program(*a) * ct), argnums=5)(*args)
    assert np.all(np.asarray(dbias) == 0.0)
    drouter = jax.grad(lambda *a: jnp.sum(program(*a) * ct),
                       argnums=1)(*args)
    assert float(jnp.abs(drouter).max()) > 0


def _parent_routing(x2, router_weight, top_k, norm_topk):
    """`ops/experts._routing` as it stood at the parent commit (PR 33)."""
    logits = jnp.dot(x2, router_weight.astype(x2.dtype).T,
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, experts = lax.top_k(probs, top_k)
    weights = top_p
    if norm_topk:
        weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, top_p, weights, experts


def _parent_routing_bwd(x2, router_weight, probs, top_p, top_e, norm_topk,
                        dw):
    """`ops/experts._routing_bwd` as it stood at the parent commit."""
    if norm_topk:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        dw = (dw - jnp.sum(dw * top_p, axis=-1, keepdims=True) / total) / \
            total
    experts = jnp.arange(probs.shape[1], dtype=top_e.dtype)
    dprobs = jnp.sum(jnp.where(top_e[:, :, None] == experts, dw[:, :, None],
                               jnp.float32(0)), axis=1)
    dlogits = probs * (dprobs - jnp.sum(probs * dprobs, axis=-1,
                                        keepdims=True))
    dlogits = dlogits.astype(x2.dtype)
    dx2 = jnp.dot(dlogits, router_weight.astype(x2.dtype),
                  preferred_element_type=jnp.float32)
    drouter = lax.dot_general(dlogits, x2, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return dx2, drouter.astype(router_weight.dtype)


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_softmax_router_is_bit_equal_to_the_parents(norm_topk, dtype):
    """`qwen3_next`'s router (softmax, no bias, no epsilon) through
    the parametrised routing: outputs and both gradients bit for bit what
    the parent commit's code gives on fixed inputs, and the same jaxpr."""
    x = _rand(70, 64, C).astype(dtype)
    router = _rand(71, E, C, scale=0.5).astype(dtype)
    dw = _rand(72, 64, TOPK)
    now = experts_ops.Router("softmax", norm_topk, 0.0)

    def new(x, router, dw):
        out = experts_ops._routing(x, router, None, TOPK, now)
        return out + experts_ops._routing_bwd(x, router, out[0], out[1],
                                              out[3], now, dw)

    def old(x, router, dw):
        out = _parent_routing(x, router, TOPK, norm_topk)
        return out + _parent_routing_bwd(x, router, out[0], out[1], out[3],
                                         norm_topk, dw)
    for got, want in zip(jax.jit(new)(x, router, dw),
                         jax.jit(old)(x, router, dw)):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                              np.asarray(want.astype(jnp.float32)))
    assert str(jax.make_jaxpr(new)(x, router, dw)) == \
        str(jax.make_jaxpr(old)(x, router, dw))


# -- routed experts with the new router ---------------------------------------------

def _ref_routed(p, x, offset, count, cfg=_ROUTER):
    share = dict(p, **{n: p[n][offset:offset + count]
                       for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
    return REF.moe(share, x, cfg, "float32", held=(offset, count))


def _program_routed(p, x, offset, count, train=True, num=E, top_k=TOPK):
    op = registry.get("RoutedExperts")
    params = op.canonicalize_params(dict(
        num_experts=num, top_k=top_k, experts_offset=offset,
        experts_count=count, scoring="sigmoid", select_bias=True,
        norm_eps=1e-6))
    params["_train"] = train
    return op.fn(params, x, p["moe.router.w"],
                 p["moe.gate.w"][offset:offset + count],
                 p["moe.up.w"][offset:offset + count],
                 p["moe.down.w"][offset:offset + count], p["moe.bias"],
                 jnp.zeros((count,)), jnp.zeros((2,)))


def test_operator_hands_the_bias_back_and_counts():
    p, x = _moe_leaves(), _rand(30, 2, N // 2, C)
    out, bias, load, dropped = _program_routed(p, x, 4, 4)
    want, want_load = _ref_routed(p, x, 4, 4)
    _close(out, want)
    assert np.array_equal(np.asarray(bias), np.asarray(p["moe.bias"]))
    _close(load, want_load, 0)
    assert np.asarray(dropped).tolist() == [0.0, float(N)]
    _close(_program_routed(p, x, 4, 4, train=False), out, 0)
    op = registry.get("RoutedExperts")
    biased = op.canonicalize_params(dict(
        num_experts=E, top_k=TOPK, experts_count=4, select_bias=True))
    plain = op.canonicalize_params(dict(
        num_experts=E, top_k=TOPK, experts_count=4))
    assert op.num_aux(biased) == 3 and op.num_aux(plain) == 2
    assert op.list_input_names(biased)[-3:] == ["select_bias", "load",
                                                "dropped"]
    assert op.list_input_names(plain)[-2:] == ["load", "dropped"]
    with pytest.raises(mx.MXNetError, match="select_bias"):
        op.fn(dict(biased), x, p["moe.router.w"], p["moe.gate.w"][:4],
              p["moe.up.w"][:4], p["moe.down.w"][:4], jnp.zeros((4,)),
              jnp.zeros((2,)))
    with pytest.raises(mx.MXNetError, match="scoring"):
        experts_ops.routed_experts(
            x, p["moe.router.w"], p["moe.gate.w"], p["moe.up.w"],
            p["moe.down.w"], E, TOPK, 0, scoring="tanh")


# the kernel driver, interpreted, at a size it tiles, the intermediate width
# TWO tiles of 128 (forced: at these sizes a whole expert fits any VMEM)
KE, KTOPK, KC, KI, KN = 8, 2, 128, 256, 512
_KERNEL_CFG = dict(_ROUTER, num_experts_per_tok=KTOPK)
_KERNEL_NAMES = ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")


@pytest.mark.parametrize("driver", ["xla", "interpret"])
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_routed_experts_both_forms_both_drivers_two_tiles(form, driver,
                                                          monkeypatch):
    """The sigmoid router with its bias in front of the grouped form and of
    the dense one (a load above the capacity), under XLA's products and
    under the kernels, interpreted, whose grids walk the row blocks once a
    tile of the intermediate width: output and all five gradients against
    the reference's dense mask."""
    offset, count = 2, 4
    if driver == "interpret":
        monkeypatch.setattr(experts_ops, "_tile", lambda *a: 128)
    if form == "dense":
        monkeypatch.setattr(experts_ops, "capacity",
                            lambda *a: (128, 128 + count * 128, 128))
    p = _moe_leaves(80, KE, KC, KI, scales=(0.2, 0.1))
    x = _rand(81, 2, KN // 2, KC)

    def program(x, router, gate, up, down):
        held = slice(offset, offset + count)
        return experts_ops.routed_experts(
            x, router, gate[held], up[held], down[held], KE, KTOPK, offset,
            interpret=driver == "interpret", bias=p["moe.bias"],
            scoring="sigmoid", norm_eps=1e-6)[0]

    def reference(x, *ws):
        return _ref_routed(dict(p, **dict(zip(_KERNEL_NAMES, ws))), x,
                           offset, count, _KERNEL_CFG)[0]
    args = (x,) + tuple(p[n] for n in _KERNEL_NAMES)
    if driver == "interpret":
        assert experts_ops._tiles(KN, KC, KI, 128, 4) == (128, 128)
    held = tuple(a[offset:offset + count] for a in args[2:])
    _, counts, dropped = experts_ops.routed_experts(
        x, args[1], *held, KE, KTOPK, offset, bias=p["moe.bias"],
        scoring="sigmoid", norm_eps=1e-6)
    assert int(dropped) == 0 and int(counts.sum()) > 128
    _same_with_grads(program, reference, args)


def test_capacity_factor_keeps_a_concentrated_load_grouped(monkeypatch):
    """A router drawn to the experts held (a bias lifts them past every
    score): over three assignments a token where the mean is one.  At the
    default 2 mean loads the step takes the dense form, at 4 the grouped
    one, and both give the reference's result."""
    assert experts_ops.capacity(16384, 4, 64, 8) == (16384, 17408, 128)
    assert experts_ops.capacity(16384, 4, 64, 8, 4.0) == (32768, 33792, 128)
    # never more rows than the worst routing fills
    assert experts_ops.capacity(16384, 4, 64, 8, 16.0)[0] == 16384 * 4
    offset, count = 0, 4
    p, x = _moe_leaves(90), _rand(91, 2, N // 2, C)
    bias = p["moe.bias"].at[:3].set(2.0)
    held = tuple(p[n][offset:offset + count]
                 for n in ("moe.gate.w", "moe.up.w", "moe.down.w"))

    def program(factor):
        return experts_ops.routed_experts(
            x, p["moe.router.w"], *held, E, TOPK, offset, bias=bias,
            scoring="sigmoid", norm_eps=1e-6, capacity_factor=factor)
    want = _ref_routed(dict(p, **{"moe.bias": bias}), x, offset, count)[0]
    cap = {f: experts_ops.capacity(N, TOPK, E, count, f)[0]
           for f in (2.0, 4.0)}
    for factor in (2.0, 4.0):
        out, counts, dropped = program(factor)
        assert np.all(np.asarray(counts)[:3] == N)
        assert cap[2.0] < int(counts.sum()) <= cap[4.0]
        assert int(dropped) == 0
        _close(out, want)
    # which form ran: with the dense one emptied, only the step that takes
    # it gives nothing
    monkeypatch.setattr(experts_ops, "_dense",
                        lambda x2, *a: jnp.zeros_like(x2))
    experts_ops._apply_fn.cache_clear()
    try:
        _close(program(4.0)[0], want)
        assert not np.any(np.asarray(program(2.0)[0]))
    finally:
        experts_ops._apply_fn.cache_clear()
    with pytest.raises(mx.MXNetError, match="capacity_factor"):
        program(0.5)


def test_tiles_follow_the_bytes_a_grid_step_needs(monkeypatch):
    """Whole matrices where they fit the VMEM a grid step may ask for, the
    widest tile that does where they do not, XLA where none does."""
    # qwen3_next_80b_a3b: one tile either pass, as before there were tiles
    assert experts_ops._tiles(8192, 2048, 512, 128, 2) == (512, 512)
    # lfm2_24b_a2b: the backward pass's residents pass 96 MiB whole
    assert experts_ops._step_bytes(2048, 1536, 2, True) > \
        experts_ops.VMEM_BYTES
    assert experts_ops._tiles(16384, 2048, 1536, 128, 2) == (1536, 768)
    assert experts_ops._tile(2048, 4096, 2, False) == 2048
    assert experts_ops._tile(2048, 4096, 2, True) == 512
    # a tile divides the width in whole lanes
    assert experts_ops._tile(2048, 1280, 2, True) == 640
    assert experts_ops._tiles(8192, 2048, 500, 128, 2) is None
    # a hidden size no tile brings under the limit
    assert experts_ops._tile(1 << 15, 1536, 2, True) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert experts_ops._driver(16384, 2048, 1536, 128, False) == "kernel"
    assert experts_ops._driver(8192, 2048, 512, 128, False) == "kernel"
    assert experts_ops._driver(8192, 1 << 15, 1536, 128, False) == "xla"
    monkeypatch.undo()
    with pytest.raises(mx.MXNetError, match="VMEM"):
        experts_ops._driver(8192, 1 << 15, 1536, 128, True)


@pytest.mark.parametrize("count", [2, 8, 16])
def test_shares_add_up_to_the_uncut_layer(count):
    """Over all disjoint shares of E / count experts (8 shares of 2 among
    them) the partial outputs add up to the layer that holds every expert:
    nothing is computed by every share alike (no shared expert)."""
    p, x = _moe_leaves(40), _rand(41, 2, N // 2, C)
    whole, whole_load = _ref_routed(p, x, 0, E)
    shares = [ExpertShare.of_chip(E, E // count, i) for i in range(E // count)]
    parts = [_program_routed(p, x, s.offset, s.count) for s in shares]
    _close(sum(part[0] for part in parts), whole)
    _close(jnp.concatenate([part[2] for part in parts]), whole_load, 0)
    assert float(whole_load.sum()) == N * TOPK
    for part, s in zip(parts, shares):
        _close(part[0], _ref_routed(p, x, s.offset, s.count)[0])


# -- the model through Module.fit ------------------------------------------------

def _tiny_cell():
    return cells.Cell(cells.benchmark_json(), CELL, tiny=True)


@pytest.fixture(scope="module")
def fitted():
    """The benchmark's own set-up at the `tiny` size: ONE module driven
    through `Module.fit` for a block of K = 8 fused steps with the guardian
    on, and the plain reference's 8 steps from the same seed."""
    from benchmark.harness import compare, runner
    from incubator_mxnet_tpu.obs import trace as obs_trace
    cell = _tiny_cell()
    obs_trace.enable()
    obs_trace.reset()
    program = runner.Program(cell, 2147483777)
    spans = obs_trace.buffered()
    reference = compare.run_reference(cell.reference, cell.cfg, program.key,
                                      program.pool, program.k)
    return cell, program, reference, spans


def test_fit_block_matches_the_reference(fitted):
    from benchmark.harness import compare
    cell, program, reference, _ = fitted
    assert program.unfused == 0 and program.k == 8
    fs = program.mod._fused_step
    assert fs is not None and not fs.broken
    # the three conv + routed layers, and nothing else, as one scanned run
    assert [run[1] for run in fs.scan_runs] == [3]
    assert program.mod._guardian is not None
    nums = compare.numbers(program.prog, reference)
    # float32 on both sides: the gaps are roundings, 8 steps deep
    for name in ("loss_gap", "loss0_gap", "out0_gap", "dw_gap", "mom_gap",
                 "aux_gap"):
        assert nums[name][0] < 1e-5, (name, nums[name])
    assert reference["loss"][-1] < reference["loss"][0]     # it trains
    # the tied matrix is one leaf on either side, and it moved by the sum of
    # the embedding's and the head's gradients: the reference's `jax.grad`
    # sums both uses of `embed.w`
    assert "head.w" not in reference["dw"] and reference["dw"]["embed.w"] > 0
    assert abs(program.prog["dw"]["embed.w"] - reference["dw"]["embed.w"]) \
        < 1e-5 * reference["dw"]["embed.w"]
    # the selection bias moved on neither side, and is no leaf of the program
    assert all(v == 0.0 for n, v in reference["dw"].items()
               if n.endswith("moe.bias"))
    assert not any(n.endswith("moe.bias") for n in program.prog["dw"])
    assert not any(n.endswith("moe.bias") for n in program.prog["aux"])


def test_the_head_alone_does_not_make_the_embeddings_gradient(fitted):
    """Were the embedding's gradient the head's alone (the lookup's part
    lost), the tied leaf's change would differ: the reference with the
    lookup cut off from the gradient reads another norm."""
    cell, program, reference, _ = fitted
    cfg, ref = cell.cfg, cell.reference
    params, aux = ref.init_params(program.key, cfg)
    data, label = (jnp.asarray(a) for a in program.pool[0])
    whole = jax.grad(lambda p: ref.loss_fn(p, aux, data, label, cfg)[0])(
        params)["embed.w"]

    def head_only(p):
        x, _ = ref._trunk(
            dict(p, **{"embed.w": lax.stop_gradient(p["embed.w"])}),
            aux, data.astype(jnp.int32), cfg, "float32")
        logits = ref._mm(x.reshape(-1, x.shape[-1]), p["embed.w"], "float32")
        prob = jnp.take_along_axis(
            jax.nn.softmax(logits, -1),
            label.reshape(-1, 1).astype(jnp.int32), axis=-1)
        return -jnp.mean(jnp.log(prob[:, 0] + cfg["metric_eps"]))
    part = jax.grad(head_only)(params)["embed.w"]
    assert float(jnp.linalg.norm(whole - part)) > \
        0.01 * float(jnp.linalg.norm(whole))


def test_moe_load_span_says_which_router(fitted):
    cell, program, reference, spans = fitted
    (load,) = [s for s in spans if s["name"] == "moe.load"]
    args = load["args"]
    tokens = 8 * cell.traffic["batch_per_chip"] * cell.cfg["seq_len"]
    assert args["scoring"] == "sigmoid"
    assert args["tokens"] == tokens and args["dropped"] == 0
    assert args["layers"] == 4          # the dense layer routes nothing
    assert 0 < args["assigned"] <= tokens * 4 * \
        cell.cfg["num_experts_per_tok"]
    assert args["max"] >= args["mean"] > 0


def test_scan_plan_folds_exactly_the_three_conv_routed_layers():
    from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
    plan = scan_plan(lfm2_moe_symbol(Lfm2MoeConfig()))
    (run,) = plan["runs"]
    assert run["length"] == 3 and not plan["rejected"]
    # every slot of the scanned body stacks one variable of three layers in
    # a row: three routed feed-forwards and three conv mixers
    assert len(run["params"]) == 9 and len(run["aux"]) == 3
    for slot in run["params"] + run["aux"]:
        layers = [int(v.name.split("_")[1][len("layer"):]) for v in slot]
        assert layers == list(range(layers[0], layers[0] + 3))
        assert len({v.name.split("_", 2)[2] for v in slot}) == 1
    kinds = {n.op.name for n in run["segments"][0]}
    assert {"GatedShortConv", "RoutedExperts", "RMSNorm"} <= kinds
    assert "BlockwiseAttention" not in kinds
    assert any(n.op.scan_remat for n in run["segments"][0])
    # the dense layer and the attention mixer stay inlined
    symbol = lfm2_moe_symbol(Lfm2MoeConfig())
    inlined = [n for n in symbol._topo()
               if not n.is_variable and id(n) not in run["covered"]]
    assert {"BlockwiseAttention", "RotaryEmbedding", "Embedding",
            "SoftmaxOutput"} <= {n.op.name for n in inlined}
    read = {src.name for n in inlined for src, _ in n.inputs
            if src.is_variable}
    stacked = {v.name for slot in run["params"] + run["aux"] for v in slot}
    for name in symbol.list_arguments():
        if "_layer0_" in name or "_attn_" in name:
            assert name in read and name not in stacked
    # two periods behind the dense layer fold as periods of four
    deep = Lfm2MoeConfig(
        num_hidden_layers=9,
        layer_types=["conv"] + ["full_attention", "conv", "conv", "conv"] * 2)
    (run,) = scan_plan(lfm2_moe_symbol(deep))["runs"]
    assert run["length"] == 2
    assert "BlockwiseAttention" in {n.op.name for n in run["segments"][0]}


def test_config_from_the_published_keys():
    cell = cells.Cell(cells.benchmark_json(), CELL)
    cfg = Lfm2MoeConfig.from_dict(cell.cfg)
    assert cfg.rope_theta == 1000000 and cfg.head_dim == 64
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert cfg.experts_held == ExpertShare(64, 0, 8)
    assert cfg.num_experts == 64 and cfg.num_dense_layers == 1
    assert cfg.router() == {"scoring": "sigmoid", "norm_eps": 1e-6,
                            "select_bias": True, "capacity_factor": 4.0}
    # the published scale of the weights is 1: no other value has a path
    with pytest.raises(mx.MXNetError, match="routed_scaling_factor"):
        Lfm2MoeConfig(routed_scaling_factor=2.5)
    with pytest.raises(mx.MXNetError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=2)
    with pytest.raises(mx.MXNetError, match="layer_types"):
        Lfm2MoeConfig(layer_types=["conv", "window", "conv", "conv", "conv"])


def test_declared_bfloat16_parameters_and_fresh_initialisation():
    """Parameters declared bfloat16 bind so; the bias and the counters are
    float32 auxiliary states; a fresh `Module.fit` initialises plain norms
    to 1 and the bias and the counters to 0."""
    cfg = Lfm2MoeConfig(param_dtype="bfloat16", vocab_size=32)
    mod = mx.mod.Module(lfm2_moe_symbol(cfg), context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    exe = mod._exec_group.execs[0]
    for name in ("lm_embed_weight", "lm_layer0_conv_conv_weight",
                 "lm_layer0_ffn_w2_weight", "lm_layer1_attn_q_norm_gamma",
                 "lm_layer2_moe_experts_down_weight"):
        assert str(exe.arg_dict[name].dtype) == "bfloat16", name
    for name in ("lm_layer1_moe_select_bias", "lm_layer4_moe_load"):
        assert str(exe.aux_dict[name].dtype) == "float32", name
    assert "lm_head_weight" not in exe.arg_dict
    mod.init_params(mx.init.Normal(0.02))
    args, aux = mod.get_params()
    for name in ("lm_layer0_norm1_gamma", "lm_layer1_attn_k_norm_gamma",
                 "lm_final_norm_gamma"):
        assert float(args[name].asnumpy().min()) == 1.0, name
    assert float(np.abs(aux["lm_layer2_moe_select_bias"].asnumpy()).max()) \
        == 0.0
    assert float(aux["lm_layer3_moe_load"].asnumpy().max()) == 0.0
    assert 0 < float(np.abs(args["lm_embed_weight"].asnumpy()).max()) < 0.2


_FRESH = """
import json, sys
import numpy as np
import incubator_mxnet_tpu as mx
sym = mx.sym.load(sys.argv[1])
loaded = "incubator_mxnet_tpu.llm.lfm2" in sys.modules
exe = sym.simple_bind(mx.cpu(), data=(2, 16), softmax_label=(2, 16))
rng = np.random.default_rng(0)
for name, arr in exe.arg_dict.items():
    if name == "data":
        arr[:] = rng.integers(0, 32, arr.shape)
    elif name != "softmax_label":
        arr[:] = 0.05 * rng.standard_normal(arr.shape)
out = exe.forward(is_train=False)[0].asnumpy()
print(json.dumps({"shape": list(out.shape), "rowsum": float(out.sum(-1).mean()),
                  "ops": sorted({n["op"] for n in json.loads(sym.tojson())["nodes"]}),
                  "aux": len(sym.list_auxiliary_states()), "llm": loaded}))
"""


def test_saved_symbol_loads_in_a_fresh_process(tmp_path):
    path = str(tmp_path / "lfm2-symbol.json")
    lfm2_moe_symbol(Lfm2MoeConfig(vocab_size=32)).save(path)
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH, path], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["shape"] == [32, 32] and abs(got["rowsum"] - 1.0) < 1e-4
    assert {"RMSNorm", "RotaryEmbedding", "GatedShortConv", "RoutedExperts",
            "BlockwiseAttention", "FullyConnected", "Embedding"} \
        <= set(got["ops"])
    assert got["aux"] == 12      # four routed layers: bias, load, dropped


def test_cost_and_sharding_analyzers_know_the_gated_short_conv():
    from incubator_mxnet_tpu import analysis
    cfg = Lfm2MoeConfig(vocab_size=32)
    sym = lfm2_moe_symbol(cfg)
    shapes = {"data": (2, 16), "softmax_label": (2, 16)}
    report = analysis.check_cost(sym, shapes=shapes)
    flops = {}
    for op in report.per_op:
        flops[op.op] = flops.get(op.op, 0.0) + op.flops
    tokens = 32
    assert flops["GatedShortConv"] == 4 * 2.0 * (3 + 1) * tokens * \
        cfg.hidden_size
    per_layer = 2.0 * tokens * cfg.hidden_size * (
        cfg.num_experts + cfg.num_experts_per_tok * 3 *
        cfg.moe_intermediate_size)
    assert flops["RoutedExperts"] == 4 * per_layer
    assert report.unknown_ops == 0
    shard = analysis.check_sharding(sym, shapes=shapes, mesh="dp=2")
    known = analysis.check_sharding(
        mx.sym.CausalConv1D(mx.sym.Variable("data"), kernel=3, name="c"),
        shapes={"data": (2, 16, 8)}, mesh="dp=2")
    assert bool(shard.fallback_ops.get("GatedShortConv")) == \
        bool(known.fallback_ops.get("CausalConv1D"))
