"""The Qwen3-Next family on the training path, at a small size on the CPU,
against the plain float32 reference the benchmark keeps
(benchmark/configs/qwen3_next_80b_a3b_reference.py, loaded by path: it
imports nothing of the program).  Seeded random weights throughout.

Tolerances.  Everything here runs in float32 on the CPU, where a matrix
product is a true float32 product; program and reference differ in the ORDER
of their sums only (a chunked scan against a token-by-token recurrence, a
blocked softmax against a whole one, sorted groups against a dense mask).
TOL = 2e-5 relative to the largest entry covers a few hundred float32
roundings (6e-8 each) amplified by the delta rule's unit-triangular inverse;
gradients get 5 TOL, having passed through both passes.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.llm import Qwen3NextConfig, qwen3_next_symbol
from incubator_mxnet_tpu.ops import registry, experts as experts_ops
from incubator_mxnet_tpu.parallel import ExpertShare

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


sys.path.insert(0, ROOT)
from benchmark.harness import cells  # noqa: E402

REF = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "qwen3_next_80b_a3b_reference.py"))


def _op(name, **params):
    op = registry.get(name)
    params = op.canonicalize_params(params)
    return lambda *xs: op.fn(dict(params), *xs)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.all(np.isfinite(got))
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max(), scale)


def _same_with_grads(fn, ref_fn, args, tol=TOL):
    """Outputs, and the gradients of a fixed random projection of them
    with respect to every argument, agree."""
    out, want = fn(*args), ref_fn(*args)
    _close(out, want, tol)
    ct = jax.random.normal(jax.random.PRNGKey(99), want.shape)
    got_g = jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                     argnums=tuple(range(len(args))))(*args)
    want_g = jax.grad(lambda *a: jnp.sum(ref_fn(*a) * ct),
                      argnums=tuple(range(len(args))))(*args)
    for g, w in zip(got_g, want_g):
        _close(g, w, 5 * tol)


def _rand(seed, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


# -- the small operators ------------------------------------------------------

@pytest.mark.parametrize("kind", ["plain", "zero_centered", "gated"])
def test_rms_norm(kind):
    x, w, gate = _rand(1, 2, 5, 3, 16), _rand(2, 16), _rand(3, 2, 5, 3, 16)
    if kind == "gated":
        _same_with_grads(
            _op("RMSNorm", gated=True),
            lambda x, w, g: REF._norm(x, w, 1e-6, False) * jax.nn.silu(g),
            (x, w, gate))
    else:
        zero = kind == "zero_centered"
        _same_with_grads(_op("RMSNorm", zero_centered=zero),
                         lambda x, w: REF._norm(x, w, 1e-6, zero), (x, w))


@pytest.mark.parametrize("rotary_dim", [8, 32])
def test_rotary_embedding(rotary_dim):
    x = _rand(4, 2, 11, 3, 32)
    _same_with_grads(_op("RotaryEmbedding", rotary_dim=rotary_dim, base=1e7),
                     lambda x: REF._rotary(x, rotary_dim, 1e7), (x,))
    # position 0 is not turned, and what lies beyond rotary_dim passes
    out = _op("RotaryEmbedding", rotary_dim=rotary_dim, base=1e7)(x)
    _close(out[:, 0], x[:, 0])
    if rotary_dim < 32:
        _close(out[..., rotary_dim:], x[..., rotary_dim:])


def test_causal_conv1d():
    x, w = _rand(5, 2, 9, 12), _rand(6, 12, 4)

    def ref(x, w):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return sum(padded[:, j:j + 9] * w[:, j] for j in range(4))
    _same_with_grads(_op("CausalConv1D", kernel=4), ref, (x, w))
    # causal: what comes later changes nothing before it
    later = x.at[:, 6:].add(1.0)
    _close(_op("CausalConv1D", kernel=4)(later, w)[:, :6], ref(x, w)[:, :6])


def test_delta_gates():
    a, b = _rand(7, 2, 6, 4), _rand(8, 2, 6, 4)
    a_log = jnp.log(jnp.asarray([0.5, 2.0, 7.0, 15.0], jnp.float32))
    dt = jnp.ones((4,), jnp.float32)
    g, beta = _op("GatedDeltaGates")(a, b, a_log, dt)
    _close(g, -jnp.exp(a_log) * jax.nn.softplus(a + dt))
    _close(beta, jax.nn.sigmoid(b))
    assert g.dtype == jnp.float32 and np.all(np.asarray(g) < 0)


@pytest.mark.parametrize("block_size", [None, 4, 5])
def test_grouped_query_attention(block_size):
    """4 query heads on 2 key-value heads of 8, the value's head size 6:
    against the whole score matrix with every key-value head repeated."""
    b, t, h, kv, d, dv = 2, 10, 4, 2, 8, 6
    q, k, v = _rand(9, b, t, h * d), _rand(10, b, t, kv * d), \
        _rand(11, b, t, kv * dv)

    def ref(q, k, v):
        qh = q.reshape(b, t, h, d)
        kh = jnp.repeat(k.reshape(b, t, kv, d), h // kv, axis=2)
        vh = jnp.repeat(v.reshape(b, t, kv, dv), h // kv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), vh)
        return out.reshape(b, t, h * dv)
    _same_with_grads(_op("BlockwiseAttention", num_heads=h, num_kv_heads=kv,
                         block_size=block_size), ref, (q, k, v))


def test_attention_without_kv_heads_is_what_it_was():
    """`num_kv_heads` left at None takes the path the existing LM takes."""
    q, k, v = _rand(12, 2, 8, 16), _rand(13, 2, 8, 16), _rand(14, 2, 8, 16)
    from incubator_mxnet_tpu.ops.attention import naive_attention
    _close(_op("BlockwiseAttention", num_heads=4)(q, k, v),
           naive_attention(q, k, v, 4), 1e-5)
    jaxpr = str(jax.make_jaxpr(_op("BlockwiseAttention", num_heads=4))(
        q, k, v))
    assert "checkpoint" not in jaxpr and "remat" not in jaxpr


# -- the gated delta rule -------------------------------------------------------

def _delta_inputs(b, length, hk, hv, dk, dv):
    """Decay rates from nearly none to exp(-20) a token."""
    q, k = _rand(15, b, length, hk * dk), _rand(16, b, length, hk * dk)
    v = _rand(17, b, length, hv * dv)
    a_log = jnp.log(jnp.asarray(([0.01, 1.0, 6.0, 15.0] * hv)[:hv],
                                jnp.float32))
    g = -jnp.exp(a_log) * jax.nn.softplus(_rand(18, b, length, hv) + 1.0)
    beta = jax.nn.sigmoid(_rand(19, b, length, hv))
    return q, k, v, g, beta


def _delta_recurrence(b, length, hk, hv, dk, dv):
    def ref(q, k, v, g, beta):
        qh = REF._l2(q.reshape(b, length, hk, dk)) * dk ** -0.5
        kh = REF._l2(k.reshape(b, length, hk, dk))
        qh, kh = (jnp.repeat(x, hv // hk, axis=2) for x in (qh, kh))
        o = REF.delta_rule(qh, kh, v.reshape(b, length, hv, dv), g, beta)
        return o.reshape(b, length, hv * dv)
    return ref


@pytest.mark.parametrize("chunk,length,batch", [
    (8, 37, 2), (16, 37, 2), (64, 50, 2), (16, 32, 2), (8, 64, 1),
    (24, 100, 1)])
def test_chunked_delta_rule_against_the_recurrence(chunk, length, batch):
    """The scan driver (what the CPU takes) against the token-by-token
    recurrence through the operator's custom VJP, values and all five
    gradients: four chunk sizes, lengths that are multiples of the chunk
    and lengths that are not, a chunk that is no power of two."""
    hk, hv, dk, dv = 2, 4, 8, 6
    _same_with_grads(
        _op("GatedDeltaRule", num_heads=hk, num_v_heads=hv, chunk_size=chunk),
        _delta_recurrence(batch, length, hk, hv, dk, dv),
        _delta_inputs(batch, length, hk, hv, dk, dv))


@pytest.mark.parametrize("batch,length", [(1, 128), (2, 200)])
def test_delta_rule_kernel_interpreted_against_the_recurrence(batch, length):
    """The KERNEL, interpreted, at shapes it tiles (key and value size 128,
    chunks of 64, 2 key and 4 value heads) against the same recurrence:
    one block of two chunks a grid step and two, without padding and with
    it (200 positions are padded to 256)."""
    from incubator_mxnet_tpu.ops import delta_rule
    hk, hv, dk, dv = 2, 4, 128, 128

    def kernel(q, k, v, g, beta):
        o = delta_rule.gated_delta_rule(
            q.reshape(batch, length, hk, dk), k.reshape(batch, length, hk, dk),
            v.reshape(batch, length, hv, dv), g, beta, interpret=True)
        return o.reshape(batch, length, hv * dv)
    _same_with_grads(kernel, _delta_recurrence(batch, length, hk, hv, dk, dv),
                     _delta_inputs(batch, length, hk, hv, dk, dv))


def test_delta_rule_driver_follows_backend_and_shape(monkeypatch):
    """The compiled kernel on ``tpu`` where the shapes tile, the scan
    anywhere else; each traced call counts the driver it took."""
    from incubator_mxnet_tpu.ops import delta_rule
    assert delta_rule._driver(128, 128, 64, False) == "scan"      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule._driver(128, 128, 64, False) == "kernel"
    assert delta_rule._driver(128, 256, 8, False) == "kernel"
    assert delta_rule._driver(8, 128, 64, False) == "scan"        # key size
    assert delta_rule._driver(128, 6, 64, False) == "scan"        # value size
    assert delta_rule._driver(128, 128, 12, False) == "scan"      # chunk
    monkeypatch.undo()
    with pytest.raises(mx.MXNetError, match="tiles"):
        delta_rule._driver(8, 128, 64, True)

    def counts():
        return tuple(mx.obs.counter("ops.delta_rule.lowered." + d).value
                     for d in ("kernel", "scan"))
    hk, hv, dk, dv = 1, 2, 8, 128
    args = _delta_inputs(1, 16, hk, hv, dk, dv)
    before = counts()
    jax.jit(_op("GatedDeltaRule", num_heads=hk, num_v_heads=hv,
                chunk_size=8)).lower(*args)
    assert counts() == (before[0], before[1] + 1)
    q, k, v, g, beta = _delta_inputs(1, 16, 1, 1, 128, 128)
    delta_rule.gated_delta_rule(
        q.reshape(1, 16, 1, 128), k.reshape(1, 16, 1, 128),
        v.reshape(1, 16, 1, 128), g, beta, chunk_size=8, interpret=True)
    assert counts() == (before[0] + 1, before[1] + 1)


# -- routed experts ---------------------------------------------------------------

E, TOPK, C, I, N = 16, 4, 24, 12, 40


def _moe_leaves(seed=20, e=E, c=C, i=I, scales=(0.5, 0.3)):
    return {"moe.router.w": _rand(seed, e, c, scale=scales[0]),
            "moe.gate.w": _rand(seed + 1, e, i, c, scale=scales[1]),
            "moe.up.w": _rand(seed + 2, e, i, c, scale=scales[1]),
            "moe.down.w": _rand(seed + 3, e, c, i, scale=scales[1]),
            "moe.shared_gate.w": jnp.zeros((i, c)),
            "moe.shared_up.w": jnp.zeros((i, c)),
            "moe.shared_down.w": jnp.zeros((c, i)),
            "moe.shared_sigmoid.w": jnp.zeros((1, c))}


_MOE_CFG = {"num_experts_per_tok": TOPK, "norm_topk_prob": True}


def _ref_routed(p, x, offset, count, cfg=_MOE_CFG):
    """The reference's dense mask over experts [offset, offset + count);
    the shared expert's weights are zero, so only the routed part is left."""
    share = dict(p, **{n: p[n][offset:offset + count]
                       for n in ("moe.gate.w", "moe.up.w", "moe.down.w")})
    return REF.moe(share, x, cfg, "float32", held=(offset, count))


def _program_routed(p, x, offset, count, train=True):
    fn = _op("RoutedExperts", num_experts=E, top_k=TOPK,
             experts_offset=offset, experts_count=count)
    op = registry.get("RoutedExperts")
    params = op.canonicalize_params(dict(
        num_experts=E, top_k=TOPK, experts_offset=offset,
        experts_count=count))
    params["_train"] = train
    return op.fn(params, x, p["moe.router.w"],
                 p["moe.gate.w"][offset:offset + count],
                 p["moe.up.w"][offset:offset + count],
                 p["moe.down.w"][offset:offset + count],
                 jnp.zeros((count,)), jnp.zeros((2,)))


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_routed_experts_against_the_dense_mask(form, monkeypatch):
    """Both forms of the dispatch -- the grouped one and the dense one that a
    load above the capacity takes -- against the reference, forward and
    gradient."""
    offset, count = 4, 4
    plans = {"grouped": (N * TOPK, N * TOPK + count * 8, 8),
             "dense": (8, 8 + count * 8, 8)}
    monkeypatch.setattr(experts_ops, "capacity", lambda *a: plans[form])
    p, x = _moe_leaves(), _rand(30, 2, N // 2, C)
    out, load, dropped = _program_routed(p, x, offset, count)
    want, want_load = _ref_routed(p, x, offset, count)
    assert float(load.sum()) > 8        # the load does pass the small capacity
    _close(out, want)
    _close(load, want_load, 0)
    assert np.asarray(dropped).tolist() == [0.0, float(N)]
    names = ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")

    def wrap(fn):
        def run(x, *ws):
            return fn(dict(p, **dict(zip(names, ws))), x, offset, count)[0]
        return run
    _same_with_grads(wrap(_program_routed), wrap(_ref_routed),
                     (x,) + tuple(p[n] for n in names))


@pytest.mark.parametrize("count", [2, 4, 8, 16])
def test_shares_add_up_to_the_uncut_layer(count):
    """Over all disjoint shares of E / count experts the partial outputs add
    up to the layer that holds every expert; what every share computes alike
    (the shared expert, zero here) is counted once."""
    p, x = _moe_leaves(40), _rand(41, 2, N // 2, C)
    whole, whole_load = _ref_routed(p, x, 0, E)
    shares = [ExpertShare.of_chip(E, E // count, i) for i in range(E // count)]
    assert [s.chips for s in shares] == [E // count] * len(shares)
    parts = [_program_routed(p, x, s.offset, s.count) for s in shares]
    _close(sum(out for out, _, _ in parts), whole)
    _close(jnp.concatenate([load for _, load, _ in parts]), whole_load, 0)
    assert float(whole_load.sum()) == N * TOPK
    for (out, _, _), s in zip(parts, shares):
        _close(out, _ref_routed(p, x, s.offset, s.count)[0])


def test_no_token_is_dropped_when_all_route_to_one_expert():
    """Every token's largest probability on expert 5: its group alone is
    N rows, five times the mean load a tier is sized for."""
    p = _moe_leaves(50)
    x = jnp.abs(_rand(51, 2, N // 2, C)) + 0.5
    p["moe.router.w"] = p["moe.router.w"].at[5].set(4.0)
    out, load, dropped = _program_routed(p, x, 4, 4)
    assert float(load[1]) == N and float(dropped[0]) == 0.0
    _close(out, _ref_routed(p, x, 4, 4)[0])
    # not in training: the output alone, the counters untouched
    alone = _program_routed(p, x, 4, 4, train=False)
    _close(alone, out, 0)


def test_dropped_counts_the_assignments_left_without_a_row(monkeypatch):
    """`dropped` is counted where the rows are placed: were the rows sized too
    small for a load the grouped form is given, it would say by how much."""
    offset, count = 4, 4
    p, x = _moe_leaves(), _rand(30, 2, N // 2, C)
    _, load, dropped = _program_routed(p, x, offset, count)
    assert float(dropped[0]) == 0.0 and float(load.sum()) > 16
    monkeypatch.setattr(experts_ops, "capacity", lambda *a: (N * TOPK, 16, 8))
    _, load, dropped = _program_routed(p, x, offset, count)
    assert 0 < float(dropped[0]) <= float(load.sum()) - 16 + count * 7
    assert float(dropped[1]) == N


# the kernel driver, interpreted, at a size it tiles: hidden and intermediate
# size 128, a mean group of 128 rows, so blocks of 128
KE, KTOPK, KC, KI, KN = 8, 2, 128, 128, 512
_KERNEL_CFG = {"num_experts_per_tok": KTOPK, "norm_topk_prob": True}


def _kernel_leaves(seed=60):
    return _moe_leaves(seed, KE, KC, KI, scales=(0.2, 0.1))


_KERNEL_NAMES = ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")


def _kernel_pair(p, offset, count, interpret=True):
    """(the operator through `routed_experts`, the reference's dense mask)
    as functions of (x, router, gate, up, down), all experts' weights given."""
    def program(x, router, gate, up, down):
        held = slice(offset, offset + count)
        return experts_ops.routed_experts(
            x, router, gate[held], up[held], down[held], KE, KTOPK, offset,
            interpret=interpret)[0]

    def reference(x, *ws):
        return _ref_routed(dict(p, **dict(zip(_KERNEL_NAMES, ws))), x,
                           offset, count, _KERNEL_CFG)[0]
    return program, reference


@pytest.mark.parametrize("load", ["even", "one_expert"])
def test_experts_kernel_interpreted_against_the_dense_mask(load):
    """The KERNEL driver, interpreted, against the reference, the output and
    every gradient (the router's among them): an even load, and every token
    on one held expert, whose group then fills four blocks while the others'
    stay short of one."""
    offset, count = 2, 4
    assert experts_ops.capacity(KN, KTOPK, KE, count) == (1024, 1536, 128)
    p, x = _kernel_leaves(), _rand(70, 2, KN // 2, KC)
    if load == "one_expert":
        x = jnp.abs(x) + 0.5
        p["moe.router.w"] = p["moe.router.w"].at[3].set(1.0)
    program, reference = _kernel_pair(p, offset, count)
    args = (x,) + tuple(p[n] for n in _KERNEL_NAMES)
    counts = experts_ops.routed_experts(
        x, *(a[offset:offset + count] if i else a
             for i, a in enumerate(args[1:])), KE, KTOPK, offset)[1]
    if load == "one_expert":
        assert int(counts[1]) == KN
    _same_with_grads(program, reference, args)


def test_experts_driver_follows_backend_and_shape(monkeypatch):
    """The compiled kernel on ``tpu`` where the shapes tile, XLA's products
    anywhere else; each traced call counts the driver it took."""
    assert experts_ops._driver(512, 128, 128, 128, False) == "xla"   # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert experts_ops._driver(512, 128, 128, 128, False) == "kernel"
    assert experts_ops._driver(8192, 2048, 512, 128, False) == "kernel"
    assert experts_ops._driver(40, 128, 128, 128, False) == "xla"    # tokens
    assert experts_ops._driver(512, 24, 128, 128, False) == "xla"    # hidden
    assert experts_ops._driver(512, 128, 12, 128, False) == "xla"    # inner
    assert experts_ops._driver(512, 128, 128, 8, False) == "xla"     # block
    monkeypatch.undo()
    with pytest.raises(mx.MXNetError, match="tile"):
        experts_ops._driver(512, 24, 128, 128, True)

    def counts():
        return tuple(mx.obs.counter("ops.experts.lowered." + d).value
                     for d in ("kernel", "xla"))
    p, x = _moe_leaves(), _rand(30, 2, N // 2, C)
    before = counts()
    jax.jit(lambda x: _program_routed(p, x, 4, 4)[0]).lower(x)
    assert counts() == (before[0], before[1] + 1)
    p, x = _kernel_leaves(), _rand(70, 2, KN // 2, KC)
    program, _ = _kernel_pair(p, 2, 4)
    jax.jit(program).lower(x, *(p[n] for n in _KERNEL_NAMES))
    assert counts() == (before[0] + 1, before[1] + 1)


@pytest.mark.parametrize("interpret", [False, True])
def test_experts_that_receive_nothing_get_exact_zeros(interpret):
    """Two of the four experts held are never chosen: their groups are one
    block of no rows each, and the gradients of their weights are zeros, not
    what was left in a buffer."""
    offset, count = 2, 4
    p = _kernel_leaves()
    x = jnp.abs(_rand(71, 2, KN // 2, KC)) + 0.5
    p["moe.router.w"] = p["moe.router.w"].at[jnp.asarray([2, 5])].set(-1.0)
    program, _ = _kernel_pair(p, offset, count, interpret)
    args = (x,) + tuple(p[n] for n in _KERNEL_NAMES)
    held = tuple(a[offset:offset + count] for a in args[2:])
    out, counts, dropped = experts_ops.routed_experts(
        x, args[1], *held, KE, KTOPK, offset, interpret=interpret)
    assert np.asarray(counts).tolist()[0] == 0 == np.asarray(counts)[3]
    assert int(counts.sum()) > 0 and int(dropped) == 0
    grads = jax.grad(lambda *a: jnp.sum(jnp.square(program(*a))),
                     argnums=(2, 3, 4))(*args)
    for g in grads:
        g = np.asarray(g)
        assert np.all(g[[2, 5]] == 0.0) and np.all(np.isfinite(g))
        assert np.abs(g[3]).max() > 0 and np.abs(g[4]).max() > 0
        assert np.all(g[:2] == 0.0) and np.all(g[6:] == 0.0)    # not held


def test_expert_share():
    share = ExpertShare(512, 32, 16)
    assert share.chips == 32 and share.op_params() == {
        "num_experts": 512, "experts_offset": 32, "experts_count": 16}
    assert ExpertShare.of_chip(512, 32, 2) == share
    assert ExpertShare(8).count == 8
    for bad in ((8, 4, 8), (8, -1, 2), (8, 0, 0)):
        with pytest.raises(mx.MXNetError):
            ExpertShare(*bad)
    with pytest.raises(mx.MXNetError):
        ExpertShare.of_chip(10, 4, 0)


# -- the model through Module.fit ------------------------------------------------

def _tiny_cell():
    return cells.Cell(cells.benchmark_json(), "qwen3_next_train_hostfed",
                      tiny=True)


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
    assert fs.scan_runs and fs.scan_runs[0][1] == 3   # the delta-rule layers
    assert program.mod._guardian is not None
    nums = compare.numbers(program.prog, reference)
    # float32 on both sides: the gaps are roundings, 8 steps deep
    for name in ("loss_gap", "loss0_gap", "out0_gap", "dw_gap", "mom_gap",
                 "aux_gap"):
        assert nums[name][0] < 1e-5, (name, nums[name])
    assert reference["loss"][-1] < reference["loss"][0]     # it trains


def test_moe_load_span_and_counters(fitted):
    cell, program, reference, spans = fitted
    (load,) = [s for s in spans if s["name"] == "moe.load"]
    (epoch_end,) = [s for s in spans if s["name"] == "fit.epoch_end"]
    (counters,) = [s for s in spans if s["name"] == "fit.op_counters"]
    assert load["pa"] == counters["sp"] and counters["pa"] == epoch_end["sp"]
    args = load["args"]
    tokens = 8 * cell.traffic["batch_per_chip"] * cell.cfg["seq_len"]
    assert args["tokens"] == tokens and args["dropped"] == 0
    assert args["layers"] == cell.cfg["num_hidden_layers"]
    want = sum(v ** 2 for n, v in reference["aux"].items()) ** 0.5
    assert 0 < args["assigned"] <= tokens * args["layers"] * \
        cell.cfg["num_experts_per_tok"]
    assert args["max"] >= args["mean"] > 0 and want > 0
    values = mx.obs.metrics.registry().collect()
    assert values["moe.assigned"] >= args["assigned"]
    assert values["moe.dropped"] == 0


def test_declared_bfloat16_parameters_bind_in_bfloat16():
    """A parameter declared bfloat16 is bound so though the token ids are
    float32, and the multi-precision optimizer keeps float32 masters."""
    cfg = Qwen3NextConfig(param_dtype="bfloat16", vocab_size=32)
    mod = mx.mod.Module(qwen3_next_symbol(cfg), context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    exe = mod._exec_group.execs[0]
    assert str(exe.arg_dict["data"].dtype) == "float32"
    for name in ("lm_embed_weight", "lm_layer0_gdn_a_log",
                 "lm_layer3_attn_q_norm_gamma",
                 "lm_layer1_moe_experts_down_weight"):
        assert str(exe.arg_dict[name].dtype) == "bfloat16", name
        assert str(exe.grad_dict[name].dtype) == "bfloat16", name
    assert str(exe.aux_dict["lm_layer0_moe_load"].dtype) == "float32"
    # an undeclared graph is bound as it always was
    plain = mx.mod.Module(qwen3_next_symbol(Qwen3NextConfig(vocab_size=32)),
                          context=mx.cpu(), label_names=("softmax_label",))
    plain.bind(data_shapes=[("data", (2, 16))],
               label_shapes=[("softmax_label", (2, 16))])
    assert str(plain._exec_group.execs[0].arg_dict["lm_embed_weight"]
               .dtype) == "float32"


def test_fresh_module_initialises_by_name():
    """Without given weights `Module.fit`'s initializer finds every variable:
    zero-centred norms 0, the delta rule's norm and dt_bias 1, A_log in
    log (0, 16), the counters 0."""
    mod = mx.mod.Module(qwen3_next_symbol(Qwen3NextConfig(vocab_size=32)),
                        context=mx.cpu(), label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    mod.init_params(mx.init.Normal(0.02))
    args, aux = mod.get_params()
    assert float(args["lm_layer0_norm1_gamma"].asnumpy().max()) == 0.0
    assert float(args["lm_layer3_attn_k_norm_gamma"].asnumpy().max()) == 0.0
    assert float(args["lm_layer0_gdn_norm_gamma"].asnumpy().min()) == 1.0
    assert float(args["lm_layer0_gdn_dt_bias"].asnumpy().min()) == 1.0
    a_log = args["lm_layer1_gdn_a_log"].asnumpy()
    assert np.all(a_log < np.log(16.0)) and len(set(a_log.tolist())) > 1
    assert float(aux["lm_layer2_moe_load"].asnumpy().max()) == 0.0
    assert 0 < float(np.abs(args["lm_head_weight"].asnumpy()).max()) < 0.2


def test_scan_plan_folds_the_delta_rule_layers_and_recomputes_them():
    from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
    from incubator_mxnet_tpu.llm import LMConfig, lm_symbol
    plan = scan_plan(qwen3_next_symbol(Qwen3NextConfig()))
    (run,) = plan["runs"]
    assert run["length"] == 3 and not plan["rejected"]
    kinds = {n.op.name for n in run["segments"][0]}
    assert {"GatedDeltaRule", "RoutedExperts", "RMSNorm"} <= kinds
    assert "BlockwiseAttention" not in kinds
    assert any(n.op.scan_remat for n in run["segments"][0])
    # two whole periods fold as periods, softmax layer and all
    deep = scan_plan(qwen3_next_symbol(Qwen3NextConfig(num_hidden_layers=8)))
    (run,) = deep["runs"]
    assert run["length"] == 2
    assert "BlockwiseAttention" in {n.op.name for n in run["segments"][0]}
    # the existing LM asks for no recomputation
    (run,) = scan_plan(lm_symbol(LMConfig(num_layers=3)))["runs"]
    assert not any(n.op.scan_remat for n in run["segments"][0])


_FRESH = """
import json, sys
import numpy as np
import incubator_mxnet_tpu as mx
assert "incubator_mxnet_tpu.llm.qwen3_next" not in sys.modules or True
sym = mx.sym.load(sys.argv[1])
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
                  "aux": len(sym.list_auxiliary_states())}))
"""


def test_saved_symbol_loads_in_a_fresh_process(tmp_path):
    path = str(tmp_path / "qwen3next-symbol.json")
    qwen3_next_symbol(Qwen3NextConfig(vocab_size=32)).save(path)
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH, path], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["shape"] == [32, 32] and abs(got["rowsum"] - 1.0) < 1e-4
    assert {"RMSNorm", "RotaryEmbedding", "CausalConv1D", "GatedDeltaGates",
            "GatedDeltaRule", "RoutedExperts", "BlockwiseAttention"} \
        <= set(got["ops"])
    assert got["aux"] == 8


# -- the analyzers price and report the new operators -------------------------------

def test_cost_and_sharding_analyzers_know_the_new_operators():
    from incubator_mxnet_tpu import analysis
    cfg = Qwen3NextConfig(vocab_size=32)
    sym = qwen3_next_symbol(cfg)
    shapes = {"data": (2, 16), "softmax_label": (2, 16)}
    report = analysis.check_cost(sym, shapes=shapes)
    flops = {}
    for op in report.per_op:
        flops[op.op] = flops.get(op.op, 0.0) + op.flops
    tokens, hv = 32, cfg.linear_num_value_heads
    assert flops["GatedDeltaRule"] == 3 * 6.0 * tokens * hv * \
        cfg.linear_key_head_dim * cfg.linear_value_head_dim
    per_layer = 2.0 * tokens * cfg.hidden_size * (
        cfg.num_experts + cfg.num_experts_per_tok * 3 *
        cfg.moe_intermediate_size)
    assert flops["RoutedExperts"] == 4 * per_layer
    assert flops["RMSNorm"] > 0 and flops["CausalConv1D"] > 0
    assert report.unknown_ops == 0
    shard = analysis.check_sharding(sym, shapes=shapes, mesh="dp=2")
    for kind in ("GatedDeltaRule", "RoutedExperts"):
        assert shard.fallback_ops.get(kind), shard.fallback_ops


# -- a carry too large to copy whole --------------------------------------------

@pytest.mark.parametrize("in_place", [False, True])
def test_large_carry_is_reowned_in_place(in_place, monkeypatch):
    """Past `REOWN_IN_PLACE_BYTES` the cold dispatch copies its carry leaf by
    leaf into the holders' place instead of all at once: the same training,
    bit for bit, and the executors and the optimizer hold live buffers."""
    from incubator_mxnet_tpu import fused
    calls = []
    real = fused.FusedTrainStep._reown_in_place
    monkeypatch.setattr(fused.FusedTrainStep, "_reown_in_place",
                        lambda self, states: calls.append(1) or
                        real(self, states))
    if in_place:
        monkeypatch.setattr(fused, "REOWN_IN_PLACE_BYTES", 0)

    def run():
        rng = np.random.default_rng(3)
        x = rng.random((64, 10), dtype=np.float32)
        y = rng.integers(0, 4, (64,)).astype(np.float32)
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                    name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mx.random.seed(11)
        for _ in range(2):      # the second call meets the step's own carry
            mod.fit(mx.io.NDArrayIter(x, y, batch_size=8), num_epoch=1,
                    optimizer="sgd", initializer=mx.init.Xavier(),
                    optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        assert mod._fused_step is not None and not mod._fused_step.broken
        args, _ = mod.get_params()
        exe = mod._exec_group.execs[0]
        for name in ("fc1_weight", "fc2_bias"):
            assert not exe.arg_dict[name]._data.is_deleted()
        return {n: a.asnumpy() for n, a in args.items()}

    got = run()
    assert bool(calls) == in_place
    monkeypatch.undo()
    want = run()
    for name in want:
        assert np.array_equal(got[name], want[name]), name
