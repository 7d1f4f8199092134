"""A re-materialised scanned layer keeps what its operators name.

A scanned layer body that holds an operator registered `scan_remat` is
under `jax.checkpoint` (`symbol.graph_eval_fn`): its backward pass computes
the layer again from the carry.  The custom-VJP forward rules of the flash
attention kernels and of the gated delta rule name what their kernels made
(`ops.registry.scan_kept`), the checkpoint's policy saves the values of that
name, and the forward kernel then runs once a layer in the gradient
program, not twice.  Held here, on the CPU, at sizes that tile:

* the forward sweep / forward kernel is CALLED once in the lowered gradient
  program where the body under a bare `jax.checkpoint` calls it twice;
* outputs, auxiliary states and gradients are those of the bare checkpoint
  to the last bit, and the inlined graph's to rounding;
* a body whose operators name nothing, and a forward-only bind, lower to
  the text they lowered to before;
* `scan.remat.kept` and `scan.remat.kept_bytes` read what the shapes give.

`BatchNorm` is `scan_remat` too, for its float32 statistics: where the
linearised body stacks three float32 copies of every normalised activation,
the re-materialised ResNet body keeps each BatchNorm's input in the type it
arrives in and its per-channel mean and variance, and no convolution runs
twice (the ResNet cases below).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax._src.ad_checkpoint import remat_p, saved_residuals

from incubator_mxnet_tpu import obs, sym
from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import (
    BottleneckV1, ResNetV1)
from incubator_mxnet_tpu.llm import Qwen3NextConfig, qwen3_next_symbol
from incubator_mxnet_tpu.llm.lfm2 import Lfm2MoeConfig, lfm2_moe_symbol
from incubator_mxnet_tpu.llm.sdar import SdarMoeConfig, sdar_moe_symbol
from incubator_mxnet_tpu.ops import attention, delta_rule, registry
from incubator_mxnet_tpu.ops import nn as nn_ops
from incubator_mxnet_tpu.symbol import symbol as symbol_mod

BATCH = 2


# -- the graphs ---------------------------------------------------------------

def _delta_graph():
    """Three delta-rule layers (one scanned run) and a softmax layer, 16
    positions in chunks of 64 (padded): the `lax.scan` driver, which shares
    `_sweep`'s VJP with the kernel."""
    return qwen3_next_symbol(Qwen3NextConfig(vocab_size=32)), 16


class _CausalSdar(SdarMoeConfig):
    def attention_mask(self):
        return {"causal": True}


def _attention_graph(mask):
    """Two layers (grouped-query attention over 2 x 128 rows, 2 query heads
    of 64 on one key-value head; routed experts), one scanned run: sizes
    the flash kernels tile, interpreted."""
    cls = SdarMoeConfig if mask == "block_diffusion" else _CausalSdar
    cfg = cls(vocab_size=32, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=2, num_key_value_heads=1, head_dim=64,
              moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
              block_length=4, noise_seed=11)
    return sdar_moe_symbol(cfg), 128


def _lfm2_graph():
    """`lfm2_24b_a2b`'s `tiny`: the scanned body is (routed experts + gated
    short convolution) x 3, the attention layer inlined."""
    return lfm2_moe_symbol(Lfm2MoeConfig()), 16


# -- a graph as a program -----------------------------------------------------

def _inputs(symbol, length, seed=0):
    shapes = {"data": (BATCH, length), "softmax_label": (BATCH, length)}
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    rng = np.random.default_rng(seed)
    args = []
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in shapes:
            args.append(jnp.asarray(rng.integers(0, 32, shape), jnp.float32))
        elif name.endswith("_gamma"):
            args.append(jnp.ones(shape, jnp.float32))
        else:
            args.append(jnp.asarray(0.1 * rng.standard_normal(shape),
                                    jnp.float32))
    aux = [jnp.zeros(shape, jnp.float32) for shape in aux_shapes]
    return tuple(args), tuple(aux), jax.random.PRNGKey(seed)


def _step(symbol, scan=True, train=True, grad=True):
    """(args, aux, key) -> outputs, auxiliary states and, with `grad`, the
    gradients of every argument."""
    fn, _, _, _ = symbol_mod.graph_eval_fn(
        symbol, train, scan=scan_plan(symbol) if scan else None)
    if not grad:
        return fn

    def step(args, aux, key):
        (outs, new_aux), vjp = jax.vjp(lambda a: fn(a, aux, key), args)
        grads, = vjp((tuple(jnp.ones_like(o) for o in outs),
                      tuple(jnp.zeros_like(a) for a in new_aux)))
        return outs, new_aux, grads
    return step


def _bare(monkeypatch):
    """The executor as it was: the body under a bare `jax.checkpoint`."""
    monkeypatch.setattr(symbol_mod, "_rematerialised", jax.checkpoint)


def _calls(text, name):
    """Call sites of the jitted `name` that take activations, an operand of
    three axes or more (partial evaluation splits the function's constants
    off, offsets, masks and iotas, as a call of its own in front of the
    loop, under the same name)."""
    sites = re.findall(r"call @%s(?:_\d+)?\(.*?\) : \((.*?)\) ->" % name, text)
    return sum(bool(re.search(r"tensor<\d+x\d+x\d+", types))
               for types in sites)


def _equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.abs(x - y).max() <= 2e-5 * max(np.abs(y).max(), 1e-3)


# -- the cases ----------------------------------------------------------------

@pytest.fixture
def delta_case(monkeypatch):
    # a name for the forward sweep's call sites in the lowered text: the
    # `lax.scan` driver is not a function of its own there as the kernel is
    monkeypatch.setattr(delta_rule, "_scan_forward", jax.jit(
        delta_rule._scan_forward, static_argnames=("c", "save")))
    symbol, length = _delta_graph()
    return symbol, _inputs(symbol, length), "_scan_forward"


@pytest.fixture(params=["causal", "block_diffusion"])
def attention_case(request, monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    symbol, length = _attention_graph(request.param)
    return symbol, _inputs(symbol, length), "_kernel_forward"


def _check_runs_once(case, monkeypatch, layers):
    symbol, inputs, forward = case
    (run,) = scan_plan(symbol)["runs"]
    assert run["length"] == layers
    kept = jax.jit(_step(symbol))
    text = kept.lower(*inputs).as_text()
    result = kept(*inputs)
    with monkeypatch.context() as m:
        _bare(m)
        bare = jax.jit(_step(symbol))
        bare_text = bare.lower(*inputs).as_text()
        bare_result = bare(*inputs)
    # (a), (b): the scan's forward body, and its backward body once more
    # under the bare checkpoint
    assert _calls(bare_text, forward) == 2
    assert _calls(text, forward) == 1
    # (c): what is kept is what would have been computed again
    _equal(result, bare_result)
    _close(result, jax.jit(_step(symbol, scan=False))(*inputs))
    grads = jax.tree_util.tree_leaves(result[2])
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert sum(float(jnp.abs(g).sum()) > 0 for g in grads) > len(grads) // 2


def test_delta_rule_forward_sweep_runs_once_a_layer(delta_case, monkeypatch):
    _check_runs_once(delta_case, monkeypatch, layers=3)


def test_flash_forward_kernel_runs_once_a_layer(attention_case, monkeypatch):
    before = obs.counter("ops.attention.lowered.kernel").value
    _check_runs_once(attention_case, monkeypatch, layers=2)
    assert obs.counter("ops.attention.lowered.kernel").value > before


def _lowered(symbol, inputs, **how):
    """The lowered text, and what the counters read while it was traced."""
    n, b = (obs.counter("scan.remat." + c) for c in ("kept", "kept_bytes"))
    before = n.value, b.value
    text = jax.jit(_step(symbol, **how)).lower(*inputs).as_text()
    return text, (n.value - before[0], b.value - before[1])


def test_a_body_that_names_nothing_lowers_as_it_did(monkeypatch):
    """(d) `RoutedExperts` + `GatedShortConv`: re-materialised for the
    experts' sake, and nothing in it is named."""
    symbol, length = _lfm2_graph()
    (run,) = scan_plan(symbol)["runs"]
    kinds = {n.op.name for n in run["segments"][0]}
    assert {"RoutedExperts", "GatedShortConv"} <= kinds
    assert not kinds & {"BlockwiseAttention", "GatedDeltaRule"}
    inputs = _inputs(symbol, length)
    text, counted = _lowered(symbol, inputs)
    assert counted == (0, 0)
    _bare(monkeypatch)
    assert _lowered(symbol, inputs)[0] == text


def _attention_bytes(batch, rows, heads, d, itemsize):
    """A layer's `o` and its float32 log-sum-exp a row and head."""
    return batch * rows * heads * (d * itemsize + 4)


def _delta_bytes(batch, t, hv, dk, dv, c, block, itemsize):
    """A layer's `o`, the float32 state that entered each chunk of `c`
    positions and the float32 inverse of each block (a chunk under the
    scan, two chunks in the kernel)."""
    return batch * hv * (t * dv * itemsize + t // c * dk * dv * 4
                         + t // block * block * block * 4)


def test_counters_read_what_the_shapes_give(delta_case, monkeypatch):
    """(e) the named values a layer, and their bytes over the run: one
    arithmetic, held to the counters at the test's size and read at the
    cells' (what ISSUE 37 priced)."""
    symbol, inputs, _ = delta_case
    cfg = Qwen3NextConfig()
    layer = _delta_bytes(       # 16 positions, padded to one chunk of 64
        BATCH, 64, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim, 64, 64, 4)
    assert _lowered(symbol, inputs)[1] == (3, 3 * layer)
    # a forward-only program is not differentiated: nothing is counted
    assert _lowered(symbol, inputs, train=False, grad=False)[1] == (0, 0)
    # qwen3_next_train_hostfed: 2 x 4,096 positions, 32 value heads of
    # 128 x 128, bfloat16, the kernel's blocks of two chunks
    cell = _delta_bytes(2, 4096, 32, 128, 128, 64, 128, 2)
    assert round(cell / 1e6, 1) == 469.8 and 3 * cell == 1409286144

    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    symbol, length = _attention_graph("block_diffusion")
    layer = _attention_bytes(BATCH, 2 * length, 2, 64, 4)
    assert _lowered(symbol, _inputs(symbol, length))[1] == (2, 2 * layer)
    # sdar_moe_bd_train_hostfed: 2 x 8,192 rows, 32 heads of 128, bfloat16
    cell = _attention_bytes(2, 8192, 32, 128, 2)
    assert round(cell / 1e6, 1) == 136.3 and 4 * cell == 545259520


@pytest.mark.parametrize("graph", ["delta", "causal", "block_diffusion"])
def test_forward_only_bind_lowers_as_it_did(graph, monkeypatch):
    """(f) the name is an identity where nothing is differentiated."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    symbol, length = _delta_graph() if graph == "delta" else \
        _attention_graph(graph)
    inputs = _inputs(symbol, length)

    def texts():
        return [_lowered(symbol, inputs, train=train, grad=False)[0]
                for train in (False, True)]
    kept = texts()
    monkeypatch.setattr(attention, "scan_kept", lambda x: x)
    monkeypatch.setattr(delta_rule, "scan_kept", lambda x: x)
    _bare(monkeypatch)
    assert texts() == kept


# -- ResNet: BatchNorm keeps its input and its statistics ---------------------

IMAGE, IMAGES = 32, 6     # a batch that is no width of the graph's


def _resnet_graph():
    """The model zoo's ResNet-50 v1 composition at widths the CPU holds: two
    stages of three bottleneck blocks, whose last two form a scanned run
    each, `Activation -> (Convolution -> BatchNorm -> Activation) x 2 ->
    Convolution -> BatchNorm -> broadcast_add`."""
    net = ResNetV1(BottleneckV1, [3, 3], [8, 16, 32], classes=10)
    return sym.SoftmaxOutput(net(sym.Variable("data")), name="softmax")


def _image_inputs(symbol, dtype=jnp.bfloat16, seed=0):
    """Images and parameters in `dtype`, float32 labels and statistics: the
    cell's types, as the default has it."""
    shapes = {"data": (IMAGES, 3, IMAGE, IMAGE), "softmax_label": (IMAGES,)}
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    rng = np.random.default_rng(seed)
    args = []
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name == "softmax_label":
            args.append(jnp.asarray(rng.integers(0, 10, shape), jnp.float32))
        elif name.endswith("_gamma"):
            args.append(jnp.ones(shape, dtype))
        else:
            args.append(jnp.asarray(0.3 * rng.standard_normal(shape), dtype))
    aux = [jnp.full(shape, float(name.endswith("_var")), jnp.float32)
           for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes)]
    return tuple(args), tuple(aux), jax.random.PRNGKey(seed)


@pytest.fixture
def resnet_case():
    symbol = _resnet_graph()
    runs = scan_plan(symbol)["runs"]
    assert [r["length"] for r in runs] == [2, 2]
    for run in runs:
        kinds = [n.op.name for n in run["segments"][0]]
        assert kinds.count("BatchNorm") == 3
        assert kinds.count("Convolution") == 3
    return symbol, _image_inputs(symbol)


def _bare_batch_norm(monkeypatch):
    """The executor as it was for BatchNorm: its scanned layers not
    re-materialised, the bare `lax.scan`."""
    monkeypatch.setattr(registry.get("BatchNorm"), "scan_remat", False)


def _scan_bodies(jaxpr):
    """The bodies of every `scan` in `jaxpr`, at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["jaxpr"].jaxpr
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scan_bodies(sub)


def test_resnet_body_is_under_the_checkpoint(resnet_case, monkeypatch):
    """(a) every scanned body of the training program is a checkpoint, and
    none of the bare scan's is."""
    symbol, (args, aux, key) = resnet_case
    fn = _step(symbol, grad=False)

    def checkpointed():
        jaxpr = jax.make_jaxpr(fn)(args, aux, key).jaxpr
        return [any(e.primitive is remat_p for e in body.eqns)
                for body in _scan_bodies(jaxpr)]
    assert checkpointed() == [True, True]
    _bare_batch_norm(monkeypatch)
    fn = _step(symbol, grad=False)
    assert checkpointed() == [False, False]


def test_resnet_runs_no_convolution_twice(resnet_case, monkeypatch):
    """(b) the gradient program holds as many convolutions as the bare
    scan's: what the backward pass computes again starts from each
    BatchNorm's kept input, not from the convolution that made it."""
    symbol, inputs = resnet_case
    text = jax.jit(_step(symbol)).lower(*inputs).as_text()
    _bare_batch_norm(monkeypatch)
    bare = jax.jit(_step(symbol)).lower(*inputs).as_text()
    assert text.count("stablehlo.convolution") == \
        bare.count("stablehlo.convolution") > 0


def _stacked(symbol, inputs):
    """The scans' saved residuals of the training program: (dtype, shape)."""
    args, aux, key = inputs
    fn = _step(symbol, grad=False)

    def loss(a):
        return fn(a, aux, key)[0][0].astype(jnp.float32).sum()
    return [(a.dtype, a.shape) for a, why in saved_residuals(loss, args)
            if "scan" in why]


def test_resnet_stacks_no_float32_activation(resnet_case, monkeypatch):
    """(c) the bare scan stacks float32 arrays of an activation's size (a
    layer axis in front of N, C, H, W) for every BatchNorm; the
    re-materialised body stacks bfloat16 activations and float32 vectors
    of C alone."""
    symbol, inputs = resnet_case

    def activations(stacked, dtype):
        return sum(dt == dtype and len(shape) == 5 and shape[1] == IMAGES
                   for dt, shape in stacked)
    kept = _stacked(symbol, inputs)
    # a layer's three BatchNorm inputs and its carry, in each run
    assert activations(kept, jnp.bfloat16) == 2 * 4
    assert activations(kept, jnp.float32) == 0
    assert {len(shape) for dt, shape in kept if dt == jnp.float32} == {2}
    _bare_batch_norm(monkeypatch)
    bare = _stacked(symbol, inputs)
    assert activations(bare, jnp.float32) == 2 * 3 * 3


def test_resnet_matches_the_bare_scan(monkeypatch):
    """(d) outputs, running statistics and gradients of the bare scan, to
    float rounding: the same operations in the same types, only what is
    stored and what is computed again differs.  In float32: in bfloat16 the
    compiler's excess precision (a convolution's float32 result read by
    BatchNorm without the round trip through bfloat16, where nothing stores
    it) moves an output by an ulp, and the gradients of the biases in front
    of a BatchNorm, zero but for rounding, by their whole size.  Each leaf
    is held to the rounding of the largest of its kind: those biases' are
    noise around zero in both programs."""
    symbol = _resnet_graph()
    inputs = _image_inputs(symbol, jnp.float32)
    result = jax.jit(_step(symbol))(*inputs)
    _bare_batch_norm(monkeypatch)
    bare = jax.jit(_step(symbol))(*inputs)
    for got, want in zip(result, bare):
        got, want = (np.asarray(x, np.float64) for x in got), \
            [np.asarray(x, np.float64) for x in want]
        scale = max(np.abs(w).max() for w in want)
        for g, w in zip(got, want):
            assert np.abs(g - w).max() <= 2e-5 * scale
    grads = jax.tree_util.tree_leaves(result[2])
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert sum(float(jnp.abs(g).sum()) > 0 for g in grads) > len(grads) // 2


def _bn_bytes(layers, batch, size, mid, itemsize):
    """A run of bottleneck layers of width `mid` at size x size: each layer's
    three BatchNorm inputs (mid, mid and 4 mid channels) in the activations'
    type, and their float32 mean and variance."""
    return layers * 6 * mid * (batch * size * size * itemsize + 2 * 4)


def test_resnet_counters_read_what_the_shapes_give(resnet_case):
    """(e) nine named values a layer (three BatchNorms' input, mean and
    variance), their bytes over each run; the arithmetic held to the
    counters at the test's size and read at the cell's."""
    symbol, inputs = resnet_case
    # stage 1: width 4 at 8 x 8 (the stem halves 32 twice); stage 2: width
    # 8 at 4 x 4
    size = IMAGE // 4
    want = _bn_bytes(2, IMAGES, size, 4, 2) + \
        _bn_bytes(2, IMAGES, size // 2, 8, 2)
    assert _lowered(symbol, inputs)[1] == (2 * 9, want)
    assert _lowered(symbol, inputs, train=False, grad=False)[1] == (0, 0)
    # resnet50_train_hostfed: batch 128 at 224 x 224, runs of 2, 3, 5 and 2
    # layers of width 64, 128, 256 and 512 at 56, 28, 14 and 7, bfloat16
    cell = sum(_bn_bytes(n, 128, s, mid, 2) for n, s, mid in
               [(2, 56, 64), (3, 28, 128), (5, 14, 256), (2, 7, 512)])
    assert cell == 1541541888
    assert sum(_bn_bytes(n, 0, 1, mid, 2) for n, mid in
               [(2, 64), (3, 128), (5, 256), (2, 512)]) == 135168


def _numbered(text):
    """`text` with the lowering's private functions unnumbered: the suffixes
    that keep a module's symbols apart count the equations traced before,
    the named values' among them."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize(
    "how", [dict(train=True, grad=False), dict(scan=False)],
    ids=["forward-only", "inlined"])
def test_resnet_lowers_as_it_did(how, monkeypatch):
    """(f) a forward-only training bind, and the inlined graph's gradient
    program, lower to the text they lowered to before BatchNorm named
    anything, but for the numbering of private functions."""
    symbol = _resnet_graph()
    inputs = _image_inputs(symbol)
    text = _lowered(symbol, inputs, **how)[0]
    monkeypatch.setattr(nn_ops, "scan_kept", lambda x: x)
    _bare_batch_norm(monkeypatch)
    bare = _lowered(symbol, inputs, **how)[0]
    assert text != bare
    assert _numbered(text) == _numbered(bare)


def test_resnet_predict_bind_computes_as_it_did(monkeypatch):
    """(f) a predict bind: the same convolutions and reductions, and the
    same outputs to the last bit.  Its text differs in one thing: the bare
    scan hands the running statistics, which a predicting BatchNorm returns
    as it read them, from its inputs to its outputs; through the checkpoint
    they come out as stacks of their own, (layers, channels) a statistic."""
    symbol = _resnet_graph()
    inputs = _image_inputs(symbol)
    fn = jax.jit(_step(symbol, train=False, grad=False))
    text, result = fn.lower(*inputs).as_text(), fn(*inputs)
    _bare_batch_norm(monkeypatch)
    fn = jax.jit(_step(symbol, train=False, grad=False))
    bare, bare_result = fn.lower(*inputs).as_text(), fn(*inputs)
    for op in ("convolution", "reduce(", "while"):
        op = "stablehlo." + op
        assert text.count(op) == bare.count(op) > 0
    _equal(result, bare_result)
