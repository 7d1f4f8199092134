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
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
from incubator_mxnet_tpu.llm import Qwen3NextConfig, qwen3_next_symbol
from incubator_mxnet_tpu.llm.lfm2 import Lfm2MoeConfig, lfm2_moe_symbol
from incubator_mxnet_tpu.llm.sdar import SdarMoeConfig, sdar_moe_symbol
from incubator_mxnet_tpu.ops import attention, delta_rule
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
