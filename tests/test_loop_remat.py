"""A loop's body (`contrib.foreach`, ops/control_flow.py) under training is
re-materialised under the scanned layers' rule where it names values
`ops.registry.scan_kept` or holds a `scan_remat` operator: each step keeps
its inputs and the named values, and computes the rest again in the
backward pass.  Held here on the CPU with the flash kernels interpreted, at
sizes that tile, on a looped LM (`llm.ouro_symbol`): two layers run twice.

* the forward kernel is CALLED once a layer application in the lowered
  gradient program (the loop body's two layers), where the body under a
  bare `jax.checkpoint` calls it twice as often;
* outputs, auxiliary states and gradients are the bare checkpoint's to the
  last bit, and those of the body without re-materialisation to rounding;
* the counters read what the shapes give: `scan.remat.kept` /
  `.kept_bytes`, `loop.steps`;
* a body that names nothing (XLA's attention, an RNN cell) lowers to the
  text it lowered to without re-materialisation;
* a forward-only program counts nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.llm import OuroConfig, ouro_symbol
from incubator_mxnet_tpu.symbol import symbol as symbol_mod

from test_scan_remat_keep import _bare, _calls, _close, _equal, _inputs, \
    _step

STEPS, LAYERS, LENGTH = 2, 2, 128
COUNTERS = ("scan.remat.kept", "scan.remat.kept_bytes", "loop.steps")


def _looped():
    cfg = OuroConfig(vocab_size=32, hidden_size=32, num_hidden_layers=LAYERS,
                     num_attention_heads=2, num_key_value_heads=2,
                     head_dim=64, intermediate_size=16, total_ut_steps=STEPS)
    symbol = ouro_symbol(cfg)
    return symbol, _inputs(symbol, LENGTH)


@pytest.fixture
def looped(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    return _looped()


def _rnn_loop():
    """A recurrent cell through `contrib.foreach`: a body of products and a
    tanh, no operator that names a value."""
    import incubator_mxnet_tpu as mx
    sym = mx.sym
    data, init = sym.Variable("data"), sym.Variable("init")
    i2h, h2h = sym.Variable("i2h_weight"), sym.Variable("h2h_weight")

    def body(x, s):
        h = sym.tanh(sym.dot(x, i2h) + sym.dot(s, h2h))
        return h, h
    outs, _ = sym.contrib.foreach(body, data, init)
    symbol = sym.make_loss(sym.sum(outs))
    shapes = {"data": (5, 2, 4), "init": (2, 8), "i2h_weight": (4, 8),
              "h2h_weight": (8, 8)}
    rng = np.random.default_rng(0)
    args = tuple(jnp.asarray(0.3 * rng.standard_normal(shapes[n]),
                             jnp.float32) for n in symbol.list_arguments())
    return symbol, (args, (), jax.random.PRNGKey(0))


def _lowered(fn, inputs):
    """The lowered text, and what the counters read while it was traced."""
    before = [obs.counter(c).value for c in COUNTERS]
    text = jax.jit(fn).lower(*inputs).as_text()
    return text, [obs.counter(c).value - b for c, b in
                  zip(COUNTERS, before)]


def test_flash_forward_runs_once_an_application(looped, monkeypatch):
    symbol, inputs = looped
    kernel = obs.counter("ops.attention.lowered.kernel").value
    text, counted = _lowered(_step(symbol), inputs)
    assert obs.counter("ops.attention.lowered.kernel").value > kernel
    result = jax.jit(_step(symbol))(*inputs)
    with monkeypatch.context() as m:
        # the body under a bare checkpoint, which keeps no named value
        _bare(m)
        m.setattr(symbol_mod, "_count_kept", lambda *a: 1)
        bare_text, _ = _lowered(_step(symbol), inputs)
        bare = jax.jit(_step(symbol))(*inputs)
        # the body as it was before: no checkpoint at all
        m.setattr(symbol_mod, "_rematerialised", lambda f: f)
        stacked = jax.jit(_step(symbol))(*inputs)
    # the loop's body is one scan body: its layers' call sites, forward;
    # the bare checkpoint calls each again in the backward body
    assert _calls(text, "_kernel_forward") == LAYERS
    assert _calls(bare_text, "_kernel_forward") == 2 * LAYERS
    _equal(result, bare)
    _close(result, stacked)
    grads = jax.tree_util.tree_leaves(result[2])
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    # the kept set: each layer's `o` (rows x heads x 64, float32 here) and
    # log-sum-exp, over the steps
    kept, kept_bytes, steps = counted
    assert kept == 2 * LAYERS
    rows, heads, d = 2 * LENGTH, 2, 64
    assert kept_bytes >= STEPS * LAYERS * rows * heads * d * 4
    assert steps == STEPS


def test_a_forward_only_loop_counts_nothing(looped):
    symbol, inputs = looped
    fn = _step(symbol, train=False, grad=False)
    _, counted = _lowered(lambda a, x, k: fn(a, x, k), inputs)
    assert counted == [0, 0, 0]


@pytest.mark.parametrize("case", ["ouro_xla_attention", "rnn_cell"])
def test_a_body_that_names_nothing_lowers_as_it_did(case, monkeypatch):
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    symbol, inputs = _looped() if case == "ouro_xla_attention" \
        else _rnn_loop()
    text, counted = _lowered(_step(symbol), inputs)
    with monkeypatch.context() as m:
        # the loop as it was before: its body under no checkpoint
        m.setattr(symbol_mod, "_rematerialised", lambda f: f)
        plain, _ = _lowered(_step(symbol), inputs)
    assert text == plain
    assert counted[:2] == [0, 0] and counted[2] > 0
