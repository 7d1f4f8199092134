"""The Ouro family (LoopLM) on the training path -- one stack of layers run
`total_ut_steps` times in one loop, a head and an exit gate at every step,
the entropy-regularised exit objective -- at a small size on the CPU,
against the plain float32 reference the benchmark keeps
(benchmark/configs/ouro_2_6b_reference.py, loaded by path: it imports
nothing of the program).  Seeded random weights throughout.

Tolerances.  Everything here runs in float32 on the CPU, where a matrix
product is a true float32 product; program and reference differ in the ORDER
of their sums only (a blocked softmax against a whole masked one, the
objective in log space against the products of the formula).  The helpers
and their tolerance are tests/test_qwen3_next.py's: 2e-5 relative to the
largest entry covers a few hundred float32 roundings (6e-8 each); gradients
get five times that, having passed through both passes and T steps.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.llm import OuroConfig, ouro_symbol
from incubator_mxnet_tpu.ops import registry
from incubator_mxnet_tpu.ops.loss_output import exit_distribution

from test_qwen3_next import cells, _close, _rand

CELL = "ouro_loop_train_hostfed"

REF = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "ouro_2_6b_reference.py"))


def _tiny_cell():
    return cells.Cell(cells.benchmark_json(), CELL, tiny=True)


# -- the objective operator --------------------------------------------------------

T, ROWS, C, V = 3, 10, 8, 40


def _exit_inputs(seed=0, steps=T):
    return (_rand(seed, steps, ROWS, C), _rand(seed + 1, V, C),
            _rand(seed + 2, 1, C), _rand(seed + 3, 1),
            jnp.arange(ROWS, dtype=jnp.float32) % V)


def _exit_op(beta=0.1, train=False):
    op = registry.get("LoopExitOutput")
    params = op.canonicalize_params({"beta": beta})
    params["_train"] = train
    return lambda *xs: op.fn(dict(params), *xs)


def _plain_objective(h, w, gw, gb, y, beta):
    """The objective summed over the rows, every step's logits at once:
    what the operator must compute the gradient of without them."""
    z = jnp.einsum("trc,vc->trv", h, w)
    labels = jnp.broadcast_to(y.astype(jnp.int32)[None, :, None],
                              z.shape[:2] + (1,))
    losses = jax.nn.logsumexp(z, -1) - \
        jnp.take_along_axis(z, labels, -1)[..., 0]
    gate = jnp.einsum("trc,c->tr", h, gw[0]) + gb[0]
    p = REF.exit_distribution(gate)
    entropy = -jnp.sum(jax.scipy.special.xlogy(p, p), axis=0)
    return jnp.sum(jnp.sum(p * losses, axis=0) - beta * entropy)


def _op_grads(inputs, beta):
    h, w, gw, gb, y = inputs
    stats = jnp.zeros((h.shape[0] + 3,))
    out, vjp = jax.vjp(lambda *a: _exit_op(beta)(*a, y, stats), h, w, gw, gb)
    # the incoming gradient is ignored, as SoftmaxOutput's is
    return out, vjp(jnp.full(out.shape, 7.0, out.dtype))


@pytest.mark.parametrize("beta", [0.0, 0.1])
def test_exit_objective_gradient_against_autodiff(beta):
    inputs = _exit_inputs()
    out, got = _op_grads(inputs, beta)
    want = jax.grad(_plain_objective, argnums=(0, 1, 2, 3))(*inputs, beta)
    for g, w in zip(got, want):
        _close(g, w, 1e-4)
    h, w = inputs[:2]
    _close(out, jax.nn.softmax(h[-1] @ w.T, axis=-1))


def test_one_step_without_entropy_is_the_plain_cross_entropy():
    """T = 1, beta = 0: p_1 = 1, and the gradient is SoftmaxOutput's."""
    h, w, gw, gb, y = _exit_inputs(5, steps=1)
    out, (dh, dw, dgw, dgb) = _op_grads((h, w, gw, gb, y), 0.0)
    sm = registry.get("SoftmaxOutput")
    params = sm.canonicalize_params({})

    def head(h, w):
        return sm.fn(dict(params), h[0] @ w.T, y)
    want_out, vjp = jax.vjp(head, h, w)
    _close(out, want_out)
    want_dh, want_dw = vjp(jnp.ones_like(want_out))
    _close(dh, want_dh)
    _close(dw, want_dw)
    # the gate decides nothing with one step
    assert float(jnp.abs(dgw).max()) == 0.0 and float(jnp.abs(dgb).max()) == 0


def test_exit_distribution_sums_to_one_and_follows_the_formula():
    gate = _rand(9, 4, 6, scale=3.0)
    log_p, p = exit_distribution(gate)
    _close(jnp.sum(p, axis=0), jnp.ones(6), 1e-6)
    lam = jax.nn.sigmoid(gate)
    want = [lam[0], lam[1] * (1 - lam[0]),
            lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    _close(p, jnp.stack(want), 1e-6)
    _close(p, REF.exit_distribution(gate), 1e-6)
    _close(jnp.exp(log_p), p, 1e-6)
    # saturated gates underflow no product: log p stays finite
    log_p, p = exit_distribution(jnp.full((4, 2), -200.0))
    assert np.all(np.isfinite(np.asarray(log_p)))
    _close(p[-1], jnp.ones(2), 1e-6)


def test_exit_stats_count_in_training_alone():
    h, w, gw, gb, y = _exit_inputs(3)
    stats = jnp.arange(T + 3, dtype=jnp.float32)
    assert not isinstance(_exit_op()(h, w, gw, gb, y, stats), tuple)
    _, new = _exit_op(train=True)(h, w, gw, gb, y, stats)
    gate = jnp.einsum("trc,c->tr", h, gw[0]) + gb[0]
    p = REF.exit_distribution(gate)
    t = jnp.arange(1, T + 1, dtype=jnp.float32)[:, None]
    _close(new - stats, jnp.concatenate([
        jnp.array([ROWS], jnp.float32), p.sum(1), jnp.sum(t * p)[None],
        -jnp.sum(jax.scipy.special.xlogy(p, p))[None]]), 1e-5)
    op = registry.get("LoopExitOutput")
    assert op.num_aux({}) == 1 and op.num_outputs({}) == 1
    note = op.counters([
        {"exit_stats": np.array([10.0, 5.0, 3.0, 2.0, 17.0, 8.0]),
         "params": {}},
        {"exit_stats": np.array([10.0, 3.0, 3.0, 4.0, 21.0, 6.0]),
         "params": {}}])
    assert note["span"] == "loop.exit"
    assert note["args"]["rows"] == 20 and note["args"]["steps"] == 3
    _close(note["args"]["p"], [0.4, 0.3, 0.3], 1e-12)
    assert note["gauges"] == {"loop.exit.expected_steps": 1.9,
                              "loop.exit.entropy": 0.7}
    assert note["counters"] == {"loop.exit.rows": 20}
    with pytest.raises(mx.MXNetError, match="LoopExitOutput"):
        _exit_op()(h, w, gw, gb, y, jnp.zeros((T + 2,)))


def _avals(jaxpr):
    """Every value's aval in a jaxpr and in the jaxprs it calls."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else \
                    (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


def test_backward_holds_no_value_of_every_steps_logits():
    """Read from the gradient's jaxpr: no (T, rows, V) value, and no value
    holds more than one step's logits; the plain objective, which takes
    every step's logits at once, does hold one (the reading can see)."""
    inputs = _exit_inputs()

    def grads(h, w, gw, gb):
        return _op_grads((h, w, gw, gb, inputs[4]), 0.1)[1]
    jaxpr = jax.make_jaxpr(grads)(*inputs[:4]).jaxpr
    sizes = [getattr(a, "size", 0) for a in _avals(jaxpr)]
    shapes = [tuple(getattr(a, "shape", ())) for a in _avals(jaxpr)]
    assert (ROWS, V) in shapes
    assert (T, ROWS, V) not in shapes and max(sizes) <= ROWS * V
    plain = jax.make_jaxpr(jax.grad(_plain_objective, argnums=(0, 1)))(
        *inputs, 0.1).jaxpr
    assert (T, ROWS, V) in [tuple(getattr(a, "shape", ()))
                            for a in _avals(plain)]


# -- the model through Module.fit -----------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """The benchmark's own set-up at the `tiny` size: ONE module driven
    through `Module.fit` for a block of K = 2 fused steps with the guardian
    on, and the plain reference's 2 steps from the same seed.  The
    configuration's adapter sets the process's block knob; it is put back
    after this file."""
    from benchmark.harness import compare, runner
    from incubator_mxnet_tpu.obs import trace as obs_trace
    was = os.environ.get("MXNET_FUSED_STEP_BLOCK")
    cell = _tiny_cell()
    obs_trace.enable()
    obs_trace.reset()
    program = runner.Program(cell, 2147483777)
    spans = obs_trace.buffered()
    reference = compare.run_reference(cell.reference, cell.cfg, program.key,
                                      program.pool, program.k)
    yield cell, program, reference, spans
    if was is None:
        os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)
    else:
        os.environ["MXNET_FUSED_STEP_BLOCK"] = was


def test_fit_block_matches_the_reference(fitted):
    """The first step's probabilities, the objective's running loss, and
    every leaf's change and momentum after the block's two SGD steps."""
    from benchmark.harness import compare
    cell, program, reference, _ = fitted
    assert program.unfused == 0 and program.k == 2
    fs = program.mod._fused_step
    assert fs is not None and not fs.broken
    assert program.mod._guardian is not None
    # the loop is one node: no layer of the outer graph to fold
    kinds = [n.op.name for n in program.mod.symbol._topo()
             if not n.is_variable]
    assert kinds.count("_foreach") == 1 and "BlockwiseAttention" not in kinds
    nums = compare.numbers(program.prog, reference)
    for name in ("loss_gap", "loss0_gap", "out0_gap", "dw_gap", "mom_gap",
                 "aux_gap"):
        assert nums[name][0] < 1e-5, (name, nums[name])
    assert reference["loss"][-1] < reference["loss"][0]     # it trains
    assert min(reference["dw"].values()) > 0
    assert set(program.prog["dw"]) == set(reference["dw"])
    assert set(program.prog["aux"]) == {"exit.stats"}


def _bound(cell, program, cfg=None):
    """An executor of the cell's symbol (of `cfg`, where given) bound to the
    reference's initial leaves and the pool's first batch."""
    cfg, ref, adapter = cfg or cell.cfg, cell.reference, cell.adapter
    params, aux = ref.init_params(program.key, cfg)
    data, label = (jnp.asarray(a) for a in program.pool[0])
    symbol = adapter.build_symbol(mx, cfg)
    exe = symbol.simple_bind(mx.cpu(), data=data.shape,
                             softmax_label=label.shape)
    names = symbol.list_arguments() + symbol.list_auxiliary_states()
    for n, a in adapter.to_program({**params, **aux}, cfg, names).items():
        (exe.arg_dict if n in exe.arg_dict else exe.aux_dict)[n][:] = \
            np.asarray(a)
    exe.arg_dict["data"][:] = np.asarray(data)
    exe.arg_dict["softmax_label"][:] = np.asarray(label)
    return exe, params, aux, data, label


def _grads(exe, cell):
    return cell.adapter.from_program(
        {n: g.asnumpy() for n, g in exe.grad_dict.items()
         if g is not None and n not in ("data", "softmax_label")}, cell.cfg)


def test_every_gradient_leaf_against_the_objectives_gradient(fitted):
    """One forward and backward pass of the bound symbol: the last step's
    probabilities, and per leaf the gradient the objective sends back
    (summed over the positions) against `jax.grad` of the reference's
    objective (a mean over them)."""
    cell, program, _, _ = fitted
    cfg, ref = cell.cfg, cell.reference
    exe, params, aux, data, label = _bound(cell, program)
    out = exe.forward(is_train=True)[0].asnumpy()
    exe.backward()
    _close(out, ref.outputs(params, aux, data, cfg))
    want = jax.grad(lambda p: ref.loss_fn(p, aux, data, label, cfg)[0])(
        params)
    got = _grads(exe, cell)
    assert set(got) == set(want)
    for leaf, g in want.items():
        _close(got[leaf], g * data.size, 1e-4)


def test_a_shared_weight_gets_the_sum_of_its_applications(fitted):
    """beta = 0: the gradient of a layer weight run T times is the sum of
    the gradients of its T applications, each given a copy of its own in
    the reference's unrolled loop."""
    cell, program, _, _ = fitted
    cfg = dict(cell.cfg, exit_entropy_weight=0.0)
    exe, params, _, data, label = _bound(cell, program, cfg)
    exe.forward(is_train=True)
    exe.backward()
    got = _grads(exe, cell)
    steps, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    x, y = data.astype(jnp.int32), label.astype(jnp.int32)

    def untied(copies):
        """The reference's loop with a copy of every layer's leaves for
        every step."""
        h = params["embed.w"][x]
        hs = []
        for t in range(steps):
            for i in range(layers):
                h = REF.layer(copies[t][i], h, cfg, "float32")
            h = REF._norm(h, params["norm.w"], cfg["rms_norm_eps"])
            hs.append(h)
        losses, gates = (jnp.stack(a) for a in zip(
            *[REF._step_terms(params, h, y, cfg, "float32") for h in hs]))
        return jnp.sum(REF.exit_distribution(gates) * losses)

    def layer_leaves(i):
        L = f"l{i}."
        return {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
    copies = [[layer_leaves(i) for i in range(layers)] for _ in range(steps)]
    per_step = jax.grad(untied)(copies)
    for i in range(layers):
        for leaf in ("attn.q.w", "attn.v.w", "mlp.down.w", "norm1b.w"):
            parts = [per_step[t][i][leaf] for t in range(steps)]
            # every application contributes
            assert all(float(jnp.abs(p).max()) > 0 for p in parts)
            _close(got[f"l{i}.{leaf}"], sum(parts), 1e-4)


def test_spans_and_counters_of_the_loop(fitted):
    cell, program, reference, spans = fitted
    tokens = 2 * cell.traffic["batch_per_chip"] * cell.cfg["seq_len"]
    (note,) = [s for s in spans if s["name"] == "loop.exit"]
    args = note["args"]
    assert args["rows"] == tokens and args["steps"] == 4
    assert abs(sum(args["p"]) - 1.0) < 1e-5
    assert 1.0 <= args["expected_steps"] <= 4.0
    assert 0.0 < args["entropy"] <= np.log(4) + 1e-6
    values = mx.obs.metrics.registry().collect()
    assert values["loop.exit.rows"] >= tokens
    assert 1.0 <= values["loop.exit.expected_steps"] <= 4.0


def test_config_from_the_published_keys():
    cell = cells.Cell(cells.benchmark_json(), CELL)
    cfg = OuroConfig.from_dict(cell.cfg)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
            cfg.num_key_value_heads, cfg.intermediate_size) == \
        (2048, 16, 128, 16, 5632)
    assert cfg.total_ut_steps == 4 and cfg.vocab_size == 49152
    assert cfg.num_hidden_layers == 4 and len(cfg.layer_types) == 48
    for bad in ({"layer_types": ["sliding_attention"]},
                {"tie_word_embeddings": True}, {"total_ut_steps": 0}):
        with pytest.raises(mx.MXNetError, match="OuroConfig"):
            OuroConfig(**bad)


def test_declared_bfloat16_parameters_and_a_json_round_trip():
    cfg = OuroConfig(param_dtype="bfloat16", vocab_size=32)
    symbol = ouro_symbol(cfg)
    mod = mx.mod.Module(symbol, context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    exe = mod._exec_group.execs[0]
    for name in ("lm_embed_weight", "lm_head_weight", "lm_exit_gate_weight",
                 "lm_layer0_attn_q_proj_weight", "lm_layer3_mlp_w2_weight"):
        assert str(exe.arg_dict[name].dtype) == "bfloat16", name
    assert exe.aux_dict["lm_exit_stats"].shape == (7,)
    mod.init_params(mx.init.Normal(0.02))
    args, _ = mod.get_params()
    for name in ("lm_layer0_norm1a_gamma", "lm_layer2_norm2b_gamma",
                 "lm_final_norm_gamma"):
        assert float(args[name].asnumpy().min()) == 1.0, name
    assert float(np.abs(args["lm_exit_gate_bias"].asnumpy()).max()) == 0.0
    # no q/k norms in the layers; one loop node, whose body holds them all
    names = symbol.list_arguments()
    assert not any("q_norm" in n or "k_norm" in n for n in names)
    again = mx.sym.load_json(symbol.tojson())
    assert again.list_arguments() == names
    nodes = json.loads(symbol.tojson())["nodes"]
    (loop,) = [n for n in nodes if n["op"] == "_foreach"]
    body = json.loads(loop["attrs"]["subgraph"])["nodes"]
    assert sum(n["op"] == "BlockwiseAttention" for n in body) == 4


# -- the objective compiled for the described v5e -----------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A TPU v5e that is described, not attached; JAX's persistent cache
    kept out (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_objective_at_the_cells_size_compiles_for_the_v5e(one_chip,
                                                          monkeypatch):
    """Value, exit counts and gradient of the heads and objective at
    `ouro_loop_train_hostfed`'s size (4 steps of 8,192 rows, 49,152 ids,
    bfloat16), on the path a TPU takes (`default_backend()` steered: here it
    is the CPU): the Pallas kernels of ops/loop_exit.py, two forward and
    one a step and a superblock of rows backward, and temporaries under one
    step's float32 logits (1.61 GB), which XLA's form holds with what is
    made of them (~3 GB)."""
    from incubator_mxnet_tpu.ops import loop_exit
    steps, rows, c, v = 4, 8192, 2048, 49152
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    op = _exit_op(train=True)

    def step(h, w, gw, gb, y, stats):
        (out, new), vjp = jax.vjp(lambda *a: op(*a, y, stats), h, w, gw, gb)
        return out, new, vjp((jnp.zeros_like(out), jnp.zeros_like(new)))
    kernel = mx.obs.counter("ops.loop_exit.lowered.kernel")
    before = kernel.value
    compiled = jax.jit(step).lower(
        shape(steps, rows, c), shape(v, c), shape(1, c), shape(1),
        shape(rows, dtype=jnp.float32),
        shape(steps + 3, dtype=jnp.float32)).compile()
    assert kernel.value == before + 1
    superblock = loop_exit._tiles(rows, c, v, 2).superblock
    assert compiled.as_text().count("tpu_custom_call") == \
        2 + steps * rows // superblock
    assert compiled.memory_analysis().temp_size_in_bytes < rows * v * 4
