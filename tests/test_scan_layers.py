"""Scan-over-layers graph dedup + auto-donation + coldstart budgets.

The cold-start tentpole: runs of structurally identical layer blocks
are detected on the Symbol graph (`analysis.graph_passes.scan_plan`),
lowered to ONE `lax.scan` body over stacked per-layer parameters
(`symbol.graph_eval_fn(..., scan=plan)`), and the fused train step
donates dying step inputs decided by jaxpr liveness
(`fused._decide_autodonate`).  Parameters and checkpoints keep the
per-layer layout; the deduped jaxpr re-keys the unified program cache.

Parity policy (established empirically on the CPU backend, jax 0.9.0):
the scan-lowered FORWARD of a matmul + elementwise stack (Dense/FC) is
bitwise identical to the inlined one.  Through training, XLA compiles
a layer body inside a `while` loop with different fusion than inlined
(conv, batch-norm reductions, FC weight/bias grad reductions under a
scanned cotangent chain), so trained parameters agree to
float-rounding level only — every model asserts one tight allclose
(`_SCAN_RTOL`/`_SCAN_ATOL`, explained where defined) and bitwise
determinism of each path individually, never looser tolerances.
"""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import io, sym
from incubator_mxnet_tpu.analysis import budgets
from incubator_mxnet_tpu.analysis.graph_passes import (SCAN_HINT_RUN,
                                                       SCAN_MIN_RUN,
                                                       check, scan_plan)


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _stacked_fc(n_layers=6, hidden=32, classes=4):
    net = sym.Variable("data")
    for i in range(n_layers):
        net = sym.FullyConnected(net, num_hidden=hidden,
                                 name="blk%d_fc" % i)
        net = sym.Activation(net, act_type="relu", name="blk%d_relu" % i)
    net = sym.FullyConnected(net, num_hidden=classes, name="out_fc")
    return sym.SoftmaxOutput(net, name="softmax")


def _shared_weight_fc(n_layers=5, hidden=32):
    w = sym.Variable("w_shared")
    net = sym.Variable("data")
    for i in range(n_layers):
        net = sym.FullyConnected(net, w, num_hidden=hidden, no_bias=True,
                                 name="blk%d_fc" % i)
        net = sym.Activation(net, act_type="relu", name="blk%d_relu" % i)
    net = sym.FullyConnected(net, num_hidden=4, name="out_fc")
    return sym.SoftmaxOutput(net, name="softmax")


def _resnet_ish(n_blocks=4):
    net = sym.Variable("data")
    net = sym.Convolution(net, num_filter=8, kernel=(3, 3), pad=(1, 1),
                          name="stem")
    for i in range(n_blocks):
        net = sym.Convolution(net, num_filter=8, kernel=(3, 3),
                              pad=(1, 1), name="blk%d_conv" % i)
        net = sym.BatchNorm(net, name="blk%d_bn" % i)
        net = sym.Activation(net, act_type="relu", name="blk%d_relu" % i)
    net = sym.Pooling(net, global_pool=True, pool_type="avg",
                      kernel=(1, 1), name="gap")
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


def _stacked_lstm(layers=4, T=3, hidden=8, vocab=10):
    """Manually-unrolled LSTM stack: each layer consumes the concat of
    the previous layer's per-step hiddens and emits its own concat, so
    layers >= 1 are structurally identical blocks under a single-tensor
    carry (layer 0 reads the raw data variable and stays inlined)."""
    x = sym.Variable("data")
    for layer in range(layers):
        p = "l%d_" % layer
        h = sym.Variable(p + "h0", shape=(0, hidden), __layout__="NC",
                         init="zeros")
        c = sym.Variable(p + "c0", shape=(0, hidden), __layout__="NC",
                         init="zeros")
        outs = []
        for t in range(T):
            xt = sym.slice_axis(x, axis=1, begin=t * hidden,
                                end=(t + 1) * hidden, name=p + "x%d" % t)
            gates = sym.FullyConnected(xt, num_hidden=4 * hidden,
                                       name=p + "i2h%d" % t) \
                + sym.FullyConnected(h, num_hidden=4 * hidden,
                                     name=p + "h2h%d" % t)
            i = sym.Activation(sym.slice_axis(gates, axis=1, begin=0,
                                              end=hidden),
                               act_type="sigmoid")
            f = sym.Activation(sym.slice_axis(gates, axis=1,
                                              begin=hidden,
                                              end=2 * hidden),
                               act_type="sigmoid")
            o = sym.Activation(sym.slice_axis(gates, axis=1,
                                              begin=2 * hidden,
                                              end=3 * hidden),
                               act_type="sigmoid")
            g = sym.Activation(sym.slice_axis(gates, axis=1,
                                              begin=3 * hidden,
                                              end=4 * hidden),
                               act_type="tanh")
            c = f * c + i * g
            h = o * sym.Activation(c, act_type="tanh")
            outs.append(h)
        x = sym.Concat(*outs, dim=1, name=p + "cat")
    net = sym.FullyConnected(x, num_hidden=vocab, name="pred")
    return sym.SoftmaxOutput(net, name="softmax")


# ---------------------------------------------------------------------------
# training driver
# ---------------------------------------------------------------------------

def _train(symbol, X, y, scan_on, steps=5, batch=16, momentum=0.9,
           autodonate=True, mod=None):
    """Train `steps` fit_steps; returns (arg_params, aux_params, fused,
    module).  Toggles MXNET_FUSED_SCAN / MXNET_FUSED_AUTODONATE for the
    duration of the build."""
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    os.environ["MXNET_FUSED_SCAN"] = "1" if scan_on else "0"
    os.environ["MXNET_FUSED_AUTODONATE"] = "1" if autodonate else "0"
    try:
        np.random.seed(7)
        mx.random.seed(7)
        it = io.NDArrayIter(X, y, batch_size=batch, shuffle=False,
                            label_name="softmax_label")
        if mod is None:
            mod = mx.mod.Module(symbol, context=mx.cpu())
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label)
            mod.init_params(mx.initializer.Xavier())
            opt = {"learning_rate": 0.1}
            if momentum:
                opt["momentum"] = momentum
            mod.init_optimizer(optimizer="sgd", optimizer_params=opt)
        metric = mx.metric.create("acc")
        batches = list(it)
        for s in range(steps):
            mod.fit_step(batches[s % len(batches)], metric)
        fused = mod._fused_step
        assert fused is not None and not fused.broken, \
            "fused train step must engage"
        args, auxs = mod.get_params()
        return ({k: v.asnumpy() for k, v in args.items()},
                {k: v.asnumpy() for k, v in auxs.items()}, fused, mod)
    finally:
        for k in ("MXNET_FUSED_TRAIN_STEP", "MXNET_FUSED_SCAN",
                  "MXNET_FUSED_AUTODONATE"):
            os.environ.pop(k, None)


def _fc_data(n=64, d=32, k=4, seed=1):
    rng = np.random.RandomState(seed)
    return rng.randn(n, d).astype("f4"), rng.randint(0, k, n).astype("f4")


# ---------------------------------------------------------------------------
# detection
# ---------------------------------------------------------------------------

def test_scan_plan_detects_fc_run():
    plan = scan_plan(_stacked_fc(6))
    assert plan["runs"], "stacked FC must yield an eligible run"
    run = plan["runs"][0]
    assert run["length"] >= 5
    assert run["length"] >= SCAN_MIN_RUN
    # per-layer parameter layout: every param slot stacks one node per
    # layer, and no node repeats across layers
    for slot in run["params"]:
        assert len(slot) == run["length"]
        assert len({id(n) for n in slot}) == run["length"]
    # the carry chains layer boundaries
    assert run["carry"][0] is not None


def test_scan_plan_period_grouping_covers_multi_op_layers():
    # each layer is fc+relu: TWO unit segments per layer — only the
    # period-p grouper can see the repeat
    s = _stacked_fc(6)
    run = scan_plan(s)["runs"][0]
    covered = {id(n) for seg in run["segments"] for n in seg}
    fc = sum(1 for n in s._topo()
             if not n.is_variable and n.name.startswith("blk")
             and id(n) in covered)
    assert fc >= 2 * run["length"], \
        "each scanned layer must cover its fc AND its activation"


def _stack_under_a_weight(n_layers, head=0, tail=0, read_in_layer=None):
    """A stack under a value that one op in front of it makes and an op
    behind it reads, as a loss weighs its rows by what the graph drew from
    its input (`llm/sdar.py`); `head` / `tail`: further ops between that
    op and the stack, the stack and the reader; or the value read inside
    layer `read_in_layer` too."""
    halves = sym.split(sym.Variable("data"), num_outputs=2, axis=1,
                       name="halves")
    net, weight = halves[0], halves[1]
    for i in range(head):
        net = sym.Activation(net, act_type="tanh", name="head%d" % i)
    for i in range(n_layers):
        net = sym.FullyConnected(net, num_hidden=32, name="blk%d_fc" % i)
        if i == read_in_layer:
            net = sym.broadcast_mul(net, weight, name="blk%d_w" % i)
        net = sym.Activation(net, act_type="relu", name="blk%d_relu" % i)
    for i in range(tail):
        net = sym.Activation(net, act_type="tanh", name="tail%d" % i)
    net = sym.broadcast_mul(net, weight, name="weighted")
    return sym.SoftmaxOutput(net, name="softmax")


def _layer_runs(plan):
    return [r["length"] for r in plan["runs"]
            if r["segments"][0][0].name.startswith("blk")]


@pytest.mark.parametrize("n_layers", [2, 4])
def test_scan_plan_folds_a_stack_under_an_edge_that_passes_over_it(n_layers):
    """At two layers too: of the 7 ops the weight's edge spans 6, more
    than a layer of any run could hold (7 // 2)."""
    plan = scan_plan(_stack_under_a_weight(n_layers))
    assert _layer_runs(plan) == [n_layers]
    covered = {n.name for r in plan["runs"] for seg in r["segments"]
               for n in seg}
    assert not {"halves", "weighted"} & covered


def test_scan_plan_keeps_an_edge_that_could_be_a_layers_own():
    """The weight made 3 ops into a graph of 17 and read 7 ops later: an
    edge no longer than 17 // 2 may be a layer's residual, so it counts,
    and the two layers under it stay inlined, as they did before any edge
    was left out: unfolded, not folded around the value."""
    net = sym.Variable("data")
    for i in range(3):
        net = sym.Activation(net, act_type="tanh", name="head%d" % i)
    halves = sym.split(net, num_outputs=2, axis=1, name="halves")
    net, weight = halves[0], halves[1]
    for i in range(3):
        net = sym.FullyConnected(net, num_hidden=32, name="blk%d_fc" % i)
        net = sym.Activation(net, act_type="relu", name="blk%d_relu" % i)
    net = sym.broadcast_mul(net, weight, name="weighted")
    for i in range(5):
        net = sym.Activation(net, act_type="tanh", name="tail%d" % i)
    symbol = sym.SoftmaxOutput(net, name="softmax")
    assert len([n for n in symbol._topo() if not n.is_variable]) == 17
    assert _layer_runs(scan_plan(symbol)) == []


def test_scan_plan_rejects_a_run_that_reads_the_passing_value():
    plan = scan_plan(_stack_under_a_weight(4, read_in_layer=1))
    assert 4 not in _layer_runs(plan)


def test_scan_plan_rejects_shared_weights():
    plan = scan_plan(_shared_weight_fc())
    assert not plan["runs"], "shared-weight stack must not be scanned"
    assert plan["rejected"], "rejection must be recorded, not silent"
    assert any("shared" in r["reason"] for r in plan["rejected"])


def test_scan_plan_respects_min_run():
    plan = scan_plan(_stacked_fc(6), min_run=7)
    assert not plan["runs"]


def test_stacked_lstm_layers_detected():
    plan = scan_plan(_stacked_lstm(layers=4))
    assert plan["runs"], "identical LSTM layers must form a run"
    # layer 0 reads the raw data variable, so 3 of 4 layers scan
    assert plan["runs"][0]["length"] == 3


# ---------------------------------------------------------------------------
# mxlint hint
# ---------------------------------------------------------------------------

def test_scan_opportunity_hint_when_lowering_disabled():
    s = _stacked_fc(6)
    os.environ["MXNET_FUSED_SCAN"] = "0"
    try:
        rep = check(s, hints=True)
    finally:
        os.environ.pop("MXNET_FUSED_SCAN", None)
    hints = [f for f in rep if f.code == "scan-opportunity"]
    assert hints, "eligible run >= %d must hint when not lowered" \
        % SCAN_HINT_RUN
    assert all(f.severity == "hint" for f in hints)


def test_scan_opportunity_silent_when_lowered():
    s = _stacked_fc(6)
    os.environ["MXNET_FUSED_SCAN"] = "1"
    try:
        rep = check(s, hints=True)
    finally:
        os.environ.pop("MXNET_FUSED_SCAN", None)
    assert not [f for f in rep if f.code == "scan-opportunity"], \
        "a run the fused path lowers must not hint"


def test_scan_opportunity_hint_for_rejected_run():
    # shared weights keep the run un-lowerable — the hint must fire
    # even with lowering enabled, pointing at the blocker
    os.environ["MXNET_FUSED_SCAN"] = "1"
    try:
        rep = check(_shared_weight_fc(), hints=True)
    finally:
        os.environ.pop("MXNET_FUSED_SCAN", None)
    assert [f for f in rep if f.code == "scan-opportunity"]


# ---------------------------------------------------------------------------
# lowering parity
# ---------------------------------------------------------------------------

def test_graph_eval_fn_forward_bitwise():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn

    s = _stacked_fc(6)
    plan = scan_plan(s)
    fn0, args0, _, _ = graph_eval_fn(s, True)
    fn1, args1, _, _ = graph_eval_fn(s, True, scan=plan)
    assert [a.name for a in args0] == [a.name for a in args1], \
        "argument order must not change under scan lowering"
    rng = np.random.RandomState(0)
    vals = []
    for a in args0:
        if a.name == "data":
            vals.append(jnp.asarray(rng.randn(8, 32).astype("f4")))
        elif a.name == "softmax_label":
            vals.append(jnp.asarray(rng.randint(0, 4, 8).astype("f4")))
        elif "bias" in a.name:
            vals.append(jnp.zeros(
                (4,) if a.name.startswith("out") else (32,), "f4"))
        else:
            shape = (4, 32) if a.name.startswith("out") else (32, 32)
            vals.append(jnp.asarray(rng.randn(*shape).astype("f4") * 0.1))
    key = jax.random.PRNGKey(0)
    o0, _ = fn0(tuple(vals), (), key)
    o1, _ = fn1(tuple(vals), (), key)
    for a, b in zip(o0, o1):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "scan-lowered forward must be bitwise equal"

    def eqns(f):
        closed = jax.make_jaxpr(lambda v, k: f(v, (), k))(tuple(vals), key)
        return len(closed.jaxpr.eqns)

    assert eqns(fn1) < eqns(fn0), \
        "scan lowering must shrink the traced graph"


# scan-on vs scan-off parity bound.  Under jax 0.9.0 XLA CPU emits a
# layer body inside a `while` loop with different fusion (and so a
# different accumulation order in the batch reductions) than the same
# body inlined: measured on the 6-layer FC stack, step 1 is bitwise,
# and from step 2 the weights differ by at most 2 ulp at their own
# magnitude (max abs 6e-8 on O(0.3) weights after 5 momentum steps).
# A real defect — a layer reading its neighbour's parameters, a dropped
# update — shows at lr*grad scale (~1e-3), four decades above this
# bound.  Each path is individually deterministic (tested below).
_SCAN_RTOL, _SCAN_ATOL = 2e-5, 2e-6


def test_module_training_fc_stack_matches_inlined():
    X, y = _fc_data()
    a1, _, f1, m1 = _train(_stacked_fc(6), X, y, scan_on=True)
    a0, _, f0, m0 = _train(_stacked_fc(6), X, y, scan_on=False)
    assert f1.scan_runs and not f0.scan_runs
    assert f1._core_closed.num_eqns() < f0._core_closed.num_eqns()
    for k in a0:
        np.testing.assert_allclose(a0[k], a1[k], rtol=_SCAN_RTOL,
                                   atol=_SCAN_ATOL, err_msg=k)
    # continued training stays within the bound: momentum state matched
    a1c, _, _, _ = _train(None, X, y, scan_on=True, steps=2, mod=m1)
    a0c, _, _, _ = _train(None, X, y, scan_on=False, steps=2, mod=m0)
    for k in a0c:
        np.testing.assert_allclose(
            a0c[k], a1c[k], rtol=_SCAN_RTOL, atol=_SCAN_ATOL,
            err_msg="optimizer state diverged on continuation: %s" % k)


def test_module_training_resnet_style_allclose():
    """Conv/BN bodies: XLA CPU compiles their kernels with different
    rounding inside a while-loop body than inlined — both paths are
    individually deterministic, and agree to float-rounding level."""
    rng = np.random.RandomState(1)
    X = rng.randn(32, 3, 8, 8).astype("f4")
    y = rng.randint(0, 3, 32).astype("f4")
    a1, x1, f1, _ = _train(_resnet_ish(4), X, y, scan_on=True, steps=4)
    a0, x0, f0, _ = _train(_resnet_ish(4), X, y, scan_on=False, steps=4)
    assert f1.scan_runs and not f0.scan_runs
    assert f1._core_closed.num_eqns() < f0._core_closed.num_eqns()
    for k in a0:
        np.testing.assert_allclose(a0[k], a1[k], rtol=_SCAN_RTOL,
                                   atol=_SCAN_ATOL, err_msg=k)
    for k in x0:   # BN running stats ride the scan as stacked aux ys
        np.testing.assert_allclose(x0[k], x1[k], rtol=_SCAN_RTOL,
                                   atol=_SCAN_ATOL, err_msg=k)


def test_module_training_resnet_scan_deterministic():
    rng = np.random.RandomState(1)
    X = rng.randn(32, 3, 8, 8).astype("f4")
    y = rng.randint(0, 3, 32).astype("f4")
    a1, _, _, _ = _train(_resnet_ish(4), X, y, scan_on=True, steps=3)
    a2, _, _, _ = _train(_resnet_ish(4), X, y, scan_on=True, steps=3)
    for k in a1:
        assert np.array_equal(a1[k], a2[k]), \
            "scan path must be deterministic run-to-run (%s)" % k


def test_module_training_stacked_lstm():
    rng = np.random.RandomState(2)
    X = rng.randn(32, 3 * 8).astype("f4")
    y = rng.randint(0, 10, 32).astype("f4")
    a1, _, f1, _ = _train(_stacked_lstm(), X, y, scan_on=True, steps=5)
    a0, _, f0, _ = _train(_stacked_lstm(), X, y, scan_on=False, steps=5)
    assert [(n, l) for n, l in f1.scan_runs] and f1.scan_runs[0][1] == 3
    assert f1._core_closed.num_eqns() < f0._core_closed.num_eqns()
    # FC-bias cotangent reductions under the scanned backward round
    # differently on CPU: rounding-level agreement, tightly bounded
    for k in a0:
        np.testing.assert_allclose(a0[k], a1[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_scan_rekeys_program_cache():
    # the deduped jaxpr IS the cache identity: scan on/off must never
    # collide in the unified program cache
    X, y = _fc_data()
    _, _, f1, _ = _train(_stacked_fc(6), X, y, scan_on=True, steps=1)
    _, _, f0, _ = _train(_stacked_fc(6), X, y, scan_on=False, steps=1)
    assert f1._core_closed.graph_hash != f0._core_closed.graph_hash


# ---------------------------------------------------------------------------
# gluon HybridSequential
# ---------------------------------------------------------------------------

def test_gluon_hybrid_sequential_scan_parity():
    from incubator_mxnet_tpu import gluon, nd

    def run(scan_on, depth=6, steps=5):
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
        os.environ["MXNET_FUSED_SCAN"] = "1" if scan_on else "0"
        try:
            rng = np.random.RandomState(9)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(16))
            for _ in range(depth):
                net.add(gluon.nn.Dense(16, activation="relu"))
            net.add(gluon.nn.Dense(3))
            net.initialize()
            net(nd.array(np.zeros((2, 12), "f4")))
            for p in net.collect_params().values():
                if p.name.endswith("bias"):
                    p.set_data(nd.array(np.zeros(p.shape, "f4")))
                else:
                    p.set_data(nd.array(
                        (rng.randn(*p.shape) * 0.2).astype("f4")))
            trainer = gluon.Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.1,
                                     "momentum": 0.9})
            est = gluon.contrib.estimator.Estimator(
                net, gluon.loss.SoftmaxCrossEntropyLoss(),
                train_metrics=[mx.metric.Accuracy()], trainer=trainer)
            X = np.random.RandomState(4).randn(64, 12).astype("f4")
            y = np.random.RandomState(4).randint(0, 3, 64).astype("f4")
            batches = [(nd.array(X[i:i + 16]), nd.array(y[i:i + 16]))
                       for i in range(0, 64, 16)] * 3
            est.fit(iter(batches[:steps]), epochs=1, event_handlers=[])
            fs = est._fused
            assert fs is not None and not fs.broken
            # gluon param names use global counters: compare positionally
            return ([p.data().asnumpy()
                     for p in net.collect_params().values()],
                    fs._core_closed.num_eqns())
        finally:
            os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)
            os.environ.pop("MXNET_FUSED_SCAN", None)

    p1, e1 = run(True)
    p0, e0 = run(False)
    assert e1 < e0, "identical Dense run must scan (eqns %d vs %d)" \
        % (e1, e0)
    for i, (a, b) in enumerate(zip(p0, p1)):
        assert np.array_equal(a, b), "param %d differs" % i


# ---------------------------------------------------------------------------
# auto-donation
# ---------------------------------------------------------------------------

def test_autodonate_engages_on_dying_inputs():
    X, y = _fc_data()
    _, _, fused, _ = _train(_stacked_fc(3), X, y, scan_on=False, steps=2)
    assert fused._autodonate_on, \
        "batch inputs die in a plain train step: donation must engage"


def test_autodonate_never_fires_on_live_buffer():
    """Negative fixture: a head echoes the data variable, so the input
    buffer stays live past the step — liveness must refuse donation."""
    data = sym.Variable("data")
    x = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Group([sym.SoftmaxOutput(x, name="softmax"), data])
    X, y = _fc_data(d=32, k=8)
    _, _, fused, _ = _train(net, X, y, scan_on=False, steps=2)
    assert not fused._autodonate_on, \
        "an input that IS a program output must never be donated"


def test_autodonate_env_kill_switch():
    X, y = _fc_data()
    _, _, fused, _ = _train(_stacked_fc(3), X, y, scan_on=False, steps=2,
                            autodonate=False)
    assert not fused._autodonate_on


def test_autodonate_training_parity():
    X, y = _fc_data()
    a1, _, _, _ = _train(_stacked_fc(4), X, y, scan_on=False, steps=4,
                         autodonate=True)
    a0, _, _, _ = _train(_stacked_fc(4), X, y, scan_on=False, steps=4,
                         autodonate=False)
    for k in a0:
        assert np.array_equal(a0[k], a1[k]), \
            "donation must not change results (%s)" % k


def test_jaxpr_dying_inputs_liveness():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.analysis import cost

    def f(a, b, c):
        return a + 1.0, b   # b is returned: still live; c unused: dies

    closed = jax.make_jaxpr(f)(jnp.zeros(3), jnp.zeros(3), jnp.zeros(3))
    dying = cost.jaxpr_dying_inputs(closed, [0, 1, 2])
    assert 0 in dying and 2 in dying and 1 not in dying


# ---------------------------------------------------------------------------
# checkpoint round-trip across the scan boundary
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_across_scan_boundary(tmp_path):
    X, y = _fc_data()
    a1, _, fused, mod = _train(_stacked_fc(6), X, y, scan_on=True,
                               steps=3, momentum=0)
    assert fused.scan_runs
    prefix = str(tmp_path / "scan_ckpt")
    mod.save_checkpoint(prefix, 0)

    # params saved from the scan-lowered run keep per-layer layout:
    # a scan-off module loads them bit-identically
    mod2 = mx.mod.Module.load(prefix, 0, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (16, 32))],
              label_shapes=[("softmax_label", (16,))])
    mod2.init_params(mx.initializer.Xavier())   # overridden by loaded
    a2, _ = mod2.get_params()
    for k in a1:
        assert np.array_equal(a1[k], a2[k].asnumpy()), \
            "checkpoint must round-trip per-layer params (%s)" % k

    # resume on BOTH sides of the boundary: the continuations agree to
    # the scan parity bound (the checkpoint itself round-trips bitwise)
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    try:
        conts = {}
        for scan_on in (True, False):
            os.environ["MXNET_FUSED_SCAN"] = "1" if scan_on else "0"
            m = mx.mod.Module.load(prefix, 0, context=mx.cpu())
            m.bind(data_shapes=[("data", (16, 32))],
                   label_shapes=[("softmax_label", (16,))])
            m.init_params(mx.initializer.Xavier())
            m.init_optimizer(optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1})
            it = io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                                label_name="softmax_label")
            metric = mx.metric.create("acc")
            for b in list(it)[:2]:
                m.fit_step(b, metric)
            args, _ = m.get_params()
            conts[scan_on] = {k: v.asnumpy() for k, v in args.items()}
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)
        os.environ.pop("MXNET_FUSED_SCAN", None)
    for k in conts[True]:
        np.testing.assert_allclose(
            conts[True][k], conts[False][k], rtol=_SCAN_RTOL,
            atol=_SCAN_ATOL,
            err_msg="resume across the scan boundary diverged (%s)" % k)


# ---------------------------------------------------------------------------
# compile-phase stats + budget gates
# ---------------------------------------------------------------------------

def test_compile_phase_stats_shape():
    X, y = _fc_data()
    _, _, fused, _ = _train(_stacked_fc(4), X, y, scan_on=True, steps=2)
    st = fused.compile_phase_stats()
    assert st["trace_s"] > 0
    assert st["jaxpr_eqns"] > 0
    assert st["scan_runs"], "scan run must be reported"
    assert st["autodonate"] is True
    assert st["programs"], "unified-cache program entries must appear"
    p = st["programs"][0]
    assert {"label", "compiles", "disk_hits", "lower_s",
            "compile_s"} <= set(p)
    assert p["compiles"] >= 1 and p["compile_s"] > 0


def test_program_cache_compile_timing_stats():
    from incubator_mxnet_tpu import compile as mxc

    st = mxc.stats()
    assert "lower_s_total" in st["counters"]
    assert "compile_s_total" in st["counters"]
    assert "disk_misses" in st["counters"]
    # this process compiled fused programs in the tests above
    assert st["counters"]["compile_s_total"] >= 0.0
    for prog in st["programs"]:
        assert {"disk_misses", "lower_s", "compile_s"} <= set(prog)


def test_check_measured_regression_and_missing():
    base = {"measured": {
        "p": {"compile_s": 1.0, "peak_hbm_mb": 100.0}}}
    ok, _ = budgets.check_measured(
        {"p": {"compile_s": 1.2, "peak_hbm_mb": 108.0}}, base)
    assert not [f for f in ok if f.severity == "error"]
    bad, deltas = budgets.check_measured(
        {"p": {"compile_s": 1.0, "peak_hbm_mb": 120.0}}, base)
    errs = [f for f in bad if f.severity == "error"]
    assert errs and "peak_hbm_mb" in errs[0].message
    assert deltas["p"]["peak_hbm_mb"]["ok"] is False
    miss, _ = budgets.check_measured({"q": {"compile_s": 1.0}}, base)
    assert [f for f in miss if f.code == "budget-missing"]


def test_check_measured_ratio_cap_and_informational():
    base = {"measured": {"f": {
        "compile_ratio_vs_jax": 1.5, "jaxpr_eqns": 141,
        "jax_control_compile_s": 0.1}}}
    # under the pinned cap: no error AND no slack noise
    rep, _ = budgets.check_measured(
        {"f": {"compile_ratio_vs_jax": 1.05, "jaxpr_eqns": 141,
               "jax_control_compile_s": 99.0,
               "peak_hbm_source": "estimated"}}, base)
    assert not list(rep), [f.format() for f in rep]
    # over the cap: hard error; eqn growth: hard error
    rep, _ = budgets.check_measured(
        {"f": {"compile_ratio_vs_jax": 1.6, "jaxpr_eqns": 150}}, base)
    codes = [(f.code, f.severity) for f in rep]
    assert codes.count(("budget-regression", "error")) == 2


def test_snapshot_measured_floors_and_merge():
    b = budgets.snapshot_measured(
        {"f": {"compile_ratio_vs_jax": 0.9, "compile_s": 0.05,
               "peak_hbm_mb": 10.0, "peak_hbm_source": "estimated"}})
    entry = b["measured"]["f"]
    assert entry["compile_ratio_vs_jax"] == 1.5   # contract floor
    assert entry["compile_s"] == 0.5              # noise floor
    assert entry["peak_hbm_mb"] == 10.0
    assert "peak_hbm_source" not in entry         # non-numeric skipped
    b2 = budgets.snapshot_measured({"g": {"compile_s": 2.0}}, b)
    assert b2["measured"]["f"]["peak_hbm_mb"] == 10.0   # merge keeps f
    assert b2["measured"]["g"]["compile_s"] == 2.0
    assert b2["measured_tolerances"]["peak_hbm_mb"] == 0.15


def test_cost_budgets_json_has_measured_section():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    committed = budgets.load(os.path.join(root, "COST_BUDGETS.json"))
    measured = committed.get("measured") or {}
    spec = importlib.util.spec_from_file_location(
        "warmup_tool", os.path.join(root, "tools", "warmup.py"))
    warmup = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(warmup)
    for name in warmup.REQUIRED_MEASURED:
        assert name in measured, "budget entry missing: %s" % name
        assert "compile_s" in measured[name]
    assert "peak_hbm_mb" in measured["quantization.convnet_fp32"]
    assert measured["fused.convnet_step"]["compile_ratio_vs_jax"] <= 1.5
    assert committed["measured_tolerances"]["peak_hbm_mb"] == \
        pytest.approx(0.15)
