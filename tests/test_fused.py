"""Fused public train path: Module.fit / Trainer.step must run as one
donated XLA program AND match the unfused reference semantics exactly.

This is the round-3 contract (bulk-exec + fused optimizer parity with
reference `graph_executor.cc:1194-1316` / `optimizer_op.cc`): the numbers a
user gets from the fast path are the numbers the per-op path produces.
"""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, fused, gluon, io, nd, sym


def _make_symbol():
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=64, d=16, k=4):
    rng = np.random.RandomState(0)
    return rng.randn(n, d).astype("f4"), \
        rng.randint(0, k, n).astype("f4")


def _run_module(fused_on, optimizer, opt_params, contexts=None, steps=6,
                metric_name="acc"):
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1" if fused_on else "0"
    try:
        np.random.seed(7)
        mx.random.seed(7)
        X, y = _data()
        it = io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                            label_name="softmax_label")
        mod = mx.mod.Module(_make_symbol(),
                            context=contexts or mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(kvstore="device", optimizer=optimizer,
                           optimizer_params=opt_params)
        metric = mx.metric.create(metric_name)
        batches = list(it)
        for s in range(steps):
            mod.fit_step(batches[s % len(batches)], metric)
        args, _ = mod.get_params()
        return ({k: v.asnumpy() for k, v in args.items()},
                dict(metric.get_name_value()), mod)
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3}),
    ("adam", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("ftml", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
])
def test_fused_matches_unfused(optimizer, opt_params):
    a, ma, mod = _run_module(True, optimizer, opt_params)
    b, mb, _ = _run_module(False, optimizer, opt_params)
    assert mod._fused_step is not None and not mod._fused_step.broken, \
        "fused step must actually engage"
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-5, atol=2e-6,
                                   err_msg=k)
    for k in ma:
        assert abs(ma[k] - mb[k]) < 1e-6, (k, ma, mb)


def test_fused_multi_device_matches_single():
    ctxs = [mx.cpu(i) for i in range(4)]
    a, ma, mod = _run_module(True, "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             contexts=ctxs)
    assert mod._fused_step is not None and not mod._fused_step.broken
    b, mb, _ = _run_module(True, "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    assert ma == mb


def test_fused_lr_scheduler_is_dynamic():
    """A per-step lr schedule must take effect WITHOUT retriggering
    compilation (lr is a traced input, not a baked constant)."""
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    a, _, mod = _run_module(True, "sgd",
                            {"learning_rate": 0.2, "lr_scheduler": sched})
    sched2 = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    b, _, _ = _run_module(False, "sgd",
                          {"learning_rate": 0.2, "lr_scheduler": sched2})
    assert mod._fused_step is not None and not mod._fused_step.broken
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-5, atol=1e-6,
                                   err_msg=k)


def test_fused_metric_composite_in_graph():
    comp = mx.metric.CompositeEvalMetric(
        metrics=[mx.metric.Accuracy(), mx.metric.CrossEntropy()])
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    try:
        np.random.seed(7)
        mx.random.seed(7)
        X, y = _data()
        it = io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                            label_name="softmax_label")
        mod = mx.mod.Module(_make_symbol(), context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
        batches = list(it)
        # host-side reference accumulation on identical outputs
        ref_acc, ref_ce = mx.metric.Accuracy(), mx.metric.CrossEntropy()
        for b in batches[:4]:
            mod.fit_step(b, comp)
            ref_acc.update(b.label, mod.get_outputs())
            ref_ce.update(b.label, mod.get_outputs())
        got = dict(comp.get_name_value())
        assert abs(got["accuracy"] - ref_acc.get()[1]) < 1e-6
        assert abs(got["cross-entropy"] - ref_ce.get()[1]) < 1e-4
        assert mod._fused_step is not None and not mod._fused_step.broken
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


def test_fused_optimizer_state_save_load_roundtrip():
    a, _, mod = _run_module(True, "adam", {"learning_rate": 0.01}, steps=3)
    assert mod._fused_step is not None and not mod._fused_step.broken
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        f = os.path.join(d, "opt.states")
        mod.save_optimizer_states(f)
        mod.load_optimizer_states(f)
    # states survived the round trip and training continues
    X, y = _data()
    it = io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    m = mx.metric.create("acc")
    mod.fit_step(next(iter(it)), m)
    assert not mod._fused_step.broken


def test_trainer_fused_update_matches_manual_sgd():
    """gluon.Trainer.step applies every update in ONE program
    (fused.FusedOptimizer) and must equal hand-computed SGD-momentum."""
    np.random.seed(3)
    mx.random.seed(3)
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.array(np.random.randn(16, 8).astype("f4"))
    params = {p.name: p for p in net.collect_params().values()}
    ref = {k: (p.data().asnumpy().copy(),
               np.zeros_like(p.data().asnumpy()))
           for k, p in params.items()}
    for _ in range(3):
        with autograd.record():
            out = net(x)
            loss = (out * out).sum()
        loss.backward()
        trainer.step(1)
        for k, p in params.items():
            w, mom = ref[k]
            g = p.grad().asnumpy()
            mom = 0.9 * mom - 0.1 * g
            w = w + mom
            ref[k] = (w, mom)
    assert trainer._fused is not None and not trainer._fused[0]._broken, \
        "Trainer must use the fused multi-tensor apply"
    for k, p in params.items():
        np.testing.assert_allclose(p.data().asnumpy(), ref[k][0],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_fused_optimizer_fallback_is_safe():
    """An untraceable optimizer must fall back to the per-parameter path
    and still produce the correct result."""

    @mx.optimizer.register
    class HostRng(mx.optimizer.Optimizer):
        def update(self, index, weight, grad, state):
            self._update_count(index)
            # host-side numpy draw: cannot trace -> must fall back
            noise = float(np.random.RandomState(0).rand())
            weight -= self._get_lr(index) * (grad + 0 * noise)

    opt = HostRng(learning_rate=0.5)
    fo = fused.FusedOptimizer(opt)
    w = nd.array(np.ones(4, "f4"))
    g = nd.array(np.full(4, 2.0, "f4"))
    fo([0], [w], [g], [None])
    np.testing.assert_allclose(w.asnumpy(), np.zeros(4), atol=1e-6)
    del mx.optimizer.Optimizer.opt_registry["hostrng"]


def test_fused_metric_swap_mid_training():
    """Changing the eval metric after steady-state steps must rebuild the
    program WITHOUT touching the donated (deleted) exec buffers: the
    deferred write-backs flush first, training continues, and both metric
    objects report sane values (regression: the metric-change path once
    demoted to the cold path after the flush decision was made)."""
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    try:
        np.random.seed(7)
        mx.random.seed(7)
        X, y = _data()
        it = io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                            label_name="softmax_label")
        mod = mx.mod.Module(_make_symbol(), context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        batches = list(it)
        m1 = mx.metric.create("acc")
        for s in range(3):   # step 1 cold+flush, 2-3 steady (deferred)
            mod.fit_step(batches[s % len(batches)], m1)
        assert not mod._fused_step.broken
        m2 = mx.metric.create("ce")   # new metric object: program rebuild
        for s in range(3):
            mod.fit_step(batches[s % len(batches)], m2)
        assert not mod._fused_step.broken, \
            "metric swap must not break the fused step"
        assert np.isfinite(dict(m2.get_name_value())["cross-entropy"])
        args, _ = mod.get_params()
        for k, v in args.items():
            assert np.isfinite(v.asnumpy()).all(), k
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


def test_fused_bf16_multiprecision_derived_masters():
    """bf16 weights with fp32 masters: the fused program derives the
    low-precision weights from the masters in-graph (no weight args on
    the dispatch), and matches the unfused multi-precision path."""
    import ml_dtypes

    def run(fused_on):
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "1" if fused_on else "0"
        try:
            np.random.seed(3)
            mx.random.seed(3)
            X, y = _data()
            Xb = X.astype(ml_dtypes.bfloat16)
            it = io.NDArrayIter(Xb, y, batch_size=32, shuffle=False,
                                label_name="softmax_label")
            mod = mx.mod.Module(_make_symbol(), context=mx.cpu())
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label)
            mod.init_params(mx.initializer.Xavier())
            mod.init_optimizer(
                kvstore=None, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "multi_precision": True})
            metric = mx.metric.create("acc")
            batches = list(it)
            for s in range(5):
                mod.fit_step(batches[s % len(batches)], metric)
            args, _ = mod.get_params()
            return ({k: np.asarray(v.asnumpy(), np.float32)
                     for k, v in args.items()}, mod)
        finally:
            os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)

    w_fused, mod = run(True)
    assert mod._fused_step is not None and not mod._fused_step.broken
    assert mod._fused_step._derive_ws, \
        "all-bf16 multi-precision training must use derived masters"
    w_eager, _ = run(False)
    for k in w_fused:
        np.testing.assert_allclose(w_fused[k], w_eager[k], rtol=2e-2,
                                   atol=1e-2, err_msg=k)


def test_fused_prestage_matches_direct():
    """Module.prepare pre-stages the NEXT batch's transfer; results must be
    identical to calling fit_step without any prestage."""
    def run(with_prepare):
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
        try:
            np.random.seed(5)
            mx.random.seed(5)
            X, y = _data()
            it = io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                                label_name="softmax_label")
            mod = mx.mod.Module(_make_symbol(), context=mx.cpu())
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label)
            mod.init_params(mx.initializer.Xavier())
            mod.init_optimizer(kvstore=None, optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1})
            metric = mx.metric.create("acc")
            batches = list(it)
            for s in range(4):
                b = batches[s % len(batches)]
                mod.fit_step(b, metric)
                if with_prepare:
                    nb = batches[(s + 1) % len(batches)]
                    mod.prepare(nb)  # pre-stage next batch mid-flight
            args, _ = mod.get_params()
            return {k: v.asnumpy() for k, v in args.items()}
        finally:
            os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)

    w_pre = run(True)
    w_direct = run(False)
    for k in w_pre:
        np.testing.assert_array_equal(w_pre[k], w_direct[k], err_msg=k)


def test_fused_lr_mult_change_invalidates_hyper_cache():
    """Freezing a layer mid-training via lr_mult must take effect on the
    very next fused step (the hyper-vector cache keys on multipliers)."""
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    try:
        np.random.seed(6)
        mx.random.seed(6)
        X, y = _data()
        it = io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                            label_name="softmax_label")
        mod = mx.mod.Module(_make_symbol(), context=mx.cpu())
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        metric = mx.metric.create("acc")
        batches = list(it)
        for s in range(3):
            mod.fit_step(batches[s % len(batches)], metric)
        frozen = mod.get_params()[0]["fc1_weight"].asnumpy().copy()
        mod._optimizer.lr_mult = {"fc1_weight": 0.0}   # freeze fc1
        for s in range(3):
            mod.fit_step(batches[s % len(batches)], metric)
        after = mod.get_params()[0]["fc1_weight"].asnumpy()
        np.testing.assert_array_equal(after, frozen,
                                      err_msg="lr_mult=0 must freeze fc1")
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


def _fit_with_block(block_k, reset_at=None, num_epoch=1):
    """Run Module.fit at a given MXNET_FUSED_STEP_BLOCK, recording what
    every batch-end callback observes; optionally reset the metric
    inside the callback at batch `reset_at` (Speedometer auto_reset)."""
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(block_k)
    try:
        np.random.seed(7)
        mx.random.seed(7)
        X, y = _data()
        it = io.NDArrayIter(X, y, batch_size=8, shuffle=False,
                            label_name="softmax_label")
        mod = mx.mod.Module(_make_symbol())
        seen = []

        def cb(param):
            _name, val = param.eval_metric.get()
            seen.append((param.nbatch, val))
            if reset_at is not None and param.nbatch == reset_at:
                param.eval_metric.reset()

        mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                eval_metric="acc", initializer=mx.initializer.Xavier(),
                batch_end_callback=cb, kvstore=None)
        assert mod._fused_step is not None and not mod._fused_step.broken
        return seen
    finally:
        os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)


def test_block_callbacks_fire_per_logical_step():
    """K>1 fused blocks: each batch-end callback must observe BATCH-j
    metric state — identical to per-batch (K=1) dispatch — not the
    block-final totals."""
    ref = _fit_with_block(1)
    blocked = _fit_with_block(4)
    assert [b for b, _ in ref] == [b for b, _ in blocked]
    for (nb, v1), (_nb2, vk) in zip(ref, blocked):
        np.testing.assert_allclose(vk, v1, rtol=1e-6, atol=1e-7,
                                   err_msg=f"batch {nb}")
    # the per-step values must actually differ across the burst (a
    # constant block-final value would also pass a weaker check)
    assert len({round(v, 6) for _, v in blocked}) > 1


def test_block_callback_metric_reset_mid_burst():
    """A callback that RESETS the metric mid-burst (Speedometer
    auto_reset) must see post-reset windows identical to per-batch
    dispatch — the old burst semantics silently dropped the rest of the
    block from the next window."""
    ref = _fit_with_block(1, reset_at=1)
    blocked = _fit_with_block(4, reset_at=1)
    for (nb, v1), (_nb2, vk) in zip(ref, blocked):
        np.testing.assert_allclose(vk, v1, rtol=1e-6, atol=1e-7,
                                   err_msg=f"batch {nb}")


def test_block_metric_view_touched_before_first_expose():
    """Defensive paths of the per-step metric view: a reader that
    materializes (get) or resets the metric BETWEEN the block dispatch
    and the first burst callback must still land exact per-step totals
    — and must never touch the donated entry-carry buffers."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.fused import _BlockMetricView

    def build():
        m = mx.metric.Accuracy()
        # cumulative carries C_{-1}..C_1 = (0,0),(1,1),(2,2); final (3,3)
        pre = [(jnp.asarray([0., 1., 2.]), jnp.asarray([0, 1, 2]))]
        finals = [(jnp.asarray(3.), jnp.asarray(3))]
        view = _BlockMetricView([m], pre, finals)
        m._device_totals = finals[0]
        view.arm()
        return m, view

    # materialize before the burst: host absorbed the block-final totals
    m, view = build()
    assert m.get()[1] == 1.0          # 3/3 (armed finals)
    for j, want in enumerate([(1, 1), (2, 2), (3, 3)]):
        view.expose(j)
        s, n = want
        name, v = m.get()
        assert abs(v - s / n) < 1e-6, (j, v)
    assert m.num_inst == 3            # block-final state after the burst

    # reset before the burst: the new window starts at batch 0's delta
    m, view = build()
    m.reset()
    view.expose(0)
    assert m.get()[1] == 1.0 and m.num_inst == 1   # delta_0 = (1, 1)
    view.expose(1)
    assert m.get()[1] == 1.0 and m.num_inst == 2   # + delta_1
