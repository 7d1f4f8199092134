"""Gluon fused train step (gluon/fused_step.py via Estimator.fit): one
donated XLA program per signature, with exact parity against the eager
record/backward/step loop."""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd


def _data(n=64, d=12, k=3, seed=4):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, d).astype("f4"),
            rng.randint(0, k, n).astype("f4"))


def _net_init(seed=9):
    rng = np.random.RandomState(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(3))
    net.initialize()
    net(nd.array(np.zeros((2, 12), "f4")))
    for p in net.collect_params().values():
        p.set_data(nd.array(rng.randn(*p.shape).astype("f4") * 0.2))
    return net


def _run(fused_on, optimizer="sgd", opt_params=None, steps=6, bn=False):
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1" if fused_on else "0"
    try:
        rng = np.random.RandomState(9)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16))
        if bn:
            net.add(gluon.nn.BatchNorm())
        net.add(gluon.nn.Dense(3))
        net.initialize()
        net(nd.array(np.zeros((2, 12), "f4")))
        for p in net.collect_params().values():
            r = rng.randn(*p.shape) * 0.2 if p.shape else 0
            if p.name.endswith(("gamma", "running_var")):
                p.set_data(nd.array(np.ones(p.shape, "f4")))
            elif p.name.endswith(("beta", "running_mean", "bias")):
                p.set_data(nd.array(np.zeros(p.shape, "f4")))
            else:
                p.set_data(nd.array(r.astype("f4")))
        trainer = gluon.Trainer(net.collect_params(), optimizer,
                                opt_params or {"learning_rate": 0.1})
        est = gluon.contrib.estimator.Estimator(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            train_metrics=[mx.metric.Accuracy()], trainer=trainer)
        X, y = _data()
        batches = [(nd.array(X[i:i + 16]), nd.array(y[i:i + 16]))
                   for i in range(0, 64, 16)] * (steps // 4 + 1)
        est.fit(iter(batches[:steps]), epochs=1,
                event_handlers=[])
        metric_val = dict(m.get_name_value()[0] if isinstance(
            m.get_name_value(), list) else [m.get_name_value()]
            for m in est.train_metrics)
        params = [p.data().asnumpy()
                  for p in net.collect_params().values()]
        states = None
        if 0 in trainer._updaters[0].states and \
                trainer._updaters[0].states[0] is not None:
            from incubator_mxnet_tpu.fused import _state_data
            import jax
            states = jax.tree_util.tree_leaves(
                _state_data(trainer._updaters[0].states[0]))
        return params, metric_val, est, states
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


@pytest.mark.parametrize("optimizer,opt_params,bn", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, False),
    ("adam", {"learning_rate": 0.01}, False),
    ("sgd", {"learning_rate": 0.1}, True),
])
def test_estimator_fused_matches_eager(optimizer, opt_params, bn):
    p_fused, m_fused, est, s_fused = _run(True, optimizer, opt_params, bn=bn)
    p_eager, m_eager, _, s_eager = _run(False, optimizer, opt_params, bn=bn)
    assert est._fused is not None and not est._fused.broken, \
        "Estimator must engage the fused Gluon step"
    for i, (a, b) in enumerate(zip(p_fused, p_eager)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"param {i}")
    for k in m_eager:
        np.testing.assert_allclose(m_fused[k], m_eager[k], rtol=1e-6,
                                   err_msg=k)
    if s_eager is not None:
        for a, b in zip(s_fused, s_eager):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


def test_estimator_fused_falls_back_on_dropout():
    """RNG-consuming nets (dropout) must fall back to the eager loop and
    still train."""
    os.environ["MXNET_FUSED_TRAIN_STEP"] = "1"
    try:
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"))
        net.add(gluon.nn.Dropout(0.5))
        net.add(gluon.nn.Dense(3))
        net.initialize(mx.initializer.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        est = gluon.contrib.estimator.Estimator(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer=trainer)
        X, y = _data()
        batches = [(nd.array(X[:16]), nd.array(y[:16]))] * 4
        est.fit(iter(batches), epochs=1, event_handlers=[])
        for p in net.collect_params().values():
            assert np.isfinite(p.data().asnumpy()).all()
    finally:
        os.environ.pop("MXNET_FUSED_TRAIN_STEP", None)


def test_estimator_device_error_is_raised_not_replaced(monkeypatch):
    """Only Python that cannot trace selects the eager loop (the dropout
    case above); a compile or device error in the selected step is raised
    with its cause."""
    import jax
    from incubator_mxnet_tpu.base import MXNetError
    _, _, est, _ = _run(True, steps=2)
    assert est._fused is not None and not est._fused.broken

    def refuse(*args):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: injected by the test")

    monkeypatch.setattr(est._fused, "_jit", refuse)
    monkeypatch.setenv("MXNET_FUSED_TRAIN_STEP", "1")
    monkeypatch.setenv("MXNET_FUSED_STEP_BLOCK", "1")
    X, y = _data()
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED") as ei:
        est.fit(iter([(nd.array(X[:16]), nd.array(y[:16]))]), epochs=1,
                event_handlers=[])
    assert isinstance(ei.value.__cause__, jax.errors.JaxRuntimeError)


def test_estimator_fused_then_eager_state_shared():
    """Switching to the eager path mid-training (new kvstore etc.) keeps
    optimizer state: both paths use the trainer's updater store."""
    p_fused, _, est, _ = _run(True, "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9},
                              steps=3)
    upd = est.trainer._updaters[0]
    assert any(v is not None for v in upd.states.values()), \
        "fused path must keep state in the trainer's updater"


def _estimator_fit_with_block(block_k, steps=8):
    """Estimator.fit at a given block size, recording what each
    batch_end handler observes from the train metric."""
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(block_k)
    try:
        np.random.seed(4)
        mx.random.seed(4)
        net = _net_init()
        X, y = _data(n=64)
        loader = gluon.data.DataLoader(
            gluon.data.ArrayDataset(nd.array(X), nd.array(y)),
            batch_size=8, shuffle=False)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05})
        metric = mx.metric.Accuracy()
        est = gluon.contrib.estimator.Estimator(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            train_metrics=[metric], trainer=trainer)
        seen = []

        class Probe:
            def train_begin(self, est):
                pass

            def epoch_begin(self, est):
                pass

            def batch_begin(self, est):
                pass

            def batch_end(self, est):
                seen.append((est.batch_idx, metric.get()[1]))

            def epoch_end(self, est):
                pass

            def train_end(self, est):
                pass

        est.fit(loader, epochs=1, event_handlers=[Probe()])
        assert est._fused is not None and not est._fused.broken, \
            "Estimator must engage the fused Gluon step"
        return seen
    finally:
        os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)


def test_estimator_block_handlers_fire_per_logical_step():
    """K>1 Estimator blocks: batch-j handlers must observe batch-j
    metric state, matching per-batch dispatch exactly (the K>1
    callback semantics)."""
    ref = _estimator_fit_with_block(1)
    blocked = _estimator_fit_with_block(4)
    assert [b for b, _ in ref] == [b for b, _ in blocked]
    for (nb, v1), (_nb2, vk) in zip(ref, blocked):
        np.testing.assert_allclose(vk, v1, rtol=1e-6, atol=1e-7,
                                   err_msg=f"batch {nb}")
    assert len({round(v, 6) for _, v in blocked}) > 1
