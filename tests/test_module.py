"""Module API tests (reference tests/python/unittest/test_module.py and
tests/python/train/test_mlp.py — the Module.fit e2e gate)."""
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, sym
from incubator_mxnet_tpu.io import NDArrayIter
from incubator_mxnet_tpu.test_utils import get_mnist_like


def _lenet():
    data = sym.Variable("data")
    c1 = sym.Convolution(data, kernel=(5, 5), num_filter=8, name="conv1")
    a1 = sym.Activation(c1, act_type="tanh")
    p1 = sym.Pooling(a1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = sym.Convolution(p1, kernel=(5, 5), num_filter=16, name="conv2")
    a2 = sym.Activation(c2, act_type="tanh")
    p2 = sym.Pooling(a2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    fl = sym.Flatten(p2)
    f1 = sym.FullyConnected(fl, num_hidden=64, name="fc1")
    a3 = sym.Activation(f1, act_type="tanh")
    f2 = sym.FullyConnected(a3, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(f2, name="softmax")


def _mlp():
    data = sym.Variable("data")
    f1 = sym.FullyConnected(data, num_hidden=64, name="fc1")
    a1 = sym.Activation(f1, act_type="relu")
    f2 = sym.FullyConnected(a1, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(f2, name="softmax")


def test_module_fit_mnist_like():
    """Gate #1: LeNet-style training via mx.mod.Module reaches high accuracy
    on the synthetic MNIST stand-in (reference train_mnist.py contract)."""
    X, y = get_mnist_like(512)
    train = NDArrayIter(X, y, batch_size=64, shuffle=True)
    val = NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(_lenet(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            num_epoch=5, batch_end_callback=None)
    score = mod.score(val, "acc")
    assert score[0][1] > 0.95, score


def test_module_basic_api():
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    assert mod.data_names == ["data"]
    assert set(mod._param_names) == {"fc1_weight", "fc1_bias", "fc2_weight",
                                     "fc2_bias"}
    mod.bind(data_shapes=[("data", (8, 20))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    from incubator_mxnet_tpu.io import DataBatch
    batch = DataBatch(data=[nd.random.uniform(shape=(8, 20))],
                      label=[nd.array(np.arange(8) % 10)])
    mod.forward(batch, is_train=True)
    outs = mod.get_outputs()
    assert outs[0].shape == (8, 10)
    mod.backward()
    mod.update()
    arg_params, aux_params = mod.get_params()
    assert "fc1_weight" in arg_params


def test_module_save_load_checkpoint(tmp_path):
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 20))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 3)
    mod2 = mx.mod.Module.load(prefix, 3, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 20))],
              label_shapes=[("softmax_label", (4,))])
    mod2.init_params()
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        np.testing.assert_allclose(a1[k].asnumpy(), a2[k].asnumpy(), rtol=1e-6)


def test_module_multi_device_data_parallel():
    """Reference test_multi_device_exec.py analogue on the virtual mesh."""
    import jax
    if len(jax.devices()) < 2:
        return
    X, y = get_mnist_like(256)
    X = X.reshape(256, -1)
    train = NDArrayIter(X, y, batch_size=64, shuffle=True)
    contexts = [mx.tpu(0), mx.tpu(1)]
    mod = mx.mod.Module(_mlp(), context=contexts)
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            num_epoch=6, kvstore="device")
    score = mod.score(NDArrayIter(X, y, batch_size=64), "acc")
    assert score[0][1] > 0.9, score


def test_module_predict():
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    X = np.random.rand(32, 20).astype("f4")
    it = NDArrayIter(X, np.zeros(32, "f4"), batch_size=8)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (32, 10)
    np.testing.assert_allclose(out.asnumpy().sum(1), 1.0, rtol=1e-5)


def test_bucketing_module():
    """Reference test_bucketing.py pattern: per-length graphs share params."""
    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        f = sym.FullyConnected(data, num_hidden=16, name="fc_shared",
                               flatten=False)
        f = sym.Reshape(sym.mean(f, axis=1), shape=(-1, 16))
        out = sym.FullyConnected(f, num_hidden=4, name="out_shared")
        return sym.SoftmaxOutput(out, label, name="softmax"), ("data",), \
            ("softmax_label",)

    from incubator_mxnet_tpu.io import DataBatch, DataDesc
    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (4, 8, 12))],
             label_shapes=[DataDesc("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")
    for key in (8, 4, 8, 12):
        batch = DataBatch(
            data=[nd.random.uniform(shape=(4, key, 12))],
            label=[nd.array(np.arange(4) % 4)],
            bucket_key=key,
            provide_data=[DataDesc("data", (4, key, 12))],
            provide_label=[DataDesc("softmax_label", (4,))])
        mod.forward_backward(batch)
        mod.update()
    assert set(mod._buckets) == {4, 8, 12}


def test_python_loss_module():
    """PythonLossModule (reference module/python_module.py): forward keeps
    scores, backward calls grad_func; chains after a symbol Module via
    SequentialModule-style manual wiring."""
    import numpy as np
    import incubator_mxnet_tpu as mx

    def grad_func(scores, labels):
        # d/ds of 0.5*(s - onehot)^2 = s - onehot
        s = scores.asnumpy()
        lab = labels.asnumpy().astype(int)
        one = np.zeros_like(s)
        one[np.arange(len(lab)), lab] = 1.0
        return s - one

    m = mx.mod.PythonLossModule(grad_func=grad_func)
    m.bind(data_shapes=[mx.io.DataDesc("data", (4, 3))],
           label_shapes=[mx.io.DataDesc("softmax_label", (4,))])
    m.init_params()
    m.init_optimizer()
    assert m.output_shapes == [("pyloss_output", (4, 3))]
    rng = np.random.RandomState(0)
    scores = mx.nd.array(rng.rand(4, 3).astype("f4"))
    labels = mx.nd.array(np.array([0, 1, 2, 1], "f4"))
    batch = mx.io.DataBatch(data=[scores], label=[labels])
    m.forward(batch)
    np.testing.assert_allclose(m.get_outputs()[0].asnumpy(),
                               scores.asnumpy())
    m.backward()
    g = m.get_input_grads()[0].asnumpy()
    np.testing.assert_allclose(g, grad_func(scores, labels), rtol=1e-6)


def test_sequential_module_fit():
    """A container of modules has no graph of its own (`symbol` is None):
    `fit` runs through its epoch ends all the same, and trains."""
    rng = np.random.RandomState(0)
    x = rng.rand(64, 10).astype("f4")
    y = (x[:, :4].argmax(axis=1)).astype("f4")
    body = sym.Activation(sym.FullyConnected(
        sym.Variable("data"), num_hidden=16, name="fc1"), act_type="relu")
    head = sym.SoftmaxOutput(sym.FullyConnected(
        sym.Variable("data"), num_hidden=4, name="fc2"), name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(body, label_names=None, context=mx.cpu()))
    seq.add(mx.mod.Module(head, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    assert seq.symbol is None
    seq.fit(NDArrayIter(x, y, batch_size=8), num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), eval_metric="acc")
    (name, acc), = seq.score(NDArrayIter(x, y, batch_size=8), "acc")
    assert acc > 0.5, acc
