"""Subgraph partition framework + Pallas fused-kernel tests (reference
tests/python/unittest/test_subgraph_op.py strategy: partitioned graph is
numerically identical to the original)."""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, subgraph


@pytest.fixture(autouse=True)
def _interpreted_kernel(monkeypatch):
    """Off-TPU the fused op is the plain XLA expression; asking for
    interpret mode by name makes these tests run the Pallas KERNEL."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=8, name="fc2")
    h = mx.sym.Activation(h, act_type="relu", name="relu2")
    return mx.sym.FullyConnected(h, num_hidden=4, name="fc3")


def _run(sym, x, args, grad=False):
    exe = sym.simple_bind(ctx=mx.cpu(), grad_req="write" if grad else "null",
                          data=x.shape)
    exe.copy_params_from(args, {})
    out = exe.forward(is_train=grad, data=nd.array(x))[0]
    if not grad:
        return out.asnumpy(), None
    exe.backward(nd.ones(out.shape))
    return out.asnumpy(), {k: v.asnumpy() for k, v in
                           exe.grad_dict.items() if v is not None}


def _init(sym, shape):
    rng = np.random.RandomState(0)
    arg_shapes, _, _ = sym.infer_shape(data=shape)
    return {n: nd.array(rng.normal(0, 0.5, s).astype("f4"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n != "data"}


def test_partition_replaces_chains():
    sym = _mlp()
    part = subgraph.partition_graph(sym, "TPU_PALLAS")
    js = part.tojson()
    assert js.count("_sg_pallas_fc_relu") == 2          # fc1/relu1, fc2/relu2
    assert "relu1" not in [n for n in part.get_internals().list_outputs()]
    # same parameter surface
    assert set(part.list_arguments()) == set(sym.list_arguments())


def test_partitioned_forward_and_grad_match():
    sym = _mlp()
    x = np.random.RandomState(1).normal(0, 1, (8, 10)).astype("f4")
    args = _init(sym, x.shape)
    ref_out, ref_grads = _run(sym, x, args, grad=True)
    part = subgraph.partition_graph(sym, "TPU_PALLAS")
    out, grads = _run(part, x, args, grad=True)
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)
    for k in ref_grads:
        np.testing.assert_allclose(grads[k], ref_grads[k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


def test_convexity_guard():
    """A chain whose interior feeds an outside consumer must NOT fuse."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    relu = mx.sym.Activation(fc, act_type="relu")
    out = relu + fc                     # fc has a second consumer
    part = subgraph.partition_graph(out, "TPU_PALLAS")
    assert "_sg_pallas_fc_relu" not in part.tojson()


def test_env_var_bind_partition():
    sym = _mlp()
    x = np.random.RandomState(2).normal(0, 1, (4, 10)).astype("f4")
    args = _init(sym, x.shape)
    ref, _ = _run(sym, x, args)
    os.environ["MXNET_SUBGRAPH_BACKEND"] = "TPU_PALLAS"
    try:
        got, _ = _run(sym, x, args)
    finally:
        del os.environ["MXNET_SUBGRAPH_BACKEND"]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_custom_property_registration():
    class NoopProp(subgraph.SubgraphProperty):
        name = "NOOP_TEST"

    subgraph.register_subgraph_property(NoopProp())
    assert "NOOP_TEST" in subgraph.list_backends()
    sym = _mlp()
    part = subgraph.partition_graph(sym, "NOOP_TEST")
    assert part.tojson() == sym.tojson()
    with pytest.raises(mx.MXNetError):
        subgraph.get_subgraph_property("NOT_REGISTERED")
