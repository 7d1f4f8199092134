"""CPU-vs-TPU operator parity via check_consistency
(reference `tests/python/gpu/test_operator_gpu.py`, which re-runs the CPU
operator suite under the GPU context; here every case runs the same symbol
on both contexts and compares outputs AND gradients)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.test_utils import check_consistency, set_default_context


def _ctxs(**shapes):
    return [{"ctx": mx.cpu(), **shapes}, {"ctx": mx.tpu(), **shapes}]


def _strict_matmul():
    """MXU ops ingest bf16 by default (fp32 accumulate) — force full fp32
    inputs for exact parity checks; a separate test documents the default
    precision envelope."""
    import jax
    return jax.default_matmul_precision("highest")


def test_fully_connected():
    data = mx.sym.Variable("data")
    sym = mx.sym.FullyConnected(data, num_hidden=16, name="fc")
    with _strict_matmul():
        check_consistency(sym, _ctxs(data=(8, 12)))


def test_fully_connected_default_mxu_precision():
    """Default MXU precision: bf16 inputs, fp32 accumulation — parity
    within the bf16 envelope (the documented TPU trade)."""
    data = mx.sym.Variable("data")
    sym = mx.sym.FullyConnected(data, num_hidden=16, name="fc")
    check_consistency(sym, _ctxs(data=(8, 12)), tol=0.1)


def test_convolution():
    data = mx.sym.Variable("data")
    sym = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                             name="conv")
    with _strict_matmul():
        check_consistency(sym, _ctxs(data=(2, 3, 10, 10)))


def test_batchnorm_inference():
    data = mx.sym.Variable("data")
    sym = mx.sym.BatchNorm(data, fix_gamma=False, use_global_stats=True,
                           name="bn")
    check_consistency(sym, _ctxs(data=(4, 6, 5, 5)), grad_req="null")


def test_pooling():
    data = mx.sym.Variable("data")
    for pt in ("max", "avg"):
        sym = mx.sym.Pooling(data, kernel=(2, 2), stride=(2, 2),
                             pool_type=pt)
        check_consistency(sym, _ctxs(data=(2, 4, 8, 8)))


def test_activation_softmax():
    data = mx.sym.Variable("data")
    for act in ("relu", "sigmoid", "tanh", "softrelu"):
        check_consistency(mx.sym.Activation(data, act_type=act),
                          _ctxs(data=(6, 10)))
    check_consistency(mx.sym.softmax(data), _ctxs(data=(6, 10)))


def test_elementwise_and_broadcast():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    check_consistency(mx.sym.broadcast_add(a, b),
                      _ctxs(a=(4, 5), b=(1, 5)))
    check_consistency(mx.sym.broadcast_mul(a, b),
                      _ctxs(a=(4, 5), b=(4, 1)))
    with _strict_matmul():
        check_consistency(mx.sym.dot(a, b), _ctxs(a=(4, 6), b=(6, 3)))


def test_reduce_and_shape_ops():
    data = mx.sym.Variable("data")
    check_consistency(mx.sym.sum(data, axis=1), _ctxs(data=(4, 5, 6)))
    check_consistency(mx.sym.mean(data, axis=(0, 2)), _ctxs(data=(4, 5, 6)))
    check_consistency(mx.sym.transpose(data, axes=(1, 0, 2)),
                      _ctxs(data=(3, 4, 5)))
    check_consistency(mx.sym.Reshape(data, shape=(6, -1)),
                      _ctxs(data=(3, 4, 5)))


def test_embedding_layernorm():
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=20, output_dim=8)
    ctxs = [{"ctx": mx.cpu(), "data": (4, 6),
             "type_dict": {"data": np.int32}},
            {"ctx": mx.tpu(), "data": (4, 6),
             "type_dict": {"data": np.int32}}]
    check_consistency(emb, ctxs, grad_req="null")
    check_consistency(mx.sym.LayerNorm(mx.sym.Variable("x")),
                      _ctxs(x=(4, 10)))


def test_gluon_block_on_tpu():
    """High-level flow under the TPU default context (the reference reruns
    entire suites this way; one representative training step here)."""
    from incubator_mxnet_tpu import autograd, nd, gluon
    set_default_context(mx.tpu())
    try:
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"),
                gluon.nn.Dense(4))
        net.initialize(mx.initializer.Xavier(), ctx=mx.tpu())
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        x = nd.random.uniform(shape=(8, 10), ctx=mx.tpu())
        y = nd.zeros((8,), ctx=mx.tpu())
        with autograd.record():
            out = net(x)
            loss = gluon.loss.SoftmaxCrossEntropyLoss()(out, y)
        loss.backward()
        trainer.step(8)
        assert np.isfinite(loss.asnumpy()).all()
        assert out.context.device_type in ("tpu", "gpu")
    finally:
        set_default_context(mx.cpu())


@pytest.mark.parametrize("shape,rd,copies", [
    ((2, 8192, 32, 192), 64, 1),        # kanana2_30b_a3b's q: pe first
    ((2, 4096, 16, 128), 128, 1),       # ouro_2_6b
    ((2, 8192, 4, 128), 128, 2),        # sdar_30b_a3b_chat's key, copies 2
    ((2, 4096, 2, 256), 64, 1),         # qwen3_next_80b_a3b's key
    ((2, 8192, 32, 64), 64, 1),         # lfm2_24b_a2b: two heads a window
])
def test_rotary_kernel_lowered(shape, rd, copies):
    """`RotaryEmbedding`'s Pallas pass (ops/rotary.py) lowered on the chip
    at the cells' shapes in bfloat16, forward and backward, against XLA's
    form and its `jax.vjp` on the same chip: both round the same float32
    products and sums once, so an entry may round to its neighbour (one
    rounding of the largest entry, 2^-8); the lanes past `rd` are equal."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import obs
    from incubator_mxnet_tpu.ops import registry, rotary
    op = registry.get("RotaryEmbedding")
    params = op.canonicalize_params({"rotary_dim": rd, "base": 1e6,
                                     "copies": copies})
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x, g = (jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
            for k in ks)
    before = obs.counter("ops.rotary.lowered.kernel").value

    def step(fn):
        def value_and_grad(a, c):
            y, vjp = jax.vjp(fn, a)
            return y, vjp(c)[0]
        return jax.jit(value_and_grad)(x, g)
    got = step(lambda a: op.fn(dict(params), a))
    assert obs.counter("ops.rotary.lowered.kernel").value == before + 1
    want = step(lambda a: rotary.xla_form(a, rd, 1e6, copies))
    for a, b in zip(got, want):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.abs(a - b).max() <= 2.0 ** -8 * np.abs(b).max()
        np.testing.assert_array_equal(a[..., rd:], b[..., rd:])
