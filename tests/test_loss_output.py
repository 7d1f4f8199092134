"""`SoftmaxOutput`'s optional per-row weight (`use_weight`): the backward
pass is (p - onehot) * weight under the normalizations the head has, the
forward pass the probabilities as before, and without the weight the head
is what it was, to the bit.  float32 on the CPU: the written backward and
`jax.grad` of the weighted loss differ by roundings (1e-6 of the largest
entry)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import registry

N, K = 12, 7


def _head(**params):
    op = registry.get("SoftmaxOutput")
    params = op.canonicalize_params(params)
    return lambda *xs: op.fn(dict(params), *xs)


def _inputs(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    data = jax.random.normal(keys[0], (N, K))
    label = jax.random.randint(keys[1], (N,), 0, K).astype(jnp.float32)
    # zeros among them, as a masked objective's rows
    weight = jax.random.uniform(keys[2], (N,), minval=-1.0, maxval=3.0)
    return data, label, jnp.maximum(weight, 0.0) * 4.0


def _sent_back(head, *xs):
    """What the head sends back for its data: it ignores the incoming
    gradient, so any cotangent does."""
    return jax.grad(lambda d: jnp.sum(head(d, *xs[1:])))(xs[0])


def _weighted_loss(data, label, weight, denominator=1.0, ignore=None):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                                 axis=-1)[:, 0]
    if ignore is not None:
        weight = jnp.where(label == ignore, 0.0, weight)
    return -jnp.sum(weight * picked) / denominator


@pytest.mark.parametrize("normalization,grad_scale,use_ignore", [
    ("null", 1.0, False), ("batch", 1.0, False), ("valid", 1.0, False),
    ("valid", 0.5, True), ("null", 2.0, True)])
def test_weighted_backward_is_the_weighted_losss_gradient(
        normalization, grad_scale, use_ignore):
    data, label, weight = _inputs()
    ignore = float(label[0]) if use_ignore else None
    head = _head(use_weight=True, normalization=normalization,
                 grad_scale=grad_scale, use_ignore=use_ignore,
                 ignore_label=ignore if use_ignore else -1.0)
    valid = float(jnp.sum(label != ignore)) if use_ignore else float(N)
    denominator = {"null": 1.0, "batch": float(N), "valid": valid}[
        normalization] / grad_scale
    want = jax.grad(_weighted_loss)(data, label, weight, denominator, ignore)
    got = _sent_back(head, data, label, weight)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 *
                               float(jnp.abs(want).max()))
    # the forward pass does not know the weight
    assert np.array_equal(np.asarray(head(data, label, weight)),
                          np.asarray(_head()(data, label)))
    # and no gradient reaches the weight or the label
    for arg in (1, 2):
        back = jax.grad(lambda *xs: jnp.sum(head(*xs)), argnums=arg)(
            data, label, weight)
        assert not np.any(np.asarray(back))


@pytest.mark.parametrize("normalization", ["null", "batch", "valid"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weight_absent_is_bit_identical_to_before(normalization, dtype):
    """Without `use_weight` the head sends back softmax - onehot under its
    normalization, computed as it always was; a weight of ones gives the
    same bits."""
    data, label, _ = _inputs(1)
    data = data.astype(dtype)
    plain = _head(normalization=normalization)
    out = jax.nn.softmax(data.astype(jnp.float32), axis=-1)
    want = out - jax.nn.one_hot(label.astype("int32"), K, dtype=out.dtype)
    if normalization == "batch":
        want = want / out.shape[0]
    elif normalization == "valid":
        want = want / float(label.size)
    want = (want * 1.0).astype(dtype)
    got = _sent_back(plain, data, label)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))
    ones = _sent_back(_head(normalization=normalization, use_weight=True),
                      data, label, jnp.ones((N,)))
    assert np.array_equal(np.asarray(ones.astype(jnp.float32)),
                          np.asarray(got.astype(jnp.float32)))
    # the graph of the unweighted head holds no multiply by a weight
    jaxpr = str(jax.make_jaxpr(lambda d: _sent_back(plain, d, label))(data))
    assert "expand_dims" not in jaxpr and jaxpr.count(" mul ") <= 2


def test_inputs_follow_the_parameter():
    op = registry.get("SoftmaxOutput")
    assert op.list_input_names(op.canonicalize_params({})) == \
        ["data", "label"]
    assert op.list_input_names(op.canonicalize_params(
        {"use_weight": True})) == ["data", "label", "weight"]
    data, label, weight = _inputs(2)
    with pytest.raises(mx.MXNetError, match="use_weight"):
        _head()(data, label, weight)
    with pytest.raises(mx.MXNetError, match="use_weight"):
        _head(use_weight=True)(data, label)
    # the symbol composes its missing inputs by name
    sym = mx.sym.SoftmaxOutput(mx.sym.Variable("data"), name="softmax",
                               use_weight=True)
    assert sym.list_arguments() == ["data", "softmax_label",
                                    "softmax_weight"]
    assert mx.sym.SoftmaxOutput(mx.sym.Variable("data"), name="softmax") \
        .list_arguments() == ["data", "softmax_label"]


def test_weighted_head_flattens_trailing_axes_as_the_plain_one():
    """(batch, time, classes) data in the default mode is (batch, time *
    classes): one row a sample, and one weight a sample."""
    data = jax.random.normal(jax.random.PRNGKey(3), (4, 3, 5))
    label = jnp.asarray([1.0, 7.0, 14.0, 0.0])
    weight = jnp.asarray([2.0, 0.0, 1.0, 0.5])
    got = _sent_back(_head(use_weight=True), data, label, weight)
    plain = _sent_back(_head(), data, label)
    np.testing.assert_allclose(got, plain * weight[:, None, None], rtol=1e-6)
