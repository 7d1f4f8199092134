"""Loss-head output ops with implicit gradients.

Reference: `src/operator/softmax_output.cc` (SoftmaxOutput — the classic
classification head whose *backward ignores the incoming gradient* and emits
softmax-minus-onehot), `regression_output.cc` (Linear/Logistic/MAE regression
outputs), `make_loss.cc`, `svm_output.cc`.  These require custom vjps — they
are the reference ops whose FGradient is NOT the autodiff of their forward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register
from ..base import MXNetError

_SOFTMAX_OUT_PARAMS = {
    "grad_scale": 1.0, "ignore_label": -1.0, "multi_output": False,
    "use_ignore": False, "preserve_shape": False, "normalization": "null",
    "out_grad": False, "smooth_alpha": 0.0, "use_weight": False,
}


@register("SoftmaxOutput", nin=-1, params=dict(_SOFTMAX_OUT_PARAMS),
          aliases=("Softmax",),
          input_names=lambda p: ["data", "label"] +
          (["weight"] if p.get("use_weight") else []))
def _softmax_output(params, data, label, *weight):
    """Forward = softmax; backward = (softmax - onehot(label)) * grad_scale,
    with ignore-label masking and normalization (reference
    `softmax_output-inl.h` SoftmaxOutputBackward).  With `use_weight` a
    third input of the label's shape weighs each row's gradient, (softmax -
    onehot) * weight under the same normalizations (an objective that
    weighs its rows, such as masked diffusion's m / t); the forward pass is
    the probabilities as before, and no gradient reaches the weight."""
    weighted = bool(params.get("use_weight"))
    if len(weight) != weighted:
        raise MXNetError(
            "SoftmaxOutput: %d inputs after the label, use_weight=%s takes "
            "%d" % (len(weight), weighted, weighted))
    multi = bool(params["multi_output"])
    preserve = bool(params["preserve_shape"])
    axis = 1 if multi else -1
    gs = float(params["grad_scale"])
    ignore = float(params["ignore_label"])
    use_ignore = bool(params["use_ignore"])
    normalization = params["normalization"]
    smooth = float(params["smooth_alpha"])

    orig_shape = data.shape
    flattened = False
    if not multi and not preserve and data.ndim > 2:
        # reference default mode flattens trailing dims into one class axis:
        # data is treated as (batch, prod(rest)) (softmax_output-inl.h)
        data = data.reshape(orig_shape[0], -1)
        label = label.reshape(orig_shape[0])
        weight = tuple(w.reshape(orig_shape[0]) for w in weight)
        flattened = True

    # softmax and its (softmax - onehot) gradient run in fp32 even for
    # bf16 activations: exp/sum in 8-bit mantissa loses real accuracy and
    # costs nothing to avoid (the matmuls stay bf16 on the MXU)
    in_dtype = data.dtype

    @jax.custom_vjp
    def f(d, l, *w):
        return jax.nn.softmax(d.astype(jnp.float32), axis=axis) \
            .astype(in_dtype)

    def fwd(d, l, *w):
        out = jax.nn.softmax(d.astype(jnp.float32), axis=axis)
        return out.astype(in_dtype), (out, l) + w

    def bwd(res, g):
        out, l = res[:2]
        k = out.shape[axis]
        li = l.astype("int32")
        onehot = jax.nn.one_hot(li, k, dtype=out.dtype, axis=axis)
        if smooth > 0:
            onehot = onehot * (1 - smooth) + smooth / (k - 1) * (1 - onehot)
        grad = out - onehot
        for w in res[2:]:
            grad = grad * jnp.expand_dims(
                w.reshape(l.shape), axis if axis != -1 else l.ndim) \
                .astype(out.dtype)
        if use_ignore:
            mask = (l != ignore)
            mshape = list(l.shape)
            mask_b = jnp.expand_dims(mask, axis if axis != -1 else l.ndim)
            grad = grad * mask_b.astype(out.dtype)
        scale = gs
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            if use_ignore:
                valid = jnp.maximum(jnp.sum((l != ignore).astype(out.dtype)), 1.0)
            else:
                valid = float(l.size)
            grad = grad / valid
        grad = grad * scale
        if params["out_grad"]:
            grad = grad * g.astype(out.dtype)
        return (grad.astype(in_dtype), jnp.zeros_like(l)) + \
            tuple(jnp.zeros_like(w) for w in res[2:])

    f.defvjp(fwd, bwd)
    out = f(data, label, *weight)
    if flattened:
        out = out.reshape(orig_shape)
    return out


def _regression(link, grad_fn):
    def fn(params, data, label):
        gs = float(params["grad_scale"])

        @jax.custom_vjp
        def f(d, l):
            return link(d)

        def fwd(d, l):
            out = link(d)
            return out, (out, l)

        def bwd(res, g):
            out, l = res
            # reference scales by grad_scale / num_output (regression_output-inl.h)
            num_out = max(out.size // out.shape[0], 1)
            grad = grad_fn(out, l.reshape(out.shape)) * (gs / num_out)
            return grad.astype(out.dtype), jnp.zeros_like(l)

        f.defvjp(fwd, bwd)
        return f(data, label)
    return fn


# reference regression_output-inl.h: grad = (pred - label) (linear/logistic),
# sign(pred - label) for MAE; scaled by grad_scale / num_output.
register("LinearRegressionOutput", nin=2, params={"grad_scale": 1.0},
         input_names=["data", "label"])(
    _regression(lambda d: d, lambda o, l: (o - l)))
register("LogisticRegressionOutput", nin=2, params={"grad_scale": 1.0},
         input_names=["data", "label"])(
    _regression(jax.nn.sigmoid, lambda o, l: (o - l)))
register("MAERegressionOutput", nin=2, params={"grad_scale": 1.0},
         input_names=["data", "label"])(
    _regression(lambda d: d, lambda o, l: jnp.sign(o - l)))


@register("MakeLoss", nin=1,
          params={"grad_scale": 1.0, "valid_thresh": 0.0, "normalization": "null"})
def _make_loss_op(params, data):
    """Reference `make_loss.cc`: forward identity, backward = grad_scale
    (ignores incoming gradient; optional valid normalization)."""
    gs = float(params["grad_scale"])
    normalization = params["normalization"]
    thresh = float(params["valid_thresh"])

    @jax.custom_vjp
    def f(d):
        return d

    def fwd(d):
        return d, (d,)

    def bwd(res, g):
        (d,) = res
        grad = jnp.full_like(d, gs)
        if normalization == "batch":
            grad = grad / d.shape[0]
        elif normalization == "valid":
            valid = jnp.maximum(jnp.sum((d > thresh).astype(d.dtype)), 1.0)
            grad = grad / valid
        return (grad,)

    f.defvjp(fwd, bwd)
    return f(data)


@register("SVMOutput", nin=2,
          params={"margin": 1.0, "regularization_coefficient": 1.0,
                  "use_linear": False}, input_names=["data", "label"])
def _svm_output(params, data, label):
    """Reference `svm_output.cc`: forward identity; backward hinge-loss grad."""
    margin = float(params["margin"])
    reg = float(params["regularization_coefficient"])
    linear = bool(params["use_linear"])

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        k = d.shape[1]
        onehot = jax.nn.one_hot(l.astype("int32"), k, dtype=d.dtype)
        target = 2 * onehot - 1  # +1 for true class, -1 otherwise
        viol = (margin - target * d) > 0
        if linear:
            grad = jnp.where(viol, -target * reg, 0.0)
        else:
            grad = jnp.where(viol, -2 * (margin - target * d) * target * reg, 0.0)
        return grad.astype(d.dtype), jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("IdentityAttachKLSparseReg", nin=1,
          params={"sparseness_target": 0.1, "penalty": 0.001, "momentum": 0.9})
def _identity_kl(params, data):
    return data + 0
