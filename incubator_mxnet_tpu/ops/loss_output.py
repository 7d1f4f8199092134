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
import numpy as np

from . import loop_exit
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


def exit_distribution(gate):
    """(log p, p) of the exit distribution over T recurrent steps, from the
    exit gate's logits `gate` (T, rows): lambda_t = sigmoid(gate_t), p_t =
    lambda_t prod_{j<t} (1 - lambda_j) for t < T and p_T = prod_{j<T}
    (1 - lambda_j) (LoopLM, arXiv:2510.25741); the last step's gate is not
    read.  In log space, so that no product of small numbers underflows."""
    survive = jnp.cumsum(jax.nn.log_sigmoid(-gate[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(gate[:1]), survive])
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(gate[:-1]) + before[:-1], before[-1:]])
    return log_p, jnp.exp(log_p)


def exit_objective(gate, losses, beta):
    """Per row: sum_t p_t L_t - beta H(p), H(p) = -sum_t p_t log p_t, the
    entropy-regularised exit objective of LoopLM's stage I; `gate` and
    `losses` (T, rows)."""
    log_p, p = exit_distribution(gate)
    return jnp.sum(p * losses, axis=0) + beta * jnp.sum(p * log_p, axis=0)


def _exit_counters(deltas):
    """An epoch's exit distribution, from what every `LoopExitOutput` node
    of a graph has added to `exit_stats` (`OpDef.counters`): rows, then per
    step the sum of p_t, the sum of the expected step count and of H(p)."""
    total = sum(np.asarray(d["exit_stats"], np.float64) for d in deltas)
    rows = float(total[0])
    mean = total[1:] / rows if rows else 0.0 * total[1:]
    args = {"rows": int(rows), "steps": len(total) - 3,
            "p": [float(v) for v in mean[:-2]],
            "expected_steps": float(mean[-2]), "entropy": float(mean[-1])}
    return {"span": "loop.exit", "args": args,
            "counters": {"loop.exit.rows": args["rows"]},
            "gauges": {"loop.exit.expected_steps": args["expected_steps"],
                       "loop.exit.entropy": args["entropy"]}}


def _exit_logits(h, weight):
    return jnp.einsum("nc,vc->nv", h, weight,
                      preferred_element_type=jnp.float32)


@register("LoopExitOutput", nin=6, naux=1, mode_dependent=True,
          params={"beta": 0.1},
          input_names=["data", "head_weight", "gate_weight", "gate_bias",
                       "label", "exit_stats"],
          counters=_exit_counters)
def _loop_exit_output(params, states, weight, gate_w, gate_b, label, stats):
    """The head, the exit gate and the entropy-regularised exit objective of
    a looped LM (LoopLM, arXiv:2510.25741, stage I), as one loss output.

    `data` (T, rows, C): the normed state after each of T recurrent steps;
    `head_weight` (V, C), `gate_weight` (1, C), `gate_bias` (1,), `label`
    (rows,).  Step t's logits z_t = W h_t, its loss L_t = -log
    softmax(z_t)[label], its exit gate lambda_t = sigmoid(w_g h_t + b_g);
    the exit distribution p (`exit_distribution`).  Forward: the LAST
    step's probabilities, in the input's type (what a model run to its last
    step serves).  Backward, as `SoftmaxOutput`'s, ignores the incoming
    gradient: the gradient of sum over rows of sum_t p_t L_t - beta H(p)
    with respect to every input but the label.  The forward pass keeps each
    step's loss, the backward pass computes each step's logits again.  In
    training on a TPU, where `loop_exit.driver` holds the shapes, Pallas
    kernels walk the vocabulary in tiles and no float32 logits reach HBM
    (ops/loop_exit.py); elsewhere XLA walks the steps one at a time, so no
    two steps' logits, and no (T, rows, V) value, exist at once.  Which a
    traced call took is counted: `ops.loop_exit.lowered.kernel` / `.xla`.
    The auxiliary state `exit_stats` (T + 3,) is added to in every training
    step: rows, the sum of p_t per step, of the expected step count sum_t t
    p_t and of H(p)."""
    steps, rows, c = states.shape
    if weight.ndim != 2 or weight.shape[1] != c or \
            gate_w.shape != (1, c) or gate_b.shape != (1,) or \
            label.size != rows or stats.shape != (steps + 3,):
        raise MXNetError(
            "LoopExitOutput: data (T, rows, C), head_weight (V, C), "
            "gate_weight (1, C), gate_bias (1,), label of rows entries and "
            "exit_stats (T + 3,); got %s, %s, %s, %s, %s and %s" % tuple(
                tuple(a.shape) for a in (states, weight, gate_w, gate_b,
                                         label, stats)))
    beta = float(params["beta"])
    in_dtype = states.dtype
    label = label.reshape(rows).astype(jnp.int32)
    # out of training only `f`'s primal runs: the last step's softmax, in
    # XLA's form
    driver, tiles = loop_exit.driver(
        rows, c, weight.shape[0], in_dtype.itemsize) \
        if params.get("_train", False) else ("xla", None)
    loop_exit.count(driver)
    interpret = driver == "interpret"

    def gates(h, gw, gb):
        return jnp.einsum("trc,c->tr", h, gw[0],
                          preferred_element_type=jnp.float32) + \
            gb[0].astype(jnp.float32)

    def step_loss(h, w, y):
        z = _exit_logits(h, w)
        return jax.nn.logsumexp(z, axis=-1) - \
            jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0], z

    def forward(h, w, y):
        """(the last step's probabilities, every step's loss (T, rows))."""
        early = jax.lax.map(lambda ht: step_loss(ht, w, y)[0], h[:-1])
        last, z = step_loss(h[-1], w, y)
        probs = jax.nn.softmax(z, axis=-1).astype(in_dtype)
        return probs, jnp.concatenate([early, last[None]])

    @jax.custom_vjp
    def f(h, w, gw, gb, y):
        return jax.nn.softmax(_exit_logits(h[-1], w), axis=-1) \
            .astype(in_dtype)

    def fwd(h, w, gw, gb, y):
        if driver == "xla":
            probs, losses = forward(h, w, y)
            return probs, (h, w, gw, gb, y, losses, None)
        probs, losses, lse = loop_exit.forward(
            h, w, y, rows=tiles.rows, vocab=tiles.vocab, interpret=interpret)
        return probs, (h, w, gw, gb, y, losses, lse)

    def bwd(res, g):
        h, w, gw, gb, y, losses, lse = res
        a = gates(h, gw, gb)
        d_gate, d_loss = jax.grad(
            lambda a_, l_: jnp.sum(exit_objective(a_, l_, beta)),
            argnums=(0, 1))(a, losses)
        dgw = jnp.einsum("tr,trc->c", d_gate, h,
                         preferred_element_type=jnp.float32)[None]
        if driver == "xla":
            dh, dw = xla_grads(h, w, gw, y, d_loss, d_gate)
        else:
            dh, dw = loop_exit.backward(
                h, w, y, lse, d_loss, d_gate, gw, rows=tiles.grad_rows,
                vocab=tiles.vocab, superblock=tiles.superblock,
                interpret=interpret)
        return (dh, dw.astype(w.dtype), dgw.astype(gw.dtype),
                jnp.sum(d_gate).reshape(1).astype(gb.dtype),
                np.zeros(y.shape, jax.dtypes.float0))

    def xla_grads(h, w, gw, y, d_loss, d_gate):
        """(dh in the input's type, dw float32): a scan over the steps, each
        step's logits made again in float32."""

        def one(dw, xs):
            ht, dl = xs
            z = _exit_logits(ht, w)
            dz = (jax.nn.softmax(z, axis=-1) -
                  jax.nn.one_hot(y, z.shape[-1], dtype=z.dtype)) * \
                dl[:, None]
            dz = dz.astype(in_dtype)
            return dw + jnp.einsum("nv,nc->vc", dz, ht,
                                   preferred_element_type=jnp.float32), \
                jnp.einsum("nv,vc->nc", dz, w,
                           preferred_element_type=jnp.float32)
        dw, dh = jax.lax.scan(one, jnp.zeros(w.shape, jnp.float32),
                              (h, d_loss))
        dh = dh + d_gate[..., None] * gw[0].astype(jnp.float32)
        return dh.astype(in_dtype), dw

    f.defvjp(fwd, bwd)
    out = f(states, weight, gate_w, gate_b, label)
    if not params.get("_train", False):
        return out
    # the exit distribution of this step, counted (no gradient flows here)
    log_p, p = exit_distribution(jax.lax.stop_gradient(
        gates(states, gate_w, gate_b)))
    t = jnp.arange(1, steps + 1, dtype=jnp.float32)[:, None]
    return out, stats + jnp.concatenate([
        jnp.full((1,), rows, jnp.float32), jnp.sum(p, axis=1),
        jnp.sum(t * p).reshape(1),
        -jnp.sum(p * log_p).reshape(1)]).astype(stats.dtype)
