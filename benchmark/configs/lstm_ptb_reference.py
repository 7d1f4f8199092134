"""Plain reference of the `lstm_ptb` configuration: the word-level Penn
Treebank language model of Zaremba, Sutskever and Vinyals 2014
(arXiv:1409.2329), "large" widths -- embedding, two LSTM layers, a softmax
over the vocabulary -- written from the paper's equations in straightforward
`jax.numpy`, float32 at `Precision.HIGHEST`:

    i, f, o = sigm(.), g = tanh(.)  of  W_x x_t + b_x + W_h h_{t-1} + b_h
    c_t = f * c_{t-1} + i * g,      h_t = o * tanh(c_t)

It imports nothing of the program under test and takes nothing the program
has made.  Departures from the paper (listed as `assumed` in lstm_ptb.json):
no dropout, zero initial state for every batch, untied output layer, two bias
vectors per layer (as cuDNN and the program pack them; they receive the same
gradient), gates in the order i, f, g, o.
"""
import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def init_params(key, cfg):
    """(params, aux): uniform(-s, s) with s = sqrt(3 / fan_in) everywhere
    (the paper's large model draws uniformly in +-0.04, which is this rule
    at a fan-in of 1,875), zero biases; no auxiliary state."""
    h, e, v = cfg["hidden_size"], cfg["embed_size"], cfg["vocab_size"]
    keys = iter(jax.random.split(key, 2 + 2 * cfg["num_layers"]))

    def uni(shape, fan_in):
        s = (3.0 / fan_in) ** 0.5
        return jax.random.uniform(next(keys), shape, jnp.float32, -s, s)

    params = {"embed.w": uni((v, e), e)}
    for layer in range(cfg["num_layers"]):
        nin = e if layer == 0 else h
        params[f"l{layer}.wx"] = uni((4 * h, nin), nin)
        params[f"l{layer}.wh"] = uni((4 * h, h), h)
        params[f"l{layer}.bx"] = jnp.zeros((4 * h,), jnp.float32)
        params[f"l{layer}.bh"] = jnp.zeros((4 * h,), jnp.float32)
    params["pred.w"] = uni((v, h), h)
    params["pred.b"] = jnp.zeros((v,), jnp.float32)
    return params, {}


def _quant(x, numerics):
    if numerics == "float8":
        q = lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
        return x + lax.stop_gradient(q - x)      # straight-through
    if numerics == "bfloat16":
        return x.astype(jnp.bfloat16)
    return x


def _dot(a, b, numerics):
    return jnp.dot(_quant(a, numerics), _quant(b, numerics), precision=HI)


def forward(params, tokens, cfg, numerics="float32"):
    """Logits of every position, (batch * steps, vocab), batch-major as the
    program's `Reshape(outputs, (-1, hidden))` lays them out."""
    b, t = tokens.shape
    h = cfg["hidden_size"]
    x = params["embed.w"][tokens]                       # (B, T, E)
    x = jnp.swapaxes(x, 0, 1)                           # (T, B, E)
    if numerics == "bfloat16":
        x = x.astype(jnp.bfloat16)
    for layer in range(cfg["num_layers"]):
        wx, wh = params[f"l{layer}.wx"], params[f"l{layer}.wh"]
        bias = (params[f"l{layer}.bx"] + params[f"l{layer}.bh"])
        xw = _dot(x, wx.T, numerics) + bias.astype(x.dtype)

        def cell(carry, xw_t, wh=wh):
            hp, cp = carry
            gates = xw_t + _dot(hp, wh.T, numerics)
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * cp + jax.nn.sigmoid(i) * jnp.tanh(g)
            hn = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (hn, c), hn

        zero = jnp.zeros((b, h), x.dtype)
        _, x = lax.scan(cell, (zero, zero), xw)
    x = jnp.swapaxes(x, 0, 1).reshape(b * t, h)
    return _dot(x, params["pred.w"].T, numerics).astype(jnp.float32) + \
        params["pred.b"]


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """Mean over all tokens of -log(softmax(logits)[label] + eps), as the
    program's cross-entropy metric reports it."""
    logits = forward(params, data.astype(jnp.int32), cfg, numerics)
    prob = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               label.reshape(-1, 1).astype(jnp.int32), axis=-1)
    return -jnp.mean(jnp.log(prob[:, 0] + cfg["metric_eps"])), aux


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of the example's SGD.  The program's SoftmaxOutput head sums
    the gradient over all batch * steps rows and the optimizer rescales by
    1 / batch, so the step follows `steps` times the gradient of the mean
    loss; weight decay falls on `*.w` leaves of embedding and head only
    (the program's rule: names ending in `_weight`).

    `rows` exists for the planted fault of the control test."""
    opt = cfg["optimizer"]
    if rows is not None:
        data, label = data[rows], label[rows]
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, aux, data, label, cfg, numerics)
    scale = float(cfg["seq_len"])
    new_p, new_m = {}, {}
    for n, w in params.items():
        wd = opt["wd"] if n in ("embed.w", "pred.w") else 0.0
        g = grads[n].astype(jnp.float32) * scale + wd * w
        new_m[n] = opt["momentum"] * mom[n] - opt["learning_rate"] * g
        new_p[n] = w + new_m[n]
    return new_p, new_m, aux, loss
