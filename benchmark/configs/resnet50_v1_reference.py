"""Plain reference of the `resnet50_v1` configuration: ResNet-50 v1 (He et al.
2015, arXiv:1512.03385, table 1, 50-layer; the gluon model-zoo variant, whose
1x1 body convolutions carry a bias) trained with softmax cross-entropy and
SGD with momentum, in straightforward `jax.numpy` and float32 at
`Precision.HIGHEST`.  It imports nothing of the program under test and takes
nothing the program has made: weights come from `init_params(seed)`.

Started from a copy of `bench.py`'s `_pure_jax_resnet50` (PR 26); departures
from that copy: explicit padding as the model zoo pads (7x7 pad 3, 3x3 pad 1,
max-pool pad 1), conv biases where the zoo has them, eps 1e-5, He-normal
weights, and each bottleneck under `jax.checkpoint` so that a float32 batch
of 256 fits one chip beside nothing else.

`numerics` selects the arithmetic: "float32" is the reference; "bfloat16" and
"float8" are the lower precisions the control runs (see harness/compare.py):
the inputs of every convolution and of the head's matrix product are rounded
to that precision, which is what a later PR that ran its convolutions in fp8
would do; BatchNorm, loss and the optimizer stay float32, and the backward
pass is straight-through.
"""
import jax
import jax.numpy as jnp
from jax import lax

STAGES = (3, 4, 6, 3)
WIDTHS = ((64, 256), (128, 512), (256, 1024), (512, 2048))
HI = lax.Precision.HIGHEST


def _conv_shapes(cfg):
    """name -> (shape, has_bias) of every convolution, in forward order."""
    out = [("stem", (64, cfg["in_channels"], 7, 7), False)]
    cin = 64
    for si, (n, (mid, cout)) in enumerate(zip(cfg["layers"], cfg["widths"])):
        for bi in range(n):
            p = f"s{si + 1}.b{bi}"
            out.append((p + ".c1", (mid, cin if bi == 0 else cout, 1, 1), True))
            out.append((p + ".c2", (mid, mid, 3, 3), False))
            out.append((p + ".c3", (cout, mid, 1, 1), True))
            if bi == 0:
                out.append((p + ".ds", (cout, cin, 1, 1), False))
        cin = cout
    return out


def init_params(key, cfg):
    """(params, aux) from a jax PRNG key: He-normal convolutions and head,
    unit gammas, zero betas and biases, running mean 0 and variance 1.
    Values are rounded to `cfg["param_dtype"]` (the type the program holds
    them in) and returned as float32."""
    params, aux = {}, {}
    store = jnp.finfo(jnp.dtype(cfg["param_dtype"]))

    def he(k, shape):
        fan_in = shape[1] * (shape[2] * shape[3] if len(shape) == 4 else 1)
        w = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
        # NOT `w.astype(store).astype(float32)`: on the TPU XLA folds that
        # pair of converts away (excess precision allowed) and hands back
        # the unrounded draw, so the program, which rounds the weights when
        # it binds them, would start from other weights than the reference
        return lax.reduce_precision(w, exponent_bits=store.nexp,
                                    mantissa_bits=store.nmant)

    convs = _conv_shapes(cfg)
    keys = jax.random.split(key, len(convs) + 1)
    for k, (name, shape, bias) in zip(keys, convs):
        params[name + ".w"] = he(k, shape)
        if bias:
            params[name + ".b"] = jnp.zeros((shape[0],), jnp.float32)
        params[name + ".g"] = jnp.ones((shape[0],), jnp.float32)
        params[name + ".beta"] = jnp.zeros((shape[0],), jnp.float32)
        aux[name + ".mean"] = jnp.zeros((shape[0],), jnp.float32)
        aux[name + ".var"] = jnp.ones((shape[0],), jnp.float32)
    params["fc.w"] = he(keys[-1], (cfg["classes"], cfg["widths"][-1][1]))
    params["fc.b"] = jnp.zeros((cfg["classes"],), jnp.float32)
    return params, aux


def _quant(x, numerics):
    """What a matmul or convolution input looks like under `numerics`."""
    if numerics == "float8":
        # e4m3: 4 exponent and 3 mantissa bits; `reduce_precision` is an op
        # of its own, which no compiler pass may fold away as it may a pair
        # of converts
        q = lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
        return x + lax.stop_gradient(q - x)      # straight-through
    if numerics == "bfloat16":
        return x.astype(jnp.bfloat16)
    return x


def _conv(x, w, stride, pad, numerics):
    x, w = _quant(x, numerics), _quant(w, numerics)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)


def _bn(x, p, aux, name, new_aux, cfg):
    xm = x.astype(jnp.float32)
    mean = xm.mean((0, 2, 3))
    var = jnp.square(xm - mean[None, :, None, None]).mean((0, 2, 3))
    m = cfg["bn_momentum"]
    new_aux[name + ".mean"] = m * aux[name + ".mean"] + (1 - m) * mean
    new_aux[name + ".var"] = m * aux[name + ".var"] + (1 - m) * var
    inv = lax.rsqrt(var + cfg["bn_eps"]) * p[name + ".g"]
    out = (xm - mean[None, :, None, None]) * inv[None, :, None, None] + \
        p[name + ".beta"][None, :, None, None]
    return out.astype(x.dtype)


def _unit(x, p, aux, name, stride, pad, new_aux, cfg, numerics, relu=True):
    h = _conv(x, p[name + ".w"], stride, pad, numerics)
    if name + ".b" in p:
        h = h + p[name + ".b"].astype(h.dtype)[None, :, None, None]
    h = _bn(h, p, aux, name, new_aux, cfg)
    return jax.nn.relu(h) if relu else h


def forward(params, aux, x, cfg, numerics="float32"):
    """Logits (float32) and the new running statistics, training mode."""
    new_aux = {}
    if numerics == "bfloat16":
        x = x.astype(jnp.bfloat16)
    h = _unit(x, params, aux, "stem", 2, 3, new_aux, cfg, numerics)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for si, n in enumerate(cfg["layers"]):
        for bi in range(n):
            pre = f"s{si + 1}.b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            names = [k for k in params if k.startswith(pre + ".")]
            auxn = [k for k in aux if k.startswith(pre + ".")]

            def block(h, bp, ba, pre=pre, stride=stride, first=(bi == 0)):
                na = {}
                o = _unit(h, bp, ba, pre + ".c1", stride, 0, na, cfg, numerics)
                o = _unit(o, bp, ba, pre + ".c2", 1, 1, na, cfg, numerics)
                o = _unit(o, bp, ba, pre + ".c3", 1, 0, na, cfg, numerics,
                          relu=False)
                idn = _unit(h, bp, ba, pre + ".ds", stride, 0, na, cfg,
                            numerics, relu=False) if first else h
                return jax.nn.relu(o + idn), na

            h, na = jax.checkpoint(block)(
                h, {k: params[k] for k in names}, {k: aux[k] for k in auxn})
            new_aux.update(na)
    h = h.astype(jnp.float32).mean((2, 3))
    w = params["fc.w"]
    logits = jnp.dot(_quant(h, numerics), _quant(w, numerics).T,
                     precision=HI).astype(jnp.float32) + params["fc.b"]
    return logits, new_aux


def outputs(params, aux, data, cfg, numerics="float32"):
    """What the program's SoftmaxOutput head hands out for one batch in
    training mode: the class probabilities of every row, (batch, classes)."""
    return jax.nn.softmax(forward(params, aux, data, cfg, numerics)[0], -1)


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """Mean over the rows of -log(softmax(logits)[label] + eps): what the
    program's cross-entropy metric reports and what its SoftmaxOutput
    head differentiates (batch-summed, rescaled by 1/batch)."""
    logits, new_aux = forward(params, aux, data, cfg, numerics)
    prob = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               label[:, None].astype(jnp.int32), axis=-1)
    return -jnp.mean(jnp.log(prob[:, 0] + cfg["metric_eps"])), new_aux


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of SGD with momentum as the program's optimizer applies it:
    mom = momentum * mom - lr * (grad + wd * w); w += mom.

    `rows` exists for the planted fault of the control test (a batch of
    which only `rows` are used)."""
    opt = cfg["optimizer"]
    if rows is not None:
        data, label = data[rows], label[rows]
    (loss, new_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, aux, data, label, cfg, numerics)
    new_p, new_m = {}, {}
    for n, w in params.items():
        g = grads[n].astype(jnp.float32) + opt["wd"] * w
        new_m[n] = opt["momentum"] * mom[n] - opt["learning_rate"] * g
        new_p[n] = w + new_m[n]
    return new_p, new_m, new_aux, loss
