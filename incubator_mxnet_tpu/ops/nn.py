"""Core neural-network operators.

Reference: `src/operator/nn/` (fully_connected.cc, convolution.cc,
deconvolution.cc, batch_norm.cc, layer_norm.cc, pooling.cc, softmax.cc,
activation.cc, dropout.cc, lrn.cc, upsampling.cc) and legacy top-level ops
(`leaky_relu.cc`, `instance_norm.cc`, `l2_normalization.cc`, `rnn.cc`).

TPU mapping: FullyConnected/Convolution lower to single MXU matmul/conv HLOs;
BatchNorm & friends are elementwise chains XLA fuses around them; the fused
RNN op (reference cudnn_rnn-inl.h) is a `lax.scan` over time steps whose body
is one fused XLA computation — the TPU-native analogue of cuDNN's fused
multi-layer kernel.  All data layouts follow the reference (NCHW / TNC); XLA's
layout assignment maps them onto TPU-friendly tilings internally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, REQUIRED
from ..base import MXNetError


# ---------------------------------------------------------------------------
# FullyConnected (reference src/operator/nn/fully_connected.cc:239-328)
# ---------------------------------------------------------------------------

@register("FullyConnected", nin=-1,
          params={"num_hidden": REQUIRED, "no_bias": False, "flatten": True},
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]))
def _fully_connected(params, x, weight, *rest):
    weight = weight.astype(x.dtype)  # mixed-precision: params may be fp32
    if params["flatten"]:
        x2 = x.reshape(x.shape[0], -1)
        out = jnp.dot(x2, weight.T)
    else:
        out = jnp.dot(x, weight.T)
    if not params["no_bias"]:
        bias = rest[0].astype(out.dtype)
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (reference convolution.cc, deconvolution.cc)
# ---------------------------------------------------------------------------

def _conv_dims(kernel):
    nd = len(kernel)
    if nd == 1:
        return ("NCH", "OIH", "NCH")
    if nd == 2:
        return ("NCHW", "OIHW", "NCHW")
    if nd == 3:
        return ("NCDHW", "OIDHW", "NCDHW")
    raise MXNetError("Convolution supports 1D/2D/3D kernels")


def _tup(v, n, default):
    if not v:
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


_CONV_PARAMS = {
    "kernel": REQUIRED, "stride": (), "dilate": (), "pad": (),
    "num_filter": REQUIRED, "num_group": 1, "no_bias": False,
    "workspace": 1024, "cudnn_tune": None, "cudnn_off": False, "layout": None,
}


@register("Convolution", nin=-1, params=dict(_CONV_PARAMS),
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]))
def _convolution(params, x, weight, *rest):
    kernel = tuple(params["kernel"])
    nd = len(kernel)
    stride = _tup(params["stride"], nd, 1)
    dilate = _tup(params["dilate"], nd, 1)
    pad = _tup(params["pad"], nd, 0)
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape, _conv_dims(kernel))
    out = jax.lax.conv_general_dilated(
        x, weight.astype(x.dtype), window_strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * nd, rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(params["num_group"]),
        preferred_element_type=None)
    if not params["no_bias"]:
        bias = rest[0].astype(out.dtype)
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


_DECONV_PARAMS = dict(_CONV_PARAMS)
_DECONV_PARAMS.update({"adj": (), "target_shape": ()})


@register("Deconvolution", nin=-1, params=_DECONV_PARAMS,
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]))
def _deconvolution(params, x, weight, *rest):
    """Transposed convolution = gradient of Convolution w.r.t. its input
    (reference deconvolution-inl.h).  weight layout: (Cin, Cout/g, *kernel)."""
    kernel = tuple(params["kernel"])
    nd = len(kernel)
    stride = _tup(params["stride"], nd, 1)
    dilate = _tup(params["dilate"], nd, 1)
    pad = _tup(params["pad"], nd, 0)
    adj = _tup(params["adj"], nd, 0)
    groups = int(params["num_group"])
    if params["target_shape"]:
        tgt = _tup(params["target_shape"], nd, 0)
        adj = tuple(
            tgt[i] - ((x.shape[2 + i] - 1) * stride[i] + (
                (kernel[i] - 1) * dilate[i] + 1) - 2 * pad[i])
            for i in range(nd))
    # flip kernel spatially; swap I/O axes per group
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    cin, cog = w.shape[0], w.shape[1]
    w = w.reshape((groups, cin // groups, cog) + kernel)
    w = jnp.swapaxes(w, 1, 2)  # (g, cog, cin/g, *k)
    w = w.reshape((groups * cog, cin // groups) + kernel)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims(kernel))
    eff_k = tuple((kernel[i] - 1) * dilate[i] + 1 for i in range(nd))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd,
        padding=[(eff_k[i] - 1 - pad[i], eff_k[i] - 1 - pad[i] + adj[i])
                 for i in range(nd)],
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=groups)
    if not params["no_bias"]:
        out = out + rest[0].reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (reference pooling.cc + pool.h)
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("Pooling_v1",),
          params={"kernel": (), "pool_type": "max", "global_pool": False,
                  "cudnn_off": False, "pooling_convention": "valid",
                  "stride": (), "pad": (), "count_include_pad": True})
def _pooling(params, x):
    nd = x.ndim - 2
    if params["global_pool"]:
        axes = tuple(range(2, 2 + nd))
        if params["pool_type"] == "max":
            out = jnp.max(x, axis=axes, keepdims=True)
        elif params["pool_type"] in ("avg", "sum"):
            red = jnp.sum if params["pool_type"] == "sum" else jnp.mean
            out = red(x, axis=axes, keepdims=True)
        else:
            raise MXNetError("bad pool_type")
        return out
    kernel = _tup(params["kernel"], nd, 1)
    stride = _tup(params["stride"], nd, 1)
    pad = _tup(params["pad"], nd, 0)
    ceil_mode = params["pooling_convention"] == "full"

    pads = []
    for i in range(nd):
        lo = pad[i]
        hi = pad[i]
        if ceil_mode:
            size = x.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            if rem != 0:
                hi += stride[i] - rem
        pads.append((lo, hi))

    window = (1, 1) + kernel
    strides = (1, 1) + stride
    full_pads = [(0, 0), (0, 0)] + pads
    ptype = params["pool_type"]
    # NOTE: init values must be python/np scalars so jax recognizes the
    # max/add monoids and uses the differentiable reduce_window primitives
    if ptype == "max":
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = np.array(-np.inf, x.dtype)[()]
        else:
            init = np.array(np.iinfo(np.dtype(x.dtype)).min, x.dtype)[()]
        return jax.lax.reduce_window(x, init, jax.lax.max,
                                     window, strides, full_pads)
    if ptype in ("avg", "sum"):
        s = jax.lax.reduce_window(x, np.zeros((), x.dtype)[()], jax.lax.add,
                                  window, strides, full_pads)
        if ptype == "sum":
            return s
        if params["count_include_pad"]:
            denom = 1
            for k in kernel:
                denom *= k
            return s / jnp.asarray(denom, x.dtype)
        ones = jnp.ones_like(x)
        cnt = jax.lax.reduce_window(ones, jnp.asarray(0, x.dtype), jax.lax.add,
                                    window, strides, full_pads)
        return s / jnp.maximum(cnt, 1)
    raise MXNetError(f"Pooling: bad pool_type {ptype}")


# ---------------------------------------------------------------------------
# Normalization ops
# ---------------------------------------------------------------------------

def _bn_nout(params):
    return 3 if params.get("output_mean_var") else 1


def _bn_axis_bound(name):
    """True when the named mesh axis is bound in the current trace (a
    `shard_map`/pmap region): probing with a zero-size psum either
    traces fine or raises NameError — never dispatches real work."""
    try:
        jax.lax.psum(jnp.zeros(()), name)
        return True
    except NameError:
        return False


@register("BatchNorm", nin=3, naux=2, nout=_bn_nout, mode_dependent=True,
          params={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                  "use_global_stats": False, "output_mean_var": False,
                  "axis": 1, "cudnn_off": False, "sync": False,
                  "sync_axis": "dp"},
          aliases=("BatchNorm_v1",),
          input_names=["data", "gamma", "beta", "moving_mean", "moving_var"])
def _batch_norm(params, x, gamma, beta, moving_mean, moving_var):
    """Reference `src/operator/nn/batch_norm.cc`.  Aux states
    (moving_mean/var) are inputs 4-5 and returned as updates in train mode.

    ``sync=True`` asks for GLOBAL-batch statistics (the reference's
    `sync_batch_norm-inl.h` distributed BatchNorm, per the MLPerf-pods
    recipe): inside an explicit SPMD region (`shard_map` over a mesh
    with the ``sync_axis`` axis bound — `parallel.data_parallel_step`,
    `zero_train_step`) the moments psum over that axis.  Inside the
    fused train step the whole program is GLOBAL-view (the batch is
    merely sharded over dp), so the plain reductions already ARE
    global-batch statistics and ``sync`` adds nothing — sync-BN is the
    fused path's default semantics."""
    axis = int(params["axis"]) % x.ndim
    eps = float(params["eps"])
    momentum = float(params["momentum"])
    train = params.get("_train", False) and not params["use_global_stats"]
    sync = bool(params.get("sync", False))
    sync_axis = str(params.get("sync_axis", "dp"))

    if params["fix_gamma"]:
        gamma = jnp.ones_like(gamma)

    red_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]

    # statistics in float32 even for low-precision activations (matches the
    # reference's cuDNN path which accumulates in fp32)
    xs = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    if train:
        mean = jnp.mean(xs, axis=red_axes)
        if sync and _bn_axis_bound(sync_axis):
            # distributed BN: psum of moments over the dp axis — with
            # equal per-device batches, pmean of local moments around
            # the GLOBAL mean is exactly the big-batch statistics
            mean = jax.lax.pmean(mean, sync_axis)
            var = jnp.mean(jnp.square(xs - mean.reshape(bshape)),
                           axis=red_axes)
            var = jax.lax.pmean(var, sync_axis)
        else:
            var = jnp.mean(jnp.square(xs - mean.reshape(bshape)),
                           axis=red_axes)
    else:
        mean, var = moving_mean, moving_var

    inv = jax.lax.rsqrt(var + eps).reshape(bshape)
    out = (xs - mean.reshape(bshape)) * inv * gamma.reshape(bshape) \
        + beta.reshape(bshape)
    out = out.astype(x.dtype)

    outs = (out,)
    if params["output_mean_var"]:
        outs = (out, mean, jax.lax.rsqrt(var + eps))
    if params.get("_train", False):
        new_mean = moving_mean * momentum + mean * (1 - momentum)
        new_var = moving_var * momentum + var * (1 - momentum)
        return outs + (new_mean, new_var)
    return outs if len(outs) > 1 else out


def _ln_nout(params):
    return 3 if params.get("output_mean_var") else 1


@register("LayerNorm", nin=3, nout=_ln_nout,
          params={"axis": -1, "eps": 1e-5, "output_mean_var": False},
          input_names=["data", "gamma", "beta"])
def _layer_norm(params, x, gamma, beta):
    """Reference `src/operator/nn/layer_norm.cc`."""
    axis = int(params["axis"]) % x.ndim
    eps = float(params["eps"])
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axis, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    out = (x - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    if params["output_mean_var"]:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(inv, axis)
    return out


@register("InstanceNorm", nin=3, params={"eps": 1e-3},
          input_names=["data", "gamma", "beta"])
def _instance_norm(params, x, gamma, beta):
    """Reference `src/operator/instance_norm.cc`: normalize over spatial dims
    per (n, c)."""
    eps = float(params["eps"])
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(bshape) \
        + beta.reshape(bshape)


@register("L2Normalization", params={"eps": 1e-10, "mode": "instance"})
def _l2_normalization(params, x):
    """Reference `src/operator/l2_normalization.cc`."""
    eps = float(params["eps"])
    mode = params["mode"]
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    elif mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
    elif mode == "spatial":
        axes = tuple(range(2, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    else:
        raise MXNetError("bad L2Normalization mode")
    return x / norm


@register("LRN", params={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": REQUIRED})
def _lrn(params, x):
    """Local response norm across channels (reference `src/operator/nn/lrn.cc`)."""
    n = int(params["nsize"])
    alpha, beta, k = float(params["alpha"]), float(params["beta"]), float(params["knorm"])
    sq = jnp.square(x)
    half = n // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2)
    sq_p = jnp.pad(sq, pad)
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + jax.lax.dynamic_slice_in_dim(sq_p, i, x.shape[1], axis=1)
    return x * jnp.power(k + (alpha / n) * acc, -beta)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation", params={"act_type": REQUIRED})
def _activation(params, x):
    t = params["act_type"]
    if t == "relu":
        return jax.nn.relu(x)
    if t == "sigmoid":
        return jax.nn.sigmoid(x)
    if t == "tanh":
        return jnp.tanh(x)
    if t == "softrelu":
        return jax.nn.softplus(x)
    if t == "softsign":
        return jax.nn.soft_sign(x)
    if t == "silu":
        return jax.nn.silu(x)
    raise MXNetError(f"Activation: unknown act_type {t}")


@register("LeakyReLU", nin=-1,
          params={"act_type": "leaky", "slope": 0.25, "lower_bound": 0.125,
                  "upper_bound": 0.334},
          input_names=lambda p: ["data"] + (["gamma"] if p.get("act_type") == "prelu" else []))
def _leaky_relu(params, x, *rest):
    """Reference `src/operator/leaky_relu.cc` (leaky/prelu/elu/selu/gelu/rrelu)."""
    t = params["act_type"]
    if t == "leaky":
        return jnp.where(x > 0, x, x * params["slope"])
    if t == "prelu":
        gamma = rest[0]
        bshape = [1] * x.ndim
        if gamma.ndim == 1 and x.ndim > 1:
            bshape[1] = gamma.shape[0] if gamma.shape[0] > 1 else 1
            gamma = gamma.reshape(bshape)
        return jnp.where(x > 0, x, x * gamma)
    if t == "elu":
        return jnp.where(x > 0, x, params["slope"] * jnp.expm1(x))
    if t == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if t == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if t == "rrelu":
        # inference behavior (mean slope); train-time random slope documented
        slope = (params["lower_bound"] + params["upper_bound"]) / 2
        return jnp.where(x > 0, x, x * slope)
    raise MXNetError(f"LeakyReLU: unknown act_type {t}")


@register("softmax", params={"axis": -1, "temperature": None, "dtype": None})
def _softmax(params, x):
    t = params["temperature"]
    if t:
        x = x / t
    out = jax.nn.softmax(x, axis=int(params["axis"]))
    if params["dtype"]:
        out = out.astype(params["dtype"])
    return out


@register("log_softmax", params={"axis": -1, "temperature": None, "dtype": None})
def _log_softmax(params, x):
    t = params["temperature"]
    if t:
        x = x / t
    out = jax.nn.log_softmax(x, axis=int(params["axis"]))
    if params["dtype"]:
        out = out.astype(params["dtype"])
    return out


@register("softmin", params={"axis": -1, "temperature": None, "dtype": None})
def _softmin(params, x):
    t = params["temperature"]
    if t:
        x = x / t
    return jax.nn.softmax(-x, axis=int(params["axis"]))


@register("SoftmaxActivation", params={"mode": "instance"})
def _softmax_activation(params, x):
    if params["mode"] == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


@register("Dropout", needs_rng=True, mode_dependent=True,
          params={"p": 0.5, "mode": "training", "axes": ()})
def _dropout(params, x, key):
    """Reference `src/operator/nn/dropout.cc`: inverted dropout."""
    p = float(params["p"])
    train = params.get("_train", False) or params["mode"] == "always"
    if not train or p <= 0:
        return x + 0
    axes = params["axes"]
    shape = list(x.shape)
    if axes:
        for i in range(len(shape)):
            if i not in axes:
                shape[i] = 1
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# Fused RNN (reference src/operator/rnn.cc + cudnn_rnn-inl.h): multi-layer,
# optionally bidirectional vanilla/LSTM/GRU over (T, B, I) inputs with
# cuDNN-compatible flat parameter packing.  TPU-native: lax.scan time loop.
# ---------------------------------------------------------------------------

def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    """Total flat parameter count (matches cudnn packing; reference rnn-inl.h
    GetParamSize)."""
    g = _gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        size += d * g * state_size * (in_sz + state_size)  # Wx + Wh
    size += num_layers * d * g * state_size * 2  # bx + bh
    return size


def _unpack_rnn_params(flat, mode, input_size, state_size, num_layers, bidir):
    """Slice the flat cuDNN-layout parameter vector into per-layer weights.

    Layout (reference cudnn GetParams / rnn_impl.h): all weight matrices
    (layer-major, direction-minor, Wx then Wh), then all biases (same order,
    bx then bh)."""
    g = _gates(mode)
    d = 2 if bidir else 1
    ws = []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        dirs = []
        for _dir in range(d):
            wx = flat[off: off + g * state_size * in_sz].reshape(g * state_size, in_sz)
            off += g * state_size * in_sz
            wh = flat[off: off + g * state_size * state_size].reshape(g * state_size, state_size)
            off += g * state_size * state_size
            dirs.append([wx, wh])
        ws.append(dirs)
    bs = []
    for layer in range(num_layers):
        dirs = []
        for _dir in range(d):
            bx = flat[off: off + g * state_size]; off += g * state_size
            bh = flat[off: off + g * state_size]; off += g * state_size
            dirs.append([bx, bh])
        bs.append(dirs)
    return ws, bs


def _cell_step(mode, state_size):
    if mode == "lstm":
        def step(carry, xw, wh, bh):
            h, c = carry
            gates = xw + jnp.dot(h, wh.T) + bh
            i, f, gg, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            gg = jnp.tanh(gg)
            c2 = f * c + i * gg
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
    elif mode == "gru":
        def step(carry, xw, wh, bh):
            (h,) = carry
            xr, xz, xn = jnp.split(xw, 3, axis=-1)
            hr, hz, hn = jnp.split(jnp.dot(h, wh.T) + bh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
    else:
        act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
        def step(carry, xw, wh, bh):
            (h,) = carry
            h2 = act(xw + jnp.dot(h, wh.T) + bh)
            return (h2,), h2
    return step


def _rnn_nout(params):
    if not params.get("state_outputs"):
        return 1
    return 3 if params.get("mode") == "lstm" else 2


@register("RNN", nin=-1, nout=_rnn_nout, mode_dependent=True, needs_rng=True,
          input_names=lambda p: ["data", "parameters", "state"] + (
              ["state_cell"] if p.get("mode") == "lstm" else []),
          params={"state_size": REQUIRED, "num_layers": REQUIRED,
                  "bidirectional": False, "mode": REQUIRED, "p": 0.0,
                  "state_outputs": False, "projection_size": None,
                  "lstm_state_clip_min": None, "lstm_state_clip_max": None,
                  "lstm_state_clip_nan": False})
def _rnn(params, *args):
    """Fused multi-layer RNN.  Inputs: data (T,B,I), params (flat), state
    (L*D,B,H) [, state_cell for lstm]; trailing key from the RNG chain."""
    mode = params["mode"]
    key = args[-1]
    args = args[:-1]
    data, flat, state0 = args[0], args[1], args[2]
    cell0 = args[3] if mode == "lstm" and len(args) > 3 else None
    L = int(params["num_layers"])
    H = int(params["state_size"])
    bidir = bool(params["bidirectional"])
    d = 2 if bidir else 1
    T, B, I = data.shape
    dropout_p = float(params["p"])
    train = params.get("_train", False)

    ws, bs = _unpack_rnn_params(flat, mode, I, H, L, bidir)
    step = _cell_step(mode, H)

    x = data
    h_states, c_states = [], []
    for layer in range(L):
        outs = []
        for dr in range(d):
            wx, wh = ws[layer][dr]
            bx, bh = bs[layer][dr]
            h0 = state0[layer * d + dr]
            carry = (h0, cell0[layer * d + dr]) if mode == "lstm" else (h0,)
            xseq = x if dr == 0 else jnp.flip(x, axis=0)
            xw = jnp.dot(xseq, wx.T) + bx  # (T, B, g*H): one big MXU matmul

            def body(c, xw_t, _wh=wh, _bh=bh):
                return step(c, xw_t, _wh, _bh)

            carry_f, seq = jax.lax.scan(body, carry, xw)
            if dr == 1:
                seq = jnp.flip(seq, axis=0)
            outs.append(seq)
            h_states.append(carry_f[0])
            if mode == "lstm":
                c_states.append(carry_f[1])
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        if train and dropout_p > 0 and layer < L - 1:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1 - dropout_p, x.shape)
            x = jnp.where(keep, x / (1 - dropout_p), 0.0)

    outputs = (x,)
    if params["state_outputs"]:
        hN = jnp.stack(h_states, axis=0)
        if mode == "lstm":
            cN = jnp.stack(c_states, axis=0)
            outputs = (x, hN, cN)
        else:
            outputs = (x, hN)
    return outputs if len(outputs) > 1 else x


# ---------------------------------------------------------------------------
# UpSampling (reference upsampling.cc)
# ---------------------------------------------------------------------------

@register("UpSampling", nin=-1, variadic_param="num_args",
          params={"scale": REQUIRED, "num_filter": 0, "sample_type": REQUIRED,
                  "multi_input_mode": "concat", "num_args": 1, "workspace": 512})
def _upsampling(params, *xs):
    scale = int(params["scale"])
    stype = params["sample_type"]
    outs = []
    for x in xs:
        if stype == "nearest":
            out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        elif stype == "bilinear":
            n, c, h, w = x.shape
            out = jax.image.resize(x, (n, c, h * scale, w * scale), "bilinear")
        else:
            raise MXNetError("UpSampling: bad sample_type")
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if params["multi_input_mode"] == "sum":
        o = outs[0]
        for t in outs[1:]:
            o = o + t
        return o
    return jnp.concatenate(outs, axis=1)
