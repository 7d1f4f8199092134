"""Internal NHWC execution layout for spatial ops.

The reference gets layout-optimized kernels from cuDNN autotune
(`src/operator/nn/cudnn/`, `docs/faq/env_var.md:154`) and MKLDNN's opaque
blocked layouts (`src/operator/nn/mkldnn/mkldnn_base-inl.h`): the API
speaks NCHW, the kernels run whatever layout the hardware prefers, and
reorders happen at subgraph edges.  The TPU MXU strongly prefers
channels-minor (NHWC) convolutions; this module is the TPU reading of the
same idea — a graph-level rewrite used by the executor
(`symbol/symbol.py graph_eval_fn`) that:

* runs Convolution / Pooling / BatchNorm natively in NHWC,
* lets elementwise ops flow NHWC through unchanged,
* transposes back to the API's NCHW at every other consumer and at graph
  heads, so results are bit-identical module the usual float reassociation.

A same-process A/B on one v5e chip before the benchmark existed
(ResNet-50 train, batch 128, bf16; no ledger line, so a reason for the
default and not a speed statement): a hand-written NHWC control is
only ~0.5-3% faster than the NCHW control (XLA's layout assignment
already tiles NCHW convolutions onto the MXU well), and the framework
graph is ~3% SLOWER in NHWC because the per-step OIHW->HWIO weight
transposes cost more than the layout buys.  Cross-process runs differed
by more than that, which is how NHWC first looked like a big win (compare
layouts in ONE process).  The pass therefore ships DISABLED by default; the
cuDNN/MKLDNN layout-selection role is subsumed by XLA layout assignment
on TPU.

Enable with ``MXNET_INTERNAL_CONV_LAYOUT=NHWC`` (exact, bit-stable
results either way).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .nn import _tup, _batch_norm

__all__ = ["enabled", "to_nhwc", "to_nchw", "NATIVE", "AGNOSTIC",
           "layout_safe_input"]


def enabled():
    from .. import config as _config
    return str(_config.get("MXNET_INTERNAL_CONV_LAYOUT")).upper() == "NHWC"


def to_nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def to_nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


def _conv_nhwc(params, x, weight, *rest):
    """2-D Convolution on NHWC activations; weight stays OIHW at the API
    (checkpoints unchanged), transposed to HWIO inside the program (XLA
    folds the small weight transpose into its own layout assignment)."""
    stride = _tup(params["stride"], 2, 1)
    dilate = _tup(params["dilate"], 2, 1)
    pad = _tup(params["pad"], 2, 0)
    w = jnp.transpose(weight, (2, 3, 1, 0)).astype(x.dtype)  # OIHW -> HWIO
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in pad],
        lhs_dilation=(1, 1), rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=int(params["num_group"]))
    if not params["no_bias"]:
        out = out + rest[0].astype(out.dtype).reshape((1, 1, 1, -1))
    return out


def _pooling_nhwc(params, x):
    """2-D Pooling on NHWC (mirrors ops/nn.py _pooling exactly, windows on
    axes 1-2)."""
    if params["global_pool"]:
        if params["pool_type"] == "max":
            return jnp.max(x, axis=(1, 2), keepdims=True)
        red = jnp.sum if params["pool_type"] == "sum" else jnp.mean
        return red(x, axis=(1, 2), keepdims=True)
    kernel = _tup(params["kernel"], 2, 1)
    stride = _tup(params["stride"], 2, 1)
    pad = _tup(params["pad"], 2, 0)
    ceil_mode = params["pooling_convention"] == "full"
    pads = []
    for i in range(2):
        lo = hi = pad[i]
        if ceil_mode:
            size = x.shape[1 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            if rem != 0:
                hi += stride[i] - rem
        pads.append((lo, hi))
    window = (1,) + kernel + (1,)
    strides = (1,) + stride + (1,)
    full_pads = [(0, 0)] + pads + [(0, 0)]
    ptype = params["pool_type"]
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
        cnt = jax.lax.reduce_window(ones, jnp.asarray(0, x.dtype),
                                    jax.lax.add, window, strides, full_pads)
        return s / jnp.maximum(cnt, 1)


def _batch_norm_nhwc(params, x, gamma, beta, moving_mean, moving_var):
    """BatchNorm over the trailing channel axis (the op already supports an
    axis parameter; NHWC just remaps the default channel position)."""
    return _batch_norm(dict(params, axis=3), x, gamma, beta,
                       moving_mean, moving_var)


def _native_ok(opname, params, x):
    """Can this node run its NHWC variant for input `x`?"""
    if getattr(x, "ndim", 0) != 4:
        return False
    if opname == "Convolution":
        return len(tuple(params["kernel"])) == 2 and not params.get("layout")
    if opname in ("Pooling", "Pooling_v1"):
        if params["pool_type"] not in ("max", "avg", "sum"):
            return False    # NCHW fn validates and raises loudly
        return params["global_pool"] or len(_tup(params["kernel"], 2, 1)) == 2
    if opname in ("BatchNorm", "BatchNorm_v1"):
        return int(params.get("axis", 1)) == 1
    return False


# NHWC-native executors: same (params, *arrays) contract as the registered
# fn, but expecting/producing NHWC activations
NATIVE = {
    "Convolution": (_conv_nhwc, _native_ok),
    "Pooling": (_pooling_nhwc, _native_ok),
    "Pooling_v1": (_pooling_nhwc, _native_ok),
    "BatchNorm": (_batch_norm_nhwc, _native_ok),
    "BatchNorm_v1": (_batch_norm_nhwc, _native_ok),
}

# Elementwise ops through which an NHWC tag flows unchanged.  An op may
# pass only if every array input is layout-safe (see layout_safe_input):
# broadcasting a (C,) or (1,C,1,1)-shaped operand against NHWC data would
# hit the wrong axis.
AGNOSTIC = frozenset({
    "Activation", "LeakyReLU", "relu", "sigmoid", "tanh", "softsign",
    "Dropout", "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "_plus", "_sub", "_mul", "_div", "_add",
    "_plus_scalar", "_minus_scalar", "_mul_scalar", "_div_scalar",
    "_rminus_scalar", "_rdiv_scalar", "_power_scalar",
    "clip", "abs", "exp", "log", "sqrt", "square", "negative",
    "_identity", "BlockGrad", "identity", "_copy",
})


def layout_safe_input(v, tag):
    """True when value `v` (with layout tag `tag`, 'NHWC' or None) can feed
    an AGNOSTIC op alongside NHWC operands without changing semantics."""
    nd = getattr(v, "ndim", None)
    if nd is None:
        return True          # python scalar
    if nd == 0:
        return True
    if nd == 4:
        return tag == "NHWC"
    # non-4d arrays broadcast against trailing axes — only all-singleton
    # shapes are layout-neutral
    return all(d == 1 for d in getattr(v, "shape", ()))
