"""Quantization ops (reference `src/operator/quantization/` —
quantize.cc, dequantize.cc, requantize.cc, quantized_conv/fc/pooling,
calibration via min/max).

INT8 inference path: values quantized symmetric/affine into int8 with
min/max ranges carried alongside (the reference's 3-tensor convention).
Quantized compute ops dequantize-compute-requantize through XLA int8/int32
matmul where profitable; the graph rewrite lives in
`incubator_mxnet_tpu/contrib/quantization.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register, REQUIRED

# Static cost metadata (OpDef.cost_meta) for the mxcost analyzer
# (analysis/cost.py).  The honest declaration matters more than the
# numbers: every quantized compute op below runs its arithmetic in
# float32 on this design (see the _quantized_conv docstring), so each
# declares ``compute_dtype="float32"`` — which is exactly the static
# signature mxcost's dtype-flow pass flags as the int8-slower-than-fp32
# defect.  When the lowering
# moves to native XLA int8 dot/conv with fused epilogues (ROADMAP open
# item 4), these declarations change to "int8" and the findings — and
# the CI budget gate holding their count — retire with the defect.
_QUANT_ELEMWISE = {"quantized": True, "compute_dtype": "float32"}
_QUANT_COMPUTE = {"quantized": True, "compute_dtype": "float32"}


@register("_contrib_quantize", nin=3, nout=3, params={"out_type": "int8"},
          aliases=("quantize",), cost_meta=_QUANT_ELEMWISE)
def _quantize(params, data, min_range, max_range):
    """Reference quantize.cc: float -> int8 with given range."""
    q_min, q_max = -127.0, 127.0
    scale = jnp.maximum(jnp.maximum(jnp.abs(min_range), jnp.abs(max_range)),
                        1e-8)
    out = jnp.clip(jnp.round(data / scale * q_max), q_min, q_max) \
        .astype(jnp.int8)
    return out, -scale, scale


@register("_contrib_quantize_v2", nin=1, nout=3,
          params={"out_type": "int8", "min_calib_range": None,
                  "max_calib_range": None}, cost_meta=_QUANT_ELEMWISE)
def _quantize_v2(params, data):
    if params["min_calib_range"] is not None:
        mn = jnp.asarray(params["min_calib_range"], jnp.float32)
        mx = jnp.asarray(params["max_calib_range"], jnp.float32)
    else:
        mn = jnp.min(data).astype(jnp.float32)
        mx = jnp.max(data).astype(jnp.float32)
    scale = jnp.maximum(jnp.maximum(jnp.abs(mn), jnp.abs(mx)), 1e-8)
    out = jnp.clip(jnp.round(data / scale * 127.0), -127, 127).astype(jnp.int8)
    return out, -scale, scale


@register("_contrib_dequantize", nin=3, params={"out_type": "float32"},
          aliases=("dequantize",), cost_meta=_QUANT_ELEMWISE)
def _dequantize(params, data, min_range, max_range):
    """int8 carries real = q * range/127; int32 accumulators from quantized
    matmul/conv carry real = q * range/127^2 (reference dequantizes int32
    through requantize first — this op accepts both directly)."""
    scale = jnp.maximum(jnp.abs(min_range), jnp.abs(max_range))
    q_max = 127.0 if data.dtype == jnp.int8 else 127.0 * 127.0
    return data.astype(jnp.float32) * scale / q_max


@register("_contrib_requantize", nin=3, nout=3,
          params={"out_type": "int8", "min_calib_range": None,
                  "max_calib_range": None}, cost_meta=_QUANT_ELEMWISE)
def _requantize(params, data, min_range, max_range):
    """int32 accumulators -> int8 (reference requantize.cc)."""
    real = data.astype(jnp.float32) * jnp.maximum(
        jnp.abs(min_range), jnp.abs(max_range)) / (127.0 * 127.0)
    if params["min_calib_range"] is not None:
        mn = jnp.asarray(params["min_calib_range"], jnp.float32)
        mx = jnp.asarray(params["max_calib_range"], jnp.float32)
    else:
        mn = jnp.min(real)
        mx = jnp.max(real)
    scale = jnp.maximum(jnp.maximum(jnp.abs(mn), jnp.abs(mx)), 1e-8)
    out = jnp.clip(jnp.round(real / scale * 127.0), -127, 127).astype(jnp.int8)
    return out, -scale, scale


@register("_contrib_quantized_fully_connected", nin=-1, nout=3,
          params={"num_hidden": REQUIRED, "no_bias": False, "flatten": True},
          cost_meta=_QUANT_COMPUTE)
def _quantized_fc(params, *args):
    """int8 x int8 -> int32 matmul (reference quantized_fully_connected.cc).
    Inputs: data, weight, [bias], min/max for each."""
    no_bias = bool(params["no_bias"])
    if no_bias:
        data, weight, dmin, dmax, wmin, wmax = args
        bias = None
    else:
        data, weight, bias, dmin, dmax, wmin, wmax, bmin, bmax = args
    x = data.astype(jnp.int32)
    if params["flatten"]:
        x = x.reshape(x.shape[0], -1)
    out = jax.lax.dot(x, weight.astype(jnp.int32).T)
    d_scale = jnp.maximum(jnp.abs(dmin), jnp.abs(dmax)) / 127.0
    w_scale = jnp.maximum(jnp.abs(wmin), jnp.abs(wmax)) / 127.0
    if bias is not None:
        # the int8 bias carries its OWN scale (b_scale); accumulators carry
        # d_scale*w_scale — rescale into accumulator units before adding
        # (reference quantized_fully_connected float_for_one_quant_of_bias)
        b_scale = jnp.maximum(jnp.abs(bmin), jnp.abs(bmax)) / 127.0
        bias_acc = jnp.round(bias.astype(jnp.float32) * b_scale /
                             (d_scale * w_scale)).astype(jnp.int32)
        out = out + bias_acc
    out_range = d_scale * w_scale * 127.0 * 127.0
    return out, -out_range, out_range


def _pair(v, default=None):
    t = (v, v) if isinstance(v, int) else tuple(v)
    return t if t else (default or (1, 1))


@register("_contrib_quantized_conv", nin=-1, nout=3,
          params={"kernel": REQUIRED, "stride": (1, 1), "pad": (0, 0),
                  "dilate": (1, 1), "num_filter": REQUIRED, "num_group": 1,
                  "no_bias": False, "layout": "NCHW"},
          cost_meta=_QUANT_COMPUTE)
def _quantized_conv(params, *args):
    """int8 conv -> int32 accumulators (reference quantized_conv.cc).

    Arithmetic runs in f32 and is rounded back: int8 products are <= 127^2
    and partial sums stay inside f32's exact-integer window for any
    practical kernel volume, and f32 convs map onto the TPU MXU where
    int accumulation would not.
    """
    no_bias = bool(params["no_bias"])
    if no_bias:
        data, weight, dmin, dmax, wmin, wmax = args
        bias = None
    else:
        data, weight, bias, dmin, dmax, wmin, wmax, bmin, bmax = args
    stride = _pair(params["stride"])
    pad = _pair(params["pad"], (0, 0))
    dilate = _pair(params["dilate"])
    out = jax.lax.conv_general_dilated(
        data.astype(jnp.float32), weight.astype(jnp.float32),
        window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        rhs_dilation=dilate,
        feature_group_count=int(params["num_group"]),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    out = jnp.round(out).astype(jnp.int32)
    d_scale = jnp.maximum(jnp.abs(dmin), jnp.abs(dmax)) / 127.0
    w_scale = jnp.maximum(jnp.abs(wmin), jnp.abs(wmax)) / 127.0
    if bias is not None:
        # rescale the int8 bias from its own scale into accumulator units
        # (reference quantized_conv.cu float_for_one_out_quant)
        b_scale = jnp.maximum(jnp.abs(bmin), jnp.abs(bmax)) / 127.0
        bias_acc = jnp.round(bias.astype(jnp.float32) * b_scale /
                             (d_scale * w_scale)).astype(jnp.int32)
        out = out + bias_acc.reshape(1, -1, 1, 1)
    out_range = d_scale * w_scale * 127.0 * 127.0
    return out, -out_range, out_range


@register("_contrib_quantized_pooling", nin=3, nout=3,
          params={"kernel": REQUIRED, "pool_type": "max", "stride": (1, 1),
                  "pad": (0, 0), "global_pool": False,
                  "pooling_convention": "valid"},
          cost_meta=_QUANT_ELEMWISE)
def _quantized_pooling(params, data, min_range, max_range):
    """Pooling on int8 values; ranges pass through unchanged
    (reference quantized_pooling.cc: pooling is range-preserving)."""
    ptype = params["pool_type"]
    if params["global_pool"]:
        kernel = data.shape[2:]
        stride = (1, 1)
        pad = (0, 0)
    else:
        kernel = _pair(params["kernel"])
        stride = _pair(params["stride"])
        pad = _pair(params["pad"], (0, 0))
    x = data.astype(jnp.float32)
    dims = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1]))
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides,
                                    padding)
    elif ptype == "avg":
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, padding)
        out = s / float(kernel[0] * kernel[1])
    else:
        raise ValueError(f"quantized_pooling: pool_type {ptype}")
    out = jnp.clip(jnp.round(out), -127, 127).astype(data.dtype)
    return out, min_range, max_range
