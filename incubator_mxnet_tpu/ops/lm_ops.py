"""Small operators of hybrid linear-attention language models.

``RMSNorm`` (plain and zero-centred, optionally gated), ``RotaryEmbedding``
(partial, rotate-half pairing), ``CausalConv1D`` (the depthwise
convolution over time in front of a linear-attention mixer) and
``GatedShortConv`` (the same convolution between two gates: the token mixer
of the LFM2 family).  Registered
like `BlockwiseAttention`, so that a saved ``*-symbol.json`` loads in a fresh
process with no llm/ import.  Each computes in float32 whatever the
activations' type and hands back the activations' type.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register, REQUIRED
from ..base import MXNetError


def rms_norm(x, weight, eps=1e-6, zero_centered=False, gate=None):
    """x / sqrt(mean(x^2) + eps) * (1 + w) (zero-centred) or * w, over the
    last axis, in float32; with a `gate`, times SiLU(gate) after the norm."""
    xf = x.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                             + eps) * ((1.0 + w) if zero_centered else w)
    if gate is not None:
        out = out * jax.nn.silu(gate.astype(jnp.float32))
    return out.astype(x.dtype)


@register("RMSNorm", nin=-1,
          params={"eps": 1e-6, "zero_centered": False, "gated": False},
          input_names=lambda p: ["data", "gamma"] +
          (["gate"] if p.get("gated") else []))
def _rms_norm(params, x, gamma, *rest):
    """Root-mean-square normalisation over the last axis.  `zero_centered`
    scales by 1 + gamma (gamma initialised 0); `gated` takes a third input
    of data's shape and multiplies the normalised data by its SiLU."""
    if x.shape[-1] != gamma.shape[-1]:
        raise MXNetError(
            "RMSNorm: gamma has %d entries, data's last axis %d"
            % (gamma.shape[-1], x.shape[-1]))
    return rms_norm(x, gamma, float(params["eps"]),
                    bool(params["zero_centered"]),
                    rest[0] if params.get("gated") else None)


def rotary(x, rotary_dim, base):
    """x (B, T, H, D): positions 0..T-1 rotate the first `rotary_dim`
    entries of every head, entry i paired with entry i + rotary_dim / 2."""
    t, half = x.shape[1], rotary_dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32)
                               * 2.0 / rotary_dim))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :,
                                                                None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dim], \
        xf[..., rotary_dim:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                          axis=-1)
    return out.astype(x.dtype)


@register("RotaryEmbedding",
          params={"rotary_dim": REQUIRED, "base": 10000.0})
def _rotary_embedding(params, x):
    """Rotary position embedding on (batch, time, heads, head size) data:
    the first `rotary_dim` entries of each head turn with the position
    (rotate-half pairing, theta = `base`), the others pass through."""
    rd = int(params["rotary_dim"])
    if x.ndim != 4 or rd % 2 or rd > x.shape[-1]:
        raise MXNetError(
            "RotaryEmbedding: data must be (batch, time, heads, head size) "
            "and rotary_dim even and at most the head size; got %s and %d"
            % (tuple(x.shape), rd))
    return rotary(x, rd, float(params["base"]))


def _causal_conv(xf, weight):
    """`causal_conv1d` on float32 data, float32 out."""
    k = weight.shape[1]
    w = weight.astype(jnp.float32)
    padded = jnp.pad(xf, ((0, 0), (k - 1, 0), (0, 0)))
    t = xf.shape[1]
    return sum(padded[:, j:j + t] * w[:, j] for j in range(k))


def causal_conv1d(x, weight):
    """x (B, T, C), weight (C, K): y_t = sum_j w[:, j] * x_{t-(K-1)+j}, with
    zeros before the start of the sequence."""
    return _causal_conv(x.astype(jnp.float32), weight).astype(x.dtype)


@register("CausalConv1D", nin=2, params={"kernel": REQUIRED},
          input_names=["data", "weight"],
          cost_meta={"flops": lambda params, ins, outs:
                     2.0 * int(params["kernel"]) * math.prod(outs[0].shape)})
def _causal_conv1d(params, x, weight):
    """Causal depthwise convolution over time on (batch, time, channels)
    data, no bias: `kernel` shifted multiply-adds per channel, which XLA
    fuses into one pass (a grouped `Convolution` would need the layout
    turned to NCW and back)."""
    if weight.shape != (x.shape[-1], int(params["kernel"])):
        raise MXNetError(
            "CausalConv1D: weight must be (channels, kernel) = (%d, %d); "
            "got %s" % (x.shape[-1], int(params["kernel"]),
                        tuple(weight.shape)))
    return causal_conv1d(x, weight)


def gated_short_conv(bcx, weight):
    """bcx (B, T, 3C) laid out [B | C | x], weight (C, K):
    y = C * causal_conv1d(B * x), the gates and the convolution in float32,
    no activation."""
    c = weight.shape[0]
    b, gate, x = (bcx[..., i * c:(i + 1) * c].astype(jnp.float32)
                  for i in range(3))
    return (gate * _causal_conv(b * x, weight)).astype(bcx.dtype)


@register("GatedShortConv", nin=2, params={"kernel": REQUIRED},
          input_names=["data", "weight"],
          cost_meta={"flops": lambda params, ins, outs:
                     2.0 * (int(params["kernel"]) + 1) *
                     math.prod(outs[0].shape)})
def _gated_short_conv(params, bcx, weight):
    """The LFM2 family's token mixer between its two projections: data is
    the fused (batch, time, 3 x channels) projection [B | C | x], and the
    output C * CausalConv1D(B * x) over (batch, time, channels): a causal
    depthwise convolution of `kernel` taps, no bias, between two
    elementwise gates.  No product: XLA fuses the shifted multiply-adds
    and both gates into one pass over the input, and that is the one form
    there is; a traced call counts it (`ops.short_conv.lowered.xla`), as
    the operators with a kernel count theirs."""
    k = int(params["kernel"])
    if bcx.ndim != 3 or bcx.shape[-1] % 3 or \
            weight.shape != (bcx.shape[-1] // 3, k):
        raise MXNetError(
            "GatedShortConv: data must be (batch, time, 3 x channels) and "
            "weight (channels, kernel) = (%s, %d); got %s and %s"
            % (bcx.shape[-1] // 3 if bcx.ndim == 3 else "?", k,
               tuple(bcx.shape), tuple(weight.shape)))
    from .. import obs
    obs.counter("ops.short_conv.lowered.xla").inc()
    return gated_short_conv(bcx, weight)
