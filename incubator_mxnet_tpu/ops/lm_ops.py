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
import numpy as np

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


def rotary(x, rotary_dim, base, copies=1):
    """x (B, T, H, D): positions 0..T-1 rotate the first `rotary_dim`
    entries of every head, entry i paired with entry i + rotary_dim / 2.
    `copies`: the time axis holds that many copies of one sequence, each at
    positions 0..T/copies - 1 (the positions' period is T / copies)."""
    t, half = x.shape[1], rotary_dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32)
                               * 2.0 / rotary_dim))
    position = jnp.arange(t, dtype=jnp.float32)
    if copies != 1:
        position = jnp.tile(position[:t // copies], copies)
    angle = position[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :,
                                                                None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dim], \
        xf[..., rotary_dim:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                          axis=-1)
    return out.astype(x.dtype)


@register("RotaryEmbedding",
          params={"rotary_dim": REQUIRED, "base": 10000.0, "copies": 1})
def _rotary_embedding(params, x):
    """Rotary position embedding on (batch, time, heads, head size) data:
    the first `rotary_dim` entries of each head turn with the position
    (rotate-half pairing, theta = `base`), the others pass through.
    `copies` (1 unless told) gives the positions' period as a share of the
    time axis: time holds that many copies of one sequence, each at
    positions 0..time/copies - 1, as block-diffusion training's [noisy |
    clean] rows do; given so, the symbol binds at any length."""
    rd, copies = int(params["rotary_dim"]), int(params.get("copies") or 1)
    if x.ndim != 4 or rd % 2 or rd > x.shape[-1] or copies < 1 or \
            x.shape[1] % copies:
        raise MXNetError(
            "RotaryEmbedding: data must be (batch, time, heads, head size), "
            "rotary_dim even and at most the head size and time a multiple "
            "of copies; got %s, %d and %d" % (tuple(x.shape), rd, copies))
    return rotary(x, rd, float(params["base"]), copies)


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


def block_diffusion_noise(ids, block_length, mask_token, low, high,
                          seed=None, key=None):
    """(noisy ids, mask, weight) of clean ids (batch, L): every block of
    `block_length` tokens draws a noise level t uniform on the thousandths
    of [low, high], each of its tokens is replaced by `mask_token` with
    probability t (mask 1 there), and the weight is mask / t (BD3-LM's
    linear schedule, arXiv:2503.09573).  With `seed` a row's draws are
    `jax.random`'s under fold_in(fold_in(PRNGKey(seed), checksum of the
    row's ids), 0 for the levels, 1 for the tokens), checksum = sum_i id_i
    (2 i + 1) mod 2^32: a function of the tokens and the seed alone, the
    same on every platform, which a reference can make again -- the levels
    and their reciprocals are tables made on the host, so the device does
    integer work, look-ups and one comparison, nothing whose rounding a
    compiler may choose.  Without `seed` the rows split `key`."""
    batch, length = ids.shape
    blocks = -(-length // block_length)
    first, last = round(1000 * low), round(1000 * high)
    levels = np.arange(first, last + 1, dtype=np.float32) / np.float32(1000)
    if seed is not None:
        odd = 2 * jnp.arange(length, dtype=jnp.uint32) + jnp.uint32(1)
        check = jnp.sum(ids.astype(jnp.int32).astype(jnp.uint32) * odd,
                        axis=1, dtype=jnp.uint32)
        base = jax.random.PRNGKey(int(seed))
        keys = jax.vmap(lambda c: jax.random.fold_in(base, c))(check)
    else:
        keys = jax.random.split(key, batch)

    def row(k):
        level = jnp.repeat(jax.random.randint(
            jax.random.fold_in(k, 0), (blocks,), 0, len(levels)),
            block_length)[:length]
        return jax.random.uniform(jax.random.fold_in(k, 1), (length,),
                                  jnp.float32) < jnp.asarray(levels)[level], \
            level
    masked, level = jax.vmap(row)(keys)
    mask = masked.astype(jnp.float32)
    return jnp.where(masked, jnp.asarray(mask_token, ids.dtype), ids), \
        mask, mask * jnp.asarray(np.float32(1) / levels)[level]


def _noise_counters(deltas):
    """An epoch's noise, from what every `BlockDiffusionNoise` node of a
    graph has added to `stats` (`OpDef.counters`)."""
    rows, masked, weight = (float(sum(d["stats"][i] for d in deltas))
                            for i in range(3))
    args = {"rows": int(rows), "masked": int(masked), "weight_sum": weight,
            "masked_share": masked / rows if rows else 0.0}
    return {"span": "diffusion.noise", "args": args,
            "counters": {"diffusion.rows": args["rows"],
                         "diffusion.masked": args["masked"],
                         "diffusion.weight_sum": weight},
            "gauges": {}}


@register("BlockDiffusionNoise", nin=2, nout=3, naux=1, needs_rng=True,
          mode_dependent=True,
          params={"block_length": REQUIRED, "mask_token": REQUIRED,
                  "low": 0.001, "high": 1.0, "seed": None},
          input_names=["data", "stats"], counters=_noise_counters)
def _block_diffusion_noise(params, ids, stats, key):
    """The corruption of block-diffusion training, in the graph: clean ids
    (batch, L) in; the noisy ids (a masked token is `mask_token`), the mask
    m and the weight m / t out, t a block's noise level, uniform on the
    thousandths of [`low`, `high`] (`block_diffusion_noise`).  With `seed` the draw is a function
    of the tokens and the seed alone; without it the graph's random
    resource is drawn from, as `Dropout` does: fresh noise every step.  The
    auxiliary state `stats` (3,) is added to in every training step: rows
    seen, rows masked, the weights' sum."""
    block, low, high = int(params["block_length"]), float(params["low"]), \
        float(params["high"])
    if ids.ndim != 2 or block < 1 or stats.shape != (3,) or \
            not 0.0005 <= low <= high <= 1.0:
        raise MXNetError(
            "BlockDiffusionNoise: data must be (batch, length) ids, "
            "block_length >= 1, 0.001 <= low <= high <= 1 and stats (3,); got "
            "%s, %d, [%g, %g] and %s" % (tuple(ids.shape), block, low, high,
                                         tuple(stats.shape)))
    seed = params.get("seed")
    noisy, mask, weight = block_diffusion_noise(
        ids, block, float(params["mask_token"]), low, high,
        None if seed is None else int(seed), key)
    from .. import obs
    obs.counter("ops.diffusion_noise.lowered." +
                ("seeded" if seed is not None else "random")).inc()
    if not params.get("_train", False):
        return noisy, mask, weight
    return noisy, mask, weight, stats + jnp.stack([
        jnp.float32(ids.size), jnp.sum(mask), jnp.sum(weight)]).astype(
            stats.dtype)
