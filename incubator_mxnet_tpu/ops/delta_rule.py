"""The gated delta rule: the linear-attention recurrence of Gated DeltaNet
(Yang, Kautz, Hatamizadeh 2024, arXiv:2412.06464), per value head with a
float32 state S of (key size, value size), S_0 = 0:

    S' = alpha_t S_{t-1},  u_t = beta_t (v_t - S'^T k_t),
    S_t = S' + k_t u_t^T,  o_t = S_t^T q_t,      alpha_t = exp(g_t)

computed here in chunks of `chunk_size` positions (the WY form of the paper's
section 3.3): inside a chunk everything is matrix products, among them the
inverse of a unit lower-triangular matrix, taken as the finite product
(I + M)(I + M^2)(I + M^4)... of its nilpotent part; between chunks one
`lax.scan` carries S.  The chunks' own matrices are made for a group of
heads at a time and made again in the backward pass.  The backward pass is JAX's derivative of this chunked
form.  `GatedDeltaGates` turns the mixer's two small projections into the
float32 `g` and `beta` the recurrence takes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, REQUIRED
from ..base import MXNetError

HI = lax.Precision.HIGHEST
GROUP_BYTES = 32 << 20     # of one (C, C) float32 matrix per head and chunk


def l2_normalize(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _unit_lower_inverse(strict_lower):
    """(I + L)^-1 for strictly lower-triangular L of (..., C, C): with
    M = -L nilpotent, sum_k M^k = prod_j (I + M^(2^j)) while 2^j < C."""
    c = strict_lower.shape[-1]
    m = -strict_lower
    inv = jnp.eye(c, dtype=m.dtype) + m
    power = m
    for _ in range(max(0, math.ceil(math.log2(c)) - 1)):
        power = jnp.matmul(power, power, precision=HI)
        inv = inv + jnp.matmul(inv, power, precision=HI)
    return inv


def gated_delta_rule(q, k, v, g, beta, chunk_size=64):
    """q, k (B, T, Hk, Dk); v (B, T, Hv, Dv); g, beta (B, T, Hv) with Hv a
    multiple of Hk (key head h serves value heads h*r .. h*r + r - 1).
    Returns o (B, T, Hv, Dv) in v's type; the state and every sum in
    float32."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    c = int(chunk_size)
    out_dtype = v.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    q, k = l2_normalize(q) * dk ** -0.5, l2_normalize(k)
    if hv != hk:
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    pad = (-t) % c
    if pad:     # g = 0 and beta = 0: the state passes through unchanged
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    n = (t + pad) // c

    def chunks(x):      # (B, T, H, ...) -> (B, H, n, C, ...)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))

    @jax.checkpoint
    def within(q, k, v, g, beta):
        """What a chunk needs that does not depend on the state; a dozen
        (C, C) matrices per head and chunk live in here, so only the inputs
        are kept for the backward pass."""
        gc = jnp.cumsum(g, axis=-1)                       # (B, H, n, C)
        lower = jnp.tril(jnp.ones((c, c), bool))
        diff = gc[..., :, None] - gc[..., None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kb = k * beta[..., None]
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        inv = _unit_lower_inverse(jnp.where(
            strict, jnp.einsum("...id,...jd->...ij", kb, k) * decay, 0.0))
        u = jnp.matmul(inv, v * beta[..., None])          # (.., C, Dv)
        w = jnp.matmul(inv, kb * jnp.exp(gc)[..., None])  # (.., C, Dk)
        qk = jnp.einsum("...id,...jd->...ij", q, k) * decay
        q_dec = q * jnp.exp(gc)[..., None]
        k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
        return w, u, q_dec, qk, k_dec, jnp.exp(gc[..., -1])

    # heads in groups, one after another, so that the (C, C) matrices of
    # all heads never exist at once: a group's hold about GROUP_BYTES each
    per_head = b * n * c * max(c, 128) * 4
    groups = next(d for d in range(1, hv + 1)
                  if hv % d == 0 and per_head * (hv // d) <= GROUP_BYTES
                  or d == hv)
    if groups == 1:
        parts = within(q, k, v, g, beta)
    else:
        def split(x):       # (B, H, ...) -> (groups, B, H / groups, ...)
            x = x.reshape((b, groups, hv // groups) + x.shape[2:])
            return jnp.moveaxis(x, 1, 0)
        parts = lax.map(lambda xs: within(*xs),
                        tuple(split(x) for x in (q, k, v, g, beta)))
        parts = tuple(jnp.moveaxis(x, 0, 1).reshape((b, hv) + x.shape[3:])
                      for x in parts)

    def step(state, xs):
        w_i, u_i, q_i, qk_i, k_i, a_i = xs
        v_new = u_i - jnp.matmul(w_i, state)
        o_i = jnp.matmul(q_i, state) + jnp.matmul(qk_i, v_new)
        state = state * a_i[..., None, None] + \
            jnp.einsum("...cd,...ce->...de", k_i, v_new)
        return state, o_i

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in parts)
    _, o = lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 2)                             # (B, H, n, C, Dv)
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, hv, dv)
    return o[:, :t].astype(out_dtype)


def _delta_flops(params, in_avals, out_avals):
    """The recurrence's three key-size x value-size products per value
    head and position: S'^T k, k u^T, S^T q."""
    q, v = in_avals[0], in_avals[2]
    dk = int(q.shape[-1]) // int(params["num_heads"])
    return 6.0 * int(v.shape[0]) * int(v.shape[1]) * int(v.shape[2]) * dk


@register("GatedDeltaRule", nin=5,
          params={"num_heads": REQUIRED, "num_v_heads": REQUIRED,
                  "chunk_size": 64},
          input_names=["query", "key", "value", "g", "beta"],
          cost_meta={"flops": _delta_flops}, scan_remat=True)
def _gated_delta_rule(params, q, k, v, g, beta):
    """The gated delta rule on packed (batch, time, channels) activations:
    `num_heads` key heads in query and key, `num_v_heads` value heads in
    value, one g = log(alpha) and one beta per value head and position.
    Every query and key head is normalised to unit length first (the query
    also scaled by key size ** -0.5)."""
    hk, hv = int(params["num_heads"]), int(params["num_v_heads"])
    b, t = q.shape[0], q.shape[1]
    if q.shape[-1] % hk or v.shape[-1] % hv or hv % hk or \
            k.shape != q.shape or g.shape != (b, t, hv) or \
            beta.shape != (b, t, hv):
        raise MXNetError(
            "GatedDeltaRule: query/key %s, value %s, g %s, beta %s do not "
            "fit num_heads %d and num_v_heads %d"
            % (tuple(q.shape), tuple(v.shape), tuple(g.shape),
               tuple(beta.shape), hk, hv))
    out = gated_delta_rule(
        q.reshape(b, t, hk, -1), k.reshape(b, t, hk, -1),
        v.reshape(b, t, hv, -1), g, beta,
        chunk_size=int(params["chunk_size"]))
    return out.reshape(b, t, -1)


@register("GatedDeltaGates", nin=4, nout=2,
          input_names=["a", "b", "a_log", "dt_bias"])
def _gated_delta_gates(params, a, b, a_log, dt_bias):
    """(g, beta) in float32 from the mixer's projections a, b of (batch,
    time, value heads): g = -exp(A_log) * softplus(a + dt_bias), beta =
    sigmoid(b)."""
    a, b, a_log, dt_bias = (x.astype(jnp.float32)
                            for x in (a, b, a_log, dt_bias))
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias), jax.nn.sigmoid(b)
