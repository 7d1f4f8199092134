"""Attention operators for the transformer LM workload.

One registered op, ``BlockwiseAttention``: multi-head scaled-dot-product
attention over packed ``(batch, time, channels)`` activations, lowered
through `parallel/ring_attention.blockwise_attention` — the flash-style
online-softmax recurrence that never materializes the (T, T) score
matrix.  The projections around it (qkv, out_proj) stay ordinary
`FullyConnected` nodes so the megatron sharding rules
(`parallel/tensor_parallel.ShardingRules.megatron`) see them by name and
the mxcost dot-class rules price them; this op prices only the
score/value contractions it owns via `cost_meta`.

Registering the op here (rather than hiding the attention math inside a
gluon block) keeps saved LM symbol JSON self-describing: a checkpoint's
``*-symbol.json`` round-trips through `sym.load` in a fresh process with
no llm/ import.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register, REQUIRED


def _attn_flops(params, in_avals, out_avals):
    """2*B*H*T*T*D for QK^T plus the same for scores@V."""
    q = in_avals[0]
    b, t, c = (int(d) for d in q.shape[-3:])
    return 4.0 * b * t * t * c


def grouped_query_attention(q, k, v, causal=True, block_size=None):
    """q (B, T, Hq, D), k (B, S, Hkv, D), v (B, S, Hkv, Dv), Hq a multiple
    of Hkv (key-value head h serves query heads h*r .. h*r + r - 1): exact
    softmax attention in float32, one block of queries at a time against
    the keys it may see, so that only a block of scores exists at once; a
    block's scores are computed again in the backward pass, not kept."""
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    r = hq // hkv
    bs = min(int(block_size or 512), t)
    q = q.reshape(b, t, hkv, r, d)

    @jax.checkpoint
    def block(qb, kb, vb, first):
        scores = jnp.einsum("bqhrd,bkhd->bhrqk", qb, kb,
                            preferred_element_type=jnp.float32) * d ** -0.5
        if causal:
            seen = (first + jnp.arange(qb.shape[1]))[:, None] >= \
                jnp.arange(kb.shape[1])[None, :]
            scores = jnp.where(seen, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vb.dtype)
        return jnp.einsum("bhrqk,bkhe->bqhre", probs, vb)

    out = []
    for first in range(0, t, bs):
        last = min(first + bs, t)
        keys = min(last + (s - t), s) if causal else s
        out.append(block(q[:, first:last], k[:, :keys], v[:, :keys],
                         first + (s - t)))
    return jnp.concatenate(out, axis=1).reshape(b, t, hq, v.shape[-1])


@register("BlockwiseAttention", nin=3,
          params={"num_heads": REQUIRED, "causal": True,
                  "block_size": None, "num_kv_heads": None},
          input_names=["query", "key", "value"],
          cost_meta={"flops": _attn_flops})
def _blockwise_attention(params, q, k, v):
    """Multi-head attention on (B, T, C) inputs.

    Splits channels into ``num_heads`` heads, runs the blockwise exact-
    softmax recurrence, and re-packs.  ``block_size=None`` lets the
    kernel pick its tile; ``causal`` masks future positions.

    With ``num_kv_heads`` key and value hold that many heads, each serving
    ``num_heads / num_kv_heads`` consecutive query heads (grouped-query
    attention), the value's head size may differ from the key's, and the
    softmax runs in float32 (`grouped_query_attention`).  Left at None the
    op is what it was: as many key-value heads as query heads.
    """
    from ..parallel.ring_attention import blockwise_attention
    heads = int(params["num_heads"])
    causal = bool(params.get("causal", True))
    block_size = params.get("block_size")
    if block_size is not None:
        block_size = int(block_size)
    b, t, c = q.shape[-3], q.shape[-2], q.shape[-1]
    if params.get("num_kv_heads") is not None:
        kv = int(params["num_kv_heads"])
        if c % heads or heads % kv or k.shape[-1] % kv or \
                v.shape[-1] % kv or k.shape[-1] // kv != c // heads:
            from ..base import MXNetError
            raise MXNetError(
                "BlockwiseAttention: query %s, key %s, value %s do not fit "
                "num_heads %d and num_kv_heads %d"
                % (tuple(q.shape), tuple(k.shape), tuple(v.shape), heads,
                   kv))
        out = grouped_query_attention(
            q.reshape(b, t, heads, -1), k.reshape(b, k.shape[-2], kv, -1),
            v.reshape(b, v.shape[-2], kv, -1), causal=causal,
            block_size=block_size)
        return out.reshape(b, t, -1)
    if c % heads:
        from ..base import MXNetError
        raise MXNetError(
            "BlockwiseAttention: channels (%d) not divisible by "
            "num_heads (%d)" % (c, heads))
    d = c // heads

    def split(x):
        return x.reshape(b, t, heads, d)

    out = blockwise_attention(split(q), split(k), split(v),
                              block_size=block_size, causal=causal)
    return out.reshape(b, t, c)


def naive_attention(q, k, v, num_heads, causal=True):
    """Reference O(T^2)-memory attention on (B, T, C) packed inputs —
    materializes the full score matrix.  The parity oracle for
    `BlockwiseAttention` (tests/test_ring_attention.py); not a
    registered op."""
    b, t, c = q.shape
    d = c // num_heads
    qh = q.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
    kh = k.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
    vh = v.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / jnp.sqrt(
        jnp.asarray(d, dtype=q.dtype))
    if causal:
        mask = jnp.tril(jnp.ones((t, t), dtype=bool))
        scores = jnp.where(mask[None, None], scores,
                           jnp.asarray(-1e30, dtype=scores.dtype))
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, t, c)
