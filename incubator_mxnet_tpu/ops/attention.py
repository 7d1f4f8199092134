"""Attention operators for the transformer LM workload.

One registered op, ``BlockwiseAttention``: multi-head scaled-dot-product
attention over packed ``(batch, time, channels)`` activations.  The
projections around it (qkv, out_proj) stay ordinary `FullyConnected` nodes
so the megatron sharding rules
(`parallel/tensor_parallel.ShardingRules.megatron`) see them by name and
the mxcost dot-class rules price them; this op prices only the
score/value contractions it owns via `cost_meta`.

What runs where:

* without ``num_kv_heads``: `parallel/ring_attention.blockwise_attention`,
  the online-softmax recurrence in `jax.numpy`, on every platform;
* with ``num_kv_heads`` (`grouped_query_attention`): on a TPU, where the
  shapes tile (`_tiles`: queries and keys in whole lane tiles, head sizes
  of 64 or whole lane tiles, no fewer keys than queries, a grid step
  within the kernels' VMEM), the flash kernels of `ops/flash_attention.py`
  over GROUPED heads under one custom VJP (`_flash`): a grid step holds a
  block of queries of the r query heads that share a key-value head as
  rows, so K and V are read once for the r heads and no block of scores
  passes through HBM, forward or backward.  Kept for the backward pass: q,
  k, v, the output and a float32 log-sum-exp a row, the last two named
  `registry.scan_kept` (a re-materialised scanned layer stacks them and
  does not run the forward kernel again).  Anywhere else, and
  for every other shape, XLA's block form (`_xla_blocks`): a checkpointed
  block of queries at a time, what the tests compare the kernels with.
  The choice is made from the platform and the shapes alone (`_driver`;
  ``MXNET_FLASH_INTERPRET`` runs the kernels interpreted, for the CPU
  tests), and which one a traced call took is counted:
  `ops.attention.lowered.kernel` / `.xla`.

The mask is a parameter: ``causal`` (what it was), or
``mask="block_diffusion"`` with ``block_length`` over a ``time`` of 2L rows,
[noisy copy | clean copy] of one sequence of L (`flash_attention.py` states
it).  `flash_attention.tile_runs` is the one statement of which tiles of
keys a block of queries runs whole, runs under the mask and never runs; the
kernels' loops, `_xla_blocks`' key slices and the counters
`ops.attention.tiles.run` / `.masked` / `.skipped` (a lowered kernel call,
forward or backward, in tiles of block_q rows by 128 keys) all read it.

Registering the op here (rather than hiding the attention math inside a
gluon block) keeps saved LM symbol JSON self-describing: a checkpoint's
``*-symbol.json`` round-trips through `sym.load` in a fresh process with
no llm/ import.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import CAUSAL, NONE
from .registry import register, REQUIRED, scan_kept


def _mask_param(params):
    """The kernels' mask parameter `(kind, block_length)` of an operator's
    parameters: what `causal` says, unless `mask` names the one mask that
    flag cannot state."""
    from ..base import MXNetError
    kind = params.get("mask")
    if kind is None:
        return CAUSAL if bool(params.get("causal", True)) else NONE
    if kind != "block_diffusion":
        raise MXNetError("BlockwiseAttention: mask is 'block_diffusion' or "
                         "absent (`causal` states the others), not %r"
                         % (kind,))
    if int(params.get("block_length") or 0) < 1:
        raise MXNetError("BlockwiseAttention: mask='block_diffusion' "
                         "needs block_length >= 1")
    return ("block_diffusion", int(params["block_length"]))


def _attn_flops(params, in_avals, out_avals):
    """2*B*H*T*T*D for QK^T plus the same for scores@V; under
    `block_diffusion` over the entries the mask leaves, L^2 + L B of the
    (2L)^2 a sequence."""
    q = in_avals[0]
    b, t, c = (int(d) for d in q.shape[-3:])
    if params.get("mask") == "block_diffusion":
        return 4.0 * b * (t // 2) * (t // 2 + int(params["block_length"])) * c
    return 4.0 * b * t * t * c


def _xla_blocks(q, k, v, mask=CAUSAL, block_size=None):
    """XLA's form of `grouped_query_attention`: one block of queries at a
    time against the keys `tile_runs` says it may see (at a width of one
    key, so exactly those), so that only a block of scores exists at once;
    a block's scores are computed again in the backward pass, not kept."""
    from . import flash_attention as fa
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    r = hq // hkv
    bs = min(int(block_size or 512), t)
    period = _period(mask, t, s)
    if period:
        bs = min(bs, period)
        while period % bs:          # a block of queries lies in one copy
            bs -= 1
    q = q.reshape(b, t, hkv, r, d)

    @jax.checkpoint
    def block(qb, kb, vb, q_pos, k_pos):
        scores = jnp.einsum("bqhrd,bkhd->bhrqk", qb, kb,
                            preferred_element_type=jnp.float32) * d ** -0.5
        if mask[0] != "none":
            scores = jnp.where(fa.seen(mask, q_pos, k_pos, period), scores,
                               -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vb.dtype)
        return jnp.einsum("bhrqk,bkhe->bqhre", probs, vb)

    out = []
    for first in range(0, t, bs):
        last = min(first + bs, t)
        at = _first_row(mask, first, s - t)
        spans = [(lo, hi) for lo, hi, _, _ in
                 fa.tile_runs(mask, at, last - first, 1, s) if hi > lo]
        kb, vb = (x[:, spans[0][0]:spans[-1][1]] if _adjoin(spans) else
                  jnp.concatenate([x[:, lo:hi] for lo, hi in spans], axis=1)
                  for x in (k, v))
        k_pos = jnp.concatenate([jnp.arange(lo, hi) for lo, hi in spans])
        out.append(block(q[:, first:last], kb, vb,
                         at + jnp.arange(last - first), k_pos))
    return jnp.concatenate(out, axis=1).reshape(b, t, hq, v.shape[-1])


def _adjoin(spans):
    return all(a[1] == b[0] for a, b in zip(spans, spans[1:]))


def _first_row(mask, first, ahead):
    """What `tile_runs` takes for a block of queries that starts at row
    `first`, the keys `ahead` of the queries: its row for
    `block_diffusion`, its position past the first key's otherwise."""
    return first if mask[0] == "block_diffusion" else first + ahead


def _period(mask, t, s):
    """The L of `block_diffusion` (queries and keys are both the 2L rows);
    None under another mask."""
    if mask[0] != "block_diffusion":
        return None
    if t != s or t % 2:
        from ..base import MXNetError
        raise MXNetError(
            "BlockwiseAttention: mask='block_diffusion' is over 2L queries "
            "and the same 2L keys; got %d and %d" % (t, s))
    return t // 2


LANES = 128
ROWS = 1024     # stacked query rows a grid step aims at


def _tiles(r, t, s, d, dv, itemsize, period=None):
    """(block_q, block_k) of the kernels at these shapes, or None where
    they do not hold them: queries and keys in whole lane tiles, head sizes
    of half a lane tile or whole ones, no fewer keys than queries, and the
    backward kernel's grid step (the larger of the two: a head's K and V
    and their float32 gradients whole, the stacked rows' blocks, five
    float32 tiles of scores) within the VMEM the kernels ask for.  About
    `ROWS` stacked rows a step; the widest tile of keys that divides
    them.  `period` (`block_diffusion`'s L): blocks of queries and tiles of
    keys divide it, so that each lies in one copy."""
    from . import flash_attention as fa
    if t % LANES or s % LANES or d % 64 or dv % 64 or s < t or \
            (period or LANES) % LANES:
        return None
    t, s = period or t, s if period is None else period
    block_q = LANES
    while block_q * 2 * r <= ROWS and t % (block_q * 2) == 0:
        block_q *= 2
    rows = r * block_q
    pd, pdv = -(-d // LANES) * LANES, -(-dv // LANES) * LANES
    keys = s if period is None else 2 * s
    for block_k in (1024, 512, 256, LANES):
        step = 2 * keys * (pd + pdv) * (itemsize + 4) + \
            2 * rows * (2 * pd + pdv) * itemsize + 4 * rows * pd + \
            5 * 4 * rows * block_k
        if s % block_k == 0 and step <= fa.VMEM_LIMIT_BYTES:
            return block_q, block_k
    return None


def _driver(r, t, s, d, dv, itemsize, period=None):
    """"kernel", "interpret" or "xla" for a call, from the platform and the
    shapes: the flash kernel on ``tpu`` (or interpreted, where
    ``MXNET_FLASH_INTERPRET`` asks) where `_tiles` holds the shapes, XLA's
    block form anywhere else."""
    from . import flash_attention as fa
    use, interpret = fa.pallas_mode()
    if not use or _tiles(r, t, s, d, dv, itemsize, period) is None:
        return "xla"
    return "interpret" if interpret else "kernel"


def _narrow(mask, block_q, block_k, period):
    """The width of the tiles on `block_diffusion`'s noisy diagonal: a
    block of queries sees max(block_q, block_length) keys there or a few
    more, so the widest power of two of lanes within that, `block_k` and
    the period."""
    if mask[0] != "block_diffusion":
        return None
    width = LANES
    while width * 2 <= min(block_k, max(block_q, mask[1])) and \
            period % (width * 2) == 0:
        width *= 2
    return width


def tile_counts(mask, t, s, block_q, block_k, narrow=None):
    """(run whole, run masked, skipped) over all blocks of queries of one
    head, in tiles of block_q rows by `LANES` keys: `tile_runs` with Python
    integers, the kernels' own statement."""
    from . import flash_attention as fa
    whole = masked = 0
    for first in range(0, t, block_q):
        for lo, hi, width, rule in fa.tile_runs(
                mask, _first_row(mask, first, s - t), block_q, block_k, s,
                narrow):
            n = max(hi - lo, 0) * width // LANES
            whole, masked = whole + (0 if rule else n), \
                masked + (n if rule else 0)
    return whole, masked, t // block_q * (s // LANES) - whole - masked


def _count_tiles(heads, *args):
    from .. import obs
    for name, n in zip(("run", "masked", "skipped"), tile_counts(*args)):
        obs.counter("ops.attention.tiles." + name).inc(heads * n)


def _count(driver):
    from .. import obs
    obs.counter("ops.attention.lowered." +
                ("xla" if driver == "xla" else "kernel")).inc()


def _kernel_layout(q, k, v, *like_q):
    """(q4, k3, v3[, ...]) as the kernel takes them: (B Hkv, r, T, D) with
    the softmax scale folded into q where that is exact, (B Hkv, S, D),
    (B Hkv, S, Dv), what is left of the scale, and further arrays of q's
    layout (the output, its gradient) brought to q4's."""
    from . import flash_attention as fa
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]

    def rows(x):
        return x.reshape(b, t, hkv, hq // hkv, -1).transpose(
            0, 2, 3, 1, 4).reshape(b * hkv, hq // hkv, t, -1)

    def keys(x):
        return x.transpose(0, 2, 1, 3).reshape(b * hkv, s, -1)
    q, scale = fa._fold_scale(q, d)
    return (rows(q), keys(k), keys(v), scale) + tuple(rows(x) for x in like_q)


def _from_rows(x4, b):
    """(B Hkv, r, T, E) -> (B, T, Hq, E)."""
    bh, r, t, e = x4.shape
    return x4.reshape(b, bh // b, r, t, e).transpose(0, 3, 1, 2, 4).reshape(
        b, t, bh // b * r, e)


def _walk(q, k, v, mask):
    """The kernels' static keywords at these shapes, and the counters'
    arguments."""
    t, s, r = q.shape[1], k.shape[1], q.shape[2] // k.shape[2]
    period = _period(mask, t, s)
    block_q, block_k = _tiles(r, t, s, q.shape[3], v.shape[3],
                              q.dtype.itemsize, period)
    narrow = _narrow(mask, block_q, block_k, period)
    _count_tiles(q.shape[0] * k.shape[2], mask, t, s, block_q, block_k,
                 narrow)
    return dict(mask=mask, narrow=narrow, block_q=block_q, block_k=block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, mask, interpret):
    """The flash kernels over grouped heads (`ops/flash_attention.py`): the
    r query heads of a key-value head are rows of one grid step, forward
    and backward.  `mask`: the kernels' mask parameter.
    Kept for the backward pass: q, k, v, the output and a float32
    log-sum-exp a row -- no score.  Of those the output and the
    log-sum-exp are what the forward kernel made, and the forward rule
    names them `registry.scan_kept`: a re-materialised scanned layer
    stacks them (2 x rows x heads x (head size x itemsize + 4) bytes a
    layer) and computes q, k and v again, not the kernel."""
    return _flash_fwd(q, k, v, mask, interpret)[0]


def _flash_fwd(q, k, v, mask, interpret):
    from . import flash_attention as fa
    b, t = q.shape[:2]
    q4, k3, v3, scale = _kernel_layout(q, k, v)
    o4, m, l = fa._kernel_forward(
        q4, k3, v3, k.shape[1] - t, 0, scale=scale, normalize=True,
        interpret=interpret, **_walk(q, k, v, mask))
    # the primal output and the kept one are the same named value, so
    # nothing reads the kernel's raw outputs but these two
    o = scan_kept(_from_rows(o4, b))
    return o, (q, k, v, o, scan_kept(m + jnp.log(l)))


def _flash_bwd(mask, interpret, kept, g):
    from . import flash_attention as fa
    q, k, v, o, lse = kept
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    r = hq // hkv
    walk = _walk(q, k, v, mask)
    block_q = walk["block_q"]
    q4, k3, v3, scale, do4 = _kernel_layout(q, k, v, g)
    # the rows' sum of dO * O, block by block as the kernel's statistics
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(b, t // block_q, block_q, hkv, r).transpose(
        0, 3, 1, 4, 2).reshape(b * hkv, 1, t * r)
    dq4, dk3, dv3 = fa._kernel_backward(
        q4, k3, v3, do4, lse, delta, s - t, 0, scale=scale,
        dq_scale=float(d) ** -0.5, interpret=interpret, **walk)

    def keys(x3, like):
        return x3.reshape(b, hkv, s, -1).transpose(0, 2, 1, 3).astype(
            like.dtype)
    return _from_rows(dq4, b), keys(dk3 * jnp.float32(scale), k), \
        keys(dv3, v)


_flash.defvjp(_flash_fwd, _flash_bwd)


def grouped_query_attention(q, k, v, mask=CAUSAL, block_size=None):
    """q (B, T, Hq, D), k (B, S, Hkv, D), v (B, S, Hkv, Dv), Hq a multiple
    of Hkv (key-value head h serves query heads h*r .. h*r + r - 1), S >=
    T; `mask` the kernels' mask parameter: under `CAUSAL` query i sees keys
    0 .. i + S - T, under `NONE` all, `("block_diffusion", B)` is over T =
    S = 2L rows): exact softmax attention with float32 scores, maximum, sum
    and accumulator, the probabilities rounded to v's type for the second
    product.  `_driver` picks what runs, and
    `ops.attention.lowered.kernel` / `.xla` count its choice a traced
    call; `block_size` sizes XLA's blocks, the kernel's tiles come from the
    shapes (`_tiles`)."""
    r = q.shape[2] // k.shape[2]
    driver = _driver(r, q.shape[1], k.shape[1], q.shape[3], v.shape[3],
                     q.dtype.itemsize, _period(mask, q.shape[1], k.shape[1]))
    _count(driver)
    if driver == "xla":
        return _xla_blocks(q, k, v, mask, block_size)
    return _flash(q, k, v, mask, driver == "interpret")


@register("BlockwiseAttention", nin=3,
          params={"num_heads": REQUIRED, "causal": True,
                  "block_size": None, "num_kv_heads": None,
                  "mask": None, "block_length": None},
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

    ``mask="block_diffusion"`` with ``block_length``, in place of
    ``causal``, is over a time of 2L rows, [noisy
    copy | clean copy] of a sequence of L in blocks of that length: a noisy
    row sees the noisy keys of its own block and the clean keys of the
    blocks before it, a clean row the clean keys of its own block and of
    those before it.  It runs as grouped-query attention (``num_kv_heads``
    absent: as many as ``num_heads``).
    """
    from ..parallel.ring_attention import blockwise_attention
    heads = int(params["num_heads"])
    mask = _mask_param(params)
    causal = mask == CAUSAL
    block_size = params.get("block_size")
    if block_size is not None:
        block_size = int(block_size)
    b, t, c = q.shape[-3], q.shape[-2], q.shape[-1]
    if params.get("num_kv_heads") is not None or \
            mask[0] == "block_diffusion":
        kv = int(params.get("num_kv_heads") or heads)
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
            v.reshape(b, v.shape[-2], kv, -1), mask, block_size)
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
