"""Flash attention as Pallas TPU kernels.

The framework's subgraph/Pallas escape hatch earning its keep (the role
TensorRT plays behind the reference's subgraph framework,
`src/operator/subgraph/partition_graph.cc:767`): plain XLA attention
materializes the (B, H, T, T) score tensor in HBM; these kernels stream KV
tiles through VMEM with the online-softmax recurrence, so HBM traffic is
O(T·D) instead of O(T²) — the standard flash-attention win.

**The kernels.**  One forward kernel for r >= 1 query heads a key-value
head, in two grid forms, and one backward kernel:

* `_fwd_kernel` (`flash_attention_fwd`): grid (batch·key-value heads, query
  blocks).  A grid step holds a block of `block_q` queries of the r query
  heads that share the key-value head, stacked as r·block_q rows of D, and
  the head's whole K and V in VMEM (fetched once a head: their block index
  does not move over its query blocks, so they are read once for the r
  heads they serve and never repeated in HBM).  Loops walk tiles of keys
  as `tile_runs` states them for the mask (below): tiles the mask empties
  never run, tiles it leaves whole skip the mask, the others pay the
  mask's VPU passes.  Scores, running
  maximum, running sum and accumulator are float32; the probabilities are
  rounded to V's type for the second product; the softmax scale is folded
  into q where that is exact (a power of two: 1/8 and 1/16 at heads of 64
  and 256), else it multiplies the float32 scores (`_fold_scale`).  r = 1
  is plain multi-head attention, what this kernel was before grouped heads.
* `_fwd_kernel_stream`: the same tile step on a grid (…, query blocks, KV
  tiles) for K and V past the VMEM budget (`MXNET_FLASH_VMEM_MB`, through
  `flash_attention_partial` only): one tile resident, the accumulator
  carried in scratch; a tile above the diagonal is neither computed nor
  fetched (its block index stays at the last tile the queries see).
* `_bwd_kernel` (`flash_attention_bwd`): grid (batch·key-value heads, query
  blocks), whole-KV only.  Kept from the forward pass: the output and a
  float32 log-sum-exp a row; `delta` = rowsum(dO·O) is XLA's.  A tile's
  scores are computed once more, TRANSPOSED (keys down, stacked rows
  across), so the rows' statistics are lane-dense and p^T·dO, ds^T·q add
  into the head's float32 dK and dV, which stay in VMEM over its query
  blocks -- the r query heads' contributions are summed by being rows of
  one product; ds·k goes to the block's dQ.  Five products and one exp a
  score, no block of scores in HBM.

**The mask** is a parameter, `(kind, block_length)`: ("none", 0),
("causal", 0) or ("block_diffusion", B), the last over a sequence of 2L
rows and keys [noisy copy | clean copy], both copies at positions 0..L-1
in blocks of B (b(i) = i // B): a noisy row sees the noisy keys of its own
block and the clean keys of the blocks before it, a clean row the clean
keys of its own block and of those before it (BD3-LM, arXiv:2503.09573).
`tile_runs` is the ONE statement of which tiles of keys a block of queries
runs unmasked, runs under the mask and never runs; the forward kernel, the
backward kernel and the streaming forward walk it with traced bounds,
`ops/attention._xla_blocks` and the tile counters with Python integers.
For `block_diffusion` a block of noisy queries runs the few keys of its
own diagonal as narrow tiles (128 keys where the blocks are shorter), and
the in-tile mask compares a column of the rows' block numbers with a row of
the keys': one pass over the tile.

**Heads of 64** are half a lane tile, and nothing is done about it: K, V
and q tiles are padded to 128 lanes in VMEM (HBM holds them dense), the
score product runs at half the matrix unit's depth and the value product
at half its width.  Two heads a tile would need a block-diagonal operand
(the cross-head products are not wanted), which buys nothing.  Measured on
the v5e at 32 / 8 heads of 64 over 8,192 keys (PERF.md sections 5 and 6):
the forward kernel is bound by the float32 vector work over the scores
(exp, max, sum, and the per-row rescaling once a tile: the same at any head
size; 10.1 ms, and a tile of 1,024 keys where 512 took 19 ms), the
backward kernel by its five products at half the unit (16.6 ms).

Surfaces:

* `flash_attention(q, k, v, causal=...)` — full attention, differentiable
  (custom VJP recomputes blockwise on the backward pass with `jax.numpy`,
  keeping the no-T²-residual property).
* `flash_attention_partial(q, k, v, ...)` — returns the UNNORMALIZED
  accumulator plus per-row (max, sumexp): the exact contract of one ring
  step, so `parallel.ring_attention(..., use_pallas=True)` fuses its local
  block with this kernel while `ppermute` rotates the KV shards.
* `_kernel_forward` / `_kernel_backward` — the jitted kernel calls on the
  kernels' own layout, (B·Hkv, r, T, D) queries against (B·Hkv, S, D) keys:
  what `ops/attention.grouped_query_attention` runs under its custom VJP.

Layout: (B, T, H, D) at the API (the framework's attention layout).  The
platform selects the implementation (`pallas_mode`): on ``tpu`` the kernel
compiles or the call fails; on other backends both surfaces run the jnp
blockwise reference — same math, same signatures, so the CPU test mesh
exercises the identical call graph.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention", "flash_attention_partial", "pallas_mode"]

# float32, not a Python float: under jax_enable_x64 that is 64 bits wide in
# a kernel, which Mosaic cannot lower
_NEG = np.float32(-1e30)


def pallas_mode():
    """``(run the Pallas kernel, interpreted)`` for this process: the
    compiled kernel on ``tpu``, the jnp reference elsewhere.  Interpret
    mode is never chosen for the caller — only ``MXNET_FLASH_INTERPRET=1``
    asks for it, so the CPU suite can test the KERNEL's arithmetic."""
    if os.environ.get("MXNET_FLASH_INTERPRET") == "1":
        return True, True
    return jax.default_backend() == "tpu", False


# ---------------------------------------------------------------------------
# Pallas forward kernel: one (batch x key-value head, query block) grid step
# holds the block's rows of the r query heads that share the head, stacked
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a (m, d) x (n, d) product
_NN = (((1,), (0,)), ((), ()))
I32 = np.int32


NONE, CAUSAL = ("none", 0), ("causal", 0)


def _traced(x):
    return not isinstance(x, (bool, int, np.integer))


def _div(a, b):
    return jax.lax.div(a, b) if _traced(a) else a // b


def _least(a, b):
    return jnp.minimum(a, b) if _traced(a) else min(a, b)


def _clamp(x, hi):
    return jax.lax.clamp(I32(0), x, hi) if _traced(x) else min(max(x, 0), hi)


def _pick(cond, a, b):
    return jnp.where(cond, a, b) if _traced(cond) else (a if cond else b)


def tile_runs(mask, q_first, bq, block_k, kv_len, narrow=None):
    """The walk of one block of `bq` queries over the keys, as runs
    `(first, last, width, rule)`: tiles first .. last - 1 of `width` keys
    (tile i holds keys i width .. (i + 1) width - 1) run, under the in-tile
    mask `rule` ("causal", "diagonal", "clean") or, where it is None,
    whole; no other tile runs.  `q_first`: for "causal" the first query's
    position past the first key's; for "block_diffusion" its row among the
    2L (a block of queries lies in one copy, and a tile in one).  A Python
    integer gives Python integers (XLA's form, the counters), a traced one
    traced bounds (the kernels' loops).

    causal: the tiles strictly under the diagonal whole, the ones that
    touch it masked, the rest never.  block_diffusion (L = kv_len / 2, B
    the block length, blocks lo .. hi hold the queries; `lag` 1 for noisy
    queries, which see the clean blocks BEFORE their own, 0 for clean ones,
    which see their own too): noisy queries run the noisy keys of blocks lo
    .. hi as tiles of `narrow` keys ("diagonal"; whole where one block
    holds the queries and the tile), every query the clean keys before
    (lo + 1 - lag) B whole and those up to (hi + 1 - lag) B masked
    ("clean")."""
    kind, B = mask
    # int32 throughout: under jax_enable_x64 a Python 0 is 64 bits wide,
    # which Mosaic cannot lower
    c = I32 if _traced(q_first) else int
    nk = c(kv_len // block_k)
    if kind == "none":
        return [(c(0), nk, block_k, None)]
    if kind == "causal":
        n_whole = _clamp(_div(q_first, c(block_k)), nk)
        return [(c(0), n_whole, block_k, None),
                (n_whole, _clamp(_div(q_first + c(bq - 1 + block_k),
                                      c(block_k)), nk), block_k, "causal")]
    L, w, B = c(kv_len // 2), narrow or block_k, c(B)
    noisy = q_first < L
    lag = _pick(noisy, c(1), c(0))
    p0 = q_first - _pick(noisy, c(0), L)
    lo, hi = _div(p0, B), _div(p0 + c(bq - 1), B)
    # the noisy keys of the queries' own blocks: none for clean queries
    a0 = _div(lo * B, c(w))
    a1 = _pick(noisy, _div(_least((hi + c(1)) * B, L) + c(w - 1), c(w)), a0)
    runs = [(a0, a1, w, "diagonal")]
    if B >= w:
        # tiles inside the one block that holds every query: whole
        one = lo == hi
        w0 = _pick(one, _div(lo * B + c(w - 1), c(w)), a1)
        w1 = _pick(one, _div(_least((lo + c(1)) * B, L), c(w)), a1)
        w0 = _pick(noisy, w0, a0)
        w1 = _pick(noisy, _pick(w1 > w0, w1, w0), a0)
        runs = [(a0, w0, w, "diagonal"), (w0, w1, w, None),
                (w1, a1, w, "diagonal")]
    nl = c(kv_len // 2 // block_k)
    whole = _div(_least((lo + c(1) - lag) * B, L), c(block_k))
    some = _div(_least((hi + c(1) - lag) * B, L) + c(block_k - 1),
                c(block_k))
    return runs + [(nl, nl + whole, block_k, None),
                   (nl + whole, nl + some, block_k, "clean")]


def seen(mask, q_pos, k_pos, period=None):
    """(queries, keys) booleans from the rows' and keys' positions: the
    mask written out (XLA's form; the kernels' in-tile rules are its
    cases).  `period`: the L of `block_diffusion`."""
    kind, B = mask
    q, k = q_pos[:, None], k_pos[None, :]
    if kind == "causal":
        return q >= k
    q_clean, k_clean = q >= period, k >= period
    qb = (q - jnp.where(q_clean, period, 0)) // B
    kb = (k - jnp.where(k_clean, period, 0)) // B
    return jnp.where(k_clean, jnp.where(q_clean, kb <= qb, kb < qb),
                     jnp.logical_and(jnp.logical_not(q_clean), kb == qb))


def _rows_of(q_ref):
    """The block's queries as rows: (1, r, block_q, D) -> (r block_q, D),
    query head j of the group in rows j block_q .. (j + 1) block_q."""
    _, r, bq, d = q_ref.shape
    return q_ref[0].reshape(r * bq, d), r, bq


def _init(acc_scr, m_scr, l_scr):
    m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


class _Rows:
    """What the in-tile mask needs of a grid step's stacked rows, made once
    a step: for "causal" their positions as a whole tile of `block_k` keys
    (what PR 35's kernels compared), for "block_diffusion" the number of
    each row's block inside its copy, as a column (or, `across`, for the
    backward kernel's transposed tiles, as a row), and `lag`."""

    def __init__(self, mask, q_start, r, bq, block_k, kv_len, across=False):
        self.mask, self.across = mask, across
        kind, B = mask
        if kind == "causal":
            shape = (block_k, r * bq) if across else (r * bq, block_k)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, int(across))
            self.q_pos = q_start + (jax.lax.rem(row, I32(bq)) if r > 1
                                    else row)
        elif kind == "block_diffusion":
            L = I32(kv_len // 2)
            noisy = q_start < L
            self.lag = jnp.where(noisy, I32(1), I32(0))
            shape = (1, r * bq) if across else (r * bq, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, int(across))
            within = jax.lax.rem(row, I32(bq)) if r > 1 else row
            self.block = jax.lax.div(
                q_start - jnp.where(noisy, I32(0), L) + within, I32(B))
            self.period = L

    def seen(self, rule, shape, k_start):
        """The tile's booleans under `rule`, keys from `k_start` on."""
        axis = 0 if self.across else 1
        if rule == "causal":
            return self.q_pos >= k_start + jax.lax.broadcasted_iota(
                jnp.int32, shape, axis)
        width = shape[axis]
        key = jax.lax.broadcasted_iota(
            jnp.int32, (width, 1) if self.across else (1, width), axis)
        if rule == "diagonal":
            return jax.lax.div(k_start + key, I32(self.mask[1])) == self.block
        return jax.lax.div(k_start - self.period + key,
                           I32(self.mask[1])) + self.lag <= self.block


def _tile_step(q, ks, vs, acc_scr, m_scr, l_scr, scale, seen):
    """One (rows, width) tile of the online-softmax recurrence: scores,
    running maximum and sum and the accumulator float32, the probabilities
    rounded to the value's type for the second product.  `seen(shape)`: the
    tile's mask; None: no key of the tile is masked for any row."""
    s = jax.lax.dot_general(q, ks, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * np.float32(scale)
    if seen is not None:
        s = jnp.where(seen(s.shape), s, _NEG)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(vs.dtype), vs, _NN, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _finish(o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, normalize):
    _, r, bq, dv = o_ref.shape
    acc = acc_scr[...]
    if normalize:
        acc = acc / l_scr[...]
    o_ref[0] = acc.reshape(r, bq, dv).astype(o_ref.dtype)
    m_ref[0, 0] = m_scr[:, 0]
    l_ref[0, 0] = l_scr[:, 0]


def _walk_tiles(tile, runs):
    """`tile(i, width, rule)` over the runs of tiles that `tile_runs`
    states for a block of queries: a loop a run, its bounds traced (offsets
    are ring positions), so a tile the mask empties never executes (the
    structural win the unfused path cannot have -- it always materializes
    all T x T scores), a whole tile pays no mask, and only the others pay
    the mask's VPU passes."""
    for first, last, width, rule in runs:
        jax.lax.fori_loop(
            first, last,
            lambda i, _, width=width, rule=rule: tile(i, width, rule), None)


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                *, block_k, mask, narrow, kv_len, scale, normalize):
    """Whole-KV kernel: the head's K and V stay in VMEM over its query
    blocks (read from HBM once for the r query heads they serve), loops
    walk their tiles."""
    from jax.experimental import pallas as pl

    q, r, bq = _rows_of(q_ref)
    _init(acc_scr, m_scr, l_scr)
    q_start = qoff_ref[0] + pl.program_id(1) * I32(bq)
    rows = _Rows(mask, q_start, r, bq, block_k, kv_len)

    def tile(i, width, rule):
        first = pl.multiple_of(i * I32(width), width)
        at = pl.ds(first, width)
        _tile_step(q, k_ref[0, at, :], v_ref[0, at, :], acc_scr, m_scr,
                   l_scr, scale, rule and (lambda shape: rows.seen(
                       rule, shape, koff_ref[0] + first)))

    _walk_tiles(tile, tile_runs(mask, q_start - koff_ref[0], bq, block_k,
                                kv_len, narrow))
    _finish(o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, normalize)


def _fwd_kernel_stream(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                       *, block_k, mask, kv_len, scale, normalize):
    """KV-streaming variant: one (BH, q-block, KV-tile) grid step per
    invocation, accumulator carried in VMEM scratch across the innermost
    grid axis.  Holds only ONE (block_k, D) K/V tile in VMEM at a time, so
    kv_len is bounded by HBM, not VMEM -- the long-context envelope
    (T=32k+ causal) the whole-KV kernel cannot reach.  A grid step whose
    tile lies in no run of `tile_runs` skips its compute via pl.when, and
    a tile past the last one the block of queries sees is not fetched
    (`_kernel_forward`'s index map stays there); a whole tile skips the
    mask."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    q, r, bq = _rows_of(q_ref)

    @pl.when(j == I32(0))
    def _():
        _init(acc_scr, m_scr, l_scr)

    q_start = qoff_ref[0] + pl.program_id(1) * I32(bq)
    k_start = koff_ref[0] + j * I32(block_k)

    def tile(rule):
        rows = rule and _Rows(mask, q_start, r, bq, block_k, kv_len)
        _tile_step(q, k_ref[0], v_ref[0], acc_scr, m_scr, l_scr, scale,
                   rule and (lambda shape: rows.seen(rule, shape, k_start)))

    if mask[0] == "none":
        tile(None)
    else:
        for first, last, _, rule in tile_runs(
                mask, q_start - koff_ref[0], bq, block_k, kv_len):
            pl.when(jnp.logical_and(j >= first, j < last))(
                lambda rule=rule: tile(rule))

    @pl.when(j == pl.num_programs(2) - I32(1))
    def _():
        _finish(o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, normalize)


# VMEM the kernels ask for (a head's K and V whole, in the backward pass
# their float32 gradients too, the stacked rows' blocks and the float32
# tiles of scores): the v5e's scoped default is 16 MiB of its 128
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _vmem_budget_bytes():
    from .. import config as _config
    return int(float(_config.get("MXNET_FLASH_VMEM_MB")) * 2 ** 20)


def _streams(kv_len, d, itemsize):
    """Whether K and V pass the VMEM budget whole.  The pipeline
    double-buffers every blocked input, so each counts twice."""
    return 2 * 2 * kv_len * d * itemsize > _vmem_budget_bytes()


# Jitted, so that a program that traces the operator again (the primal, a
# recomputed forward, each fit's shape inference) finds the kernel's body
# traced.
@functools.partial(jax.jit, static_argnames=(
    "mask", "narrow", "block_q", "block_k", "scale", "normalize", "stream",
    "interpret"))
def _kernel_forward(q4, k3, v3, q_off, k_off, *, block_q, block_k,
                    mask=NONE, narrow=None, scale=1.0, normalize=False,
                    stream=False, interpret=False):
    """q4 (BH, r, Tq, D) against k3 (BH, S, D) and v3 (BH, S, Dv): o (BH,
    r, Tq, Dv) in q's type (the accumulator, or with `normalize` the
    output), and per row the float32 maximum and sum of exponentials, each
    (BH, 1, Tq r) BLOCK by block: (query block, head of the group, row).
    `block_q` divides Tq and `block_k` S.  `mask`: the kernels' mask
    parameter; `narrow`: the width of the tiles on
    `block_diffusion`'s noisy diagonal (the whole-KV form only)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, r, Tq, D = q4.shape
    kv_len, Dv = k3.shape[1], v3.shape[2]
    rows = r * block_q
    causal = mask == CAUSAL
    # int32 throughout: under jax_enable_x64 a Python 0 in an index map is
    # 64 bits wide, which Mosaic cannot lower
    zero = I32(0)
    common = dict(block_k=block_k, mask=mask, kv_len=kv_len, scale=scale,
                  normalize=normalize)
    if stream:
        grid = (BH, Tq // block_q, kv_len // block_k)
        kernel = functools.partial(_fwd_kernel_stream, **common)
        kv_rows = block_k

        def tile(b, i, j, qoff, koff):
            # a tile above the diagonal is not fetched: the step stays at
            # the last tile its block of queries sees
            if causal:
                last = jax.lax.div(qoff[0] + (i + I32(1)) * I32(block_q) -
                                   I32(1) - koff[0], I32(block_k))
                j = jax.lax.clamp(zero, last, j)
            return b, j, zero
    else:
        # the whole (kv_len, D) K and V of a head in VMEM: fast, and the
        # loop's dynamic bounds skip above-diagonal tiles entirely
        grid = (BH, Tq // block_q)
        kernel = functools.partial(_fwd_kernel, narrow=narrow, **common)
        kv_rows = kv_len

        def tile(b, i, qoff, koff):
            return b, zero, zero

    def rows_of(b, i, *_):      # whatever follows: a key tile, the offsets
        return b, zero, i, zero
    stat = pl.BlockSpec((1, 1, rows), lambda b, i, *_: (b, zero, i))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # q_off, k_off: (1,) each
            grid=grid,
            in_specs=[pl.BlockSpec((1, r, block_q, D), rows_of),
                      pl.BlockSpec((1, kv_rows, D), tile),
                      pl.BlockSpec((1, kv_rows, Dv), tile)],
            out_specs=[pl.BlockSpec((1, r, block_q, Dv), rows_of),
                       stat, stat],
            scratch_shapes=[pltpu.VMEM((rows, Dv), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, r, Tq, Dv), q4.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq * r), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Tq * r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")[
                :len(grid)],
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="flash_attention_fwd",
    )(jnp.asarray(q_off, jnp.int32).reshape(1),
      jnp.asarray(k_off, jnp.int32).reshape(1), q4, k3, v3)


def _bwd_kernel(qoff_ref, koff_ref, q_ref, do_ref, lse_ref, delta_ref,
                k_ref, v_ref, dq_ref, dk_ref, dv_ref, dq_scr,
                *, block_k, mask, narrow, kv_len, scale, dq_scale):
    """The backward pass of `_fwd_kernel`, one query block a grid step: the
    scores of a tile are computed once more, TRANSPOSED (keys down, the
    stacked rows across), so that the row's log-sum-exp and `delta` are
    lane-dense rows and four of the five products need no transpose: p^T
    do and ds^T q add into the head's float32 dK and dV, which stay in
    VMEM over its query blocks (the r query heads of the group summed by
    being rows of one product); ds k, the one that contracts over the
    keys, into the block's dQ."""
    from jax.experimental import pallas as pl

    q, r, bq = _rows_of(q_ref)
    do = do_ref[0].reshape(q.shape[0], do_ref.shape[3])
    lse, delta = lse_ref[0], delta_ref[0]               # (1, rows)
    i = pl.program_id(1)

    @pl.when(i == I32(0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
    q_start = qoff_ref[0] + i * I32(bq)
    rows = _Rows(mask, q_start, r, bq, block_k, kv_len, across=True)

    def tile(j, width, rule):
        first = pl.multiple_of(j * I32(width), width)
        at = pl.ds(first, width)
        ks, vs = k_ref[0, at, :], v_ref[0, at, :]
        s = jax.lax.dot_general(ks, q, _NT,
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * np.float32(scale)
        if rule:
            s = jnp.where(rows.seen(rule, s.shape, koff_ref[0] + first), s,
                          _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(vs, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dv_ref[0, at, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_ref[0, at, :] += jax.lax.dot_general(
            ds, q, _NN, preferred_element_type=jnp.float32)
        dq_scr[...] += jax.lax.dot_general(
            ds, ks, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk_tiles(tile, tile_runs(mask, q_start - koff_ref[0], bq, block_k,
                                kv_len, narrow))
    dq_ref[0] = (dq_scr[...] * np.float32(dq_scale)).reshape(
        dq_ref.shape[1:]).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mask", "narrow", "block_q", "block_k", "scale", "dq_scale",
    "interpret"))
def _kernel_backward(q4, k3, v3, do4, lse, delta, q_off, k_off, *, block_q,
                     block_k, mask=NONE, narrow=None, scale=1.0,
                     dq_scale=1.0, interpret=False):
    """dQ (BH, r, Tq, D) in q's type, float32 dK (BH, S, D) and dV (BH, S,
    Dv) of `_kernel_forward(normalize=True)` at the same blocks: `lse` the
    rows' m + log(l) and `delta` their sum of dO * O, (BH, 1, Tq r) block by
    block as the forward kernel writes them.  The whole-KV form only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, r, Tq, D = q4.shape
    kv_len, Dv = k3.shape[1], v3.shape[2]
    rows = r * block_q
    zero = I32(0)

    def rows_of(b, i, qoff, koff):
        return b, zero, i, zero

    def stat(b, i, qoff, koff):
        return b, zero, i

    def whole(b, i, qoff, koff):
        return b, zero, zero
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_k=block_k, mask=mask,
                          narrow=narrow, kv_len=kv_len, scale=scale,
                          dq_scale=dq_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, Tq // block_q),
            in_specs=[pl.BlockSpec((1, r, block_q, D), rows_of),
                      pl.BlockSpec((1, r, block_q, Dv), rows_of),
                      pl.BlockSpec((1, 1, rows), stat),
                      pl.BlockSpec((1, 1, rows), stat),
                      pl.BlockSpec((1, kv_len, D), whole),
                      pl.BlockSpec((1, kv_len, Dv), whole)],
            out_specs=[pl.BlockSpec((1, r, block_q, D), rows_of),
                       pl.BlockSpec((1, kv_len, D), whole),
                       pl.BlockSpec((1, kv_len, Dv), whole)],
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q4.shape, q4.dtype),
                   jax.ShapeDtypeStruct(k3.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v3.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="flash_attention_bwd",
    )(jnp.asarray(q_off, jnp.int32).reshape(1),
      jnp.asarray(k_off, jnp.int32).reshape(1), q4, do4, lse, delta, k3, v3)


def _fold_scale(q, d):
    """(q, what is left to apply to the scores): the softmax scale goes
    into q once (saves a VPU pass over every tile of scores) where that is
    exact, a power of two; else it multiplies the float32 scores."""
    scale = float(d) ** -0.5
    if math.frexp(scale)[0] == 0.5:
        return q * jnp.asarray(scale, q.dtype), 1.0
    return q, scale


def _partial_tpu(q3, k3, v3, q_off, k_off, causal, block_q, block_k,
                 interpret=False):
    """(BH, Tq, D) partial attention on TPU via the Pallas kernel."""
    BH, Tq, D = q3.shape
    kv_len = k3.shape[1]
    block_q = min(block_q, Tq)
    block_k = min(block_k, kv_len)
    # blocks must tile exactly (a short tail block would read out of range)
    while Tq % block_q:
        block_q //= 2
    while kv_len % block_k:
        block_k //= 2
    q3, scale = _fold_scale(q3, D)
    o, m, l = _kernel_forward(
        q3[:, None], k3, v3, q_off, k_off, mask=CAUSAL if causal else NONE,
        block_q=block_q, block_k=block_k, scale=scale,
        stream=_streams(kv_len, D, q3.dtype.itemsize), interpret=interpret)
    return o[:, 0], m, l


def _partial_ref(q3, k3, v3, q_off, k_off, causal, block_k):
    """jnp blockwise partial: the implementation off-TPU and the test
    reference (identical contract)."""
    BH, Tq, D = q3.shape
    kv_len = k3.shape[1]
    scale = 1.0 / (D ** 0.5)
    nk = -(-kv_len // block_k)
    m = jnp.full((BH, Tq), _NEG, jnp.float32)
    l = jnp.zeros((BH, Tq), jnp.float32)
    acc = jnp.zeros((BH, Tq, D), jnp.float32)
    q_pos = q_off + jnp.arange(Tq)
    for i in range(nk):
        ks = k3[:, i * block_k:(i + 1) * block_k]
        vs = v3[:, i * block_k:(i + 1) * block_k]
        s = jnp.einsum("bqd,bkd->bqk", q3, ks).astype(jnp.float32) * scale
        if causal:
            k_pos = k_off + i * block_k + jnp.arange(ks.shape[1])
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + \
            jnp.einsum("bqk,bkd->bqd", p.astype(vs.dtype), vs)
        m = m_new
    return acc.astype(q3.dtype), m, l


def flash_attention_partial(q, k, v, q_off=0, k_off=0, causal=False,
                            block_q=256, block_k=256):
    """Unnormalized attention over one KV shard.

    q: (B, Tq, H, D), k/v: (B, Tk, H, D).  Returns (o_unnorm, m, l) with
    o_unnorm (B, Tq, H, D) and m/l (B, H, Tq) in fp32 — combinable across
    shards with the online-softmax merge (ring attention's carry).
    q_off/k_off are the global sequence offsets for causal masking (traced
    scalars are fine: they ride SMEM, not the compiled shape).
    """
    B, Tq, H, D = q.shape
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], D)
    use, interpret = pallas_mode()
    if use:
        o3, m3, l3 = _partial_tpu(q3, k3, v3, q_off, k_off, causal,
                                  block_q, block_k, interpret=interpret)
    else:
        o3, m3, l3 = _partial_ref(q3, k3, v3, q_off, k_off, causal, block_k)
    o = o3.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    return o, m3.reshape(B, H, Tq), l3.reshape(B, H, Tq)


# ---------------------------------------------------------------------------
# Full attention with custom VJP (blockwise recompute backward)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, block_q=256, block_k=256):
    """Exact attention without the (T, T) score tensor in HBM.

    q/k/v: (B, T, H, D) -> (B, T, H, D).  Forward is the Pallas kernel on
    TPU; backward recomputes attention blockwise (standard
    flash-attention backward, here via jnp so XLA fuses it — residuals are
    O(T·D), never O(T²))."""
    o, m, l = flash_attention_partial(q, k, v, 0, 0, causal,
                                      block_q, block_k)
    return o / l.transpose(0, 2, 1)[..., None].astype(o.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    o, m, l = flash_attention_partial(q, k, v, 0, 0, causal,
                                      block_q, block_k)
    out = o / l.transpose(0, 2, 1)[..., None].astype(o.dtype)
    return out, (q, k, v, out, m, l)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, m, l = res
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    # delta_i = rowsum(dO * O) — the softmax-jacobian shortcut
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)          # (B, H, T)
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)     # (B, H, T, D)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    gh = g.transpose(0, 2, 1, 3).astype(jnp.float32)
    dq = jnp.zeros_like(qh)
    dk = jnp.zeros_like(kh)
    dv = jnp.zeros_like(vh)
    nk = -(-T // block_k)
    q_pos = jnp.arange(T)
    for i in range(nk):
        sl = slice(i * block_k, (i + 1) * block_k)
        ks, vs = kh[:, :, sl], vh[:, :, sl]
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, ks) * scale
        if causal:
            k_pos = jnp.arange(T)[sl]
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG)
        p = jnp.exp(s - m[..., None]) / l[..., None]     # (B, H, T, BK)
        dv = dv.at[:, :, sl].add(jnp.einsum("bhqk,bhqd->bhkd", p, gh))
        dp = jnp.einsum("bhqd,bhkd->bhqk", gh, vs)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, ks)
        dk = dk.at[:, :, sl].add(jnp.einsum("bhqk,bhqd->bhkd", ds, qh))
    back = lambda a, like: a.transpose(0, 2, 1, 3).astype(like.dtype)
    return back(dq, q), back(dk, k), back(dv, v)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
