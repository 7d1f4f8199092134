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
  heads they serve and never repeated in HBM).  A loop walks tiles of
  `block_k` keys: tiles above the diagonal never run, tiles under it skip
  the mask, the ones on it pay the mask's VPU passes.  Scores, running
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


def _rows_of(q_ref):
    """The block's queries as rows: (1, r, block_q, D) -> (r block_q, D),
    query head j of the group in rows j block_q .. (j + 1) block_q."""
    _, r, bq, d = q_ref.shape
    return q_ref[0].reshape(r * bq, d), r, bq


def _row_positions(q_start, r, bq, block_k):
    """(r block_q, block_k) sequence positions of the stacked rows."""
    row = jax.lax.broadcasted_iota(jnp.int32, (r * bq, block_k), 0)
    return q_start + (jax.lax.rem(row, I32(bq)) if r > 1 else row)


def _init(acc_scr, m_scr, l_scr):
    m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _tile_step(q, ks, vs, acc_scr, m_scr, l_scr, scale, q_pos, k_start):
    """One (rows, block_k) tile of the online-softmax recurrence: scores,
    running maximum and sum and the accumulator float32, the probabilities
    rounded to the value's type for the second product.  `q_pos` None: no
    key of the tile is masked for any row."""
    s = jax.lax.dot_general(q, ks, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * np.float32(scale)
    if q_pos is not None:
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG)
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


def _walk_tiles(tile, q_first, bq, block_k, kv_len, causal):
    """`tile(i, masked)` over the tiles of `block_k` keys that a block of
    `bq` queries sees, the first of them `q_first` positions after the
    first key.  Causal, the walk splits at the diagonal: tiles strictly
    above it are fully masked and never execute (the structural causal win
    the unfused path cannot have -- it always materializes all T x T
    scores); tiles strictly below need no mask at all; only
    diagonal-touching tiles pay the mask's VPU passes.  Offsets are traced
    ring positions, so both bounds are dynamic."""
    # int32 throughout: under jax_enable_x64 a Python 0 is 64 bits wide,
    # which Mosaic cannot lower
    zero, nk = I32(0), I32(kv_len // block_k)

    def loop(first, last, masked):
        jax.lax.fori_loop(first, last, lambda i, _: tile(i, masked), None)

    if not causal:
        return loop(zero, nk, False)
    n_unmasked = jax.lax.clamp(zero, jax.lax.div(q_first, I32(block_k)), nk)
    loop(zero, n_unmasked, False)
    loop(n_unmasked, jax.lax.clamp(
        zero, jax.lax.div(q_first + I32(bq - 1 + block_k), I32(block_k)),
        nk), True)


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                *, block_k, causal, kv_len, scale, normalize):
    """Whole-KV kernel: the head's K and V stay in VMEM over its query
    blocks (read from HBM once for the r query heads they serve), a loop
    walks their tiles."""
    from jax.experimental import pallas as pl

    q, r, bq = _rows_of(q_ref)
    _init(acc_scr, m_scr, l_scr)
    q_start = qoff_ref[0] + pl.program_id(1) * I32(bq)
    q_pos = _row_positions(q_start, r, bq, block_k) if causal else None

    def tile(i, masked):
        first = pl.multiple_of(i * I32(block_k), block_k)
        at = pl.ds(first, block_k)
        _tile_step(q, k_ref[0, at, :], v_ref[0, at, :], acc_scr, m_scr,
                   l_scr, scale, q_pos if masked else None,
                   koff_ref[0] + first)

    _walk_tiles(tile, q_start - koff_ref[0], bq, block_k, kv_len, causal)
    _finish(o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, normalize)


def _fwd_kernel_stream(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                       *, block_k, causal, scale, normalize):
    """KV-streaming variant: one (BH, q-block, KV-tile) grid step per
    invocation, accumulator carried in VMEM scratch across the innermost
    grid axis.  Holds only ONE (block_k, D) K/V tile in VMEM at a time, so
    kv_len is bounded by HBM, not VMEM -- the long-context envelope
    (T=32k+ causal) the whole-KV kernel cannot reach.  Causal grid steps
    entirely above the diagonal skip their compute via pl.when, and their
    tile is not fetched (`_kernel_forward`'s index map stays at the last
    tile the block of queries sees); those under it skip the mask."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    q, r, bq = _rows_of(q_ref)

    @pl.when(j == I32(0))
    def _():
        _init(acc_scr, m_scr, l_scr)

    q_start = qoff_ref[0] + pl.program_id(1) * I32(bq)
    k_start = koff_ref[0] + j * I32(block_k)

    def tile(masked):
        q_pos = _row_positions(q_start, r, bq, block_k) if masked else None
        _tile_step(q, k_ref[0], v_ref[0], acc_scr, m_scr, l_scr, scale,
                   q_pos, k_start)

    if causal:
        below = q_start >= k_start + I32(block_k - 1)
        pl.when(below)(lambda: tile(False))
        pl.when(jnp.logical_and(jnp.logical_not(below),
                                q_start + I32(bq - 1) >= k_start))(
            lambda: tile(True))
    else:
        tile(False)

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
    "causal", "block_q", "block_k", "scale", "normalize", "stream",
    "interpret"))
def _kernel_forward(q4, k3, v3, q_off, k_off, *, causal, block_q, block_k,
                    scale=1.0, normalize=False, stream=False,
                    interpret=False):
    """q4 (BH, r, Tq, D) against k3 (BH, S, D) and v3 (BH, S, Dv): o (BH,
    r, Tq, Dv) in q's type (the accumulator, or with `normalize` the
    output), and per row the float32 maximum and sum of exponentials, each
    (BH, 1, Tq r) BLOCK by block: (query block, head of the group, row).
    `block_q` divides Tq and `block_k` S."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, r, Tq, D = q4.shape
    kv_len, Dv = k3.shape[1], v3.shape[2]
    rows = r * block_q
    # int32 throughout: under jax_enable_x64 a Python 0 in an index map is
    # 64 bits wide, which Mosaic cannot lower
    zero = I32(0)
    common = dict(block_k=block_k, causal=causal, scale=scale,
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
        kernel = functools.partial(_fwd_kernel, kv_len=kv_len, **common)
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
                *, block_k, causal, kv_len, scale, dq_scale):
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
    if causal:
        col = jax.lax.broadcasted_iota(jnp.int32, (block_k, r * bq), 1)
        q_pos = q_start + (jax.lax.rem(col, I32(bq)) if r > 1 else col)

    def tile(j, masked):
        first = pl.multiple_of(j * I32(block_k), block_k)
        at = pl.ds(first, block_k)
        ks, vs = k_ref[0, at, :], v_ref[0, at, :]
        s = jax.lax.dot_general(ks, q, _NT,
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * np.float32(scale)
        if masked:
            k_pos = koff_ref[0] + first + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
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

    _walk_tiles(tile, q_start - koff_ref[0], bq, block_k, kv_len, causal)
    dq_ref[0] = (dq_scr[...] * np.float32(dq_scale)).reshape(
        dq_ref.shape[1:]).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "scale", "dq_scale", "interpret"))
def _kernel_backward(q4, k3, v3, do4, lse, delta, q_off, k_off, *, causal,
                     block_q, block_k, scale=1.0, dq_scale=1.0,
                     interpret=False):
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
        functools.partial(_bwd_kernel, block_k=block_k, causal=causal,
                          kv_len=kv_len, scale=scale, dq_scale=dq_scale),
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
        q3[:, None], k3, v3, q_off, k_off, causal=causal, block_q=block_q,
        block_k=block_k, scale=scale,
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
