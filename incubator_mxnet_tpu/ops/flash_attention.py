"""Flash attention as a Pallas TPU kernel.

The framework's subgraph/Pallas escape hatch earning its keep (the role
TensorRT plays behind the reference's subgraph framework,
`src/operator/subgraph/partition_graph.cc:767`): plain XLA attention
materializes the (B, H, T, T) score tensor in HBM; this kernel streams KV
blocks through VMEM with the online-softmax recurrence, so HBM traffic is
O(T·D) instead of O(T²) — the standard flash-attention win, implemented
here as a `pl.pallas_call` grid over (batch·heads, query blocks).

Two surfaces:

* `flash_attention(q, k, v, causal=...)` — full attention, differentiable
  (custom VJP recomputes blockwise on the backward pass, keeping the
  no-T²-residual property).
* `flash_attention_partial(q, k, v, ...)` — returns the UNNORMALIZED
  accumulator plus per-row (max, sumexp): the exact contract of one ring
  step, so `parallel.ring_attention(..., use_pallas=True)` fuses its local
  block with this kernel while `ppermute` rotates the KV shards.

Layout: (B, T, H, D) at the API (the framework's attention layout); the
kernel runs on (B·H, T, D).  The platform selects the implementation
(`pallas_mode`): on ``tpu`` the kernel compiles or the call fails; on
other backends both surfaces run the jnp blockwise reference — same
math, same signatures, so the CPU test mesh exercises the identical
call graph.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "flash_attention_partial", "pallas_mode"]

_NEG = -1e30


def pallas_mode():
    """``(run the Pallas kernel, interpreted)`` for this process: the
    compiled kernel on ``tpu``, the jnp reference elsewhere.  Interpret
    mode is never chosen for the caller — only ``MXNET_FLASH_INTERPRET=1``
    asks for it, so the CPU suite can test the KERNEL's arithmetic."""
    if os.environ.get("MXNET_FLASH_INTERPRET") == "1":
        return True, True
    return jax.default_backend() == "tpu", False


# ---------------------------------------------------------------------------
# Pallas kernel: one (BH, q-block) program; fori_loop over KV blocks
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                *, block_k, causal, kv_len):
    from jax.experimental import pallas as pl

    q = q_ref[0]                                # (BQ, D), PRE-SCALED
    bq = q.shape[0]
    nk = pl.cdiv(kv_len, block_k)

    m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
    l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_start = qoff_ref[0] + pl.program_id(1) * bq
    if causal:
        q_pos = q_start + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def compute(i, masked=True):
        ks = k_ref[0, pl.ds(i * block_k, block_k), :]   # (BK, D)
        vs = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, BK)
        if causal and masked:
            # only blocks touching the diagonal need the mask; interior
            # blocks skip the iota/compare/select VPU passes
            k_pos = koff_ref[0] + i * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new

    if causal:
        # split at the diagonal: blocks strictly above it are fully masked
        # and never execute (the structural causal win the unfused path
        # cannot have — it always materializes all T x T scores); blocks
        # strictly below need no mask at all; only diagonal-touching
        # blocks pay the mask's VPU passes.  Offsets are traced ring
        # positions, so both bounds are dynamic.
        koff = koff_ref[0]
        n_unmasked = jnp.clip((q_start - koff) // block_k, 0, nk)
        last = (q_start + bq - 1 - koff) // block_k
        nk_run = jnp.clip(last + 1, 0, nk)
        jax.lax.fori_loop(0, n_unmasked,
                          lambda i, _: (compute(i, masked=False), 0)[1], 0)
        jax.lax.fori_loop(n_unmasked, nk_run,
                          lambda i, _: (compute(i, masked=True), 0)[1], 0)
    else:
        jax.lax.fori_loop(0, nk,
                          lambda i, _: (compute(i, masked=False), 0)[1], 0)
    o_ref[0] = acc_scr[:].astype(o_ref.dtype)
    m_ref[0, 0] = m_scr[:, 0]
    l_ref[0, 0] = l_scr[:, 0]


def _fwd_kernel_stream(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr,
                       *, block_k, causal):
    """KV-streaming variant: one (BH, q-block, KV-block) grid step per
    invocation, accumulator carried in VMEM scratch across the innermost
    grid axis.  Holds only ONE (block_k, D) K/V tile in VMEM at a time, so
    kv_len is bounded by HBM, not VMEM — the long-context envelope
    (T=32k+ causal) the whole-KV kernel cannot reach.  Causal grid steps
    entirely above the diagonal skip their compute via pl.when (their
    block DMA still happens — the structural-skip win of the whole-KV
    kernel's dynamic loop bounds is the price of streaming)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    nk = pl.num_programs(2)
    q = q_ref[0]                                # (BQ, D), PRE-SCALED
    bq = q.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_start = qoff_ref[0] + pl.program_id(1) * bq
    k_start = koff_ref[0] + j * block_k

    def _compute():
        ks = k_ref[0]                           # (BK, D)
        vs = v_ref[0]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BQ, BK)
        if causal:
            q_pos = q_start + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new

    if causal:
        @pl.when(q_start + bq - 1 >= k_start)
        def _run():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[:, 0]
        l_ref[0, 0] = l_scr[:, 0]


def _stream_tpu(q3, k3, v3, q_off, k_off, causal, block_q, block_k,
                interpret=False):
    """KV-streaming pallas_call (see _fwd_kernel_stream)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = q3.shape
    kv_len = k3.shape[1]
    scale = 1.0 / (D ** 0.5)
    q3 = (q3.astype(jnp.float32) * scale).astype(q3.dtype)
    grid = (BH, pl.cdiv(Tq, block_q), pl.cdiv(kv_len, block_k))
    kernel = functools.partial(_fwd_kernel_stream, block_k=block_k,
                               causal=causal)
    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # q_off (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # k_off (1,)
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      q3, k3, v3)
    return o, m, l


def _vmem_budget_bytes():
    from .. import config as _config
    return int(float(_config.get("MXNET_FLASH_VMEM_MB")) * 2 ** 20)


def _partial_tpu(q3, k3, v3, q_off, k_off, causal, block_q, block_k,
                 interpret=False):
    """(BH, Tq, D) partial attention on TPU via the Pallas kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = q3.shape
    kv_len = k3.shape[1]
    block_q = min(block_q, Tq)
    block_k = min(block_k, kv_len)
    # blocks must tile exactly (a short tail block would read out of range)
    while Tq % block_q:
        block_q //= 2
    while kv_len % block_k:
        block_k //= 2
    # whole-KV kernel maps (kv_len, D) K and V blocks into VMEM (fast, and
    # its dynamic loop bounds skip above-diagonal blocks entirely); past
    # the VMEM budget, stream KV tiles through the grid instead.  The
    # pipeline double-buffers every blocked input, so K and V each
    # count twice against the budget.
    kv_bytes = 2 * 2 * kv_len * D * q3.dtype.itemsize
    if kv_bytes > _vmem_budget_bytes():
        return _stream_tpu(q3, k3, v3, q_off, k_off, causal,
                           block_q, block_k, interpret=interpret)
    # fold the softmax scale into q once (saves a full VPU pass over the
    # (BQ, BK) score block per inner iteration)
    scale = 1.0 / (D ** 0.5)
    q3 = (q3.astype(jnp.float32) * scale).astype(q3.dtype)
    grid = (BH, pl.cdiv(Tq, block_q))

    kernel = functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                               kv_len=kv_len)
    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # q_off (1,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # k_off (1,)
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_len, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, kv_len, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      q3, k3, v3)
    return o, m, l


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
