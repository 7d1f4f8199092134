"""The Pallas kernels on the REAL chip: flash attention (whole-KV and
KV-streaming variants, forward and backward) and the fused FC+ReLU of the
subgraph backend, each against its jnp reference.

On `tpu` the kernels compile or the call fails — every test here first
proves from the traced program that the Pallas path, not the reference,
produced the result.

The causal long-sequence case is the kernel's structural win over plain
XLA: it streams KV blocks through VMEM with a dynamic loop bound that never
executes above-diagonal blocks and only masks diagonal-touching ones, while
the plain path materializes and masks all T x T scores in HBM.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops import flash_attention as fa
from incubator_mxnet_tpu.ops.flash_attention import (flash_attention,
                                                     flash_attention_partial)


def _assert_pallas(fn, *args):
    """The traced program calls the Pallas kernel and lowers it to a
    Mosaic custom call — `_partial_ref`/interpret mode would show neither."""
    assert fa.pallas_mode() == (True, False)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(*args))
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _reference(q, k, v, causal, block_k=512):
    """The module's own jnp blockwise reference, in fp32."""
    B, T, H, D = q.shape
    to3 = lambda a: a.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B * H, a.shape[1], D)
    o, _, l = fa._partial_ref(to3(q), to3(k), to3(v), 0, 0, causal, block_k)
    return (o / l[..., None]).reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _naive(q, k, v):
    d = q.shape[-1]
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(B, T, H, D, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5,
                             jnp.bfloat16)
    return mk(), mk(), mk()


@pytest.mark.parametrize("T,variant", [(2048, "whole"), (16384, "stream")])
def test_flash_forward_backward_match_reference(T, variant):
    """bf16, D=128, causal: T=2048 fits the whole-KV kernel, T=16384 is
    past the VMEM budget and streams KV tiles through the grid."""
    B, H, D = 1, 2, 128
    q, k, v = _qkv(B, T, H, D)
    streams = 2 * 2 * T * D * q.dtype.itemsize > fa._vmem_budget_bytes()
    assert streams == (variant == "stream")
    tgt = jnp.asarray(
        np.random.RandomState(1).randn(B, T, H, D).astype("f4") * 0.1)

    flash = lambda q, k, v: flash_attention(q, k, v, True)
    _assert_pallas(flash, q, k, v)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            (fn(q, k, v).astype(jnp.float32) - tgt) ** 2)

    out, grads = jax.jit(lambda q, k, v: (
        flash(q, k, v),
        jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)))(q, k, v)
    ref = lambda q, k, v: _reference(q, k, v, True)
    ref_out, ref_grads = jax.jit(lambda q, k, v: (
        ref(q, k, v),
        jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)))(q, k, v)

    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out), rtol=2e-2, atol=2e-2)
    for g, rg, name in zip(grads, ref_grads, "qkv"):
        g, rg = np.asarray(g, np.float32), np.asarray(rg, np.float32)
        assert np.isfinite(g).all(), name
        # bf16 inputs, fp32 reference: compare at the gradient's own scale
        np.testing.assert_allclose(g, rg, rtol=5e-2,
                                   atol=5e-2 * float(np.abs(rg).max()),
                                   err_msg=f"d{name} ({variant})")


def test_stream_variant_matches_whole_kv_kernel(monkeypatch):
    """The same shape through both kernels (streaming forced by shrinking
    the budget), then the T=32k envelope the whole-KV kernel cannot reach."""
    B, H, D = 1, 1, 64
    q, k, v = _qkv(B, 4096, H, D, seed=1)
    o_whole, _, l_w = flash_attention_partial(q, k, v, 0, 0, True)
    monkeypatch.setenv("MXNET_FLASH_VMEM_MB", "0.1")
    o_stream, _, l_s = flash_attention_partial(q, k, v, 0, 0, True)
    np.testing.assert_allclose(np.asarray(l_w), np.asarray(l_s), rtol=2e-3)
    np.testing.assert_allclose(
        np.asarray(o_whole, dtype=np.float32),
        np.asarray(o_stream, dtype=np.float32), rtol=2e-2, atol=2e-2)

    T = 32768
    q, k, v = _qkv(B, T, H, D, seed=2)
    monkeypatch.setenv("MXNET_FLASH_VMEM_MB", "4")
    assert 2 * 2 * T * D * 2 > fa._vmem_budget_bytes(), \
        "budget must force streaming"
    o, m, l = flash_attention_partial(q, k, v, 0, 0, True)
    l_host = np.asarray(l)
    assert np.isfinite(l_host).all()
    # causal row i attends to i+1 keys: sumexp >= 1 (the diagonal term)
    assert (l_host >= 0.99).all()
    assert np.isfinite(np.asarray(o[0, -1, 0].astype(jnp.float32))).all()


def _seconds_per_call(fn, q, k, v, iters=10, reps=3):
    jax.block_until_ready(fn(q, k, v))             # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        o = q
        for _ in range(iters):
            o = fn(o, k, v)                        # chained: no overlap
        jax.block_until_ready(o)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def test_flash_attention_beats_xla_long_seq():
    B, T, H, D = 2, 8192, 8, 64
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.05,
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    flash_fn = lambda q, k, v: flash_attention(q, k, v, True, 512, 512)
    _assert_pallas(flash_fn, q, k, v)
    flash = jax.jit(flash_fn)
    naive = jax.jit(_naive)

    # correctness on-chip first
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(naive(q, k, v), np.float32), rtol=5e-2, atol=5e-2)

    t_flash = _seconds_per_call(flash, q, k, v)
    t_naive = _seconds_per_call(naive, q, k, v)
    speedup = t_naive / t_flash
    print(f"\nflash {t_flash*1e3:.2f} ms vs plain XLA {t_naive*1e3:.2f} ms "
          f"-> {speedup:.2f}x at causal T={T}")
    assert speedup >= 1.15, (
        f"Pallas flash attention must beat plain XLA by >=1.15x, got "
        f"{speedup:.2f}x ({t_flash*1e3:.1f}ms vs {t_naive*1e3:.1f}ms)")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fc_relu_pallas_matches_reference(dtype):
    """The subgraph backend's fused FC+ReLU at 256x1024 -> 1024."""
    from incubator_mxnet_tpu.subgraph import fused_ops
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 1024).astype("f4") * 0.1, dtype)
    w = jnp.asarray(rng.randn(1024, 1024).astype("f4") * 0.05, dtype)
    b = jnp.asarray(rng.randn(1024).astype("f4") * 0.1, dtype)
    _assert_pallas(fused_ops._fc_relu, x, w, b)
    got = jax.jit(fused_ops._fc_relu)(x, w, b)
    with jax.default_matmul_precision("highest"):
        want = jnp.maximum(
            x.astype(jnp.float32) @ w.astype(jnp.float32).T +
            b.astype(jnp.float32), 0.0)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)
