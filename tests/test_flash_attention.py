"""Flash-attention kernel: math parity with plain softmax attention, the
Pallas kernel itself (interpreter mode on the CPU mesh), gradients through
the custom VJP, and the ring-attention integration."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.flash_attention import (flash_attention,
                                                     flash_attention_partial)


def _naive(q, k, v, causal=False):
    B, T, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(k.shape[1])[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(B=2, T=64, H=2, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype("f4"))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, 32, 16)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_naive(causal):
    q, k, v = _qkv(T=32)
    tgt = jnp.asarray(np.random.RandomState(1)
                      .randn(*q.shape).astype("f4"))

    def loss_flash(q, k, v):
        return jnp.sum((flash_attention(q, k, v, causal, 16, 16) - tgt) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum((_naive(q, k, v, causal) - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_interpreted_matches_ref(monkeypatch, causal):
    """Run the ACTUAL Pallas kernel (interpreter mode) against the jnp
    fallback — this is what validates the kernel itself off-TPU."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    q, k, v = _qkv(T=32, D=8)
    o_k, m_k, l_k = flash_attention_partial(q, k, v, 0, 0, causal, 16, 16)
    monkeypatch.delenv("MXNET_FLASH_INTERPRET")
    o_r, m_r, l_r = flash_attention_partial(q, k, v, 0, 0, causal, 16, 16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_pallas_path(causal):
    """ring_attention(use_pallas=True) must equal the plain path and full
    attention on the 8-device mesh."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from incubator_mxnet_tpu import parallel as par
    from incubator_mxnet_tpu.parallel.ring_attention import ring_attention

    import jax as _jax
    mesh = par.make_mesh({"sp": 4}, devices=_jax.devices()[:4])
    q, k, v = _qkv(B=2, T=64, H=2, D=16)

    def run(use_pallas):
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal,
                                           use_pallas=use_pallas),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        return jax.jit(fn)(q, k, v)

    ref = _naive(q, k, v, causal)
    for use_pallas in (False, True):
        out = run(use_pallas)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"use_pallas={use_pallas}")
