"""Grouped-query attention's flash kernels on the REAL chip, at the full
shapes of the two cells that hold the operator (`lfm2_24b_a2b`: 2 x 8,192
positions, 32 query on 8 key-value heads of 64; `qwen3_next_80b_a3b`: 2 x
4,096, 16 on 2 heads of 256; bfloat16 q, k, v): the output and the three
gradients against the attention of the cells' plain references
(`benchmark/configs/*_reference.py::_attn_mixer`: float32, every key-value
head repeated, blocks of 512 queries, `Precision.HIGHEST`), written out
here because the references keep it inside their mixer.

What separates the kernels from that reference is bfloat16: the
probabilities are rounded to it for the second product (as in XLA's form),
the backward kernel rounds p and ds for its products, and o, dq, dk, dv are
bfloat16 themselves.  XLA's block form, the operator off the TPU, is run
beside them on the same chip, and the kernels are held to twice its gap or
two roundings of the largest entry (2 x 2^-8), whichever is larger.
Measured on the v5e (PR 35; largest difference over largest entry, at
`lfm2_24b_a2b`'s / `qwen3_next_80b_a3b`'s shape): o 0.0027 / 0.0024 (XLA's
form 0.0036 / 0.0037), dq 0.0043 / 0.0046, dk 0.0062 / 0.0038, dv 0.0033 /
0.0044 (XLA's form the same, but dq 0.0049 and dv 0.0067 at the first).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.ops import attention

# batch, positions, query heads, key-value heads, head size
CELLS = {"lfm2_24b_a2b": (2, 8192, 32, 8, 64),
         "qwen3_next_80b_a3b": (2, 4096, 16, 2, 256)}
ROUNDINGS = 2 * 2.0 ** -8


def _inputs(config, seed):
    b, t, hq, hkv, d = CELLS[config]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, ct = (jax.random.normal(ks[i], (b, t, hq, d)).astype(jnp.bfloat16)
             for i in (0, 3))
    k, v = (jax.random.normal(ks[i], (b, t, hkv, d)).astype(jnp.bfloat16)
            for i in (1, 2))
    return (q, k, v), ct


def _reference(q, k, v):
    b, t, hq, d = q.shape
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, hq // k.shape[2], axis=2) for x in (k, v))

    @jax.checkpoint
    def block(qb, kb, vb, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                       precision="highest") * d ** -0.5
        seen = (first + jnp.arange(qb.shape[1]))[:, None] >= \
            jnp.arange(kb.shape[1])[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, vb, precision="highest")
    return jnp.concatenate(
        [block(q[:, f:f + 512], k[:, :f + 512], v[:, :f + 512], f)
         for f in range(0, t, 512)], axis=1)


def _value_and_grad(fn, ct):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * ct.astype(jnp.float32)), o
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))


def _gaps(got, want):
    (_, o), grads = got
    (_, want_o), want_grads = want
    out = {}
    for name, x, w in zip(("o", "dq", "dk", "dv"), (o,) + tuple(grads),
                          (want_o,) + tuple(want_grads)):
        x, w = (np.asarray(a.astype(jnp.float32), np.float64) for a in (x, w))
        assert np.all(np.isfinite(x)), name
        out[name] = np.abs(x - w).max() / np.abs(w).max()
    return out


@pytest.mark.parametrize("config", sorted(CELLS))
def test_kernels_against_the_float32_reference(config):
    args, ct = _inputs(config, 0)
    kernel, xla = (obs.counter("ops.attention.lowered." + d)
                   for d in ("kernel", "xla"))
    before = kernel.value, xla.value
    run = _value_and_grad(attention.grouped_query_attention, ct)
    assert run.lower(*args).as_text().count("tpu_custom_call") == 2
    assert kernel.value > before[0] and xla.value == before[1]
    got = run(*args)
    assert got[0][1].dtype == jnp.bfloat16
    for g, given in zip(got[1], args):
        assert g.dtype == given.dtype and g.shape == given.shape
    want = _value_and_grad(_reference, ct)(*args)
    kernels = _gaps(got, want)
    blocks = _gaps(_value_and_grad(attention._xla_blocks, ct)(*args), want)
    print(config, "gaps to the float32 reference: kernels", kernels,
          "XLA's block form", blocks)
    for name, gap in kernels.items():
        assert gap <= max(2 * blocks[name], ROUNDINGS), (name, kernels,
                                                         blocks)


# -- the block-diffusion mask (PR 36) ------------------------------------------

# `sdar_30b_a3b_chat`: 2 x (2 x 4,096) rows [noisy | clean], 32 query on 4
# key-value heads of 128, blocks of 4
BD = (2, 8192, 32, 4, 128, 4)


def _bd_reference(q, k, v, block=BD[5]):
    """Float32, every key-value head repeated, a block of 256 queries at a
    time (`lax.map`, its scores made again in the backward pass: 2 x 8,192
    rows of 32 heads fit) under the dense boolean mask written from its
    four lines."""
    b, t, hq, d = q.shape
    length = t // 2
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, hq // k.shape[2], axis=2) for x in (k, v))
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(xs):
        qb, first = xs
        i = first + jnp.arange(qb.shape[1])
        bq, bk = (i % length)[:, None] // block, (keys % length)[None] // block
        noisy_q, noisy_k = i[:, None] < length, keys[None] < length
        seen = jnp.where(noisy_q, jnp.where(noisy_k, bk == bq, bk < bq),
                         jnp.where(noisy_k, False, bk <= bq))
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                       precision="highest") * d ** -0.5
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision="highest")
    out = jax.lax.map(rows, (q.reshape(b, t // 256, 256, hq, d).swapaxes(0, 1),
                             jnp.arange(0, t, 256)))
    return out.swapaxes(0, 1).reshape(b, t, hq, d)


def test_block_diffusion_kernels_against_the_float32_reference():
    """The kernels under `mask=("block_diffusion", 4)` at the cell's shape:
    taken on the TPU (`ops.attention.lowered.kernel`), and the output and
    the three gradients within the bounds above of the float32 attention
    under the dense mask; XLA's block form beside them."""
    import time
    b, t, hq, hkv, d, block = BD
    ks = jax.random.split(jax.random.PRNGKey(36), 4)
    q, ct = (jax.random.normal(ks[i], (b, t, hq, d)).astype(jnp.bfloat16)
             for i in (0, 3))
    k, v = (jax.random.normal(ks[i], (b, t, hkv, d)).astype(jnp.bfloat16)
            for i in (1, 2))
    args, mask = (q, k, v), ("block_diffusion", block)
    kernel, xla = (obs.counter("ops.attention.lowered." + n)
                   for n in ("kernel", "xla"))
    before = kernel.value, xla.value
    run = _value_and_grad(functools.partial(
        attention.grouped_query_attention, mask=mask), ct)
    assert run.lower(*args).as_text().count("tpu_custom_call") == 2
    assert kernel.value > before[0] and xla.value == before[1]
    got = run(*args)
    want = _value_and_grad(_bd_reference, ct)(*args)
    kernels = _gaps(got, want)
    blocks = _gaps(_value_and_grad(functools.partial(
        attention._xla_blocks, mask=mask), ct)(*args), want)
    forward = jax.jit(functools.partial(attention.grouped_query_attention,
                                        mask=mask))
    times = {}
    for name, fn in (("forward", forward), ("value_and_grad", run)):
        jax.block_until_ready(fn(*args))
        tic = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        times[name] = (time.perf_counter() - tic) / 10 * 1e3
    print("block_diffusion gaps to the float32 reference: kernels", kernels,
          "XLA's block form", blocks, "ms a call", times)
    for name, gap in kernels.items():
        assert gap <= max(2 * blocks[name], ROUNDINGS), (name, kernels,
                                                         blocks)
