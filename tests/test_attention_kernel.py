"""Grouped-query attention as a flash kernel over grouped heads
(`ops/attention.grouped_query_attention` through `ops/flash_attention.py`'s
forward and backward kernels): the kernels' arithmetic interpreted on the
CPU against XLA's block form and against the whole score matrix with every
key-value head repeated, the driver's choice from platform and shapes with
its counters, and both kernels compiled HERE for the chip the benchmark
runs on (a TPU v5e that is described, not attached) at the shapes of the
two cells that hold the operator.  Nothing of the last part runs, so it
says nothing about results or times (tests_tpu/test_attention_kernel.py
does, on the chip).  The topology is described inside a fixture, never at
import: only the worker that is given this file loads the TPU's library.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import attention, flash_attention

# batch, queries, keys, query heads, key-value heads, head size, value's
SMALL = {
    "r4": (2, 256, 256, 8, 2, 64, 64),          # lfm2_24b_a2b's grouping
    "r8": (1, 128, 128, 8, 1, 128, 128),        # qwen3_next_80b_a3b's
    "more_keys": (1, 128, 384, 4, 1, 64, 64),
    "wider_value": (1, 128, 256, 4, 2, 64, 128),
}
# the two cells' shapes: (q, k, v), and the temporaries XLA may keep beside
# the kernels (the transposes to and from the kernels' layout)
CELLS = {
    "lfm2_24b_a2b": ((2, 8192, 32, 64), (2, 8192, 8, 64), (2, 8192, 8, 64)),
    "qwen3_next_80b_a3b": ((2, 4096, 16, 256), (2, 4096, 2, 256),
                           (2, 4096, 2, 256)),
}


def _inputs(case, dtype=jnp.float32):
    b, t, s, hq, hkv, d, dv = SMALL[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    return (jax.random.normal(keys[0], (b, t, hq, d), dtype),
            jax.random.normal(keys[1], (b, s, hkv, d), dtype),
            jax.random.normal(keys[2], (b, s, hkv, dv), dtype))


def _whole_matrix(q, k, v, causal=True):
    """The whole score matrix, every key-value head repeated."""
    t, s, r = q.shape[1], k.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, r, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[3] ** -0.5
    if causal:
        seen = (jnp.arange(t) + (s - t))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, -1), v)


def _counts():
    return tuple(mx.obs.counter("ops.attention.lowered." + d).value
                 for d in ("kernel", "xla"))


def _value_and_grads(fn, args):
    out = fn(*args)
    ct = jax.random.normal(jax.random.PRNGKey(99), out.shape)
    return jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                    argnums=(0, 1, 2))(*args), out


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")


@pytest.mark.parametrize("against", ["xla_blocks", "whole_matrix"])
@pytest.mark.parametrize("case", sorted(SMALL))
def test_kernels_interpreted(interpreted, case, against):
    """Values and the three gradients, both kernels interpreted."""
    args = _inputs(case)
    before = _counts()
    grads, out = _value_and_grads(attention.grouped_query_attention, args)
    assert _counts() == (before[0] + 2, before[1])
    ref = attention._xla_blocks if against == "xla_blocks" else _whole_matrix
    want_grads, want = _value_and_grads(ref, args)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for g, w, name in zip(grads, want_grads, "qkv"):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)


def test_kernels_interpreted_without_a_mask(interpreted):
    """`causal=False`: every tile runs and none is masked."""
    args = _inputs("more_keys")
    grads, out = _value_and_grads(functools.partial(
        attention.grouped_query_attention, mask=flash_attention.NONE), args)
    want_grads, want = _value_and_grads(functools.partial(
        _whole_matrix, causal=False), args)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for g, w, name in zip(grads, want_grads, "qkv"):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)


def test_kernel_interpreted_bfloat16(interpreted):
    """bfloat16 in, bfloat16 out, the statistics float32: within a
    rounding of the output of XLA's form."""
    args = _inputs("r4", jnp.bfloat16)
    out = attention.grouped_query_attention(*args)
    assert out.dtype == jnp.bfloat16
    want = _whole_matrix(*(x.astype(jnp.float32) for x in args))
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=2e-2)


@pytest.mark.parametrize("why,shape", [
    ("queries off the lanes", (1, 120, 120, 4, 2, 64, 64)),
    ("keys off the lanes", (1, 128, 200, 4, 2, 64, 64)),
    ("a head of 48", (1, 128, 128, 4, 2, 48, 48)),
    ("fewer keys than queries", (1, 256, 128, 4, 2, 64, 64)),
])
def test_shapes_the_kernel_does_not_hold_take_xlas_form(interpreted, why,
                                                        shape):
    b, t, s, hq, hkv, d, dv = shape
    assert attention._tiles(hq // hkv, t, s, d, dv, 4) is None
    args = [jnp.ones((b, n, h, e)) for n, h, e in
            ((t, hq, d), (s, hkv, d), (s, hkv, dv))]
    before = _counts()
    jaxpr = str(jax.make_jaxpr(attention.grouped_query_attention)(*args))
    assert _counts() == (before[0], before[1] + 1)
    assert "pallas_call" not in jaxpr


@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", False, "xla"), ("cpu", True, "interpret"),
    ("tpu", False, "kernel"), ("gpu", False, "xla")])
def test_driver_follows_platform_and_shapes(monkeypatch, backend, interpret,
                                            want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret:
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    for q, k, v in CELLS.values():
        r = q[2] // k[2]
        assert attention._driver(r, q[1], k[1], q[3], v[3], 2) == want
    # off the lanes: XLA's form on every platform
    assert attention._driver(4, 8200, 8200, 64, 64, 2) == "xla"


def test_tiles_follow_the_shapes():
    """About a thousand stacked rows a grid step, the widest tile of keys
    that divides them; a head's K and V and their gradients whole in
    VMEM, which both cells' sizes allow."""
    assert attention._tiles(4, 8192, 8192, 64, 64, 2) == (256, 1024)
    assert attention._tiles(8, 4096, 4096, 256, 256, 2) == (128, 1024)
    assert attention._tiles(1, 384, 640, 128, 128, 4) == (128, 128)
    assert attention._tiles(4, 8192, 131072, 128, 128, 2) is None


def test_operator_takes_the_kernel(interpreted):
    """`BlockwiseAttention(num_kv_heads=...)` on packed (B, T, C) inputs
    reaches the kernel."""
    from incubator_mxnet_tpu.ops import registry
    b, t, s, hq, hkv, d, dv = SMALL["r4"]
    q, k, v = _inputs("r4")
    op = registry.get("BlockwiseAttention")
    params = op.canonicalize_params({"num_heads": hq, "num_kv_heads": hkv})

    def packed(q, k, v):
        return op.fn(dict(params), q.reshape(b, t, -1), k.reshape(b, s, -1),
                     v.reshape(b, s, -1))
    assert "flash_attention_fwd" in str(jax.make_jaxpr(packed)(q, k, v))
    np.testing.assert_allclose(
        packed(q, k, v), _whole_matrix(q, k, v).reshape(b, t, -1),
        rtol=2e-5, atol=2e-5)


# -- compiled for the described v5e ------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("calls", [1, 2])
@pytest.mark.parametrize("config", sorted(CELLS))
def test_kernels_compile_for_the_v5e(one_chip, uncached, config, calls):
    """Forward alone (one kernel) and value and gradient (two) of what
    `grouped_query_attention` runs on a TPU (here `default_backend()` is
    the CPU, so the custom VJP is named): no block of scores in HBM, so no
    `reduce-window` over one and under 0.5 GB of temporaries (XLA's form:
    2.15 and 1.83 GB at `lfm2_24b_a2b`'s shape)."""
    args = [jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)
            for dims in CELLS[config]]

    def fn(q, k, v):
        return attention._flash(q, k, v, flash_attention.CAUSAL, False)
    if calls == 2:
        forward = fn
        fn = jax.value_and_grad(lambda *a: jnp.sum(
            forward(*a).astype(jnp.float32)), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert "reduce-window" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("stream", [False, True])
def test_plain_flash_attention_compiles_for_the_v5e(one_chip, uncached,
                                                    stream):
    """r = 1 is the same kernel: `flash_attention_partial`'s whole-KV form
    and the KV-streaming variant, at 8 heads of 128 over 4,096 keys."""
    q3 = jax.ShapeDtypeStruct((8, 1, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)
    k3 = jax.ShapeDtypeStruct((8, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)

    def fn(q4, k3, v3):
        return flash_attention._kernel_forward(
            q4, k3, v3, 0, 0, mask=flash_attention.CAUSAL, block_q=256,
            block_k=256,
            stream=stream)
    text = jax.jit(fn).lower(q3, k3, k3).compile().as_text()
    assert text.count("tpu_custom_call") == 1


# -- the block-diffusion mask --------------------------------------------------

def _bd_dense(length, block):
    """The (2L, 2L) booleans of the block-diffusion mask, from its four
    lines: rows and keys [noisy copy | clean copy], b(i) = i // block."""
    b = np.arange(length) // block
    same, before = b[:, None] == b[None, :], b[None, :] < b[:, None]
    return np.block([[same, before],
                     [np.zeros_like(same), same | before]])


def _bd_whole_matrix(q, k, v, block):
    r = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, r, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[3] ** -0.5
    scores = jnp.where(_bd_dense(q.shape[1] // 2, block), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, -1), v)


def _bd_inputs(length, r, d=64, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(length + r), 3)
    return (jax.random.normal(keys[0], (batch, 2 * length, r, d)),
            jax.random.normal(keys[1], (batch, 2 * length, 1, d)),
            jax.random.normal(keys[2], (batch, 2 * length, 1, d)))


# the cell's block, one that divides a lane tile, one that does not divide a
# tile, and one that holds whole tiles (its diagonal runs unmasked tiles)
@pytest.mark.parametrize("block", [4, 32, 48, 256])
@pytest.mark.parametrize("r", [1, 8])
def test_block_diffusion_three_ways(interpreted, block, r):
    """Forward and the three gradients under `mask=("block_diffusion",
    B)`: the kernels, interpreted, against XLA's block form against the
    dense boolean mask written from the four lines."""
    args = _bd_inputs(256, r)
    mask = ("block_diffusion", block)
    before = _counts()
    grads, out = _value_and_grads(functools.partial(
        attention.grouped_query_attention, mask=mask), args)
    assert _counts() == (before[0] + 2, before[1])
    xla_grads, xla_out = _value_and_grads(functools.partial(
        attention._xla_blocks, mask=mask, block_size=64), args)
    want_grads, want = _value_and_grads(functools.partial(
        _bd_whole_matrix, block=block), args)
    for got, name in ((out, "kernel"), (xla_out, "xla")):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=name)
    for got in (grads, xla_grads):
        for g, w, name in zip(got, want_grads, "qkv"):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4,
                                       err_msg=name)


@pytest.mark.parametrize("block,length,r", [(4, 256, 8), (32, 256, 1),
                                            (48, 384, 1), (256, 512, 2),
                                            (4, 4096, 8)])
def test_tile_runs_cover_the_mask_and_no_more(block, length, r):
    """`tile_runs` against the dense mask, tile by tile: a tile with a seen
    entry runs, a whole run's tiles are whole, and a tile that runs masked
    is neither empty... nor need it be (the statement may mask a whole
    tile, never skip a live one); the counters' sums are the runs'."""
    d = 128 if length == 4096 else 64
    block_q, block_k = attention._tiles(r, 2 * length, 2 * length, d, d, 2,
                                        length)
    mask = ("block_diffusion", block)
    narrow = attention._narrow(mask, block_q, block_k, length)
    dense = _bd_dense(length, block)
    whole = masked = 0
    for first in range(0, 2 * length, block_q):
        rows = dense[first:first + block_q]
        live = np.zeros(2 * length, bool)
        for lo, hi, width, rule in flash_attention.tile_runs(
                mask, first, block_q, block_k, 2 * length, narrow):
            for i in range(lo, hi):
                tile = rows[:, i * width:(i + 1) * width]
                assert not live[i * width:(i + 1) * width].any()
                live[i * width:(i + 1) * width] = True
                assert tile.any(), (first, i, width, rule)
                if rule is None:
                    assert tile.all(), (first, i, width)
                whole += (rule is None) * width // 128
                masked += (rule is not None) * width // 128
        assert not rows[:, ~live].any(), first
    counts = attention.tile_counts(mask, 2 * length, 2 * length, block_q,
                                   block_k, narrow)
    assert counts == (whole, masked, 2 * length // block_q *
                      (2 * length // 128) - whole - masked)
    if length == 4096:
        # the cell's shapes: tiles of (128 rows of 8 heads, 1,024 keys),
        # the noisy diagonal as one tile of 128 keys a block of queries
        assert (block_q, block_k, narrow) == (128, 1024, 128)
        assert counts[2] / sum(counts) > 0.6
        entries = dense.sum() / dense.size
        assert abs(entries - 0.25) < 0.001


def test_tile_counters_count_a_lowered_call(interpreted):
    names = ("run", "masked", "skipped")

    def read():
        return tuple(mx.obs.counter("ops.attention.tiles." + n).value
                     for n in names)
    args = _bd_inputs(256, 1)
    mask = ("block_diffusion", 4)
    fn = functools.partial(attention.grouped_query_attention, mask=mask)
    want = attention.tile_counts(mask, 512, 512, 256, 256, 128)
    assert want == (0, 6, 2)        # of 2 blocks of queries x 4 lane tiles
    before = read()
    jax.make_jaxpr(fn)(*args)       # the forward kernel's call
    assert read() == tuple(b + n for b, n in zip(before, want))
    # the forward rule's call and the backward kernel's count theirs
    before = read()
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2)))(
        *args)
    assert read() == tuple(b + 2 * n for b, n in zip(before, want))
    # the causal walk counts too: 2 blocks of 128 queries against 3 tiles
    before = read()
    jax.make_jaxpr(attention.grouped_query_attention)(*_inputs("more_keys"))
    assert read()[2] - before[2] == 0 and read()[0] - before[0] == 2 and \
        read()[1] - before[1] == 1


def test_block_diffusion_operator_and_its_refusals(interpreted):
    from incubator_mxnet_tpu.ops import registry
    op = registry.get("BlockwiseAttention")
    q, k, v = _bd_inputs(128, 8)
    params = op.canonicalize_params({
        "num_heads": 8, "num_kv_heads": 1, "mask": "block_diffusion",
        "block_length": 4})
    out = op.fn(dict(params), q.reshape(1, 256, -1), k.reshape(1, 256, -1),
                v.reshape(1, 256, -1))
    np.testing.assert_allclose(
        out, _bd_whole_matrix(q, k, v, 4).reshape(1, 256, -1), rtol=2e-5,
        atol=2e-5)
    # the mask's entries, not the square's
    flops = op.cost_meta["flops"](params, [jax.ShapeDtypeStruct(
        (1, 256, 512), jnp.float32)], None)
    assert flops == 4.0 * 128 * (128 + 4) * 512
    with pytest.raises(mx.MXNetError, match="block_length"):
        op.fn(dict(params, block_length=None), q.reshape(1, 256, -1),
              k.reshape(1, 256, -1), v.reshape(1, 256, -1))
    with pytest.raises(mx.MXNetError, match="mask"):
        op.fn(dict(params, mask="window"), q.reshape(1, 256, -1),
              k.reshape(1, 256, -1), v.reshape(1, 256, -1))
    with pytest.raises(mx.MXNetError, match="2L"):
        attention.grouped_query_attention(q, k[:, :128], v[:, :128],
                                          mask=("block_diffusion", 4))


@pytest.mark.parametrize("calls", [1, 2])
def test_block_diffusion_kernels_compile_for_the_v5e(one_chip, uncached,
                                                     calls):
    """`sdar_30b_a3b_chat`'s shape: 2 x (2 x 4,096) rows, 32 / 4 heads of
    128, blocks of 4."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16,
                             sharding=one_chip)

    def fn(q, k, v):
        return attention._flash(q, k, v, ("block_diffusion", 4), False)
    if calls == 2:
        forward = fn
        fn = jax.value_and_grad(lambda *a: jnp.sum(
            forward(*a).astype(jnp.float32)), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, k, k).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
