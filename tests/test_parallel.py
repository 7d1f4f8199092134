"""Parallelism tests on the virtual 8-device CPU mesh: data-parallel SPMD
step, tensor-parallel sharding, ring attention, pipeline schedule.
(The reference's analogues are the multi-GPU nightly tests,
tests/nightly/multi_lenet.py / dist_sync_kvstore.py.)"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel as par


def test_make_mesh():
    mesh = par.make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh2 = par.make_mesh()
    assert mesh2.shape["dp"] == len(jax.devices())
    with pytest.raises(mx.MXNetError):
        par.make_mesh({"dp": 5})


def test_data_parallel_step_matches_single_device():
    """DP-8 training must match single-device training on the full batch."""
    mesh = par.make_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.rand(5, 3).astype("f4"))
    b = jnp.zeros(3, "f4")
    params = {"w": W, "b": b}
    X = jnp.asarray(rng.rand(16, 5).astype("f4"))
    Y = jnp.asarray((rng.rand(16, 3) > 0.5).astype("f4"))

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    update = par.data_parallel_step.__wrapped__ if False else None
    from incubator_mxnet_tpu.parallel.data_parallel import sgd_tree_update
    opt_state = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = par.data_parallel_step(loss_fn, sgd_tree_update(momentum=0.0),
                                  mesh, donate=False)
    p1, o1, loss1 = step(params, opt_state, (X, Y), jnp.float32(0.1))

    # single-device reference
    g = jax.grad(loss_fn)(params, (X, Y))
    ref_w = params["w"] - 0.1 * g["w"]
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(ref_w),
                               rtol=1e-5, atol=1e-6)


def test_collectives_in_shard_map():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = par.make_mesh({"dp": 8})

    def f(x):
        return par.all_reduce(x, "dp"), par.all_gather(x, "dp")

    x = jnp.arange(8.0).reshape(8, 1)
    s, g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"),
                             out_specs=(P("dp"), P("dp"))))(x)
    np.testing.assert_allclose(np.asarray(s), np.full((8, 1), 28.0))


def test_ring_attention_matches_full():
    """Ring attention over 4 sequence shards == exact full attention."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, T, H, D = 2, 16, 2, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    k = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    v = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))

    def full_attn(q, k, v):
        scale = 1.0 / np.sqrt(D)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    ref = full_attn(q, k, v)

    ring = shard_map(
        lambda q, k, v: par.ring_attention(q, k, v, "sp"),
        mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-5)


def test_ring_attention_causal():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, T, H, D = 1, 8, 1, 4
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    k = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    v = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))

    def full_causal(q, k, v):
        scale = 1.0 / np.sqrt(D)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = np.tril(np.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    ref = full_causal(q, k, v)
    ring = shard_map(
        lambda q, k, v: par.ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-5)


def test_blockwise_attention():
    B, T, H, D = 2, 32, 2, 8
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    k = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    v = jnp.asarray(rng.rand(B, T, H, D).astype("f4"))
    full = par.blockwise_attention(q, k, v, block_size=None)
    blocked = par.blockwise_attention(q, k, v, block_size=8)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(full),
                               rtol=2e-3, atol=2e-5)
    causal_full = par.blockwise_attention(q, k, v, causal=True)
    causal_blk = par.blockwise_attention(q, k, v, block_size=8, causal=True)
    np.testing.assert_allclose(np.asarray(causal_blk),
                               np.asarray(causal_full), rtol=2e-3, atol=2e-5)


def test_tensor_parallel_sharding():
    mesh = par.make_mesh({"dp": 2, "tp": 4})
    rules = par.ShardingRules.megatron("tp")
    params = {
        "layer0.qkv_weight": jnp.zeros((64, 32)),
        "layer0.out_proj_weight": jnp.zeros((32, 64)),
        "layer0.bias": jnp.zeros((64,)),
    }
    sharded = par.shard_params(params, mesh, rules)
    qkv = sharded["layer0.qkv_weight"]
    assert qkv.sharding.spec == jax.sharding.PartitionSpec("tp", None)
    proj = sharded["layer0.out_proj_weight"]
    assert proj.sharding.spec == jax.sharding.PartitionSpec(None, "tp")


def test_pipeline_step():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = par.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    n_micro = 8

    def stage_fn(params, x):
        # every stage adds its (replicated) parameter value
        return x + params

    fwd = par.pipeline_step(stage_fn, n_micro, "pp")
    microbatches = jnp.arange(n_micro, dtype=jnp.float32).reshape(n_micro, 1, 1)
    run = shard_map(fwd, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                    check_vma=False)
    out = jax.jit(run)(jnp.float32(1.0), microbatches)
    # each of 4 stages adds 1.0
    np.testing.assert_allclose(np.asarray(out).reshape(-1),
                               np.arange(n_micro) + 4.0)


def test_pipeline_train_step_decreases_loss_and_matches_sequential():
    """GPipe training over pp=2: forward == sequential stage composition,
    and the fused train step drives the loss down."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = par.make_mesh({"pp": 2}, devices=jax.devices()[:2])
    n_micro, mb, h = 4, 8, 6
    rng = np.random.RandomState(3)
    # stacked per-stage params, sharded over pp on the leading dim
    W = jnp.asarray(rng.randn(2, h, h).astype("f4") * 0.5)
    B = jnp.asarray(np.zeros((2, 1, h), "f4"))
    X = jnp.asarray(rng.randn(n_micro, mb, h).astype("f4"))
    T = jnp.asarray(rng.randn(n_micro, mb, h).astype("f4") * 0.1)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"][0] + p["b"][0])

    def loss_fn(out, tgt):
        return jnp.mean((out - tgt) ** 2)

    # forward parity vs sequential composition
    fwd = par.pipeline_step(stage_fn, n_micro, "pp")
    run = shard_map(fwd, mesh=mesh, in_specs=({"w": P("pp"), "b": P("pp")},
                                              P()),
                    out_specs=P(), check_vma=False)
    out = jax.jit(run)({"w": W, "b": B}, X)
    ref = np.tanh(np.tanh(np.asarray(X) @ np.asarray(W[0]) + np.asarray(B[0]))
                  @ np.asarray(W[1]) + np.asarray(B[1]))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    # training: loss decreases
    step = par.pipeline_train_step(stage_fn, loss_fn, n_micro,
                                   lambda p, g: p - 0.5 * g, "pp")
    train = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=({"w": P("pp"), "b": P("pp")}, P(), P()),
        out_specs=({"w": P("pp"), "b": P("pp")}, P()), check_vma=False))
    params = {"w": W, "b": B}
    losses = []
    for _ in range(12):
        params, loss = train(params, X, T)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses

    # gradient parity vs non-pipelined autodiff on the composed function
    def composed_loss(p):
        y = np.asarray(X)
        a1 = jnp.tanh(jnp.asarray(y) @ p["w"][0] + p["b"][0])
        a2 = jnp.tanh(a1 @ p["w"][1] + p["b"][1])
        return jnp.mean((a2 - T) ** 2)

    g_ref = jax.grad(composed_loss)({"w": W, "b": B})
    step1 = jax.jit(shard_map(
        par.pipeline_train_step(stage_fn, loss_fn, n_micro,
                                lambda p, g: g, "pp"),  # returns grads
        mesh=mesh,
        in_specs=({"w": P("pp"), "b": P("pp")}, P(), P()),
        out_specs=({"w": P("pp"), "b": P("pp")}, P()), check_vma=False))
    g_pipe, _ = step1({"w": W, "b": B}, X, T)
    np.testing.assert_allclose(np.asarray(g_pipe["w"]), np.asarray(g_ref["w"]),
                               rtol=1e-4, atol=1e-5)


def test_zero_sharded_optimizer_matches_replicated_adam():
    """ZeRO dp-8 adam == replicated adam; state lives sharded 1/N."""
    from incubator_mxnet_tpu.parallel.zero import (
        zero_train_step, zero_init_state, adam_shard_update)
    mesh = par.make_mesh({"dp": 8})
    n = 8
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.rand(5, 3).astype("f4")),
              "b": jnp.zeros(3, "f4")}
    X = jnp.asarray(rng.rand(16, 5).astype("f4"))
    Y = jnp.asarray(rng.rand(16, 3).astype("f4"))

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    state = zero_init_state(
        params, n,
        lambda s, d: (jnp.zeros(s, d), jnp.zeros(s, d), jnp.zeros(n, d)))
    step = zero_train_step(loss_fn, adam_shard_update(lr=0.05), mesh,
                           donate=False)

    # replicated adam reference
    ref_p = {k: np.asarray(v, "f4") for k, v in params.items()}
    ref_m = {k: np.zeros_like(v) for k, v in ref_p.items()}
    ref_v = {k: np.zeros_like(v) for k, v in ref_p.items()}

    p, s = params, state
    for t in range(1, 4):
        p, s, loss = step(p, s, (X, Y))
        g = jax.grad(loss_fn)({k: jnp.asarray(v) for k, v in ref_p.items()},
                              (X, Y))
        for k in ref_p:
            gk = np.asarray(g[k], "f4")
            ref_m[k] = 0.9 * ref_m[k] + 0.1 * gk
            ref_v[k] = 0.999 * ref_v[k] + 0.001 * gk * gk
            mhat = ref_m[k] / (1 - 0.9 ** t)
            vhat = ref_v[k] / (1 - 0.999 ** t)
            ref_p[k] = ref_p[k] - 0.05 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(p["w"]), ref_p["w"], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(p["b"]), ref_p["b"], rtol=1e-4,
                               atol=1e-5)

    # per-device state is 1/N: global m for w is padded ceil(15/8)*8 = 16,
    # each device holds 2 elements
    m_w = s["w"][0]
    assert m_w.shape == (16,)
    shard_shapes = {sh.data.shape for sh in m_w.addressable_shards}
    assert shard_shapes == {(2,)}
