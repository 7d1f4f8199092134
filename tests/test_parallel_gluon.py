"""Mesh parallelism driven through the USER-FACING Gluon API.

Tensor parallelism + ZeRO must be reachable from Block/Trainer, not only
from hand-written shard_map.  A small transformer
trains on the 8-device CPU mesh with Megatron-sharded parameters and
ZeRO-sharded optimizer state, via the ordinary autograd/Trainer loop, and
must match the single-device run.
"""
import numpy as np

import jax
from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, parallel
from incubator_mxnet_tpu.parallel import ShardingRules


class MiniTransformer(gluon.HybridBlock):
    """One attention + FFN block over embeddings — enough structure for
    column/row-parallel rules to engage on qkv/proj/fc1/fc2."""

    def __init__(self, vocab=32, dim=16, heads=2, **kw):
        super().__init__(**kw)
        self.dim = dim
        self.heads = heads
        with self.name_scope():
            self.embed = gluon.nn.Embedding(vocab, dim, prefix="embed_")
            self.qkv = gluon.nn.Dense(3 * dim, use_bias=False, flatten=False,
                                      prefix="qkv_")
            self.proj = gluon.nn.Dense(dim, use_bias=False, flatten=False,
                                       prefix="proj_")
            self.fc1 = gluon.nn.Dense(4 * dim, use_bias=False, flatten=False,
                                      prefix="fc1_")
            self.fc2 = gluon.nn.Dense(dim, use_bias=False, flatten=False,
                                      prefix="fc2_")
            self.norm = gluon.nn.LayerNorm(prefix="ln_")
            self.head = gluon.nn.Dense(vocab, use_bias=False, flatten=False,
                                       prefix="head_")

    def hybrid_forward(self, F, x):
        h = self.embed(x)                      # (B, T, D)
        qkv = self.qkv(h)                      # (B, T, 3D)
        q, k, v = (F.slice_axis(qkv, axis=2, begin=i * self.dim,
                                end=(i + 1) * self.dim) for i in range(3))
        att = F.batch_dot(q, k, transpose_b=True) / float(np.sqrt(self.dim))
        att = F.softmax(att, axis=-1)
        h = h + self.proj(F.batch_dot(att, v))
        h = self.norm(h)
        h = h + self.fc2(F.relu(self.fc1(h)))
        return self.head(h)


def _train(mesh=None, zero=False, steps=4, hybridize=False):
    np.random.seed(11)
    mx.random.seed(11)
    net = MiniTransformer()
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.randint(0, 32, (8, 6)).astype("f4"))
    y_np = np.random.randint(0, 32, (8, 6)).astype("f4")
    y = nd.array(y_np)
    # materialize deferred-init params with one forward before sharding
    net(x)
    if hybridize:
        net.hybridize()
    shardings = None
    if mesh is not None:
        rules = ShardingRules.megatron("tp")
        shardings = parallel.shard_block(net, mesh, rules)
        parallel.put(x, mesh, P("dp"))      # batch sharded over dp
        parallel.put(y, mesh, P("dp"))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.05},
                            zero=(mesh, "dp") if (zero and mesh) else None)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for _ in range(steps):
        with autograd.record():
            out = net(x)
            loss = loss_fn(out.reshape((-1, 32)), y.reshape((-1,)))
        loss.backward()
        trainer.step(x.shape[0])
        losses.append(float(loss.mean().asnumpy()))
    import re
    params = {re.sub(r"^minitransformer_\d+_", "", p.name):
              p.data().asnumpy()
              for p in net.collect_params().values()}
    return params, losses, net, trainer, shardings


def test_gluon_tp_zero_matches_single_device():
    ref_params, ref_losses, _, _, _ = _train(mesh=None)
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    params, losses, net, trainer, shardings = _train(mesh=mesh, zero=True)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=1e-5)
    for k in ref_params:
        # sharded vs single-device sums reassociate floats; a few ULP-scale
        # outliers per thousand elements are expected
        np.testing.assert_allclose(params[k], ref_params[k], rtol=1e-3,
                                   atol=5e-5, err_msg=k)
    # the column-parallel qkv weight must ACTUALLY be sharded over tp
    qkv = [p for p in net.collect_params().values()
           if "qkv" in p.name][0]
    arr = qkv.data()._data
    assert arr.sharding.spec == P("tp", None), arr.sharding
    shard = arr.addressable_shards[0].data
    assert shard.shape[0] == arr.shape[0] // 2, "qkv not split over tp"
    # ZeRO: adam state tensors are sharded over dp (1/4 per rank)
    st = trainer._updaters[0].states
    some = [s for s in jax.tree_util.tree_leaves(
        list(st.values()),
        is_leaf=lambda a: hasattr(a, "_data"))
        if hasattr(a := s, "_data") and s.ndim >= 1 and s.shape[0] % 4 == 0]
    assert some, "no shardable state found"
    sharded = [s for s in some
               if s._data.sharding.spec and s._data.sharding.spec[0] == "dp"]
    assert sharded, "optimizer state is not ZeRO-sharded over dp"


def test_gluon_tp_hybridized_matches_eager():
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    p_eager, l_eager, _, _, _ = _train(mesh=mesh)
    p_hyb, l_hyb, _, _, _ = _train(mesh=mesh, hybridize=True)
    np.testing.assert_allclose(l_hyb, l_eager, rtol=2e-4, atol=1e-5)
    for k in p_eager:
        np.testing.assert_allclose(p_hyb[k], p_eager[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)
