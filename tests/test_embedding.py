"""mxembed: the sharded sparse-embedding tier (ISSUE-19 gates).

Covers: partition correctness (range interval math + splitmix64 hash
balance), seeded deterministic shard init, push/pull round trips with
duplicate-id pre-aggregation, bit-identical parity between the
shard-side lazy optimizer step and a local row-sparse reference (SGD
momentum and Adam), the device-resident hot-row LRU cache (hits,
misses, evictions, refresh-resident-only, capacity overflow, ZERO
steady-state recompiles via program counts), structured shard-loss
diagnosis (`ServerLostError` naming the shard + owned rows; a server
that restarted empty), `replace_shard` recovery, chunked
checkpoint/restore bit-identity, Module.fit training through the
`EmbeddingFitAdapter`, the gluon `SparseEmbedding` autograd leaf with
exact duplicate-id updates, serving fan-out through `ReplicaRouter`
with mid-traffic shard failover and zero lost admitted requests, the
kvstore factory surfaces, the embedding cost model, and the
`embedding.*` obs namespace + `embedding.lookup` trace spans.
"""
import threading

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import embedding as mxembed
from incubator_mxnet_tpu import io, sym
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.embedding import (EmbeddingFitAdapter,
                                           EmbeddingServingPath,
                                           HotRowCache, ShardedEmbedding,
                                           shard_of_ids)
from incubator_mxnet_tpu.resilience import ServerLostError


@pytest.fixture(autouse=True)
def fast_failover(monkeypatch):
    """Shard-death diagnosis in well under a second (prod defaults wait
    seconds per reconnect so a GC pause is not declared a death)."""
    monkeypatch.setenv("MXNET_PS_RECONNECT_WAIT", "0.05")
    monkeypatch.setenv("MXNET_PS_MAX_RETRIES", "2")
    monkeypatch.setenv("MXNET_EMBED_BREAKER_THRESHOLD", "2")


def _spawn(n):
    from incubator_mxnet_tpu.dist.server import ParameterServer
    return [ParameterServer(num_workers=1).start() for _ in range(n)]


def _addrs(servers):
    return [("127.0.0.1", s.port) for s in servers]


def _teardown(table, servers):
    table.close()
    for s in servers:
        try:
            s.shutdown()
        except Exception:
            pass


# -- partitioning -------------------------------------------------------------

def test_shard_of_ids_range_partition():
    ids = np.arange(100)
    shards = shard_of_ids(ids, 100, 3, "range")
    # contiguous ps-lite value ranges: [0,33) [33,66) [66,100)
    assert (shards == np.repeat([0, 1, 2], [33, 33, 34])).all()
    # monotone: range partitioning preserves locality
    assert (np.diff(shards) >= 0).all()


def test_shard_of_ids_hash_partition_balanced_and_stable():
    ids = np.arange(10_000)
    shards = shard_of_ids(ids, 10_000, 4, "hash")
    assert shards.min() >= 0 and shards.max() < 4
    counts = np.bincount(shards, minlength=4)
    # splitmix64 spreads sequential hot ids: every shard within 20%
    assert counts.min() > 0.8 * 10_000 / 4
    # deterministic across calls (workers and servers must agree)
    assert (shards == shard_of_ids(ids, 10_000, 4, "hash")).all()


def test_unknown_partition_rejected():
    with pytest.raises(MXNetError, match="unknown partition"):
        ShardedEmbedding("t", 10, 2, [("127.0.0.1", 1)],
                         partition="modulo")


# -- init / pull --------------------------------------------------------------

@pytest.mark.parametrize("partition", ["range", "hash"])
def test_seeded_init_deterministic_and_init_values(partition):
    servers = _spawn(2)
    init = np.arange(40, dtype=np.float32).reshape(10, 4)
    t1 = ShardedEmbedding("det", 10, 4, _addrs(servers), seed=11,
                          partition=partition, cache_rows=0)
    a = t1.pull_rows(np.arange(10))
    servers2 = _spawn(2)
    t2 = ShardedEmbedding("det", 10, 4, _addrs(servers2), seed=11,
                          partition=partition, cache_rows=0)
    b = t2.pull_rows(np.arange(10))
    # same seed -> bit-identical rows regardless of process/server set
    assert np.array_equal(a, b)
    t3 = ShardedEmbedding("det2", 10, 4, _addrs(servers), seed=12,
                          partition=partition, cache_rows=0)
    assert not np.array_equal(a, t3.pull_rows(np.arange(10)))
    t4 = ShardedEmbedding("explicit", 10, 4, _addrs(servers),
                          partition=partition, cache_rows=0,
                          init_values=init)
    assert np.array_equal(t4.pull_rows(np.arange(10)), init)
    _teardown(t1, [])
    _teardown(t3, [])
    _teardown(t4, servers)
    _teardown(t2, servers2)


def test_lookup_shape_and_cache_hotness():
    servers = _spawn(2)
    table = ShardedEmbedding("shape", 64, 8, _addrs(servers), seed=3,
                             cache_rows=32)
    ids = np.array([[1, 40], [5, 1]])
    out = table.lookup(ids, out_np=True)
    assert out.shape == (2, 2, 8)
    # duplicate id 1 returns the same row both places
    assert np.array_equal(out[0, 0], out[1, 1])
    pulled_before = sum(table._pulled)
    again = table.lookup(ids, out_np=True)
    assert np.array_equal(again, out)
    # second lookup is fully cache-hot: no shard traffic at all
    assert sum(table._pulled) == pulled_before
    assert table.stats()["cache"]["hit_rate"] > 0
    _teardown(table, servers)


# -- training updates ---------------------------------------------------------

def test_push_grad_sgd_with_duplicate_id_aggregation():
    servers = _spawn(1)
    init = np.zeros((8, 2), dtype=np.float32)
    table = ShardedEmbedding("sgd", 8, 2, _addrs(servers), cache_rows=0,
                             init_values=init,
                             optimizer=mx.optimizer.SGD(learning_rate=0.5,
                                                        momentum=0.0))
    ids = np.array([3, 5, 3])            # id 3 appears twice
    grads = np.ones((3, 2), dtype=np.float32)
    table.push_grad(ids, grads)
    out = table.pull_rows(np.arange(8))
    # duplicates pre-sum: id 3 moves by -lr*2, id 5 by -lr*1
    assert np.allclose(out[3], -1.0)
    assert np.allclose(out[5], -0.5)
    assert np.allclose(out[[0, 1, 2, 4, 6, 7]], 0.0)
    # assign AFTER a lazy push (checkpoint restore over updated rows)
    table.assign_rows([3], np.full((1, 2), 7.0, dtype=np.float32))
    assert np.allclose(table.pull_rows([3]), 7.0)
    _teardown(table, servers)


@pytest.mark.parametrize("make_opt", [
    lambda: mx.optimizer.SGD(learning_rate=0.1, momentum=0.9),
    lambda: mx.optimizer.Adam(learning_rate=0.01),
], ids=["sgd_momentum", "adam"])
def test_shard_side_lazy_update_matches_local_reference(make_opt):
    """The shard applies optimizer.py's lazy row-sparse path on its
    local slice — bit-identical to the same updates run locally."""
    from incubator_mxnet_tpu.ndarray.sparse import RowSparseNDArray
    rng = np.random.RandomState(5)
    init = rng.randn(12, 3).astype(np.float32)
    servers = _spawn(1)
    table = ShardedEmbedding("parity", 12, 3, _addrs(servers),
                             cache_rows=0, init_values=init,
                             optimizer=make_opt())
    ref_w = mx.nd.array(init.copy())
    ref_upd = mx.optimizer.get_updater(make_opt())
    for step in range(3):
        ids = np.array([1, 7, 4])
        vals = rng.randn(3, 3).astype(np.float32)
        table.push_grad(ids, vals)
        ref_upd("embed:parity",
                RowSparseNDArray(vals, ids, (12, 3)), ref_w)
    assert np.array_equal(table.pull_rows(np.arange(12)),
                          ref_w.asnumpy())
    _teardown(table, servers)


def test_push_without_optimizer_is_structured_error():
    servers = _spawn(1)
    table = ShardedEmbedding("noopt", 4, 2, _addrs(servers), cache_rows=0)
    with pytest.raises(MXNetError, match="set_optimizer"):
        table.push_grad([1], np.ones((1, 2), dtype=np.float32))
    # op='assign' needs no optimizer (checkpoint restore path)
    table.assign_rows([1], np.full((1, 2), 9.0, dtype=np.float32))
    assert np.allclose(table.pull_rows([1]), 9.0)
    _teardown(table, servers)


def test_partition_disagreement_is_structured_error():
    servers = _spawn(2)
    table = ShardedEmbedding("oob", 10, 2, _addrs(servers), cache_rows=0)
    with pytest.raises(MXNetError, match="partition rules disagree"):
        # shard 0 owns [0,5): asking it for row 9 is a protocol bug
        table._request(0, {"cmd": "embed_pull", "table": "oob",
                           "ids": np.array([9])})
    _teardown(table, servers)


# -- hot-row cache ------------------------------------------------------------

def test_cache_hits_misses_evictions_and_lru_order():
    pulls = []

    def pull(ids):
        pulls.append(list(ids))
        return np.repeat(np.asarray(ids, np.float32)[:, None], 2, axis=1)

    c = HotRowCache(dim=2, capacity=3, name="t")
    rows, h, m = c.lookup(np.array([1, 2, 1]), pull)
    # occurrence accounting against batch-start residency: all three
    # occurrences missed (id 1 was not resident when the batch arrived)
    assert (h, m) == (0, 3)
    assert pulls == [[1, 2]]                 # distinct ids pulled once
    assert np.allclose(np.asarray(rows), [[1, 1], [2, 2], [1, 1]])
    c.lookup(np.array([3]), pull)            # cache now full: 1,2,3
    c.lookup(np.array([1]), pull)            # refresh 1 -> LRU is 2
    _, _, m = c.lookup(np.array([4]), pull)  # evicts 2
    assert m == 1
    st = c.stats()
    assert st["evictions"] == 1 and st["rows"] == 3
    _, _, m2 = c.lookup(np.array([3, 1, 4]), pull)   # all resident
    assert m2 == 0
    _, _, m3 = c.lookup(np.array([2]), pull)         # 2 was evicted
    assert m3 == 1
    assert 0 < c.stats()["hit_rate"] < 1


def test_cache_refresh_updates_resident_rows_only():
    c = HotRowCache(dim=2, capacity=4, name="t")
    c.insert([1, 2], np.zeros((2, 2), np.float32))
    c.refresh(np.array([2, 9]), np.ones((2, 2), np.float32))
    rows, _, m = c.lookup(np.array([1, 2]), None)   # both resident
    assert m == 0
    assert np.allclose(np.asarray(rows), [[0, 0], [1, 1]])
    # 9 was NOT pinned: a push must not cache rows nobody looked up
    assert c.stats()["rows"] == 2


def test_cache_capacity_overflow_is_explicit():
    c = HotRowCache(dim=2, capacity=2, name="t")
    with pytest.raises(ValueError, match="MXNET_EMBED_CACHE_ROWS"):
        c.lookup(np.array([1, 2, 3]),
                 lambda ids: np.zeros((len(ids), 2), np.float32))


def test_cache_overflow_with_resident_rows_raises_instead_of_looping():
    """Batch distinct > capacity while the MISSES alone fit used to
    livelock: the insert evicted the batch's own pinned rows, the
    post-insert check failed, and the re-pull looped forever hammering
    the shards.  The guard is on the whole batch, and pull_fn must not
    run at all."""
    pulls = []

    def pull(ids):
        pulls.append(list(ids))
        return np.repeat(np.asarray(ids, np.float32)[:, None], 2, axis=1)

    c = HotRowCache(dim=2, capacity=4, name="t")
    c.lookup(np.array([0, 1, 2]), pull)      # warm: [0,1,2] resident
    pulls.clear()
    with pytest.raises(ValueError, match="MXNET_EMBED_CACHE_ROWS"):
        c.lookup(np.arange(6), pull)         # 6 distinct, 3 misses
    assert pulls == []                       # no PS traffic, no retry


def test_cache_concurrent_lookups_return_correct_rows():
    """Disjoint hot sets churning a too-small cache from three threads:
    every lookup must still return exactly its own rows (the gather is
    dispatched under the lock so a racing insert can't swap the buffer
    between slot validation and the gather), and the bounded retry
    falls back to an uncached pull rather than spinning."""
    c = HotRowCache(dim=1, capacity=8, name="t")

    def pull(ids):
        return np.asarray(ids, np.float32)[:, None]

    errs = []

    def worker(base):
        try:
            rng = np.random.RandomState(base)
            for _ in range(60):
                ids = rng.randint(base, base + 100, size=6)
                rows, _, _ = c.lookup(ids, pull)
                got = np.asarray(rows)[:, 0]
                assert np.array_equal(got, ids.astype(np.float32)), \
                    f"lookup({ids}) returned rows for {got}"
        except Exception as e:               # pragma: no cover - failure
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(b,))
               for b in (0, 1000, 2000)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:1]


def test_cache_steady_state_has_zero_recompiles():
    """Fixed batch shape in steady state replays ONE executable: the
    padded gather/scatter signature set stops growing."""
    rng = np.random.RandomState(0)

    def pull(ids):
        return rng.randn(len(ids), 4).astype(np.float32)

    c = HotRowCache(dim=4, capacity=64, name="t")
    hot = rng.randint(0, 256, size=24)
    c.lookup(hot, pull)                      # cold fill compiles both
    warm = c.program_count()
    for _ in range(20):                      # steady state: all hits
        _, _, m = c.lookup(hot, pull)
        assert m == 0
    assert c.program_count() == warm
    # mixed cold traffic compiles at most the pow2 ladder, never per-batch
    for _ in range(40):
        c.lookup(rng.randint(0, 4096, size=24), pull)
    assert c.program_count() <= 2 * (int(np.log2(64)) + 1)


# -- failure semantics --------------------------------------------------------

def test_dead_shard_raises_server_lost_naming_shard_and_rows():
    servers = _spawn(2)
    table = ShardedEmbedding("loss", 100, 2, _addrs(servers),
                             cache_rows=0)
    servers[1]._simulate_crash()
    with pytest.raises(ServerLostError) as ei:
        table.pull_rows(np.array([80]))      # shard 1 owns [50,100)
    err = ei.value
    assert err.server == 1
    assert "loss[50:100]" in str(err.keys)
    # the healthy shard keeps serving through the other's death
    assert table.pull_rows(np.array([10])).shape == (1, 2)
    assert table.stats()["shards"]["1"]["breaker"] == "open"
    _teardown(table, servers)


def test_restarted_empty_shard_is_diagnosed():
    """A shard that answers but forgot an initialized table restarted
    empty — that is a data-loss ServerLostError, not a soft retry."""
    servers = _spawn(1)
    table = ShardedEmbedding("amnesia", 10, 2, _addrs(servers),
                             cache_rows=0)
    fresh = _spawn(1)
    from incubator_mxnet_tpu.dist.transport import Channel
    old = table._chans[0]
    table._chans[0] = Channel("127.0.0.1", fresh[0].port)
    with pytest.raises(ServerLostError, match="restarted without state"):
        table.pull_rows(np.array([1]))
    old.close()
    _teardown(table, servers + fresh)


def test_replace_shard_restores_rows_and_serving():
    servers = _spawn(2)
    table = ShardedEmbedding("heal", 20, 2, _addrs(servers), seed=4,
                             cache_rows=8,
                             optimizer=mx.optimizer.SGD(learning_rate=0.1))
    table.push_grad(np.array([3, 15]),
                    np.ones((2, 2), dtype=np.float32))
    ckpt = table.checkpoint_rows()
    servers[1]._simulate_crash()
    with pytest.raises(ServerLostError):
        table.pull_rows(np.array([15]))
    respawn = _spawn(1)
    table.replace_shard(1, "127.0.0.1", respawn[0].port, restore=ckpt)
    # bit-identical recovery, breaker re-closed, failover counted
    assert np.array_equal(table.checkpoint_rows(), ckpt)
    st = table.stats()
    assert st["failovers"] == 1
    assert st["shards"]["1"]["breaker"] == "closed"
    # the optimizer was re-shipped: grad pushes keep working post-heal
    table.push_grad(np.array([15]), np.ones((1, 2), dtype=np.float32))
    assert np.allclose(table.pull_rows([15]), ckpt[15] - 0.1)
    _teardown(table, servers + respawn)


def test_replace_shard_restore_overwrites_standby_server_rows():
    """replace_shard(restore=...) pointed at a STANDBY server that was
    already initialized must overwrite the stale rows — an idempotent
    no-op ack would silently defeat the checkpoint-restore recovery
    path.  Retried inits with no payload stay idempotent, and a
    conflicting shard spec is a structured error, never a silent keep."""
    servers = _spawn(2)
    init = np.arange(20, dtype=np.float32).reshape(10, 2)
    table = ShardedEmbedding("standby", 10, 2, _addrs(servers),
                             cache_rows=0, init_values=init)
    # re-point shard 0 at the SAME still-initialized server with a
    # restore payload: its rows must become the checkpoint's, not stay
    # at the stale init
    ckpt = init + 100.0
    table.replace_shard(0, "127.0.0.1", servers[0].port, restore=ckpt)
    out = table.pull_rows(np.arange(10))
    assert np.array_equal(out[:5], ckpt[:5])    # shard 0 owns [0,5)
    assert np.array_equal(out[5:], init[5:])    # shard 1 untouched
    # same spec, no payload: idempotent (a transport retry keeps rows)
    reply = table._request(0, {"cmd": "embed_init", "table": "standby",
                               "dim": 2, "row_start": 0, "row_end": 5})
    assert reply["ok"] and reply["rows"] == 5
    assert np.array_equal(table.pull_rows(np.arange(5)), ckpt[:5])
    # a different row range over existing state is a protocol bug
    with pytest.raises(MXNetError, match="different shard spec"):
        table._request(0, {"cmd": "embed_init", "table": "standby",
                           "dim": 2, "row_start": 0, "row_end": 7})
    _teardown(table, servers)


def test_checkpoint_restore_chunked_roundtrip(monkeypatch):
    monkeypatch.setenv("MXNET_EMBED_PULL_CHUNK", "7")   # force chunking
    servers = _spawn(2)
    t1 = ShardedEmbedding("ck1", 23, 3, _addrs(servers), seed=1,
                          cache_rows=0)
    ckpt = t1.checkpoint_rows()
    assert ckpt.shape == (23, 3)
    t2 = ShardedEmbedding("ck2", 23, 3, _addrs(servers), seed=2,
                          cache_rows=0)
    assert not np.array_equal(t2.checkpoint_rows(), ckpt)
    t2.restore_rows(ckpt)
    assert np.array_equal(t2.checkpoint_rows(), ckpt)
    with pytest.raises(MXNetError, match="checkpoint shape"):
        t2.restore_rows(np.zeros((5, 3), np.float32))
    _teardown(t1, [])
    _teardown(t2, servers)


# -- Module.fit integration ---------------------------------------------------

def _click_tower(hidden=16):
    emb = sym.Variable("emb")
    den = sym.Variable("dense")
    deep = sym.FullyConnected(emb, num_hidden=hidden, name="deep1")
    deep = sym.Activation(deep, act_type="relu")
    wide = sym.FullyConnected(den, num_hidden=hidden, name="wide1")
    out = sym.FullyConnected(deep + wide, num_hidden=2, name="head")
    return sym.SoftmaxOutput(out, name="softmax")


def test_module_fit_trains_sharded_table():
    """The wide-and-deep path: ids -> adapter lookup -> Module.fit with
    inputs_need_grad -> batch-end row-sparse push to the shards."""
    rows, dim, n, batch = 64, 4, 128, 16
    servers = _spawn(2)
    table = ShardedEmbedding("wd", rows, dim, _addrs(servers), seed=7,
                             cache_rows=32,
                             optimizer=mx.optimizer.SGD(learning_rate=0.1))
    before = table.checkpoint_rows()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, rows, size=(n, 2)).astype(np.int64)
    dense = rng.randn(n, 4).astype(np.float32)
    label = ((ids[:, 0] + ids[:, 1]) % 2).astype(np.float32)
    base = io.NDArrayIter({"emb": ids.astype(np.float32), "dense": dense},
                          {"softmax_label": label}, batch_size=batch)
    adapter = EmbeddingFitAdapter(table, base, id_field=0)
    assert adapter.provide_data[0].shape == (batch, 2 * dim)

    mod = mx.mod.Module(_click_tower(), data_names=("emb", "dense"),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=adapter.provide_data,
             label_shapes=adapter.provide_label,
             for_training=True, inputs_need_grad=True)
    mod.fit(adapter, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            batch_end_callback=adapter.make_callback(mod),
            eval_metric="acc")
    assert adapter.pushes == 2 * (n // batch)
    after = table.checkpoint_rows()
    # the embedding rows actually trained (moved off their init)
    assert not np.array_equal(before, after)
    assert np.isfinite(after).all()
    st = table.stats()
    assert st["cache"]["hit_rate"] > 0      # hot rows stayed device-hot
    assert sum(s["rows_pushed"] for s in st["shards"].values()) > 0
    _teardown(table, servers)


def test_gluon_sparse_embedding_exact_leaf_updates():
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import nn
    servers = _spawn(1)
    init = np.full((10, 3), 2.0, dtype=np.float32)
    table = ShardedEmbedding("gluon", 10, 3, _addrs(servers),
                             cache_rows=0, init_values=init,
                             optimizer=mx.optimizer.SGD(learning_rate=0.5,
                                                        momentum=0.0))
    emb = nn.SparseEmbedding(table)
    assert "10 -> 3" in repr(emb)
    with autograd.record():
        v = emb(mx.nd.array(np.array([[3, 7], [3, 0]], np.float32)))
        loss = (v * v).sum()
    loss.backward()
    emb.push_grads()
    out = table.pull_rows(np.arange(10))
    # dL/dv = 2v = 4; id 3 appears twice -> grad 8, step -0.5*8 = -4
    assert np.allclose(out[3], 2.0 - 4.0)
    assert np.allclose(out[7], 2.0 - 2.0)
    assert np.allclose(out[0], 2.0 - 2.0)
    assert np.allclose(out[[1, 2, 4, 5, 6, 8, 9]], 2.0)
    _teardown(table, servers)


# -- serving ------------------------------------------------------------------

def _emb_tower_fleet(in_dim, n_replicas=2):
    from incubator_mxnet_tpu.serving import LocalReplica
    np.random.seed(0)
    mx.random.seed(0)
    net = sym.FullyConnected(sym.Variable("emb"), num_hidden=3,
                             name="head")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("emb",),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=[io.DataDesc("emb", (2, in_dim))],
             label_shapes=[io.DataDesc("softmax_label", (2,))],
             for_training=False, grad_req="null")
    mod.init_params(mx.initializer.Xavier())
    args, auxs = mod.get_params()
    served = [mx.serving.ServedModel(net, args, auxs,
                                     data_shapes=[("emb", (1, in_dim))],
                                     buckets=(1, 2, 4), ctx=mx.cpu(),
                                     name="tower")
              for _ in range(n_replicas)]
    return [LocalReplica(s, replica_id=f"r{i}")
            for i, s in enumerate(served)]


def test_serving_path_fans_out_and_survives_shard_kill():
    """The chaos matrix's serving half, in-process: a shard SIGKILL
    mid-traffic is recovered by the on_shard_lost hook (respawn +
    replace_shard) with ZERO lost admitted requests."""
    from incubator_mxnet_tpu.serving import ReplicaRouter
    rows, dim, slots = 40, 4, 2
    servers = _spawn(2)
    table = ShardedEmbedding("serve", rows, dim, _addrs(servers), seed=9,
                             cache_rows=0)     # every lookup hits shards
    ckpt = table.checkpoint_rows()
    state = {"spawned": None}

    def on_shard_lost(err):
        state["spawned"] = _spawn(1)[0]
        table.replace_shard(err.server, "127.0.0.1",
                            state["spawned"].port, restore=ckpt)
        return True

    reps = _emb_tower_fleet(slots * dim)
    with ReplicaRouter(reps, health_interval_s=0.2) as router:
        path = EmbeddingServingPath(table, router, embed_input="emb",
                                    on_shard_lost=on_shard_lost)
        ids = np.array([[1, 30], [5, 25]])
        baseline = path.predict(ids, timeout_ms=10000)[0].asnumpy()
        servers[0]._simulate_crash()          # kill shard 0 mid-traffic
        results = [path.predict(ids, timeout_ms=10000)[0].asnumpy()
                   for _ in range(4)]
        for got in results:
            assert np.allclose(got, baseline)
    st = path.stats()
    assert st["shard_failovers"] >= 1
    assert st["completed"] == st["requests"] == 5   # zero lost
    assert table.stats()["failovers"] == 1
    _teardown(table, [s for s in servers + [state["spawned"]] if s])


def test_serving_path_without_hook_propagates():
    from incubator_mxnet_tpu.serving import ReplicaRouter
    servers = _spawn(1)
    table = ShardedEmbedding("nohook", 8, 4, _addrs(servers),
                             cache_rows=0)
    reps = _emb_tower_fleet(4, n_replicas=1)
    with ReplicaRouter(reps, health_interval_s=0.2) as router:
        path = EmbeddingServingPath(table, router, embed_input="emb")
        servers[0]._simulate_crash()
        with pytest.raises(ServerLostError):
            path.predict(np.array([[1], [2]]), timeout_ms=2000)
    _teardown(table, servers)


# -- kvstore surfaces ---------------------------------------------------------

def test_local_kvstore_has_no_embedding_plane():
    with pytest.raises(MXNetError, match="parameter-server plane"):
        mx.kv.create("local").embedding("t", 10, 2)


def test_dist_kvstore_embedding_factory(monkeypatch):
    servers = _spawn(1)
    for k, v in {"DMLC_PS_ROOT_URI": "127.0.0.1",
                 "DMLC_PS_ROOT_PORT": str(servers[0].port),
                 "DMLC_RANK": "0", "DMLC_NUM_WORKER": "1",
                 "MXNET_KVSTORE_COLLECTIVE": "0"}.items():
        monkeypatch.setenv(k, v)
    kv = mx.kv.create("dist_async")
    assert kv.server_addresses() == [("127.0.0.1", servers[0].port)]
    init = np.arange(12, dtype=np.float32).reshape(6, 2)
    table = kv.embedding("kvfac", 6, 2, cache_rows=0, init_values=init)
    assert np.array_equal(table.pull_rows(np.arange(6)), init)
    # dense keys and the embedding shard share the same server
    kv.init(1, mx.nd.ones((3,)))
    _teardown(table, servers)


# -- cost model / obs ---------------------------------------------------------

def test_embedding_cost_model():
    from incubator_mxnet_tpu.analysis import cost as mxcost
    look = mxcost.analyze_embedding(1_000_000, 128, 4096, kind="lookup")
    op = look.per_op[0]
    row = 128 * 4
    assert op.flops == 0
    assert op.bytes_out == 4096 * row
    assert op.bytes_in == 4096 * row + 4096 * 8
    # rows-touched scaling: the dense table size never enters the traffic
    assert look.param_bytes == 1_000_000 * row
    adam = mxcost.analyze_embedding(1_000_000, 128, 4096, kind="adam")
    aop = adam.per_op[0]
    assert aop.flops == 14 * 4096 * 128
    assert aop.bound == "memory"            # sparse updates stream rows
    assert aop.bytes_in > 3 * 4096 * row    # w + m + v + grad
    with pytest.raises(ValueError, match="kind"):
        mxcost.analyze_embedding(10, 2, 1, kind="nope")


def test_obs_namespace_and_lookup_trace_span():
    from incubator_mxnet_tpu.obs import metrics, trace as obs_trace
    servers = _spawn(2)
    table = ShardedEmbedding("scrape", 30, 2, _addrs(servers), seed=1)
    obs_trace.reset()
    obs_trace.enable()                      # file-less: spans buffer
    try:
        table.lookup(np.array([1, 20, 1]))
        table.lookup(np.array([1, 20, 1]))   # second pass: all hot
    finally:
        obs_trace.disable()
    spans = [s for s in obs_trace.buffered()
             if s["name"] == "embedding.lookup"]
    assert len(spans) == 2 and spans[0]["args"]["rows"] == 3
    flat = metrics.registry().collect()
    assert flat["embedding.scrape.lookups"] == 2
    assert flat["embedding.scrape.lookup_rows"] == 6
    assert flat["embedding.scrape.cache.hit_rate"] == pytest.approx(0.5)
    pulled = sum(flat[f"embedding.scrape.shards.{s}.rows_pulled"]
                 for s in ("0", "1"))
    assert pulled == 2                      # distinct ids only
    assert flat["embedding.scrape.over_hbm_ratio"] >= 0
    metrics.unregister_producer("embedding.scrape")
    _teardown(table, servers)
