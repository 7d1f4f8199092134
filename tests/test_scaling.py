"""Pod-scale SPMD fast path: bucketed gradient exchange, composed
meshes, distributed BatchNorm (ISSUE 11).

The contracts certified here:

* bucket boundaries are a pure scheduling choice — bucketed,
  single-bucket, streaming, and per-key exchanges produce bit-identical
  numbers, deterministically across runs;
* the overlapped path composes with the guardian — a non-finite bucket
  neither poisons its neighbor buckets (kvstore) nor the training state
  (in-graph skip under the pod fast path);
* `SyncBatchNorm` / `sym.BatchNorm(sync=True)` at dp=4 computes the
  single-device big-batch statistics;
* composed dp×tp meshes drive `Module` through `mesh=` / `MXNET_MESH`.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import analysis, io, nd, sym
from incubator_mxnet_tpu.resilience import faults


def _multi_key_vals(devs, shapes, seed=0):
    rng = np.random.RandomState(seed)
    vals = [rng.randn(len(devs), *s).astype("f4") for s in shapes]
    return [[nd.array(v[d], ctx=dev) for d, dev in enumerate(devs)]
            for v in vals]


def _pull_all(kv, keys, shapes):
    outs = []
    for k, s in zip(keys, shapes):
        o = nd.zeros(s)
        kv.pull(k, out=o)
        outs.append(o.asnumpy())
    return outs


# ---------------------------------------------------------------------------
# bucket-boundary invariance + determinism (kvstore plane)
# ---------------------------------------------------------------------------

SHAPES = [(64,), (8, 8), (128,), (3, 5), (256,), (64,), (2, 2)]
KEYS = ["k%d" % i for i in range(len(SHAPES))]


def _push_with_cap(cap_mb, monkeypatch, ndev=4, seed=0):
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", str(cap_mb))
    devs = [mx.cpu(i) for i in range(ndev)]
    kv = mx.kv.create("device")
    for k, s in zip(KEYS, SHAPES):
        kv.init(k, nd.zeros(s))
    kv.push(KEYS, _multi_key_vals(devs, SHAPES, seed))
    return kv, _pull_all(kv, KEYS, SHAPES)


def test_bucketed_vs_single_bucket_bit_parity(monkeypatch):
    """Bucket boundaries must not change the numbers: a tiny cap (one
    key per bucket), the old single-flatten-concat dataflow (huge cap),
    and the per-key path all produce BIT-identical reduced values."""
    kv_many, outs_many = _push_with_cap(0.0001, monkeypatch)  # ~100 B cap
    kv_one, outs_one = _push_with_cap(4096, monkeypatch)      # one bucket
    st_many, st_one = kv_many.stats(), kv_one.stats()
    assert st_many["buckets"] > 1, st_many
    assert st_one["buckets"] == 1, st_one
    # per-key reference (the base reduce, no bucketing at all)
    devs = [mx.cpu(i) for i in range(4)]
    kv_ref = mx.kv.create("device")
    vals = _multi_key_vals(devs, SHAPES, 0)
    for k, s, v in zip(KEYS, SHAPES, vals):
        kv_ref.init(k, nd.zeros(s))
        kv_ref.push(k, v)
    outs_ref = _pull_all(kv_ref, KEYS, SHAPES)
    for a, b, r, k in zip(outs_many, outs_one, outs_ref, KEYS):
        assert np.array_equal(a, b), k
        assert np.array_equal(a, r), k


def test_bucket_boundaries_deterministic_across_runs(monkeypatch):
    """Two identical runs cut identical bucket boundaries (the plan is a
    pure function of order/shapes/dtypes/cap) and produce bit-identical
    results — the reproducibility half of the scheduling claim."""
    kv1, outs1 = _push_with_cap(0.0005, monkeypatch)
    kv2, outs2 = _push_with_cap(0.0005, monkeypatch)
    s1, s2 = kv1.stats(), kv2.stats()
    assert s1["buckets"] == s2["buckets"]
    assert s1["bucket_fill_hist"] == s2["bucket_fill_hist"]
    assert s1["allreduce_dispatches"] == s2["allreduce_dispatches"]
    for a, b in zip(outs1, outs2):
        assert np.array_equal(a, b)
    # the plan itself is deterministic (unit face of the same claim)
    values = [[type("V", (), {"shape": s, "dtype": np.dtype("f4")})()]
              for s in SHAPES]
    order = list(reversed(range(len(SHAPES))))
    plans = {tuple(map(tuple, kv1._plan_buckets(order, values)))
             for _ in range(3)}
    assert len(plans) == 1


def test_streaming_push_matches_batched(monkeypatch):
    """`begin_push`/`push_part`/`end_push` (gradients arriving one at a
    time, as backward materializes them) produces the same numbers as
    one batched push, while dispatching multiple capped buckets."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "0.0005")
    devs = [mx.cpu(i) for i in range(4)]
    vals = _multi_key_vals(devs, SHAPES, 3)
    kv_s = mx.kv.create("device")
    for k, s in zip(KEYS, SHAPES):
        kv_s.init(k, nd.zeros(s))
    kv_s.begin_push()
    for k, v in zip(KEYS, vals):
        kv_s.push_part(k, v)
    kv_s.end_push()
    assert kv_s.stats()["buckets"] > 1
    kv_b = mx.kv.create("device")
    for k, s in zip(KEYS, SHAPES):
        kv_b.init(k, nd.zeros(s))
    kv_b.push(KEYS, vals)
    for a, b in zip(_pull_all(kv_s, KEYS, SHAPES),
                    _pull_all(kv_b, KEYS, SHAPES)):
        assert np.array_equal(a, b)
    # streaming misuse is a structured error, not silent corruption
    with pytest.raises(mx.MXNetError):
        kv_s.push_part("k0", vals[0])
    with pytest.raises(mx.MXNetError):
        kv_s.end_push()


def test_nonfinite_bucket_does_not_poison_neighbors(monkeypatch):
    """Guardian-skip composition, kvstore face: a NaN gradient reduces
    inside ITS bucket only — every other bucket's values stay exact.
    (The training-state face is test_pod_guardian_skip_deterministic.)"""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "0.0001")
    devs = [mx.cpu(i) for i in range(4)]
    vals = _multi_key_vals(devs, SHAPES, 5)
    expect = [sum(v.asnumpy() for v in vs) for vs in vals]
    vals[2][1][:] = nd.array(np.full(SHAPES[2], np.nan, "f4"),
                             ctx=devs[1])
    kv = mx.kv.create("device")
    for k, s in zip(KEYS, SHAPES):
        kv.init(k, nd.zeros(s))
    kv.push(KEYS, vals)
    assert kv.stats()["buckets"] > 1
    outs = _pull_all(kv, KEYS, SHAPES)
    assert np.isnan(outs[2]).all(), "the poisoned bucket reduces to NaN"
    for i, (o, e) in enumerate(zip(outs, expect)):
        if i == 2:
            continue
        assert np.isfinite(o).all(), KEYS[i]
        np.testing.assert_allclose(o, e, rtol=1e-6, err_msg=KEYS[i])


def test_kvstore_stats_and_runtime_report(monkeypatch):
    """`KVStore.stats()` exposes the communication economy (dispatches,
    bytes, bucket fill, overlap) and `analysis.runtime_report()` carries
    it as a kvstore.buckets finding."""
    kv, _ = _push_with_cap(0.0005, monkeypatch)
    st = kv.stats()
    for field in ("allreduce_dispatches", "bytes_reduced", "buckets",
                  "bucket_cap_mb", "bucket_fill_hist", "avg_bucket_fill",
                  "overlap_ratio", "batched_pushes", "pull_broadcasts"):
        assert field in st, field
    assert st["bytes_reduced"] == sum(
        int(np.prod(s)) * 4 for s in SHAPES)
    assert st["allreduce_dispatches"] == st["buckets"] > 1
    findings = [f for f in analysis.runtime_report()
                if f.pass_name == "kvstore.buckets"]
    assert findings and any("batched pushes" in f.message
                            for f in findings)


def test_gradient_compression_composes_or_raises():
    """2-bit compression composes with bucketing (in-bucket quantize +
    error feedback, elementwise-identical to the per-key reference);
    any other type is a STRUCTURED unsupported error — never the base
    class stub silently half-applying."""
    kv = mx.kv.create("tpu")
    with pytest.raises(mx.MXNetError, match="unsupported"):
        kv.set_gradient_compression({"type": "1bit"})
    devs = [mx.cpu(i) for i in range(4)]
    shapes = [(6,), (4,), (8,)]
    keys = ["c%d" % i for i in range(3)]
    rng = np.random.RandomState(9)
    raw = [rng.uniform(-1, 1, (len(devs),) + s).astype("f4")
           for s in shapes]
    vals = [[nd.array(r[d], ctx=dev) for d, dev in enumerate(devs)]
            for r in raw]
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    # two pushes: the second proves the residual (error feedback) lives
    # per bucket position exactly as the reference's per-key residual
    resid = [np.zeros(s, "f4") for s in shapes]
    for _ in range(2):
        kv.push(keys, vals)
        outs = _pull_all(kv, keys, shapes)
        for i, (r, s) in enumerate(zip(raw, shapes)):
            g = r.sum(axis=0) + resid[i]
            q = np.where(g >= 0.5, 0.5,
                         np.where(g <= -0.5, -0.5, 0.0)).astype("f4")
            resid[i] = g - q
            np.testing.assert_allclose(outs[i], q, rtol=1e-6,
                                       err_msg=keys[i])


def test_gradient_compression_residual_survives_path_switch():
    """The error-feedback residual lives PER KEY, shared by the bucketed
    and per-key fallback reduce paths: alternating between a batched
    (bucketed) push and single-key (fallback) pushes accumulates the
    exact residual the pure per-key reference does — no quantization
    error is dropped or double-counted at a path switch.  None clears
    the compression state cleanly."""
    devs = [mx.cpu(i) for i in range(4)]
    shapes = [(6,), (4,)]
    keys = ["r0", "r1"]
    rng = np.random.RandomState(11)
    raw = [rng.uniform(-1, 1, (len(devs),) + s).astype("f4")
           for s in shapes]

    def vals():
        return [[nd.array(r[d], ctx=dev) for d, dev in enumerate(devs)]
                for r in raw]

    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    rounds = []
    kv.push(keys, vals())                # bucketed
    rounds.append(_pull_all(kv, keys, shapes))
    for k, v in zip(keys, vals()):       # per-key fallback
        kv.push(k, v)
    rounds.append(_pull_all(kv, keys, shapes))
    kv.push(keys, vals())                # bucketed again
    rounds.append(_pull_all(kv, keys, shapes))
    resid = [np.zeros(s, "f4") for s in shapes]
    for outs in rounds:
        for i, r in enumerate(raw):
            g = r.sum(axis=0) + resid[i]
            q = np.where(g >= 0.5, 0.5,
                         np.where(g <= -0.5, -0.5, 0.0)).astype("f4")
            resid[i] = g - q
            np.testing.assert_allclose(outs[i], q, rtol=1e-6,
                                       err_msg=keys[i])
    kv.set_gradient_compression(None)
    assert kv._compression is None and kv._residuals == {}


# ---------------------------------------------------------------------------
# pod SPMD fast path (fused train step plane)
# ---------------------------------------------------------------------------

def _scaling_model(sync_bn=None, seed=0, hidden=16):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=hidden, name="fc1")
    if sync_bn is not None:
        net = sym.BatchNorm(net, name="bn1", sync=sync_bn,
                            fix_gamma=False)
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _scaling_data(n=128, bs=16):
    rng = np.random.RandomState(3)
    x = rng.standard_normal((n, 10)).astype("float32")
    # row-dependent scale: each dp shard of a batch sees a DIFFERENT
    # local variance, so shard-local BN statistics are measurably wrong
    x *= (1.0 + (np.arange(n) % bs)[:, None] / 4.0).astype("float32")
    y = rng.randint(0, 4, n).astype("float32")
    return io.NDArrayIter(x, y, batch_size=bs, shuffle=False)


def _fit(net, ctxs, num_epoch=2):
    mod = mx.mod.Module(net, context=ctxs)
    mod.fit(_scaling_data(), kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, eval_metric="acc",
            initializer=mx.initializer.Xavier(), num_epoch=num_epoch)
    return mod


def _params(mod):
    args, auxs = mod.get_params()
    return ({k: v.asnumpy() for k, v in args.items()},
            {k: v.asnumpy() for k, v in auxs.items()})


def test_pod_fast_path_matches_gspmd_lowering(monkeypatch):
    """The shard_map+bucketed-psum program computes what the GSPMD
    global-view program computes (the psum of per-shard gradients IS the
    cross-device sum)."""
    monkeypatch.setenv("MXNET_POD_SPMD", "1")
    a = _fit(_scaling_model(), [mx.cpu(i) for i in range(4)])
    assert a._fused_step.pod_stats is not None, "pod path must engage"
    assert a._fused_step.pod_stats["collectives_per_step"] <= \
        a._fused_step.pod_stats["params"]
    monkeypatch.setenv("MXNET_POD_SPMD", "0")
    b = _fit(_scaling_model(), [mx.cpu(i) for i in range(4)])
    assert b._fused_step.pod_stats is None
    pa, aa = _params(a)
    pb, ab = _params(b)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=2e-5, atol=2e-6,
                                   err_msg=k)
    for k in aa:
        np.testing.assert_allclose(aa[k], ab[k], rtol=2e-5, atol=2e-6,
                                   err_msg=k)


def test_pod_bucket_cap_bit_parity(monkeypatch):
    """In-graph bucket boundaries (MXNET_KVSTORE_BUCKET_MB caps the pod
    exchange's buckets too) are bit-invariant on the final params."""
    monkeypatch.setenv("MXNET_POD_SPMD", "1")
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "0.0001")
    a = _fit(_scaling_model(), [mx.cpu(i) for i in range(4)])
    assert a._fused_step.pod_stats["buckets"] > 1
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "4096")
    b = _fit(_scaling_model(), [mx.cpu(i) for i in range(4)])
    assert b._fused_step.pod_stats["buckets"] == 1
    pa, aa = _params(a)
    pb, ab = _params(b)
    for k in pa:
        assert np.array_equal(pa[k], pb[k]), k
    for k in aa:
        assert np.array_equal(aa[k], ab[k]), k


def test_pod_guardian_skip_deterministic(monkeypatch):
    """Overlap path under guardian skip-batch: an injected non-finite
    gradient inside the bundled pod exchange skips THAT step on every
    shard — deterministically (two runs bit-identical), leaving every
    parameter finite."""
    monkeypatch.setenv("MXNET_POD_SPMD", "1")
    monkeypatch.setenv("MXNET_GUARDIAN_INTERVAL", "4")
    monkeypatch.setenv("MXNET_GUARDIAN_SPIKE_WINDOW", "4")

    def run():
        faults.configure("seed=7;grad.nonfinite:error(at=3)")
        mod = _fit(_scaling_model(), [mx.cpu(i) for i in range(2)])
        st = mod._guardian.stats()
        faults.clear()
        return _params(mod), st, mod

    (pa, aa), st1, mod = run()
    (pb, ab), st2, _ = run()
    assert mod._fused_step.pod_stats is not None, "pod path must engage"
    assert st1["skips"] == 1 and st1["injected_nonfinite"] == 1
    assert st1["skips"] == st2["skips"]
    for k in pa:
        assert np.array_equal(pa[k], pb[k]), k
        assert np.isfinite(pa[k]).all(), k
    for k in aa:
        assert np.array_equal(aa[k], ab[k]), k


# ---------------------------------------------------------------------------
# distributed BatchNorm
# ---------------------------------------------------------------------------

def test_sync_batchnorm_dp4_matches_big_batch():
    """`sym.BatchNorm(sync=True)` at dp=4 == the single-device big-batch
    reference: same params AND same moving statistics, because the
    moments are exchanged over the dp axis (the fused global-view path
    and the single device both see the global batch; the pod shard_map
    path psums the moments)."""
    a = _fit(_scaling_model(sync_bn=True), [mx.cpu(i) for i in range(4)])
    b = _fit(_scaling_model(sync_bn=True), mx.cpu(0))
    pa, aa = _params(a)
    pb, ab = _params(b)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)
    for k in aa:
        np.testing.assert_allclose(aa[k], ab[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_pod_plain_batchnorm_falls_back_to_global_view():
    """Plain (sync=False) train-mode BatchNorm must NOT ride the pod
    shard_map path: inside shard_map its mean would reduce over the
    SHARD batch, silently changing the fused path's documented
    global-batch BN semantics.  The graph falls back to the GSPMD
    global-view lowering, where dp=4 still computes the single-device
    big-batch statistics."""
    a = _fit(_scaling_model(sync_bn=False), [mx.cpu(i) for i in range(4)])
    assert a._fused_step.pod_stats is None, \
        "unsynced BN must disable the pod fast path"
    b = _fit(_scaling_model(sync_bn=False), mx.cpu(0))
    pa, aa = _params(a)
    pb, ab = _params(b)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)
    for k in aa:
        np.testing.assert_allclose(aa[k], ab[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_sync_batchnorm_non_dp_axis_name_falls_back(monkeypatch):
    """A mesh whose data-parallel axis is NOT named 'dp' must not let
    sync BN go silently shard-local under the pod fast path: the op
    psums over its `sync_axis` NAME, so an axis-name mismatch falls
    back to the global-view lowering — which computes the single-device
    big-batch statistics regardless of axis names."""
    monkeypatch.setenv("MXNET_MESH", "data=4")
    a = _fit(_scaling_model(sync_bn=True), [mx.cpu(i) for i in range(4)])
    assert a._fused_step._dp_axis == "data"
    assert a._fused_step.pod_stats is None, \
        "sync_axis != mesh dp axis must disable the pod fast path"
    monkeypatch.delenv("MXNET_MESH")
    b = _fit(_scaling_model(sync_bn=True), mx.cpu(0))
    pa, aa = _params(a)
    pb, ab = _params(b)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)
    for k in aa:
        np.testing.assert_allclose(aa[k], ab[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_gluon_sync_batchnorm_sets_sync_attr():
    bn = mx.gluon.nn.SyncBatchNorm(in_channels=8)
    assert bn._kwargs["sync"] is True
    assert bn._kwargs["sync_axis"] == "dp"
    # historical contrib path stays importable and identical
    cbn = mx.gluon.contrib.nn.SyncBatchNorm(in_channels=8)
    assert cbn._kwargs["sync"] is True


# ---------------------------------------------------------------------------
# composed meshes under Module
# ---------------------------------------------------------------------------

def test_mesh_spec_parsing():
    from incubator_mxnet_tpu.parallel.mesh import (dp_axis_of,
                                                   mesh_from_spec,
                                                   parse_spec)
    assert parse_spec("dp=4,tp=2") == {"dp": 4, "tp": 2}
    assert parse_spec(" dp=8 ") == {"dp": 8}
    with pytest.raises(mx.MXNetError):
        parse_spec("dp:4")
    with pytest.raises(mx.MXNetError):
        parse_spec("dp=four")
    assert mesh_from_spec("") is None
    import jax
    mesh = mesh_from_spec("dp=4,tp=2", devices=jax.devices()[:8])
    assert tuple(mesh.axis_names) == ("dp", "tp")
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    assert dp_axis_of(mesh) == "dp"
    tp_first = mesh_from_spec({"tp": 2, "x": 4},
                              devices=jax.devices()[:8])
    assert dp_axis_of(tp_first) == "tp"   # no 'dp' -> first axis


def test_module_fit_composed_mesh(monkeypatch):
    """A composed dp×tp mesh drives the fused step from the public
    `Module` API: the batch shards over the 4-wide dp axis (not the raw
    8-device count), and training completes with finite params."""
    net = _scaling_model()
    ctxs = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.Module(net, context=ctxs)
    it = _scaling_data()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05},
                       mesh="dp=4,tp=2")
    metric = mx.metric.create("acc")
    for batch in it:
        mod.fit_step(batch, metric)
    fs = mod._fused_step
    assert fs is not None and not fs.broken
    assert fs._dp_size == 4
    assert tuple(fs._mesh.axis_names) == ("dp", "tp")
    assert fs._pod_axis is None   # composed mesh -> global-view lowering
    for k, v in _params(mod)[0].items():
        assert np.isfinite(v).all(), k
    # MXNET_MESH env drives the same lever without code changes
    monkeypatch.setenv("MXNET_MESH", "dp=2")
    mod2 = _fit(_scaling_model(), [mx.cpu(i) for i in range(2)])
    assert mod2._fused_step._dp_size == 2


def test_trainer_zero_flags():
    """`Trainer(zero=...)` boolean contract: False is a no-op (not a
    crash), True without a mesh is a structured error, and True on a
    composed mesh shards over the DATA-parallel axis by name — never
    whatever axis happens to be listed first."""
    import jax
    from incubator_mxnet_tpu.parallel.mesh import mesh_from_spec

    def make(**kw):
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net(nd.zeros((2, 8)))
        return mx.gluon.Trainer(net.collect_params(), "sgd", **kw)

    assert make(zero=False)._zero is None
    with pytest.raises(mx.MXNetError, match="mesh"):
        make(zero=True)
    mesh = mesh_from_spec("tp=2,dp=4", devices=jax.devices()[:8])
    assert make(zero=True, mesh=mesh)._zero == (mesh, "dp")
    assert make(zero=mesh)._zero == (mesh, "dp")


# ---------------------------------------------------------------------------
# unbucketed-push lint
# ---------------------------------------------------------------------------

def test_unbucketed_push_lint_fixtures():
    """Per-parameter kv.push/pull inside a training loop is the classic
    pod-scale throughput killer: one collective per key instead of
    O(buckets).  The lint names it; batched calls and non-loop pushes
    stay quiet; the disable comment suppresses."""
    bad = (
        "kv = mx.kv.create('tpu')\n"                     # 1
        "for i, p in enumerate(params):\n"               # 2
        "    kv.push(i, p.list_grad())\n"                # 3
        "    kv.pull(i, p.list_grad())\n"                # 4
        "for j in range(3):\n"                           # 5
        "    kv.push(j, grads[j])  # mxlint: disable\n"  # 6
    )
    report = analysis.check_source(bad, "train.py")
    locs = sorted(f.location for f in report
                  if f.code == "unbucketed-push")
    assert locs == ["train.py:3", "train.py:4"], report.format()
    good = (
        "kv = mx.kv.create('tpu')\n"
        "keys = list(range(len(params)))\n"
        "for epoch in range(10):\n"
        "    kv.push(keys, grads)\n"         # whole key list: batched
        "    kv.pull(keys, grads)\n"
        "kv.push(0, g0)\n"                   # outside any loop
    )
    assert not [f for f in analysis.check_source(good, "ok.py")
                if f.code == "unbucketed-push"]
