"""Benchmark: ResNet-50 training throughput (img/sec) on one chip, driven
through the PUBLIC `Module.fit` API.

Baseline (BASELINE.md): reference MXNet ResNet-50 *training* at 363.69
img/sec on V100, batch 128 (`docs/faq/perf.md:205-224`).

What is measured: `mx.mod.Module.fit` — the same user-facing loop as the
reference's `train_imagenet.py` — with a synthetic device-resident
ImageNet-shaped iterator (the reference perf harness
`benchmark_score.py` uses synthetic data the same way).  `Module.fit`
compiles the whole train step (forward + backward + SGD-momentum +
BatchNorm stats + in-graph accuracy metric) into ONE donated XLA program
per signature (`incubator_mxnet_tpu/fused.py`); nothing here hand-builds
jax — the framework path IS the benched path.

Default dtype is **bfloat16** (the TPU MXU's native matmul type) with
fp32 master weights via the multi-precision optimizer; fp32 is kept as a
lane.  A hand-written pure-JAX ResNet-50 control runs at both dtypes on
the same chip: `ratio_vs_pure_jax` / `ratio_vs_pure_jax_bf16` are the
framework-overhead metrics.

Runs on a TPU or not at all: without one it exits non-zero and says why.
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
A lane that fails ends the run: the line carries what was measured so
far plus `"error"`, and the exit code is non-zero.  A watchdog
(BENCH_BUDGET_S, default 480 s) prints a partial result, also with a
non-zero exit.

Env overrides: BENCH_BATCH (128), BENCH_IMAGE (224), BENCH_STEPS (48),
BENCH_DTYPE (bfloat16), BENCH_BUDGET_S (480), BENCH_CONTROL (1),
BENCH_FP32 (1), BENCH_REAL_DATA (1).

The fit loop runs K steps per dispatch (MXNET_FUSED_STEP_BLOCK, default
8) as one lax.scan program; callbacks fire in bursts of K after each
block, so the probe's warm-up and measurement window are sized to block
boundaries (warm = K, steps rounded up to a K multiple) — the metric
get() at each edge is a true device sync either way.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

BASELINE_IMG_S = 363.69  # reference ResNet-50 training, V100 bs=128

# fit-loop dispatch block size: probe windows align to block boundaries
_BLOCK = max(int(os.environ.get("MXNET_FUSED_STEP_BLOCK", "8") or 1), 1)

_RESULT = {
    "metric": "resnet50_train_img_per_sec",
    "value": 0.0,
    "unit": "img/sec/chip",
    "vs_baseline": 0.0,
    "phase": "startup",
}
_EMITTED = False


def _emit():
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    print(json.dumps(_RESULT), flush=True)


def _alarm(signum, frame):
    _RESULT["partial"] = True
    _emit()
    os._exit(1)


def _watchdog(budget):
    """Thread-based budget watchdog: SIGALRM delivery is deferred while the
    main thread sits in a long C call (an XLA compile), so a timer thread
    emits the partial result and ends the process — with a failing exit
    code: an overrun is not a result."""
    import threading

    def fire():
        _RESULT["partial"] = True
        _emit()
        os._exit(1)

    t = threading.Timer(budget, fire)
    t.daemon = True
    t.start()
    return t


# ---------------------------------------------------------------------------
# Framework path: public Module.fit over a synthetic device-resident iter
# ---------------------------------------------------------------------------

def _synthetic_iter(mx, batch, image, dtype, n_batches, ctx):
    """DataIter yielding the SAME device-resident batch (the reference
    benchmark harness pattern: measure compute, not host data generation)."""
    from incubator_mxnet_tpu import io, nd

    data = nd.array(np.random.rand(batch, 3, image, image).astype("f4"),
                    ctx=ctx).astype(dtype)
    label = nd.array(np.random.randint(0, 1000, batch).astype("f4"), ctx=ctx)
    data_desc = io.DataDesc("data", (batch, 3, image, image),
                            dtype=np.dtype(dtype))
    label_desc = io.DataDesc("softmax_label", (batch,), dtype=np.float32)
    batch_obj = io.DataBatch(data=[data], label=[label], pad=0,
                             provide_data=[data_desc],
                             provide_label=[label_desc])

    class SyntheticIter(io.DataIter):
        def __init__(self):
            super().__init__(batch_size=batch)
            self._i = 0

        @property
        def provide_data(self):
            return [data_desc]

        @property
        def provide_label(self):
            return [label_desc]

        def reset(self):
            self._i = 0

        def next(self):
            if self._i >= n_batches:
                raise StopIteration
            self._i += 1
            return batch_obj

    return SyntheticIter()


class _Probe:
    """Speedometer-style batch callback: syncs on the in-graph metric at
    the window edges and derives steady-state img/s."""

    def __init__(self, warm, steps, batch):
        self.warm = warm
        self.steps = steps
        self.batch = batch
        self.t0 = None
        self.img_s = None
        self.compile_s = None
        self._t_start = time.perf_counter()

    def __call__(self, param):
        if param.nbatch == 0:
            # first batch completed -> compile + first step
            param.eval_metric.get()
            self.compile_s = time.perf_counter() - self._t_start
        elif param.nbatch == self.warm:
            param.eval_metric.get()  # blocks until step `warm` is done
            self.t0 = time.perf_counter()
        elif param.nbatch == self.warm + self.steps:
            acc = dict(param.eval_metric.get_name_value())
            dt = time.perf_counter() - self.t0
            self.img_s = self.batch * self.steps / dt
            self.final_acc = acc


def _build_module(mx, batch, image, dtype, norm=None):
    from incubator_mxnet_tpu import sym
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    net = resnet50_v1(classes=1000)
    data = sym.Variable("data")
    # device-augment pipelines ship uint8 NHWC; `norm` is the in-graph
    # normalize/cast/NCHW head (iterator.normalize_symbol) XLA fuses into
    # the first convolution
    x = norm(data) if norm is not None else data
    out = net(x)  # gluon block composed symbolically
    out = sym.SoftmaxOutput(out, name="softmax")
    ctx = mx.tpu()
    return mx.mod.Module(out, context=ctx,
                         label_names=("softmax_label",)), ctx


def _run_framework(batch, image, steps, dtype):
    import incubator_mxnet_tpu as mx

    mx.random.seed(0)
    t0 = time.perf_counter()
    mod, ctx = _build_module(mx, batch, image, dtype)
    warm = _BLOCK
    # last probe edge (warm+steps) must land inside a full block: feed
    # exactly one block past it, no ragged tail
    it = _synthetic_iter(mx, batch, image, dtype, warm + steps + _BLOCK, ctx)
    probe = _Probe(warm, steps, batch)
    init_s = time.perf_counter() - t0

    mod.fit(it, num_epoch=1,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "multi_precision": dtype != "float32",
                              "rescale_grad": 1.0 / batch},
            eval_metric="acc",
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            batch_end_callback=probe,
            kvstore=None)
    assert probe.img_s is not None, "probe never hit the measurement window"
    acc = probe.final_acc.get("accuracy", float("nan"))
    assert np.isfinite(acc), "training produced non-finite metric"
    fused = mod._fused_step
    assert fused is not None and not fused.broken, \
        "public fit path must run the fused train step"
    return init_s, probe.compile_s, probe.img_s, fused.compile_phase_stats()


def _run_gluon(batch, image, steps, dtype):
    """Gluon lane: model_zoo ResNet-50 driven by the PUBLIC
    `gluon.contrib.estimator.Estimator.fit` loop — the fused Gluon step
    (gluon/fused_step.py) compiles forward+loss+backward+optimizer+metric
    into one donated program, the Gluon analogue of the Module lane."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    import jax

    mx.random.seed(0)
    ctx = mx.tpu()
    net = gluon.model_zoo.vision.resnet50_v1(classes=1000)
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian",
                                         factor_type="in", magnitude=2),
                   ctx=ctx)
    if dtype != "float32":
        net.cast(dtype)
    # materialize deferred params with one eager forward so the FIRST fit
    # batch can fuse (otherwise batch 0 runs eager and the probe's
    # compile_s would record the eager step, not the fused XLA compile)
    net(nd.array(np.zeros((1, 3, image, image), "f4"), ctx=ctx).astype(dtype))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9,
                             "multi_precision": dtype != "float32",
                             "rescale_grad": 1.0 / batch})
    est = gluon.contrib.estimator.Estimator(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        train_metrics=[mx.metric.Accuracy()], trainer=trainer)

    data = nd.array(np.random.rand(batch, 3, image, image).astype("f4"),
                    ctx=ctx).astype(dtype)
    label = nd.array(np.random.randint(0, 1000, batch).astype("f4"), ctx=ctx)
    warm = _BLOCK
    times = {}

    class Probe:
        def train_begin(self, est):
            self.t0 = time.perf_counter()

        def epoch_begin(self, est):
            pass

        def batch_begin(self, est):
            pass

        def batch_end(self, est):
            i = est.batch_idx
            if i == 0:
                for m in est.train_metrics:
                    m.get()          # sync: compile + first step done
                times["compile"] = time.perf_counter() - self.t0
            elif i == warm:
                for m in est.train_metrics:
                    m.get()
                times["t0"] = time.perf_counter()
            elif i == warm + steps:
                for m in est.train_metrics:
                    m.get()
                times["img_s"] = batch * steps / (
                    time.perf_counter() - times["t0"])

        def epoch_end(self, est):
            pass

        def train_end(self, est):
            pass

    batches = [(data, label)] * (warm + steps + _BLOCK)
    est.fit(iter(batches), epochs=1, event_handlers=[Probe()])
    assert est._fused is not None and not est._fused.broken, \
        "Estimator must run the fused Gluon step"
    assert "img_s" in times, "gluon probe missed its window"
    return times["compile"], times["img_s"], \
        est._fused.compile_phase_stats()


# ---------------------------------------------------------------------------
# PTB LSTM lane (BASELINE config #4: example/rnn/bucketing/lstm_bucketing.py
# — 2x200 LSTM, embed 200, vocab 10k, batch 32, bptt 35).  The framework
# path is the bucketing example's symbol: cell unroll emits ONE _foreach
# (lax.scan); a hand-written raw-JAX LSTM control runs the same math.
# ---------------------------------------------------------------------------

_LSTM_CFG = dict(vocab=10000, embed=200, hidden=200, layers=2,
                 batch=32, seq=35)


def _lstm_symbol(mx, cfg):
    from incubator_mxnet_tpu import rnn
    stack = rnn.SequentialRNNCell()
    for i in range(cfg["layers"]):
        stack.add(rnn.LSTMCell(cfg["hidden"], prefix=f"lstm_l{i}_"))
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    embed = mx.sym.Embedding(data, input_dim=cfg["vocab"],
                             output_dim=cfg["embed"], name="embed")
    stack.reset()
    outputs, _ = stack.unroll(cfg["seq"], inputs=embed, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, cfg["hidden"]))
    pred = mx.sym.FullyConnected(pred, num_hidden=cfg["vocab"], name="pred")
    lab = mx.sym.Reshape(label, shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, lab, name="softmax")
    n_scan = sum(1 for n in net._topo()
                 if not n.is_variable and n.op.name == "_foreach")
    assert n_scan == 1, "bucketed LSTM must compile to ONE scan"
    return net


def _run_lstm_framework(steps):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import io, nd

    cfg = _LSTM_CFG
    mx.random.seed(0)
    ctx = mx.tpu()
    net = _lstm_symbol(mx, cfg)
    batch, seq = cfg["batch"], cfg["seq"]
    rng = np.random.RandomState(0)
    data = nd.array(rng.randint(0, cfg["vocab"], (batch, seq))
                    .astype("f4"), ctx=ctx)
    label = nd.array(rng.randint(0, cfg["vocab"], (batch, seq))
                     .astype("f4"), ctx=ctx)
    warm = _BLOCK
    n_batches = warm + steps + _BLOCK
    batch_obj = io.DataBatch(
        data=[data], label=[label], pad=0,
        provide_data=[io.DataDesc("data", (batch, seq), dtype=np.float32)],
        provide_label=[io.DataDesc("softmax_label", (batch, seq),
                                   dtype=np.float32)])

    class It(io.DataIter):
        def __init__(self):
            super().__init__(batch_size=batch)
            self._i = 0

        provide_data = property(lambda s: batch_obj.provide_data)
        provide_label = property(lambda s: batch_obj.provide_label)

        def reset(self):
            self._i = 0

        def next(self):
            if self._i >= n_batches:
                raise StopIteration
            self._i += 1
            return batch_obj

    mod = mx.mod.Module(net, context=ctx)
    probe = _Probe(warm, steps, batch)
    mod.fit(It(), num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            eval_metric=mx.metric.Perplexity(0),
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.34),
            batch_end_callback=probe, kvstore=None)
    assert probe.img_s is not None, "lstm probe missed its window"
    fused = mod._fused_step
    assert fused is not None and not fused.broken, \
        "lstm lane must run the fused train step"
    return (probe.compile_s, probe.img_s * seq,   # tokens/s
            fused.compile_phase_stats())


def _pure_jax_lstm(steps):
    """Raw-JAX 2-layer LSTM LM matching _LSTM_CFG: embed -> scan -> FC ->
    CE, SGD momentum, donated step — the hand-written control."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    cfg = _LSTM_CFG
    V, E, H, L = cfg["vocab"], cfg["embed"], cfg["hidden"], cfg["layers"]
    B, T = cfg["batch"], cfg["seq"]
    rng = np.random.RandomState(0)

    def mk(shape, scale=0.1):
        return rng.uniform(-scale, scale, shape).astype("f4")

    w = {"emb": mk((V, E)), "fc_w": mk((V, H)), "fc_b": np.zeros(V, "f4")}
    for i in range(L):
        cin = E if i == 0 else H
        w[f"wx{i}"] = mk((4 * H, cin))
        w[f"wh{i}"] = mk((4 * H, H))
        w[f"b{i}"] = np.zeros(4 * H, "f4")

    def lstm_layer(p, i, xs):
        def step(carry, x):
            h, c = carry
            g = x @ p[f"wx{i}"].T + h @ p[f"wh{i}"].T + p[f"b{i}"]
            ii, f, gg, o = jnp.split(g, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(ii) * jnp.tanh(gg)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        h0 = jnp.zeros((xs.shape[1], H), xs.dtype)
        (_, _), ys = lax.scan(step, (h0, h0), xs)
        return ys

    def loss_fn(p, tok, lab):
        xs = p["emb"][tok].transpose(1, 0, 2)   # (T, B, E)
        for i in range(L):
            xs = lstm_layer(p, i, xs)
        logits = xs.reshape(-1, H) @ p["fc_w"].T + p["fc_b"]
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(
            logp, lab.transpose(1, 0).reshape(-1)[:, None], -1)
        return -jnp.mean(ll)

    def train_step(p, m, tok, lab, lr):
        loss, grads = jax.value_and_grad(loss_fn)(p, tok, lab)
        new_p, new_m = {}, {}
        for k in p:
            mom = 0.9 * m[k] - lr * grads[k]
            new_m[k] = mom
            new_p[k] = p[k] + mom
        return new_p, new_m, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    p = {k: jnp.asarray(v) for k, v in w.items()}
    m = {k: jnp.zeros_like(v) for v, k in zip(w.values(), w)}
    tok = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
    lr = jnp.float32(0.1)
    t0 = time.perf_counter()
    p, m, loss = step(p, m, tok, lab, lr)
    float(loss)
    compile_s = time.perf_counter() - t0
    p, m, loss = step(p, m, tok, lab, lr)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        p, m, loss = step(p, m, tok, lab, lr)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final)
    return compile_s, B * T * steps / dt


# ---------------------------------------------------------------------------
# Control path: hand-written raw-JAX ResNet-50 train step (no framework)
# ---------------------------------------------------------------------------

def _pure_jax_resnet50(batch, image, dtype):
    """Raw-JAX ResNet-50 v1 (NCHW, same arch as the framework model):
    conv/bn/relu stem, bottleneck stages [3,4,6,3], SGD momentum, BN
    running stats — everything a performance-minded JAX user would write."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.RandomState(0)
    params, auxs = {}, {}

    def conv_p(name, cin, cout, k):
        fan = (cin * k * k + cout * k * k) / 2.0
        s = np.sqrt(3.0 / fan)
        params[name + ".w"] = rng.uniform(-s, s, (cout, cin, k, k)).astype("f4")

    def bn_p(name, c):
        params[name + ".g"] = np.ones(c, "f4")
        params[name + ".b"] = np.zeros(c, "f4")
        auxs[name + ".mean"] = np.zeros(c, "f4")
        auxs[name + ".var"] = np.ones(c, "f4")

    # stem
    conv_p("stem", 3, 64, 7)
    bn_p("stem", 64)
    layers = [3, 4, 6, 3]
    chans = [(64, 256), (128, 512), (256, 1024), (512, 2048)]
    cin = 64
    for si, (n, (cm, cout)) in enumerate(zip(layers, chans)):
        for bi in range(n):
            p = f"s{si}b{bi}"
            conv_p(p + ".c1", cin if bi == 0 else cout, cm, 1)
            bn_p(p + ".c1", cm)
            conv_p(p + ".c2", cm, cm, 3)
            bn_p(p + ".c2", cm)
            conv_p(p + ".c3", cm, cout, 1)
            bn_p(p + ".c3", cout)
            if bi == 0:
                conv_p(p + ".ds", cin, cout, 1)
                bn_p(p + ".ds", cout)
        cin = cout
    s = np.sqrt(3.0 / ((2048 + 1000) / 2.0))
    params["fc.w"] = rng.uniform(-s, s, (1000, 2048)).astype("f4")
    params["fc.b"] = np.zeros(1000, "f4")

    def conv(x, w, stride=1):
        return lax.conv_general_dilated(
            x, w.astype(x.dtype), (stride, stride), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn(x, p, aux, name, new_aux):
        xm = x.astype(jnp.float32)
        mean = xm.mean((0, 2, 3))
        var = xm.var((0, 2, 3))
        new_aux[name + ".mean"] = 0.9 * aux[name + ".mean"] + 0.1 * mean
        new_aux[name + ".var"] = 0.9 * aux[name + ".var"] + 0.1 * var
        inv = jax.lax.rsqrt(var + 1e-5) * p[name + ".g"]
        out = (xm - mean[:, None, None]) * inv[:, None, None] + \
            p[name + ".b"][:, None, None]
        return out.astype(x.dtype)

    def forward(p, aux, x):
        new_aux = {}
        h = conv(x, p["stem.w"], 2)
        h = jax.nn.relu(bn(h, p, aux, "stem", new_aux))
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), "SAME")
        for si, (n, (cm, cout)) in enumerate(zip(layers, chans)):
            for bi in range(n):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                idn = h
                o = jax.nn.relu(bn(conv(h, p[pre + ".c1.w"], stride),
                                   p, aux, pre + ".c1", new_aux))
                o = jax.nn.relu(bn(conv(o, p[pre + ".c2.w"]),
                                   p, aux, pre + ".c2", new_aux))
                o = bn(conv(o, p[pre + ".c3.w"]), p, aux, pre + ".c3", new_aux)
                if bi == 0:
                    idn = bn(conv(h, p[pre + ".ds.w"], stride),
                             p, aux, pre + ".ds", new_aux)
                h = jax.nn.relu(o + idn)
        h = h.mean((2, 3)).astype(jnp.float32)
        return h @ p["fc.w"].astype(jnp.float32).T + p["fc.b"], new_aux

    # master weights and momentum stay fp32; low-precision lanes cast the
    # weights to `dtype` inside the step (exactly the framework's
    # multi-precision semantics, so the ratio compares equal work)
    low = dtype != "float32"
    w = {k: jnp.asarray(v) for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in w.items()}
    aux = {k: jnp.asarray(v) for k, v in auxs.items()}

    def loss_fn(w, img, label, aux):
        wl = {k: v.astype(dtype) for k, v in w.items()} if low else w
        logits, new_aux = forward(wl, aux, img)
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, label[:, None], -1)
        return -jnp.mean(ll), new_aux

    def train_step(w, m, aux, img, label, lr):
        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(w, img, label, aux)
        new_w, new_m = {}, {}
        for n in w:
            g = grads[n].astype(w[n].dtype)
            mom = 0.9 * m[n] - lr * g
            new_m[n] = mom
            new_w[n] = w[n] + mom
        return new_w, new_m, new_aux, loss

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    img = jnp.asarray(np.random.rand(batch, 3, image, image), dtype)
    label = jnp.asarray(np.random.randint(0, 1000, batch), jnp.int32)
    return step, w, m, aux, img, label


def _measure_control(step, w, m, aux, img, label, steps):
    """Returns (compile_s, steady img/s) for the pure-JAX control."""
    import jax
    lr = jax.numpy.float32(0.05)
    t0 = time.perf_counter()
    w, m, aux, loss = step(w, m, aux, img, label, lr)
    float(loss)
    compile_s = time.perf_counter() - t0
    w, m, aux, loss = step(w, m, aux, img, label, lr)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        w, m, aux, loss = step(w, m, aux, img, label, lr)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final), f"control loss diverged: {final}"
    return compile_s, img.shape[0] * steps / dt


def _run_real_data(batch, image, steps, dtype="float32"):
    """Module.fit fed by the REAL input pipeline (ImageRecordIter over a
    synthetic JPEG .rec corpus) — measures end-to-end img/s including
    decode/augment/transfer, the reference's `train_imagenet.py` shape.

    Returns (train_img_s, pipeline_img_s).  The measurement window is
    sized >= 3x the prefetch depth so it cannot be served out of batches
    pre-decoded during the compile of step 0 (round-3's artifact measured
    buffer drain); the standalone pipeline rate is measured on the same
    corpus/settings as the honest input-bound ceiling."""
    import shutil
    import tempfile
    d = tempfile.mkdtemp()
    try:
        return _run_real_data_in(d, batch, image, steps, dtype)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _h2d_probe(batch, image, n_bufs=12):
    """memcpy / blocking / pipelined-ring MB/s — ONE implementation
    shared with the run_io_bench CI gate (tools/bench_io.h2d_probe)."""
    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from bench_io import h2d_probe
    return h2d_probe(batch, image, n_bufs=n_bufs)


_REAL_PREFETCH = 8


def _real_data_iter(rec, batch, image):
    from incubator_mxnet_tpu import io as mxio
    return mxio.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, image, image), batch_size=batch,
        rand_crop=True, rand_mirror=True, shuffle=True,
        mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.1, std_b=57.4,
        preprocess_threads=4, prefetch_buffer=_REAL_PREFETCH, label_width=1,
        device_augment=True)


def _run_real_data_in(d, batch, image, steps, dtype):
    import incubator_mxnet_tpu as mx
    rec = os.path.join(d, "bench.rec")
    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from bench_io import build_corpus
    warm = _BLOCK
    steps = max(steps, 3 * _REAL_PREFETCH + 2)  # window can't be buffer-fed
    steps = -(-steps // _BLOCK) * _BLOCK        # block-aligned window
    n_img = batch * (warm + steps + _BLOCK)
    build_corpus(rec, n=n_img, size=image + 32)

    # standalone pipeline rate on the same corpus (the input-bound
    # ceiling); window >= 3x prefetch depth, same rule as the training
    # window — a short window would drain pre-decoded batches and
    # overestimate the ceiling
    it = _real_data_iter(rec, batch, image)
    for i, b in enumerate(it):
        if i >= 1:
            break
    t0 = time.perf_counter()
    n = 0
    for i, b in enumerate(it):
        n += batch
        if i >= 3 * _REAL_PREFETCH:
            break
    pipe_img_s = n / (time.perf_counter() - t0)

    mx.random.seed(0)
    mod, ctx = _build_module(
        mx, batch, image, dtype,
        norm=lambda d: it.normalize_symbol(d, dtype=dtype))
    probe = _Probe(warm, steps, batch)
    it.reset()
    mod.fit(it, num_epoch=1,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            eval_metric="acc",
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            batch_end_callback=probe, kvstore=None)
    assert probe.img_s is not None, "real-data probe missed its window"
    return probe.img_s, pipe_img_s


def main():
    batch = int(os.environ.get("BENCH_BATCH", 128))
    image = int(os.environ.get("BENCH_IMAGE", 224))
    steps = int(os.environ.get("BENCH_STEPS", 48))
    steps = -(-steps // _BLOCK) * _BLOCK   # block-aligned probe window
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    budget = int(os.environ.get("BENCH_BUDGET_S", 480))
    want_control = os.environ.get("BENCH_CONTROL", "1") == "1"
    want_fp32 = os.environ.get("BENCH_FP32", "1") == "1"

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # decided from the environment, in seconds, before any child
        # starts: this benchmark has no CPU mode
        sys.exit("bench.py measures a TPU and JAX_PLATFORMS=cpu holds JAX "
                 "to the host; refusing to run")

    signal.signal(signal.SIGALRM, _alarm)
    signal.signal(signal.SIGTERM, _alarm)
    signal.alarm(budget + 30)
    wd = _watchdog(budget)
    t_start = time.perf_counter()

    def left():
        return budget - (time.perf_counter() - t_start)

    _RESULT.update(batch=batch, image=image, steps=steps, dtype=dtype,
                   api="Module.fit")

    # -- cold-start lane FIRST, before this process touches jax: each
    # probe phase is its own subprocess that must initialize the TPU, and
    # a chip belongs to one process at a time — a parent already holding
    # it would fail or hang the probe
    if os.environ.get("BENCH_COLDSTART", "1") == "1":
        _RESULT["phase"] = "coldstart"
        if "jax" in sys.modules:
            raise RuntimeError(
                "cold-start lane must spawn its children before this "
                "process imports jax (one process per chip)")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from warmup import coldstart_probe
        probe = coldstart_probe(timeout=max(min(left() - 30, 600), 60))
        if "error" in probe:
            raise RuntimeError("cold-start probe failed: %s"
                               % probe["error"])
        for k in ("cold_compile_s", "warm_compile_s", "cold_compiles",
                  "warm_compiles", "warm_cold_ratio"):
            if k in probe:
                _RESULT[k] = probe[k]

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench.py measures a TPU and JAX came up on %r (%s); "
                 "refusing to run" % (dev.platform, dev.device_kind))
    # JAX's persistent compilation cache is placed by the library when it
    # is imported (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    # unified program cache (compile/): serialized executables keyed by
    # graph-hash x signature x donation x device — a repeat bench run's
    # compile_s records a WARM start (disk hits instead of compiles); the
    # artifact's program_cache block says which one this run was
    prog_cache_dir = os.environ.get(
        "MXNET_PROGRAM_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".mxnet_program_cache"))
    if prog_cache_dir:
        os.environ["MXNET_PROGRAM_CACHE_DIR"] = prog_cache_dir

    # -- framework path (headline dtype) -----------------------------------
    _RESULT["phase"] = f"framework-{dtype}"
    init_s, compile_s, img_s, phases = _run_framework(batch, image, steps,
                                                      dtype)
    _RESULT.update(value=round(img_s, 2),
                   vs_baseline=round(img_s / BASELINE_IMG_S, 3),
                   init_s=round(init_s, 2), compile_s=round(compile_s, 2))
    # per-lane cold-start phase breakdown: framework trace seconds,
    # traced-jaxpr equation count (the graph size XLA compiles — scan
    # dedup shows up here as one layer body per run), and per-program
    # lower vs XLA-compile seconds from the unified cache
    _RESULT["compile_phases"] = {"module": phases}

    # -- guardian overhead probe -------------------------------------------
    # the headline lane above ran with the training guardian ON (its
    # default): the in-graph health word + conditional update must cost
    # <2% — re-measure with the guardian OFF and gate the ratio
    if os.environ.get("BENCH_GUARDIAN", "1") == "1" and left() > 120 and \
            os.environ.get("MXNET_GUARDIAN", "1") not in ("0", "false"):
        # skipped when the user disabled the guardian: the headline lane
        # already ran guardian-off and the probe would measure nothing
        _RESULT["phase"] = f"guardian-off-{dtype}"
        prev = os.environ.get("MXNET_GUARDIAN")
        os.environ["MXNET_GUARDIAN"] = "0"
        try:
            _, _, img_off, _ = _run_framework(batch, image, steps, dtype)
        finally:
            if prev is None:
                os.environ.pop("MXNET_GUARDIAN", None)
            else:
                os.environ["MXNET_GUARDIAN"] = prev
        overhead = 1.0 - img_s / img_off if img_off else 0.0
        _RESULT["guardian_off_img_s"] = round(img_off, 2)
        _RESULT["guardian_overhead"] = round(overhead, 4)
        _RESULT["guardian_overhead_ok"] = bool(overhead <= 0.02)

    # -- pure-JAX control at the same dtype --------------------------------
    if want_control and left() > 90:
        _RESULT["phase"] = f"control-{dtype}"
        ctl = _pure_jax_resnet50(batch, image, dtype)
        c_compile, c_img_s = _measure_control(*ctl, steps)
        key = "ratio_vs_pure_jax" if dtype == "float32" else \
            "ratio_vs_pure_jax_bf16"
        _RESULT["pure_jax_img_s_" + dtype] = round(c_img_s, 2)
        _RESULT["pure_jax_compile_s"] = round(c_compile, 2)
        _RESULT[key] = round(img_s / c_img_s, 3)

    # -- gluon lane (public Estimator loop; fused Gluon step) ---------------
    if os.environ.get("BENCH_GLUON", "1") == "1" and left() > 150:
        _RESULT["phase"] = f"gluon-{dtype}"
        g_compile, g_img_s, g_phases = _run_gluon(batch, image, steps,
                                                  dtype)
        _RESULT["gluon_img_s"] = round(g_img_s, 2)
        _RESULT["gluon_compile_s"] = round(g_compile, 2)
        _RESULT["gluon_vs_module"] = round(g_img_s / img_s, 3)
        _RESULT.setdefault("compile_phases", {})["gluon"] = g_phases

    # -- fp32 lane ----------------------------------------------------------
    if want_fp32 and dtype != "float32" and left() > 150:
        _RESULT["phase"] = "framework-float32"
        _, _, img32, _ = _run_framework(batch, image, steps, "float32")
        _RESULT["fp32_img_s"] = round(img32, 2)
        if want_control:
            ctl = _pure_jax_resnet50(batch, image, "float32")
            _, c32 = _measure_control(*ctl, steps)
            _RESULT["pure_jax_img_s_float32"] = round(c32, 2)
            _RESULT["ratio_vs_pure_jax"] = round(img32 / c32, 3)

    # -- PTB LSTM lane (BASELINE config #4): tokens/s + raw-JAX control -----
    if os.environ.get("BENCH_LSTM", "1") == "1" and left() > 150:
        _RESULT["phase"] = "lstm"
        l_compile, tok_s, l_phases = _run_lstm_framework(steps)
        _RESULT["lstm_tokens_s"] = round(tok_s, 1)
        _RESULT["lstm_compile_s"] = round(l_compile, 2)
        _RESULT.setdefault("compile_phases", {})["lstm"] = l_phases
        if want_control and left() > 60:
            _, c_tok_s = _pure_jax_lstm(steps)
            _RESULT["lstm_pure_jax_tokens_s"] = round(c_tok_s, 1)
            _RESULT["lstm_ratio_vs_pure_jax"] = round(tok_s / c_tok_s, 3)

    # -- real-data lane: the full input pipeline feeds the chip -------------
    if os.environ.get("BENCH_REAL_DATA", "1") == "1" and left() > 180:
        _RESULT["phase"] = "real-data"
        # h2d three ways: memcpy ceiling, the old BLOCKING device_put
        # baseline, and the pipelined staging-ring rate (io_plane) —
        # says whether this lane is transfer-bound or pipeline-bound
        h2d_probe = _h2d_probe(batch, image)
        h2d = h2d_probe["blocking_MBps"]
        _RESULT["h2d_MBps"] = h2d
        _RESULT["h2d_pipelined_MBps"] = h2d_probe["pipelined_MBps"]
        # device-augment pipeline: batches cross as uint8 NHWC (the
        # normalize/cast finish is in-graph), a quarter of fp32 bytes
        from incubator_mxnet_tpu import io_plane as _io_plane
        io_before = _io_plane.stats()
        real, pipe = _run_real_data(batch, image, steps, dtype)
        io_after = _io_plane.stats()
        _RESULT["real_data_img_s"] = round(real, 2)
        _RESULT["io_pipeline_img_s"] = round(pipe, 2)
        base = img_s
        if base:
            _RESULT["real_data_vs_synthetic"] = round(real / base, 3)
        # the io lane: probe numbers + the training run's own ring
        # occupancy/stall evidence (io.* is the obs namespace too)
        fit_batches = io_after["batches"] - io_before["batches"]
        fit_stalls = io_after["stalls"] - io_before["stalls"]
        _RESULT["io"] = {
            **h2d_probe,
            "real_vs_synthetic": round(real / base, 3) if base
            else None,
            "ring_batches": fit_batches,
            "ring_stall_pct": round(100.0 * fit_stalls /
                                    max(fit_batches, 1), 2),
            "ring_stall_s": round(io_after["stall_s"] -
                                  io_before["stall_s"], 4),
            "zero_copy_transfers": io_after["zero_copy"] -
            io_before["zero_copy"],
        }
        if real > 1.15 * max(pipe, 1e-9) and real > 0.9 * (base or real):
            # can't train faster than the pipeline decodes unless the
            # window was fed from the prefetch buffer — flag it
            _RESULT["real_data_buffer_fed"] = True
        # device-augment lane ships uint8 (1 byte/element)
        xfer_img_s = h2d * 1e6 / (3 * image * image)
        if real < 0.8 * pipe and real < 1.5 * xfer_img_s:
            _RESULT["real_data_transfer_bound"] = True

    # program-cache traffic of THIS run: compiles vs disk hits says
    # whether the headline compile_s above was a cold or a warm start
    from incubator_mxnet_tpu import compile as _compile
    st = _compile.stats()
    _RESULT["program_cache"] = {
        **{k: st["counters"][k] for k in
           ("compiles", "disk_hits", "stores")},
        "disk_misses": st["counters"].get("disk_misses", 0),
        "lower_s": st["counters"].get("lower_s_total", 0.0),
        "compile_s": st["counters"].get("compile_s_total", 0.0),
        "hit_rate": st["hit_rate"],
    }
    _compile.write_stats()

    _RESULT["phase"] = "done"
    signal.alarm(0)
    wd.cancel()
    _emit()


if __name__ == "__main__":
    try:
        main()
    except Exception as e:
        # a failed lane ends the run: print what was measured, then fail
        _RESULT["error"] = repr(e)[:300]
        _emit()
        raise
