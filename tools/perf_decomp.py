"""Decompose the fused-path vs pure-JAX-control performance gap on chip.

Phases (select via argv, default all):
  control   — bench.py's hand-written raw-JAX ResNet-50 train step
  module    — public Module.fit fused path (what BENCH measures), then the
              SAME compiled program raw-called in a tight donated loop to
              split host-wrapper overhead from device-program time
  graphsgd  — framework symbol graph (graph_eval_fn) fwd+vjp with a
              hand-written SGD-momentum update: isolates graph quality from
              the traced-optimizer/metric/key epilogue

Prints one JSON line per phase.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BATCH = int(os.environ.get("PD_BATCH", 128))
IMAGE = int(os.environ.get("PD_IMAGE", 224))
STEPS = int(os.environ.get("PD_STEPS", 20))
DTYPE = os.environ.get("PD_DTYPE", "bfloat16")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def emit(phase, **kw):
    print(json.dumps({"phase": phase, **{k: (round(v, 2) if isinstance(v, float) else v) for k, v in kw.items()}}), flush=True)


def phase_control():
    import bench
    ctl = bench._pure_jax_resnet50(BATCH, IMAGE, DTYPE)
    c_compile, img_s = bench._measure_control(*ctl, STEPS)
    emit("control", compile_s=c_compile, img_s=img_s,
         ms_per_step=1000.0 * BATCH / img_s)


def phase_module():
    import bench
    import incubator_mxnet_tpu as mx

    mx.random.seed(0)
    mod, ctx = bench._build_module(mx, BATCH, IMAGE, DTYPE)
    warm = 2
    it = bench._synthetic_iter(mx, BATCH, IMAGE, DTYPE, warm + STEPS + 1, ctx)
    probe = bench._Probe(warm, STEPS, BATCH)
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "multi_precision": DTYPE != "float32",
                              "rescale_grad": 1.0 / BATCH},
            eval_metric="acc",
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            batch_end_callback=probe, kvstore=None)
    fused = mod._fused_step
    assert fused is not None and not fused.broken
    emit("module_fit", compile_s=probe.compile_s, img_s=probe.img_s,
         ms_per_step=1000.0 * BATCH / probe.img_s)

    # raw-call the SAME compiled program in a tight donated loop
    carry = fused._carry
    ws, ss, auxs = list(carry[0]), carry[1], list(carry[2])
    mcarry = [tuple(m._device_totals) for _, m in
              fused._metric_leaves(None) or []]
    # rebuild mcarry the way the wrapper does (metric was 'acc')
    import jax.numpy as jnp
    mcarry = [(jax.device_put(jnp.zeros((), jnp.float32), fused._rep_sharding),
               jax.device_put(jnp.zeros((), jnp.int32), fused._rep_sharding))]
    key = fused._key
    t_vec = fused._t_vec
    data = nd_batch_inputs(fused, it, mx)
    fixed = [fused._exec0.arg_dict[n]._data for n in fused._fixed_names]
    lr_dev, wd_dev, rescale_dev = fused._hyper_dev
    jit = fused._jit

    if fused._derive_ws:
        out = jit(tuple(ss), auxs, mcarry, key, t_vec, data, fixed,
                  lr_dev, wd_dev, rescale_dev)
        float(out[3][0][0])   # value fetch = the only reliable barrier
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = jit(out[1], list(out[2]), list(out[3]), out[4], out[5],
                      data, fixed, lr_dev, wd_dev, rescale_dev)
        float(out[3][0][0])
    else:
        out = jit(ws, tuple(ss), auxs, mcarry, key, t_vec, data, fixed,
                  lr_dev, wd_dev, rescale_dev)
        float(out[3][0][0])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = jit(list(out[0]), out[1], list(out[2]), list(out[3]),
                      out[4], out[5], data, fixed, lr_dev, wd_dev,
                      rescale_dev)
        float(out[3][0][0])
    dt = time.perf_counter() - t0
    emit("module_rawcall", img_s=BATCH * STEPS / dt,
         ms_per_step=1000.0 * dt / STEPS)


def nd_batch_inputs(fused, it, mx):
    it.reset()
    b = it.next()
    data = list(b.data) + list(b.label or [])
    out = []
    for v, name in zip(data, fused._input_names):
        raw = v._data
        out.append(jax.device_put(raw, fused._data_sharding))
    return out


def phase_graphsgd():
    import bench
    import incubator_mxnet_tpu as mx
    import jax.numpy as jnp
    from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn
    from incubator_mxnet_tpu import sym
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    mx.random.seed(0)
    net = resnet50_v1(classes=1000)
    data_v = sym.Variable("data")
    out = net(data_v)
    out = sym.SoftmaxOutput(out, name="softmax")
    ctx = mx.tpu()
    mod = mx.mod.Module(out, context=ctx, label_names=("softmax_label",))
    from incubator_mxnet_tpu import io
    data_desc = io.DataDesc("data", (BATCH, 3, IMAGE, IMAGE),
                            dtype=np.dtype(DTYPE))
    label_desc = io.DataDesc("softmax_label", (BATCH,), dtype=np.float32)
    mod.bind(data_shapes=[data_desc], label_shapes=[label_desc])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))

    symbol = mod._symbol
    gfn, arg_nodes, aux_nodes, n_rng = graph_eval_fn(symbol, True)
    arg_names = symbol.list_arguments()
    exec0 = mod._exec_group.execs[0]
    param_names = [n for n in mod._exec_group.param_names]
    input_names = mod._exec_group.data_names + mod._exec_group.label_names

    low = DTYPE != "float32"
    # master weights fp32 when low precision; cast inside like control
    w = {}
    for n in param_names:
        a = exec0.arg_dict[n]._data
        w[n] = a.astype(jnp.float32) if low else a
    auxs = [exec0.aux_dict[n]._data for n in symbol.list_auxiliary_states()]
    m = {k: jnp.zeros_like(v) for k, v in w.items()}

    def step(w, m, auxs, data, label, lr):
        def forward(pw):
            args = []
            for n in arg_names:
                if n in pw:
                    args.append(pw[n].astype(DTYPE) if low else pw[n])
                elif n == "data":
                    args.append(data)
                else:
                    args.append(label)
            outs, new_aux = gfn(tuple(args), tuple(auxs), jax.random.PRNGKey(0))
            return tuple(outs), tuple(new_aux)

        outs, vjp, new_aux = jax.vjp(forward, w, has_aux=True)
        cts = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        (grads,) = vjp(cts)
        new_w, new_m = {}, {}
        for n in w:
            g = grads[n].astype(w[n].dtype) / BATCH
            mom = 0.9 * m[n] - lr * g
            new_m[n] = mom
            new_w[n] = w[n] + mom
        return new_w, new_m, new_aux

    jit = jax.jit(step, donate_argnums=(0, 1, 2))
    data = jax.device_put(
        np.random.rand(BATCH, 3, IMAGE, IMAGE).astype(np.float32),
        ctx.jax_device).astype(DTYPE)
    label = jax.device_put(
        np.random.randint(0, 1000, BATCH).astype(np.float32), ctx.jax_device)
    lr = jnp.float32(0.05)

    t0 = time.perf_counter()
    w, m, auxs = jit(w, m, auxs, data, label, lr)
    jax.block_until_ready(w)
    compile_s = time.perf_counter() - t0
    w, m, auxs = jit(w, m, auxs, data, label, lr)
    jax.block_until_ready(w)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        w, m, auxs = jit(w, m, auxs, data, label, lr)
    jax.block_until_ready(w)
    dt = time.perf_counter() - t0
    chk = float(jax.numpy.sum(jax.numpy.abs(
        w[param_names[0]].astype(jax.numpy.float32))))
    assert np.isfinite(chk), f"non-finite weights after {STEPS} steps"
    emit("graph_sgd", compile_s=compile_s, img_s=BATCH * STEPS / dt,
         ms_per_step=1000.0 * dt / STEPS, chk=chk)


def phase_nhwc():
    """bench.py's control rewritten to execute in NHWC (channels-minor):
    input transposed NCHW->NHWC inside the step (API boundary cost paid),
    weights held HWIO, BN/pool over the trailing channel axis.  Measures
    the layout lever against phase_control on the same chip."""
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.RandomState(0)
    params, auxs = {}, {}

    def conv_p(name, cin, cout, k):
        fan = (cin * k * k + cout * k * k) / 2.0
        s = np.sqrt(3.0 / fan)
        params[name + ".w"] = rng.uniform(
            -s, s, (k, k, cin, cout)).astype("f4")  # HWIO

    def bn_p(name, c):
        params[name + ".g"] = np.ones(c, "f4")
        params[name + ".b"] = np.zeros(c, "f4")
        auxs[name + ".mean"] = np.zeros(c, "f4")
        auxs[name + ".var"] = np.ones(c, "f4")

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
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(x, p, aux, name, new_aux):
        xm = x.astype(jnp.float32)
        mean = xm.mean((0, 1, 2))
        var = xm.var((0, 1, 2))
        new_aux[name + ".mean"] = 0.9 * aux[name + ".mean"] + 0.1 * mean
        new_aux[name + ".var"] = 0.9 * aux[name + ".var"] + 0.1 * var
        inv = lax.rsqrt(var + 1e-5) * p[name + ".g"]
        out = (xm - mean) * inv + p[name + ".b"]
        return out.astype(x.dtype)

    def forward(p, aux, x):
        new_aux = {}
        x = jnp.transpose(x, (0, 2, 3, 1))  # NCHW API -> NHWC internal
        h = conv(x, p["stem.w"], 2)
        h = jax.nn.relu(bn(h, p, aux, "stem", new_aux))
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for si, (n, (cm, cout)) in enumerate(zip(layers, chans)):
            for bi in range(n):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                idn = h
                o = jax.nn.relu(bn(conv(h, p[pre + ".c1.w"], stride),
                                   p, aux, pre + ".c1", new_aux))
                o = jax.nn.relu(bn(conv(o, p[pre + ".c2.w"]),
                                   p, aux, pre + ".c2", new_aux))
                o = bn(conv(o, p[pre + ".c3.w"]), p, aux, pre + ".c3",
                       new_aux)
                if bi == 0:
                    idn = bn(conv(h, p[pre + ".ds.w"], stride),
                             p, aux, pre + ".ds", new_aux)
                h = jax.nn.relu(o + idn)
        h = h.mean((1, 2)).astype(jnp.float32)
        return h @ p["fc.w"].astype(jnp.float32).T + p["fc.b"], new_aux

    low = DTYPE != "float32"
    import jax.numpy as jnp2
    w = {k: jnp2.asarray(v) for k, v in params.items()}
    m = {k: jnp2.zeros_like(v) for k, v in w.items()}
    aux = {k: jnp2.asarray(v) for k, v in auxs.items()}

    def loss_fn(w, img, label, aux):
        wl = {k: v.astype(DTYPE) for k, v in w.items()} if low else w
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
    img = jnp.asarray(np.random.rand(BATCH, 3, IMAGE, IMAGE), DTYPE)
    label = jnp.asarray(np.random.randint(0, 1000, BATCH), jnp.int32)

    import bench
    c_compile, img_s = bench._measure_control(step, w, m, aux, img, label,
                                              STEPS)
    emit("control_nhwc", compile_s=c_compile, img_s=img_s,
         ms_per_step=1000.0 * BATCH / img_s)


if __name__ == "__main__":
    phases = sys.argv[1:] or ["control", "module", "graphsgd"]
    for p in phases:
        globals()["phase_" + p]()
