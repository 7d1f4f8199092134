#!/usr/bin/env python
"""Op-level regression bench battery.

The round bench (`bench.py`) times whole models — a kernel regression in
one op class hides inside a 3% end-to-end drift until it is expensive to
bisect.  This battery times a REPRESENTATIVE op set directly, one JSON
artifact per run, cheap enough (< 2 min on CPU) to run per PR:

* **sparse**    — lazy row-sparse SGD/Adam optimizer updates (the
  embedding-gradient path) over a (4096, 128) table;
* **control flow** — an RNN-style `nd.contrib.foreach` scan (one fused
  scan program, T=32) plus its symbolic bound counterpart;
* **quantization** — an int8-quantized convnet forward next to its fp32
  reference (the serving int8 ladder's kernel mix);
* **attention** — the blockwise online-softmax causal attention the
  transformer LM trains and serves with, vs the naive full-score-matrix
  reference, fp32 and bf16 (plus the registered `BlockwiseAttention`
  packed op costed through its OpDef cost_meta);
* **dense reference points** — conv + matmul + softmax, so a regression
  report can say "sparse moved, dense did not".

Methodology: warmup runs first (compile + cache), then ``--iters`` timed
runs with `jax.block_until_ready` on every output; the artifact records
mean/p50/min per op.  Compare two artifacts across commits to catch a
kernel regression before the round bench does.

Each op also records its **static mxcost estimate** (flops, bytes
moved, the predicted roofline bound and step lower bound from
`analysis/cost.py`) next to the measured time, so estimate drift is
visible in the artifact itself: when a measured time moves and the
static column does not, the kernel regressed; when both move, the
graph changed.  The quantization section builds its models through
`cost.build_bench_convnet` — the SAME graphs the mxcost budget
baseline (COST_BUDGETS.json) gates.

Usage:
    python tools/bench_ops.py [--iters 20] [--out BENCH_OPS.json] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _static_of(prog):
    """The artifact's static column from a mxcost ProgramCost."""
    if prog is None:
        return None
    d = prog.as_dict()
    return {"flops": d["flops"], "bytes_moved": d["bytes_moved"],
            "predicted_bound": d["bound"],
            "arithmetic_intensity": d["arithmetic_intensity"],
            "step_time_lb_ms": d["step_time_lb_ms"],
            "profile": d["profile"]}


def _static_symbol(sym, shapes, dtypes=None, name=None):
    from incubator_mxnet_tpu.analysis import cost
    try:
        return _static_of(cost.analyze_symbol(sym, shapes=shapes,
                                              dtypes=dtypes, target=name))
    except Exception:
        return None


def _static_callable(fn, avals, name=None):
    from incubator_mxnet_tpu.analysis import cost
    try:
        return _static_of(cost.analyze_callable(fn, avals, name=name))
    except Exception:
        return None


def _timeit(fn, iters, warmup=3):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return {"mean_ms": round(sum(times) / len(times), 4),
            "p50_ms": round(times[len(times) // 2], 4),
            "min_ms": round(times[0], 4),
            "iters": iters}


def _sparse_ops(mx, nd, np):
    """Lazy row-sparse optimizer updates: the embedding-table gradient
    path (touched rows only; untouched rows must stay bit-identical)."""
    from incubator_mxnet_tpu.ndarray.sparse import RowSparseNDArray
    rng = np.random.RandomState(0)
    V, D, K = 4096, 128, 64
    rows = np.sort(rng.choice(V, K, replace=False)).astype(np.int64)
    gvals = rng.randn(K, D).astype("f4")

    def bench(opt_name, opt):
        w = nd.array(rng.randn(V, D).astype("f4"))
        states = [nd.zeros((V, D)) for _ in range(
            2 if opt_name == "adam" else 1)]
        state = states if opt_name == "adam" else states[0]

        def run():
            opt.update(0, w, RowSparseNDArray(gvals, rows, (V, D)), state)
            return w._data
        return run

    # the lazy row-sparse update runs through the host-resident sparse
    # path (see ndarray/sparse.py) — no traced program to walk, but the
    # rows-touched x row-bytes model (cost.analyze_embedding) gives the
    # static column exactly: cost scales with touched rows, not table size
    from incubator_mxnet_tpu.analysis import cost as _mxcost

    def _embed_static(kind):
        try:
            return _static_of(_mxcost.analyze_embedding(
                V, D, K, kind=kind, name=f"sparse.{kind}_lazy"))
        except Exception:
            return None

    # embedding-lookup lane: the serving/fit hot path — a batched device
    # gather from a hot-row cache buffer through the unified program cache
    from incubator_mxnet_tpu.embedding import HotRowCache
    cache = HotRowCache(D, capacity=max(256, K), name="bench")
    cache.insert(rows, rng.randn(K, D).astype("f4"))
    lookup_ids = rng.choice(rows, 256, replace=True).astype(np.int64)

    def run_lookup():
        out, _h, _m = cache.lookup(lookup_ids, pull_fn=None)
        return out

    return {
        "sparse.sgd_momentum_lazy": (
            bench("sgd", mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                                          lazy_update=True)),
            f"({V},{D}) table, {K} rows", _embed_static("sgd_momentum")),
        "sparse.adam_lazy": (
            bench("adam", mx.optimizer.Adam(learning_rate=0.001,
                                            lazy_update=True)),
            f"({V},{D}) table, {K} rows", _embed_static("adam")),
        "sparse.embedding_lookup": (
            run_lookup,
            f"({V},{D}) table, 256 hot ids",
            _static_of(_mxcost.analyze_embedding(
                V, D, 256, kind="lookup",
                name="sparse.embedding_lookup"))),
    }


def _control_flow_ops(mx, nd, np):
    """RNN-style scan through `_foreach`: ONE scan program per shape,
    imperative and symbolic-bound variants."""
    rng = np.random.RandomState(1)
    T, B, H = 32, 16, 64
    xnp = rng.rand(T, B, H).astype("f4")
    snp = rng.rand(B, H).astype("f4")
    wnp = rng.rand(H, H).astype("f4")

    wa = nd.array(wnp)
    xa, sa = nd.array(xnp), nd.array(snp)

    def cell(x, s):
        out = nd.tanh(nd.dot(x, wa) + s)
        return out, out

    def run_imperative():
        outs, states = nd.contrib.foreach(cell, xa, sa)
        return outs._data

    data = mx.sym.Variable("data")
    init = mx.sym.Variable("init")
    w = mx.sym.Variable("w")

    def body(x, s):
        out = mx.sym.Activation(
            mx.sym.broadcast_add(mx.sym.dot(x, w), s), act_type="tanh")
        return out, out

    outs, states = mx.sym.contrib.foreach(body, data, init)
    g = mx.sym.Group([outs, states])
    exe = g.simple_bind(ctx=mx.cpu(), grad_req="null",
                        data=(T, B, H), init=(B, H), w=(H, H))

    def run_symbolic():
        o = exe.forward(is_train=False, data=xa, init=sa, w=wa)
        return o[0]._data

    shape = f"T={T} batch={B} hidden={H}"
    from incubator_mxnet_tpu.analysis import cost as _mxcost
    try:
        # executor-level analysis costs the scan BODY x trip count
        # (the symbol walk cannot see through the _foreach node)
        static = _static_of(_mxcost.analyze_executor(
            exe, name="control_flow.foreach_rnn"))
    except Exception:
        static = None
    return {"control_flow.foreach_rnn_imperative": (run_imperative, shape,
                                                    static),
            "control_flow.foreach_rnn_symbolic": (run_symbolic, shape,
                                                  static)}


def _quantization_ops(mx, nd, np):
    """INT8 convnet forward vs its fp32 reference executor.  The graphs
    come from `analysis.cost.build_bench_convnet` — the SAME models the
    mxcost budget baseline gates, so the measured and static columns
    describe one program."""
    from incubator_mxnet_tpu.analysis.cost import (build_bench_convnet,
                                                   BENCH_SHAPE)
    from incubator_mxnet_tpu.contrib.quantization import quantize_model
    rng = np.random.RandomState(2)
    sym, _shapes = build_bench_convnet("float32")

    shape = BENCH_SHAPE
    arg_shapes, _, aux_shapes = sym.infer_shape(data=shape)
    args = {n: nd.array(rng.normal(0, 0.5, s).astype("f4"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n != "data"}
    auxs = {n: nd.zeros(s) for n, s in
            zip(sym.list_auxiliary_states(), aux_shapes)}
    x = nd.array(rng.normal(0, 1, shape).astype("f4"))

    fexe = sym.simple_bind(ctx=mx.cpu(), grad_req="null", data=shape)
    fexe.copy_params_from(args, auxs)

    qsym, qargs, qauxs = quantize_model(sym, args, auxs, calib_mode="none")
    qexe = qsym.simple_bind(ctx=mx.cpu(), grad_req="null", data=shape)
    qexe.copy_params_from(qargs, qauxs, allow_extra_params=True)

    def run_fp32():
        return fexe.forward(is_train=False, data=x)[0]._data

    def run_int8():
        return qexe.forward(is_train=False, data=x)[0]._data

    s = "x".join(str(d) for d in shape)
    qdtypes = {n: str(a.dtype) for n, a in qargs.items()}
    return {"quantization.convnet_fp32": (
                run_fp32, s,
                _static_symbol(sym, {"data": shape},
                               name="quantization.convnet_fp32")),
            "quantization.convnet_int8": (
                run_int8, s,
                _static_symbol(qsym, {"data": shape}, dtypes=qdtypes,
                               name="quantization.convnet_int8"))}


def _attention_ops(mx, nd, np):
    """Causal self-attention: the blockwise online-softmax kernel the
    transformer LM trains and serves with, next to the naive
    full-score-matrix reference, in fp32 and the bf16 serving dtype.
    The two compute identical math (tests/test_ring_attention.py), so
    the measured gap is pure kernel shape — and the static column is
    the SAME flops either way, which is the point: mxcost estimates
    the op, not the tiling."""
    import functools
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.attention import naive_attention
    from incubator_mxnet_tpu.parallel.ring_attention import \
        blockwise_attention
    rng = np.random.RandomState(4)
    B, T, H, D = 2, 128, 4, 32
    C = H * D

    def lanes(dtype, tag):
        q4, k4, v4 = (jnp.asarray(rng.randn(B, T, H, D), dtype=dtype)
                      for _ in range(3))
        pack = lambda a: a.reshape(B, T, C)  # noqa: E731
        blockwise = jax.jit(functools.partial(
            blockwise_attention, block_size=64, causal=True))
        naive = jax.jit(functools.partial(
            naive_attention, num_heads=H, causal=True))
        shape = f"{B}x{T}x{H}x{D} {tag}"
        aval4 = [jax.ShapeDtypeStruct((B, T, H, D), dtype)] * 3
        aval3 = [jax.ShapeDtypeStruct((B, T, C), dtype)] * 3
        return {
            f"attention.blockwise_{tag}": (
                lambda: blockwise(q4, k4, v4), shape,
                _static_callable(blockwise, aval4,
                                 name=f"attention.blockwise_{tag}")),
            f"attention.naive_{tag}": (
                lambda: naive(pack(q4), pack(k4), pack(v4)), shape,
                _static_callable(naive, aval3,
                                 name=f"attention.naive_{tag}")),
        }

    ops = {}
    ops.update(lanes(jnp.float32, "fp32"))
    ops.update(lanes(jnp.bfloat16, "bf16"))
    # the registered packed-face op, costed through its OpDef cost_meta
    # (the estimate the scheduler sees) rather than a traced callable
    qp = nd.array(rng.randn(B, T, C).astype("f4"))
    data = mx.sym.Variable("data")
    asym = mx.sym.BlockwiseAttention(data, data, data, num_heads=H,
                                     causal=True)
    ops["attention.op_blockwise_fp32"] = (
        lambda: nd.BlockwiseAttention(qp, qp, qp, num_heads=H,
                                      causal=True)._data,
        f"{B}x{T}x{C} packed",
        _static_symbol(asym, {"data": (B, T, C)},
                       name="attention.op_blockwise_fp32"))
    return ops


def _dense_ops(mx, nd, np):
    """Dense reference points: a regression report should be able to say
    'sparse moved, dense did not'."""
    rng = np.random.RandomState(3)
    a = nd.array(rng.randn(256, 256).astype("f4"))
    b = nd.array(rng.randn(256, 256).astype("f4"))
    x = nd.array(rng.randn(8, 16, 32, 32).astype("f4"))
    wconv = nd.array(rng.randn(16, 16, 3, 3).astype("f4"))
    logits = nd.array(rng.randn(64, 1000).astype("f4"))

    import jax
    import jax.numpy as jnp

    def _conv_ref(xv, wv):
        return jax.lax.conv_general_dilated(
            xv, wv, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    f4 = np.float32
    return {
        "dense.matmul_256": (
            lambda: nd.dot(a, b)._data, "256x256",
            _static_callable(jnp.dot,
                             [jax.ShapeDtypeStruct((256, 256), f4)] * 2,
                             name="dense.matmul_256")),
        "dense.conv3x3": (
            lambda: nd.Convolution(x, wconv, no_bias=True, kernel=(3, 3),
                                   num_filter=16, pad=(1, 1))._data,
            "8x16x32x32",
            _static_callable(
                _conv_ref,
                [jax.ShapeDtypeStruct((8, 16, 32, 32), f4),
                 jax.ShapeDtypeStruct((16, 16, 3, 3), f4)],
                name="dense.conv3x3")),
        "dense.softmax": (
            lambda: nd.softmax(logits)._data, "64x1000",
            _static_callable(jax.nn.softmax,
                             [jax.ShapeDtypeStruct((64, 1000), f4)],
                             name="dense.softmax")),
    }


def run_battery(iters=20):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd

    ops = {}
    for builder in (_sparse_ops, _control_flow_ops, _quantization_ops,
                    _attention_ops, _dense_ops):
        ops.update(builder(mx, nd, np))

    results = {}
    for name in sorted(ops):
        fn, shape, static = ops[name]
        results[name] = dict(_timeit(fn, iters), shape=shape,
                             static=static)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bench_ops", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_OPS.json"),
                    help="artifact path ('' skips writing)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    t0 = time.time()
    results = run_battery(iters=args.iters)

    import subprocess
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO, capture_output=True, text=True,
                             timeout=10).stdout.strip() or None
    except Exception:
        rev = None
    import jax
    artifact = {
        "ops": results,
        "iters": args.iters,
        "duration_s": round(time.time() - t0, 1),
        "git_rev": rev,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
    if args.as_json:
        print(json.dumps(artifact, indent=1))
    else:
        width = max(len(n) for n in results)
        for name in sorted(results):
            r = results[name]
            st = r.get("static")
            tail = "" if not st else \
                "   static %.1f MFLOP %s-bound" % (
                    st["flops"] / 1e6, st["predicted_bound"])
            print(f"{name:<{width}}  mean {r['mean_ms']:8.3f} ms   "
                  f"p50 {r['p50_ms']:8.3f} ms   ({r['shape']}){tail}")
        print(f"bench_ops: {len(results)} op(s) in "
              f"{artifact['duration_s']:g}s"
              + (f" -> {args.out}" if args.out else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
