#!/usr/bin/env python
"""AOT warmup CLI for the unified program cache (compile/ subsystem).

Compile a model's program set ahead of traffic and persist the XLA
executables into the on-disk program cache, so the NEXT process — a
serving replica, a resumed training job, a c_predict embedder — loads
compiled programs instead of paying the cold-start compile.

Usage:

  # warm one model's bucket ladder into a cache dir
  python tools/warmup.py --cache-dir /var/cache/mxnet-programs \\
      --symbol model-symbol.json --params model-0000.params \\
      --data-shape data:1,3,224,224 --buckets 1,2,4,8,16,32

  # drive a whole manifest (several models + program payload dirs)
  python tools/warmup.py --cache-dir DIR --manifest warmup.json

  # write the manifest for later instead of (only) warming now
  python tools/warmup.py ... --emit-manifest warmup.json

  # built-in cold-start probe (run twice: cold then warm)
  python tools/warmup.py --cache-dir DIR --selftest --json

Parameters are optional: the compiled program depends on shapes only,
so zeros at the inferred parameter shapes produce the identical
executable production weights will load.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fused_vs_jax_compile():
    """Cold-compile the FULL fused train step of a tiny convnet through
    the public Module path, and a hand-written pure-JAX train step of
    the same math (conv3x3/8 + relu + fc10 + softmax-CE + momentum SGD
    + accuracy), both phase-timed.  The ratio is the coldstart budget
    gate: the framework's one-program step must compile within 1.5x of
    what the same model costs in raw JAX."""
    import time as _time

    import numpy as np
    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import io, sym

    data = sym.Variable("data")
    x = sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                        name="conv0")
    x = sym.Activation(x, act_type="relu", name="relu0")
    x = sym.Flatten(x, name="flatten0")
    x = sym.FullyConnected(x, num_hidden=10, name="fc0")
    net = sym.SoftmaxOutput(x, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.randn(32, 3, 8, 8).astype("f4")
    y = rng.randint(0, 10, 32).astype("f4")
    it = io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    ctx = mx.tpu()
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    metric = mx.metric.create("acc")
    for b in list(it)[:2]:
        mod.fit_step(b, metric)
    fused = mod._fused_step
    if fused is None or fused.broken:
        raise RuntimeError("fused train step did not engage")
    ph = fused.compile_phase_stats()
    fused_s = (ph["trace_s"] or 0.0) + sum(
        p["lower_s"] + p["compile_s"] for p in ph["programs"])

    # the pure-JAX control: same forward/loss/backward/update/metric
    def loss_fn(w, img, lab):
        z = jax.lax.conv_general_dilated(
            img, w["cw"], (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        z = z + w["cb"][None, :, None, None]
        z = jnp.maximum(z, 0.0).reshape(img.shape[0], -1)
        z = z @ w["fw"].T + w["fb"]
        z = z - jax.scipy.special.logsumexp(z, axis=1, keepdims=True)
        hot = jax.nn.one_hot(lab.astype("int32"), 10)
        return -jnp.mean(jnp.sum(hot * z, axis=1)), z

    def train_step(w, m, img, lab, lr):
        (loss, z), g = jax.value_and_grad(loss_fn, has_aux=True)(
            w, img, lab)
        new_m = jax.tree_util.tree_map(lambda mi, gi: 0.9 * mi + gi, m, g)
        new_w = jax.tree_util.tree_map(lambda wi, mi: wi - lr * mi,
                                       w, new_m)
        acc = jnp.mean((jnp.argmax(z, 1) ==
                        lab.astype("int32")).astype("f4"))
        return new_w, new_m, loss, acc

    w = {"cw": jnp.zeros((8, 3, 3, 3), "f4"),
         "cb": jnp.zeros((8,), "f4"),
         "fw": jnp.zeros((10, 8 * 8 * 8), "f4"),
         "fb": jnp.zeros((10,), "f4")}
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    img = jnp.zeros((16, 3, 8, 8), "f4")
    lab = jnp.zeros((16,), "f4")
    jfn = jax.jit(train_step)
    t0 = _time.perf_counter()
    lowered = jfn.lower(w, m, img, lab, 0.1)
    t1 = _time.perf_counter()
    lowered.compile()
    t2 = _time.perf_counter()
    jax_s = t2 - t0
    return {
        "compile_s": round(fused_s, 4),
        "trace_s": round(ph["trace_s"] or 0.0, 4),
        "jaxpr_eqns": ph["jaxpr_eqns"],
        "jax_control_compile_s": round(jax_s, 4),
        "jax_control_lower_s": round(t1 - t0, 4),
        "compile_ratio_vs_jax": round(fused_s / jax_s, 3) if jax_s else
        None,
    }


def measure_coldstart_budgets():
    """Measured cold-start numbers for the budget gate, per bench
    program (`analysis.cost.bench_programs`):

    * ``compile_s`` — jit ``lower``+``compile`` wall seconds of the
      program's inference graph;
    * ``peak_hbm_mb`` — the compiled executable's own XLA memory
      analysis (temp + argument + output buffers) on an accelerator
      backend; on CPU hosts, where the runtime does not report device
      memory, the mxcost liveness prediction stands in
      (``peak_hbm_source`` records which);
    * ``predicted_peak_hbm_mb`` — the mxcost static liveness peak, so
      the committed baseline pins measurement to prediction: a TPU run
      whose measured peak drifts past the 15% tolerance around the
      committed (predicted) entry fails the gate;

    plus ``fused.convnet_step`` — the full fused train step against a
    hand-written pure-JAX control of the same model
    (``compile_ratio_vs_jax``, gated at <=1.5x).

    Returns {program: {metric: value}} ready for
    `analysis.budgets.check_measured` / `snapshot_measured`.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.analysis import cost as _cost
    from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn

    backend = jax.default_backend()
    out = {}
    for name, (sym, shapes, dtypes) in \
            sorted(_cost.bench_programs().items()):
        prog = _cost.analyze_symbol(sym, shapes=shapes, dtypes=dtypes,
                                    target=name)
        predicted_mb = (prog.peak_hbm_bytes or 0) / float(1 << 20)
        arg_shapes, _, aux_shapes = sym.infer_shape(**shapes)
        shape_by = dict(zip(sym.list_arguments(), arg_shapes))
        aux_by = dict(zip(sym.list_auxiliary_states(), aux_shapes))
        dt = dtypes or {}
        fn, arg_nodes, aux_nodes, _n_rng = graph_eval_fn(sym, False)
        args = [jnp.zeros(shape_by[n.name], dt.get(n.name, "float32"))
                for n in arg_nodes]
        auxs = [jnp.zeros(aux_by[n.name], dt.get(n.name, "float32"))
                for n in aux_nodes]
        key = jax.random.PRNGKey(0)
        jfn = jax.jit(fn)
        t0 = _time.perf_counter()
        lowered = jfn.lower(args, auxs, key)
        t1 = _time.perf_counter()
        compiled = lowered.compile()
        t2 = _time.perf_counter()
        measured_mb = None
        if backend != "cpu":
            try:
                ma = compiled.memory_analysis()
                measured_mb = (ma.temp_size_in_bytes +
                               ma.argument_size_in_bytes +
                               ma.output_size_in_bytes) / float(1 << 20)
            except Exception:
                measured_mb = None
        out[name] = {
            "compile_s": round(t2 - t0, 4),
            "lower_s": round(t1 - t0, 4),
            "peak_hbm_mb": round(measured_mb if measured_mb is not None
                                 else predicted_mb, 4),
            "peak_hbm_source": "measured" if measured_mb is not None
            else "estimated",
            "predicted_peak_hbm_mb": round(predicted_mb, 4),
        }
    try:
        out["fused.convnet_step"] = _fused_vs_jax_compile()
    except Exception as exc:
        out["fused.convnet_step"] = {"error": repr(exc)[:200]}
    return out


# the measured programs the coldstart budget gate REQUIRES baselined
# entries for (--measure-budgets fails when one is missing from
# COST_BUDGETS.json's "measured" section)
REQUIRED_MEASURED = ("quantization.convnet_fp32",
                     "quantization.convnet_bf16",
                     "quantization.convnet_int8",
                     "fused.convnet_step")


def measured_budget_gate(budgets_path, write=False):
    """Measure, then gate against (or re-baseline into) the budget
    file's 'measured' section.  Returns a JSON-able summary with
    ``rc`` 0/1: regression or a missing required entry fails."""
    from incubator_mxnet_tpu.analysis import budgets as _budgets

    measured = measure_coldstart_budgets()
    summary = {"measured": measured}
    gated = {k: v for k, v in measured.items() if "error" not in v}
    budgets = _budgets.load(budgets_path)
    if write:
        _budgets.snapshot_measured(gated, budgets)
        _budgets.save(budgets_path, budgets)
        summary["wrote"] = budgets_path
        summary["rc"] = 0
        return summary
    report, deltas = _budgets.check_measured(gated, budgets)
    from incubator_mxnet_tpu.analysis.findings import ERROR
    findings = [f.as_dict() for f in report]
    missing = [name for name in REQUIRED_MEASURED
               if name not in (budgets.get("measured") or {})]
    errors = [f for f in report if f.severity == ERROR]
    summary.update(deltas=deltas, findings=findings, missing=missing,
                   rc=1 if errors or missing else 0)
    return summary


def _parse_shape(spec):
    name, _, dims = spec.partition(":")
    if not dims:
        raise SystemExit(f"--data-shape {spec!r}: expected name:d0,d1,...")
    return [name, [int(d) for d in dims.split(",")]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache-dir",
                    help="program cache directory (the disk tier; also "
                         "settable via MXNET_PROGRAM_CACHE_DIR); required "
                         "for every mode except --measure-budgets")
    ap.add_argument("--manifest", help="warmup manifest JSON to drive")
    ap.add_argument("--symbol", help="model symbol JSON file")
    ap.add_argument("--params", help="model .params file (optional: "
                                     "zeros at inferred shapes otherwise)")
    ap.add_argument("--data-shape", action="append", default=[],
                    metavar="name:d0,d1,...",
                    help="request input shape (repeatable); d0 is the "
                         "batch axis the buckets replace")
    ap.add_argument("--buckets", default="1,2,4,8,16,32",
                    help="batch-size ladder to compile")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--name", default="model")
    ap.add_argument("--emit-manifest", metavar="PATH",
                    help="also write the equivalent manifest JSON")
    ap.add_argument("--selftest", action="store_true",
                    help="warm the built-in probe model (cold/warm "
                         "compile-time measurement)")
    ap.add_argument("--measure-budgets", action="store_true",
                    help="measure per-program coldstart compile_s / "
                         "peak_hbm_mb and gate them against the "
                         "'measured' section of --budgets")
    ap.add_argument("--budgets", metavar="PATH",
                    help="COST_BUDGETS.json to gate --measure-budgets "
                         "against")
    ap.add_argument("--write-budgets", action="store_true",
                    help="re-baseline the measured section instead of "
                         "gating (commit the diff)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the summary as one JSON line")
    args = ap.parse_args(argv)

    if args.measure_budgets:
        if args.budgets:
            summary = measured_budget_gate(args.budgets,
                                           write=args.write_budgets)
        else:
            summary = {"measured": measure_coldstart_budgets(), "rc": 0}
        if args.as_json:
            print(json.dumps(summary))
        else:
            for name, m in sorted(summary["measured"].items()):
                print("  %s: %s" % (name, json.dumps(m)))
            for f in summary.get("findings", ()):
                print("  %(severity)s %(code)s %(message)s" % f)
        return summary.get("rc", 0)

    if not args.cache_dir:
        ap.error("--cache-dir is required (except with --measure-budgets)")

    from incubator_mxnet_tpu import compile as mxc

    if args.selftest:
        summary = mxc.warmup.selftest(args.cache_dir)
    elif args.manifest:
        summary = mxc.warm(args.manifest, cache_dir=args.cache_dir)
    else:
        if not (args.symbol and args.data_shape):
            ap.error("need --manifest, --selftest, or --symbol with "
                     "--data-shape")
        manifest = {
            "version": mxc.warmup.MANIFEST_VERSION,
            "models": [{
                "name": args.name,
                "symbol": os.path.abspath(args.symbol),
                "params": os.path.abspath(args.params) if args.params
                else None,
                "data_shapes": [_parse_shape(s) for s in args.data_shape],
                "buckets": [int(b) for b in args.buckets.split(",")],
                "dtype": args.dtype,
            }],
        }
        if args.emit_manifest:
            mxc.write_manifest(args.emit_manifest, manifest["models"])
        summary = mxc.warm(manifest, cache_dir=args.cache_dir)

    if args.as_json:
        print(json.dumps(summary))
    else:
        print("warmed: %d compiles, %d disk hits, %.2fs"
              % (summary.get("compiles", 0), summary.get("disk_hits", 0),
                 summary.get("compile_s", 0.0)))
        for m in summary.get("models", []):
            print("  %(name)s buckets=%(buckets)s compiles=%(compiles)d "
                  "disk_hits=%(disk_hits)d %(compile_s).2fs" % m)
    return 0


if __name__ == "__main__":
    sys.exit(main())
