#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, in ONE
process (a chip belongs to one process at a time):

* ``train``: ``mx.mod.Module(...).fit(...)`` -> ``fused.FusedTrainStep`` -> one
  donated XLA program, at the full width of the model the repo has always
  benchmarked — model-zoo ResNet-50 v1, 1000 classes, 224x224, batch 128 per
  chip, bfloat16 with fp32 master weights, SGD-momentum, guardian on — for
  3 blocks of K=8 steps over a synthetic iterator (weights random, from a
  seed).
* ``serve``: the same weights behind ``mx.serving.ModelServer`` with buckets
  (1, 8) and four requests of mixed sizes (``FusedInference`` + ``MicroBatcher``).

Every check that fails raises — naming the check — and the exit code is
non-zero; nothing is caught and recorded.  Without a TPU the script exits 2
before it builds anything.  ``--cpu-dry-run`` is a flag someone chooses, never a
mode the script picks: a tiny width on the CPU whose summary line is stamped
``"dry_run": true`` (and whose device reads ``"platform": "cpu"``).

What it prints are counts and set-up seconds, not speeds: there is no img/s,
utilisation or peak table here (that is the benchmark's job).  The last line of
standard output is one JSON object with exactly these keys (what else the run
found is on the ``[smoke] summary:`` line before it)::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips N`` runs the same step data-parallel on N chips (``kvstore='tpu'``);
``--model mlp`` is a BatchNorm-free graph, which on N > 1 chips takes the pod
``shard_map`` lowering (plain BatchNorm makes ResNet-50 take the GSPMD one).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

BLOCKS = 3          # train stage: BLOCKS dispatches of K steps each
BUCKETS = (1, 8)    # serve stage: the compiled batch ladder
REQUESTS = (1, 3, 8, 5)


class SmokeFailure(Exception):
    """A named check of the smoke did not hold."""


def check(name, ok, detail=""):
    if not ok:
        raise SmokeFailure(f"check failed: {name}" +
                           (f" ({detail})" if detail else ""))
    say(f"ok: {name}")


def say(msg):
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def sizes(model, dry_run):
    """Full width on the chip; a tiny width only under --cpu-dry-run."""
    if model == "resnet50":
        if dry_run:
            return dict(classes=16, sample=(3, 32, 32), batch=4)
        return dict(classes=1000, sample=(3, 224, 224), batch=128)
    if dry_run:
        return dict(classes=16, sample=(64,), batch=8, hidden=64, layers=3)
    return dict(classes=1000, sample=(2048,), batch=128, hidden=2048,
                layers=4)


def build_symbol(mx, model, cfg):
    from incubator_mxnet_tpu import sym
    data = sym.Variable("data")
    if model == "resnet50":
        from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
        out = resnet50_v1(classes=cfg["classes"])(data)
    else:
        out = data
        for i in range(cfg["layers"]):
            out = sym.FullyConnected(out, num_hidden=cfg["hidden"],
                                     name=f"fc{i}")
            out = sym.Activation(out, act_type="relu", name=f"relu{i}")
        out = sym.FullyConnected(out, num_hidden=cfg["classes"], name="head")
    return sym.SoftmaxOutput(out, name="softmax")


def synthetic_iter(mx, cfg, batch, dtype, n_batches, ctx):
    """The reference benchmark-harness pattern: ONE device-resident batch,
    yielded n_batches times (seeded)."""
    from incubator_mxnet_tpu import io, nd
    rng = np.random.RandomState(0)
    shape = (batch,) + cfg["sample"]
    data = nd.array(rng.rand(*shape).astype("f4"), ctx=ctx).astype(dtype)
    label = nd.array(rng.randint(0, cfg["classes"], batch).astype("f4"),
                     ctx=ctx)
    descs = ([io.DataDesc("data", shape, dtype=np.dtype(dtype))],
             [io.DataDesc("softmax_label", (batch,), dtype=np.float32)])
    one = io.DataBatch(data=[data], label=[label], pad=0,
                       provide_data=descs[0], provide_label=descs[1])

    class SyntheticIter(io.DataIter):
        provide_data = property(lambda self: descs[0])
        provide_label = property(lambda self: descs[1])

        def __init__(self):
            super().__init__(batch_size=batch)
            self._i = 0

        def reset(self):
            self._i = 0

        def next(self):
            if self._i >= n_batches:
                raise StopIteration
            self._i += 1
            return one

    return SyntheticIter()


# ---------------------------------------------------------------------------
# what the checks read
# ---------------------------------------------------------------------------

def jax_leaves(obj):
    """Every jax array under NDArrays / tuples / lists / dicts."""
    data = getattr(obj, "_data", None)
    if data is not None:
        return [data]
    if isinstance(obj, dict):
        obj = list(obj.values())
    if isinstance(obj, (tuple, list)):
        return [x for o in obj for x in jax_leaves(o)]
    return []


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


def compiles(mx):
    return int(mx.compile.stats()["counters"]["compiles"])


def say_programs(mx, seen):
    """Per-program cold set-up seconds and cache traffic, for the live
    programs not yet printed (a stage's programs die with its objects)."""
    for p in mx.compile.stats()["programs"]:
        if (p["compiles"] or p["disk_hits"]) and p["label"] not in seen:
            seen.add(p["label"])
            say("program (cold set-up seconds, not a speed): " + json.dumps(
                {k: p[k] for k in ("label", "compiles", "disk_hits",
                                   "lower_s", "compile_s")}))


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

def train_stage(mx, args, cfg, devices, report, seen):
    import jax
    from incubator_mxnet_tpu import config, io_plane
    chips = len(devices)
    ctxs = [mx.tpu(i) for i in range(chips)]
    k = max(int(config.get("MXNET_FUSED_STEP_BLOCK")), 1)
    n_batches = BLOCKS * k
    batch = cfg["batch"] * chips
    dtype = "bfloat16"

    mx.random.seed(0)
    symbol = build_symbol(mx, args.model, cfg)
    it = synthetic_iter(mx, cfg, batch, dtype, n_batches, ctxs[0])
    mod = mx.mod.Module(symbol, context=ctxs if chips > 1 else ctxs[0],
                        label_names=("softmax_label",))
    init = mx.initializer.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(init)
    args0 = mod.get_params()[0]
    probe = sorted(n for n in args0 if n.endswith("weight"))
    probe = [probe[0], probe[-1]]
    before = {n: args0[n].asnumpy().astype("f4") for n in probe}

    metric = mx.metric.create(["acc", "ce"])
    marks = {}
    asked = set(devices)

    def on_batch(param):
        if param.nbatch == n_batches - k - 1:
            # the last callback before the last block is dispatched
            marks["compiles_before_last_block"] = compiles(mx)
            # judged on the live carry: the next block donates it, and
            # fit's epoch-end parameter sync re-places the public arrays
            carry = jax.tree_util.tree_leaves(mod._fused_step._carry)
            marks["carry_replicated"] = bool(carry) and all(
                a.sharding.is_fully_replicated and a.devices() == asked
                for a in carry)

    io_before = io_plane.stats()
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "multi_precision": True,
                              "rescale_grad": 1.0 / batch},
            eval_metric=metric, initializer=init,
            batch_end_callback=on_batch,
            kvstore="tpu" if chips > 1 else None)
    values = dict(metric.get_name_value())   # the device sync
    fit_s = time.perf_counter() - t0
    io_after = io_plane.stats()

    fs = mod._fused_step
    check("fused step engaged and unbroken",
          fs is not None and not fs.broken)
    lowering = "single-device" if chips == 1 else \
        ("pod shard_map" if fs.pod_stats is not None else "gspmd")
    say(f"lowering: {lowering}; steps: {n_batches} in blocks of {k}; "
        f"global batch {batch}; dtype {dtype}")
    check("metric finite", all(math.isfinite(v) for v in values.values()),
          repr(values))
    args1 = mod.get_params()[0]
    after = {n: args1[n].asnumpy().astype("f4") for n in probe}
    check("weights moved and stayed finite",
          all(np.isfinite(after[n]).all() and
              not np.array_equal(after[n], before[n]) for n in probe))

    exec0 = mod._exec_group.execs[0]
    held = jax_leaves([exec0.arg_dict, exec0.aux_dict,
                       mod._updater.states, mod.get_outputs()])
    stray = [a for a in held if not a.devices() <= asked]
    check("parameters, optimizer state and outputs live on the devices "
          "asked for", not stray and len(held) > 0,
          f"{len(stray)} of {len(held)} arrays elsewhere")
    check("program-cache fallbacks == 0",
          mx.compile.stats()["counters"]["fallbacks"] == 0)
    check("zero compiles in the last block",
          marks.get("compiles_before_last_block") == compiles(mx),
          f"{marks.get('compiles_before_last_block')} -> {compiles(mx)}")
    churn = [f.message for f in mx.analysis.recompile.findings()]
    check("recompile auditor reports no churn", not churn, "; ".join(churn))
    guardian = mod._guardian
    check("guardian on with skips == 0",
          guardian is not None and guardian.stats()["skips"] == 0,
          "off" if guardian is None else repr(guardian.stats()))

    if chips > 1:
        from jax.sharding import PartitionSpec as P
        check("batch sharded over dp",
              fs._data_sharding.spec == P(fs._dp_axis) and
              fs._dp_size == chips, repr(fs._data_sharding))
        check("parameters and optimizer state replicated on every chip",
              marks["carry_replicated"])
        if args.model == "mlp":
            check("pod shard_map lowering taken", fs.pod_stats is not None)
            say(f"pod_stats: {json.dumps(fs.pod_stats)}")

    io = {key: io_after[key] - io_before[key]
          for key in ("batches", "bytes", "staged", "zero_copy", "stalls")
          if key in io_after}
    say(f"io ring over the device-resident synthetic batch: {json.dumps(io)}")
    say(f"fit wall seconds incl. compile (set-up + {n_batches} steps): "
        f"{fit_s:.1f}")
    say_programs(mx, seen)
    report["train"] = {"lowering": lowering, "steps": n_batches,
                       "metric": {n: round(v, 4) for n, v in values.items()},
                       "io": io}
    return mod, symbol, ctxs


def serve_stage(mx, cfg, mod, symbol, ctxs, devices, report, seen):
    arg_params, aux_params = mod.get_params()
    srv = mx.serving.ModelServer(ctx=ctxs[0])
    try:
        model = srv.load_model(
            "smoke", symbol=symbol, arg_params=arg_params,
            aux_params=aux_params,
            data_shapes=[("data", (1,) + cfg["sample"])], buckets=BUCKETS)
        before = compiles(mx)
        rng = np.random.RandomState(1)
        for rows in REQUESTS:
            x = rng.rand(rows, *cfg["sample"]).astype("f4")
            outs = srv.predict("smoke", [x])
            got = outs[0].asnumpy()
            check(f"request of {rows} rows answered",
                  got.shape == (rows, cfg["classes"]) and
                  np.isfinite(got).all() and
                  all(a.devices() <= {devices[0]} for a in jax_leaves(outs)),
                  f"shape {got.shape}")
        check("program count == number of buckets",
              model.program_count() == len(BUCKETS),
              str(model.program_count()))
        check("no compile while serving", compiles(mx) == before)
        say_programs(mx, seen)
    finally:
        srv.shutdown(drain=True)
    report["serve"] = {"requests": len(REQUESTS), "buckets": list(BUCKETS)}


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--model", choices=("resnet50", "mlp"),
                    default="resnet50")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny width on the CPU platform; output is stamped "
                         "as a dry run")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
        f"{dev.device_kind}; local devices {jax.local_device_count()}")
    if dev.platform != ("cpu" if args.cpu_dry_run else "tpu"):
        print(f"chip_smoke: platform is {dev.platform!r}, need "
              f"{'cpu for --cpu-dry-run' if args.cpu_dry_run else 'tpu'}; "
              "nothing was run", file=sys.stderr)
        return 2
    if args.chips > jax.local_device_count():
        print(f"chip_smoke: --chips {args.chips} but this host has "
              f"{jax.local_device_count()} device(s)", file=sys.stderr)
        return 2
    devices = jax.local_devices()[:args.chips]

    import incubator_mxnet_tpu as mx   # places the compile cache
    cache_dir = jax.config.jax_compilation_cache_dir
    entries0 = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries0} entries before); program "
        f"cache disk tier: {mx.compile.stats()['directory']}")

    report, seen = {}, set()
    cfg = sizes(args.model, args.cpu_dry_run)
    mod, symbol, ctxs = train_stage(mx, args, cfg, devices, report, seen)
    serve_stage(mx, cfg, mod, symbol, ctxs, devices, report, seen)

    counters = mx.compile.stats()["counters"]
    say(f"program cache: compiles {counters['compiles']}, disk hits "
        f"{counters['disk_hits']}, stores {counters['stores']}, fallbacks "
        f"{counters['fallbacks']}")
    say(f"compile cache: {cache_entries(cache_dir)} entries after "
        f"({entries0} before)")
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
        say(f"{d}: peak_bytes_in_use {peaks[-1]}")
    if not args.cpu_dry_run:
        check("every chip held memory", all(p and p > 0 for p in peaks),
              repr(peaks))

    summary = {"chips": args.chips, "model": args.model, "stages": report,
               "compiles": counters["compiles"],
               "disk_hits": counters["disk_hits"], "claim": None}
    if args.cpu_dry_run:
        summary["dry_run"] = True
    say("summary: " + json.dumps(summary))
    # the contract's last line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
