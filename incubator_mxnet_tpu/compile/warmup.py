"""AOT warmup: compile a declared program set ahead of traffic.

A warmup *manifest* is a JSON document naming the programs a process
will need — served models with their shape-bucket ladders, plus any
pre-exported entry payload directories:

    {
      "version": 1,
      "models": [
        {"name": "resnet", "symbol": "resnet-symbol.json",
         "params": "resnet-0000.params",        # optional: shapes suffice
         "data_shapes": [["data", [1, 3, 224, 224]]],
         "buckets": [1, 2, 4, 8, 16, 32], "dtype": "float32"}
      ],
      "programs": ["programs"]                  # entry dirs (relative ok)
    }

``warm(manifest)`` drives `jax.jit(...).lower().compile()` for every
bucket of every model through the unified program cache: with a disk
tier configured the compiles are persisted, so the NEXT process —
`ServedModel` warmup, `c_predict`, `Module.fit(resume=)` — loads
executables instead of compiling.  Parameters are optional because the
compiled program depends only on shapes/dtypes: zeros of the inferred
parameter shapes produce the identical executable the production
weights will hit.

`warm` is what `tools/warmup.py` wraps; `selftest` is the tiny built-in
model `tools/warmup.py --selftest` and tests/test_program_cache.py build
cold, then warm.
"""
from __future__ import annotations

import json
import logging
import os
import time

import numpy as _np

__all__ = ["warm", "write_manifest", "selftest", "export_all"]

_log = logging.getLogger(__name__)

MANIFEST_VERSION = 1


def write_manifest(path, models, programs=()):
    """Write a warmup manifest; `models` entries follow the schema in
    the module docstring (shapes as lists, paths relative to the
    manifest's directory where possible)."""
    doc = {"version": MANIFEST_VERSION, "models": list(models),
           "programs": list(programs)}
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return path


def _resolve(base, p):
    if p is None:
        return None
    return p if os.path.isabs(p) else os.path.join(base, p)


def _zero_params(symbol, input_shapes, dtype):
    """Zeros for every non-input argument/aux at the shapes inference
    implies — a warmup needs the program, not the weights (same shapes
    => same executable)."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    args, auxs = {}, {}
    for n, s in zip(symbol.list_arguments(), arg_shapes or []):
        if n not in input_shapes and s is not None:
            args[n] = _np.zeros(s, _np.dtype(dtype))
    for n, s in zip(symbol.list_auxiliary_states(), aux_shapes or []):
        if s is not None:
            auxs[n] = _np.zeros(s, _np.float32)
    return args, auxs


def warm(manifest, cache_dir=None):
    """Run the AOT warmup a manifest describes.  `manifest` is a path
    or an already-parsed dict.  Returns a summary dict (per-model
    compile/disk-hit counts and wall time) suitable for JSON output."""
    from . import get_cache, set_cache_dir
    from .. import symbol as _sym
    from ..serving.model import ServedModel

    base = "."
    if not isinstance(manifest, dict):
        base = os.path.dirname(os.path.abspath(manifest))
        with open(manifest) as f:
            manifest = json.load(f)
    if cache_dir:
        set_cache_dir(cache_dir)
    cache = get_cache()
    for pdir in manifest.get("programs", ()):
        cache.add_source(_resolve(base, pdir))

    summary = {"models": [], "compiles": 0, "disk_hits": 0}
    t0 = time.perf_counter()
    for spec in manifest.get("models", ()):
        name = spec.get("name", "model")
        sym = _sym.load(_resolve(base, spec["symbol"]))
        data_shapes = [(n, tuple(s)) for n, s in spec["data_shapes"]]
        dtype = spec.get("dtype", "float32")
        params_file = _resolve(base, spec.get("params"))
        if params_file:
            from .. import nd as _nd
            args, auxs = {}, {}
            for k, v in _nd.load(params_file).items():
                tp, _, pname = k.partition(":")
                (args if tp == "arg" else auxs)[pname or k] = v
        else:
            args, auxs = _zero_params(
                sym, {n: s for n, s in data_shapes}, dtype)
        model = ServedModel(
            sym, args, auxs, data_shapes=data_shapes,
            buckets=tuple(spec.get("buckets", (1,))), name=name,
            dtype=dtype)
        before = dict(cache.counters)
        t_model = time.perf_counter()
        model.warmup()
        summary["models"].append({
            "name": name,
            "buckets": list(model.buckets),
            "compile_s": round(time.perf_counter() - t_model, 3),
            "compiles": cache.counters["compiles"] - before["compiles"],
            "disk_hits": cache.counters["disk_hits"] -
            before["disk_hits"],
        })
    summary["compiles"] = sum(m["compiles"] for m in summary["models"])
    summary["disk_hits"] = sum(m["disk_hits"] for m in summary["models"])
    summary["compile_s"] = round(time.perf_counter() - t0, 3)
    cache.write_stats()
    return summary


def export_all(directory):
    """Serialize every live cached program into `directory` as entry
    files (the checkpoint ``programs/`` payload writer)."""
    from . import get_cache
    wrote = 0
    for p in get_cache().programs():
        wrote += p.export_to(directory)
    return wrote


def _selftest_symbol():
    """A small MLP — big enough that XLA compile time is measurable,
    small enough for a sub-second warmup when the disk tier hits."""
    from .. import sym
    x = sym.Variable("data")
    h = sym.FullyConnected(x, num_hidden=256, name="fc1")
    h = sym.Activation(h, act_type="relu")
    h = sym.FullyConnected(h, num_hidden=128, name="fc2")
    h = sym.Activation(h, act_type="tanh")
    out = sym.FullyConnected(h, num_hidden=10, name="fc3")
    return sym.SoftmaxOutput(out, name="softmax")


def selftest(cache_dir, buckets=(1, 4)):
    """Warm a built-in model against `cache_dir` and report what it
    cost — run once cold and once (in a fresh process) warm, the two
    numbers are the cold-start story for this machine."""
    from . import get_cache, set_cache_dir
    set_cache_dir(cache_dir)
    manifest = {
        "version": MANIFEST_VERSION,
        "models": [{
            "name": "selftest-mlp",
            "symbol": None,   # built below, not loaded
            "data_shapes": [["data", [1, 64]]],
            "buckets": list(buckets),
        }],
    }
    # inline model: bypass the file round trip warm() normally does
    from ..serving.model import ServedModel
    symbol = _selftest_symbol()
    args, auxs = _zero_params(symbol, {"data": (1, 64)}, "float32")
    cache = get_cache()
    before = dict(cache.counters)
    t0 = time.perf_counter()
    model = ServedModel(symbol, args, auxs,
                        data_shapes=[("data", (1, 64))],
                        buckets=tuple(buckets), name="selftest-mlp")
    model.warmup()
    out = {
        "compile_s": round(time.perf_counter() - t0, 3),
        "compiles": cache.counters["compiles"] - before["compiles"],
        "disk_hits": cache.counters["disk_hits"] - before["disk_hits"],
        "buckets": list(buckets),
        "audit_key": model.audit_key,
        "manifest": manifest,
    }
    cache.write_stats()
    return out
