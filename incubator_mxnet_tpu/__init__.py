"""incubator_mxnet_tpu — a TPU-native framework with MXNet's capabilities.

Brand-new implementation (NOT a port) of the Apache MXNet API surface
(reference: yieldbot/incubator-mxnet ~v1.2) on JAX/XLA/PJRT/Pallas:

* `nd` — async NDArray data plane in TPU HBM (PJRT buffers)
* `sym` + executors — symbolic graphs compiled to single XLA computations
* `autograd` — eager tape with XLA-compiled vjps
* `gluon` — imperative-first API; `hybridize()` = trace-to-XLA JIT
* `kvstore` — push/pull as collectives over the ICI mesh
* `module`/`mod` — classic symbolic training API
* `io`/`recordio` — high-throughput input pipeline

Typical use: ``import incubator_mxnet_tpu as mx``.
"""
from __future__ import annotations

import time as _time
_import_t0 = _time.time_ns() // 1000    # the `mx.import` phase's start

__version__ = "0.1.0"

from .attribute import AttrScope
from .base import MXNetError
from . import analysis
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import config
from . import engine
from . import random
from . import autograd
from . import ndarray
from . import ndarray as nd
from .ndarray.ndarray import NDArray

# re-attach registry methods now that all op modules are loaded
from .ndarray.ndarray import _attach_methods as _am
_am()

# Layered subsystems import in dependency order; each guard block is removed
# as the subsystem lands (round-1 build plan, SURVEY.md §7).
import importlib as _importlib

for _mod_name, _aliases in [
    ("symbol", ("sym",)), ("executor", ()), ("initializer", ()),
    ("optimizer", ()), ("lr_scheduler", ()), ("metric", ()),
    ("kvstore", ("kv",)), ("callback", ()), ("monitor", ()),
    ("io", ()), ("recordio", ()), ("gluon", ()), ("module", ("mod",)),
    ("model", ()), ("profiler", ()), ("visualization", ("viz",)),
    ("parallel", ()), ("test_utils", ()), ("image", ()), ("operator", ()),
    ("contrib", ()), ("rnn", ()), ("compat", ()), ("dist", ()),
    ("subgraph", ()), ("storage", ()), ("libinfo", ()),
    ("checkpoint", ()), ("serving", ()), ("resilience", ()),
    ("kvstore_server", ()), ("native", ()), ("compile", ()),
    ("obs", ()), ("embedding", ()), ("loop", ()),
]:
    try:
        _m = _importlib.import_module("." + _mod_name, __name__)
    except ModuleNotFoundError as _e:
        if _e.name and _e.name.endswith(_mod_name):
            continue  # subsystem not yet built this round
        raise
    globals()[_mod_name] = _m
    for _a in _aliases:
        globals()[_a] = _m

if "symbol" in globals():
    from .symbol.symbol import Symbol  # noqa: E402
if "initializer" in globals():
    init = initializer  # noqa: F821
if "optimizer" in globals():
    from .optimizer import Optimizer  # noqa: E402

rnd = random

# env-var knobs that act at import time (config.py documents the full table)
config.apply_startup_knobs()
from .compile import place_compilation_cache as _place  # noqa: E402
_place()
from .obs import trace as _obs_trace  # noqa: E402
_obs_trace.watch_jax()
_obs_trace.record_phase("mx.import", _import_t0,
                        _time.time_ns() // 1000 - _import_t0, cat="compile")
