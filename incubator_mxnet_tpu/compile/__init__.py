"""Unified program cache with a persistent on-disk tier and AOT warmup.

This package replaces the three independent per-signature program
caches the framework grew — `fused.FusedTrainStep`'s train programs,
`fused.FusedInference` (serving / c_predict), and Gluon's CachedOp
graphs (`gluon/block.py`) — with ONE cache product:

* **memory tier** — `CachedProgram` (program.py): a jit-shaped wrapper,
  one compiled executable per input signature, centrally registered so
  signatures/compiles/hit-rates are observable in one place;
* **disk tier** — `ProgramCache` (cache.py): XLA serialized
  executables keyed by graph-hash x shapes x dtypes x donation x
  device/mesh fingerprint, CRC'd and atomically published, versioned
  eviction; a second process loads instead of compiling;
* **AOT warmup** — warmup.py: manifest-driven
  ``jax.jit(...).lower().compile()`` so serving ladders and resumed
  training jobs pay compilation before traffic, or never (disk hit);
* **stats plane** — `stats()` / `findings()` feed
  `analysis.runtime_report()` and the ``mxlint --cache-report`` CLI;
  compiles are attributable to churned signatures via the recompile
  auditor's history;
* **names** — `op_scopes()` (scopes.py): for a live program, which
  phase of the train step and which graph node each HLO instruction of
  its executable came from — what turns a profile's ``fusion.3426``
  into ``Convolution bwd``.

Knobs: ``MXNET_PROGRAM_CACHE`` (master switch),
``MXNET_PROGRAM_CACHE_DIR`` (disk tier location),
``MXNET_PROGRAM_CACHE_LIMIT_MB`` (LRU size cap),
``MXNET_PROGRAM_CACHE_CHECKPOINT`` (ship programs/ with elastic
checkpoints).
"""
from __future__ import annotations

import atexit
import os

from ..analysis import locks as _alocks

from .cache import ProgramCache, device_fingerprint, entry_key  # noqa: F401
from .program import (CachedProgram, cached_jit,  # noqa: F401
                      graph_hash_of_jaxpr, graph_hash_of_text)
from . import warmup  # noqa: F401
from .warmup import warm, write_manifest, export_all  # noqa: F401

__all__ = ["ProgramCache", "CachedProgram", "cached_jit", "get_cache",
           "set_cache_dir", "add_source", "enabled", "stats", "op_scopes",
           "write_stats", "findings", "warm", "write_manifest",
           "export_all", "graph_hash_of_jaxpr", "graph_hash_of_text",
           "device_fingerprint", "entry_key"]

_cache = None
_cache_lock = _alocks.make_lock("compile.registry")
_enabled = None   # tri-state: None = read MXNET_PROGRAM_CACHE lazily
_atexit_armed = False


def place_compilation_cache():
    """Place JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads the
    variable itself — nothing is overridden), else at the fixed
    ``<checkout>/.jax_cache``.  The path is part of what makes a cache
    findable again, so it is never a temporary name, a pid or a time.
    Called once, when the package is imported — before anything can
    compile, whichever of the library's `jax.jit` sites compiles first
    (a config update; no backend is touched).

    The cache's key takes the programs' metadata in: `op_scopes` reads
    the scope names out of an executable's `op_name` metadata, and a key
    without them (JAX's default) hands a program whose scopes changed
    the executable of the old ones, stale names and all.  The price is
    that a moved source line compiles again."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


def enabled():
    """Master switch (MXNET_PROGRAM_CACHE): off -> every wrapper is a
    plain jax.jit, the pre-unification behavior."""
    global _enabled
    if _enabled is None:
        from .. import config as _config
        _enabled = bool(_config.get("MXNET_PROGRAM_CACHE"))
    return _enabled


def get_cache():
    """The process-wide ProgramCache (disk tier configured from
    MXNET_PROGRAM_CACHE_DIR on first use)."""
    global _cache
    if _cache is None:
        with _cache_lock:
            if _cache is None:
                from .. import config as _config
                c = ProgramCache()
                d = str(_config.get("MXNET_PROGRAM_CACHE_DIR") or "")
                if d:
                    c.set_directory(d)
                    _arm_atexit(c)
                _cache = c
    return _cache


def _arm_atexit(cache):
    """Persist the stats sidecar at exit when a disk tier exists (the
    mxlint cache-report aggregates these across runs).  The handler
    resolves the CURRENT singleton at exit time, so re-pointing the
    cache (tests, embedding processes) flushes the right directory."""
    del cache
    global _atexit_armed
    if _atexit_armed:
        return
    _atexit_armed = True

    def _flush():
        c = _cache
        if c is not None and c.directory is not None:
            try:
                c.write_stats()
            except Exception:
                pass
    atexit.register(_flush)


def set_cache_dir(path):
    """Point (or re-point) the disk tier at `path`; also the test/tool
    entry point (MXNET_PROGRAM_CACHE_DIR is the env equivalent)."""
    c = get_cache()
    c.set_directory(path)
    if c.directory:
        _arm_atexit(c)
    return c


def add_source(path):
    """Register a read-only entry payload (checkpoint programs/ dir)."""
    get_cache().add_source(path)


def stats():
    return get_cache().stats()


def write_stats(path=None):
    return get_cache().write_stats(path)


_scope_maps = {}    # id(executable) -> (executable, its parsed map)


def op_scopes(label=None):
    """``{hlo_instruction_name: {"phase", "op", "node", "mixed"}}`` of a
    program the cache holds live, parsed (once) from its executable's own
    optimized HLO text — compiled here, or loaded from JAX's or this
    cache's disk tier: the text comes with all three.  Of the programs
    labelled `label` (of all, without one): the busiest live wrapper's
    newest executable, else the live tier's most recently used one — a
    fit's executable outlives its module there.

    `phase` is one of ``fwd``, ``bwd``, ``exchange``, ``optimizer``,
    ``guardian``, ``metric``, ``other``; `op` and `node` the MXNet
    operator kind and node name (None outside a graph node); `mixed`
    whether a fusion's insides fall in more than one phase or in another
    than its own, listed as `inside` then (scopes.py has the rules).
    Instruction names are those a `jax.profiler` trace gives its device
    events.  The names are those of the graph that COMPILED the
    executable: this cache keys a program by its structure, so a graph
    that differs in node names alone shares the executable, and reads the
    first graph's `node`s.  {} where no such program holds an executable
    that can print its HLO."""
    from . import scopes as _scopes
    cache = get_cache()
    held = sorted(cache.programs(), key=lambda p: -p.mem_hits)
    candidates = [(p.label, exe) for p in held
                  for exe in reversed(p.executables())]
    candidates += reversed(cache.live_programs())
    for exe_label, exe in candidates:
        if label is not None and exe_label != label:
            continue
        parsed = _scope_maps.get(id(exe))
        if parsed is None or parsed[0] is not exe:
            parsed = _scope_maps[id(exe)] = (exe, _scopes.of_executable(exe))
            while len(_scope_maps) > 8:
                _scope_maps.pop(next(iter(_scope_maps)))
        if parsed[1]:
            return parsed[1]
    return {}


def reset_for_tests():
    """Drop the singleton (tests that flip env knobs between cases).
    The atexit flush reads the live singleton, so a replacement cache
    created after this still gets its stats written."""
    global _cache, _enabled
    with _cache_lock:
        _cache = None
    _enabled = None
    _scope_maps.clear()


def findings():
    """Program-cache findings for `analysis.runtime_report()`: a summary
    HINT plus a WARN per program whose repeat compiles line up with
    signatures the recompile auditor flagged as churn."""
    from ..analysis.findings import Finding, WARN, HINT
    from ..analysis import recompile as _recompile
    cache = _cache
    if cache is None:
        return []
    st = cache.stats()
    c = st["counters"]
    out = []
    lookups = c["compiles"] + c["mem_hits"] + c["disk_hits"]
    if lookups:
        out.append(Finding(
            "cache.programs", "summary", HINT,
            "program cache: %d compiles, %d disk hits, %d memory hits "
            "(hit rate %.1f%%), %d stored, %d corrupt, %d evicted"
            % (c["compiles"], c["disk_hits"], c["mem_hits"],
               100.0 * (c["mem_hits"] + c["disk_hits"]) / lookups,
               c["stores"], c["corrupt"], c["evicted"]),
            location=st["directory"] or "<memory>"))
    # attribute repeat compiles to churn only when the recompile auditor
    # actually flagged the program (pre-registered warmup buckets are
    # declared signatures, not churn)
    churn_keys = {f.location for f in _recompile.findings()}
    for p in st["programs"]:
        if p["compiles"] > 1 and p["label"] in churn_keys:
            out.append(Finding(
                "cache.programs", "churn-compiles", WARN,
                "%s: %d XLA compiles across %d signatures — each extra "
                "signature paid a full compile; see the recompile "
                "auditor's shape-churn findings for the argument that "
                "moved" % (p["label"], p["compiles"], p["signatures"]),
                location=p["label"]))
    return out
