"""CachedProgram: the jit-shaped front of the unified program cache.

Every compiled-program site in the framework — the fused train steps
(`fused.FusedTrainStep`, `gluon.fused_step.GluonFusedStep`), the
inference cache (`fused.FusedInference`), Gluon's CachedOp graphs —
used to keep its own private per-signature jit cache.  They now share
this wrapper: one `CachedProgram` per logical graph, holding one
compiled executable per input signature, with the signatures, compile
counts and disk-tier traffic visible on the central `ProgramCache`.

Call path per signature:

1. memory tier — the executable this wrapper already holds;
2. disk tier  — `ProgramCache.load` (a serialized executable written by
   an earlier process/warmup/checkpoint payload), when a graph key and
   a cache location exist;
3. compile    — ``jit.lower(*args).compile()`` (the AOT build the
   warmup API also drives), then best-effort serialize to the disk
   tier for the next process.

AOT executables validate their inputs strictly (exact dtypes/shardings,
no weak-type promotion).  A signature whose dispatch trips that
validation permanently falls back to the plain ``jax.jit`` path for
this wrapper — never an error on the caller, and donation is checked
before any replay so a consumed buffer is never dispatched twice.

``MXNET_PROGRAM_CACHE=0`` disables the whole layer: every wrapper
degrades to its plain jit (the pre-unification behavior).
"""
from __future__ import annotations

import hashlib
import logging
import re

from ..analysis import locks as _alocks
from ..obs import trace as _obs_trace

__all__ = ["CachedProgram", "cached_jit", "graph_hash_of_jaxpr",
           "graph_hash_of_text"]

_log = logging.getLogger(__name__)

_ADDR_RE = re.compile(r"0x[0-9a-f]+")


def graph_hash_of_text(*parts):
    """Stable hash over textual graph identities (symbol JSON, op names,
    parameter partitions...)."""
    h = hashlib.sha256()
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x00")
    return h.hexdigest()[:32]


def graph_hash_of_jaxpr(closed_jaxpr):
    """Stable cross-process hash of a traced core: the jaxpr
    pretty-print with memory addresses scrubbed (function reprs inside
    eqn params would otherwise churn the key every process), PLUS the
    closure constants' VALUES — the print shows consts only as typed
    constvars, so two cores baking different lookup tables would
    otherwise hash identically and a disk hit would silently replay the
    other table."""
    h = hashlib.sha256()
    h.update(_ADDR_RE.sub("0x", str(closed_jaxpr)).encode())
    import numpy as _np
    for c in getattr(closed_jaxpr, "consts", ()):
        try:
            a = _np.asarray(c)
            h.update(repr((str(a.dtype), a.shape)).encode())
            h.update(a.tobytes())
        except Exception:
            h.update(repr(c).encode())
    return h.hexdigest()[:32]


def _leaf_sig(leaf):
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        return (tuple(shape), str(leaf.dtype))
    # weak-typed python scalar: distinct from a committed 0-d array
    return ("py", type(leaf).__name__)


_PLAIN = object()   # sentinel: this signature dispatches via plain jit


def _arg_devices(args):
    """The device assignment a call's arguments commit the program to:
    the widest sharding among the array leaves, in mesh order (what
    `jit` lowers against); the default device when no leaf is a device
    array."""
    import jax
    from jax.sharding import NamedSharding
    best = ()
    for leaf in jax.tree_util.tree_leaves(args):
        sh = getattr(leaf, "sharding", None)
        if sh is None or len(sh.device_set) <= len(best):
            continue
        best = tuple(sh.mesh.devices.flat) if isinstance(sh, NamedSharding) \
            else tuple(sorted(sh.device_set, key=lambda d: d.id))
    return best or (jax.devices()[0],)


class CachedProgram:
    """One logical program; one executable per input signature."""

    def __init__(self, fn, donate_argnums=(), graph_key=None, label="",
                 cache=None):
        import jax
        self._fn = fn
        self._donate = tuple(donate_argnums or ())
        self._jit = jax.jit(fn, donate_argnums=self._donate) \
            if self._donate else jax.jit(fn)
        self.graph_key = graph_key
        self.label = label or (graph_key[:12] if graph_key else "program")
        self._programs = {}     # sig -> executable | _PLAIN
        self._entry_keys = {}   # sig -> disk entry key (for export)
        self._lock = _alocks.make_lock("compile.program")
        self.compile_count = 0
        self.disk_hits = 0
        self.disk_misses = 0   # disk tier enabled but had no entry
        self.mem_hits = 0   # plain int: the warm path must not take locks
        self.lower_s_total = 0.0    # trace->StableHLO seconds (cold only)
        self.compile_s_total = 0.0  # XLA compile seconds (cold only)
        if cache is None:
            from . import get_cache
            cache = get_cache()
        self._cache = cache
        cache.register_program(self)

    # -- signature -----------------------------------------------------------
    def _sig(self, args):
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(_leaf_sig(l) for l in leaves))

    def signatures(self):
        with self._lock:
            return list(self._programs)

    def _cache_size(self):
        """Signature count — the drop-in for ``jax.jit._cache_size()``
        that `FusedInference.program_count` (and the serving zero-
        recompile certification) reads."""
        return len(self._programs)

    def executables(self):
        """The AOT executables this wrapper holds, oldest first (the
        plain-jit signatures hold none)."""
        with self._lock:
            return [e for e in self._programs.values()
                    if e is not _PLAIN and e is not None]

    # -- acquire -------------------------------------------------------------
    def _entry_key(self, sig, devices):
        from . import cache as _cache
        sig_repr = (str(sig[0]), sig[1], tuple(d.id for d in devices))
        return _cache.entry_key(self.graph_key, sig_repr, self._donate)

    def _acquire(self, sig, args):
        from . import enabled as _enabled
        cache = self._cache
        if not _enabled():
            return _PLAIN
        key = None
        if self.graph_key is not None:
            # an executable is specialized to its device assignment: the
            # devices key the entry, and a disk hit loads onto them
            devices = _arg_devices(args)
            key = self._entry_key(sig, devices)
            self._entry_keys[sig] = key
            # live tier first: an in-process restart (fit failover,
            # guardian rollback, supervisor shrink-and-resume) rebuilds
            # its wrappers around executables this process ALREADY holds
            # — reuse them directly.  Deserializing a disk clone of a
            # still-live executable is never correct here: wasted work,
            # and the clone's coexistence with the original corrupts
            # runtime state on teardown (see ProgramCache._live).
            exe = cache.live_get(key)
            if exe is not None:
                return exe
            if cache.enabled():
                with _obs_trace.phase("compile.load", cat="compile",
                                      label=self.label):
                    exe = cache.load(key, devices)
                if exe is not None:
                    self.disk_hits += 1
                    cache.live_put(key, exe, self.label)
                    return exe
                self.disk_misses += 1
                cache.bump("disk_misses")
        sig_repr = "%d leaves: %s" % (len(sig[1]), repr(sig[1])[:160])
        self.compile_count += 1
        # phase-split timing: lower (trace -> StableHLO) vs the XLA
        # compile proper — the cold-start debt mxtop's CACHE line and
        # benchmark/'s program_build_s report per program
        with _obs_trace.phase("compile.lower", cat="compile",
                              label=self.label) as lower:
            lowered = self._jit.lower(*args)
        # XLA's compile, or the load from JAX's persistent cache
        with _obs_trace.phase("compile.compile", cat="compile",
                              label=self.label) as comp:
            exe = lowered.compile()
        self.lower_s_total += lower.s
        self.compile_s_total += comp.s
        cache.note_compile(self.label, sig_repr, lower_s=lower.s,
                           compile_s=comp.s)
        if key is not None:
            cache.live_put(key, exe, self.label)
            if cache.enabled():
                cache.store(key, exe, meta={"label": self.label,
                                            "graph": self.graph_key,
                                            "donate": list(self._donate)})
        return exe

    # -- dispatch ------------------------------------------------------------
    def __call__(self, *args):
        sig = self._sig(args)
        exe = self._programs.get(sig)
        warm = exe is not None
        if not warm:
            with self._lock:
                exe = self._programs.get(sig)
                warm = exe is not None
                if not warm:
                    try:
                        exe = self._acquire(sig, args)
                    except Exception:
                        # a failed lower/compile never consumed buffers;
                        # surface through the plain path so the caller's
                        # existing triage (fused fallbacks) sees the
                        # same exception surface as before unification
                        exe = _PLAIN
                    self._programs[sig] = exe
        if warm:
            # per-program plain increment: the steady-state dispatch path
            # takes no lock (GIL-racy across threads costs at most a few
            # stat counts, never correctness); stats() aggregates
            self.mem_hits += 1
        if exe is _PLAIN:
            return self._jit(*args)
        try:
            return exe(*args)
        except TypeError as e:
            # AOT input validation is stricter than jit (weak types,
            # shardings).  Validation raises BEFORE execution, so the
            # args are intact — but donation makes replay destructive,
            # so verify nothing was consumed before re-dispatching.
            from ..analysis import donation as _donation
            if self._donate and _donation.any_deleted(args):
                raise
            _log.warning("program %s: AOT dispatch rejected the inputs "
                         "(%s); pinning this signature to the plain jit "
                         "path", self.label, str(e)[:200])
            with self._lock:
                self._programs[sig] = _PLAIN
            self._cache.bump("fallbacks")
            return self._jit(*args)

    # -- export (checkpoint programs/ payload, warmed images) ---------------
    def export_to(self, directory):
        """Serialize every AOT-held executable into `directory` as
        standard cache entries (skipping ones already on disk there).
        Returns the number of entries written."""
        from . import cache as _cache
        import os
        wrote = 0
        with self._lock:
            items = list(self._programs.items())
        if self.graph_key is None:
            return 0
        target = os.path.join(str(directory), "v%d" % _cache.FORMAT_VERSION)
        for sig, exe in items:
            if exe is _PLAIN or exe is None:
                continue
            key = self._entry_keys[sig]
            path = os.path.join(target, key + ".xprog")
            if os.path.exists(path) and \
                    key not in self._cache.corrupt_keys:
                # a key the loader flagged corrupt (torn payload copy we
                # could not delete in a read-only source) is REWRITTEN:
                # skipping it would leave every future resume paying the
                # full compile while exports report the payload shipped
                continue
            header = {"label": self.label, "graph": self.graph_key,
                      "donate": list(self._donate),
                      "format": _cache.FORMAT_VERSION,
                      "fingerprint": _cache.device_fingerprint()}
            try:
                blob = self._cache.serialize_entry(exe, header)
                self._cache.write_entry(target, key, blob, overwrite=True)
                self._cache.corrupt_keys.discard(key)
                wrote += 1
            except Exception as e:
                _log.debug("program export skipped for %s (%s)",
                           self.label, str(e)[:200])
        return wrote


def cached_jit(fn, donate_argnums=(), graph_key=None, label="",
               cache=None):
    """`jax.jit`-shaped constructor for a `CachedProgram`."""
    return CachedProgram(fn, donate_argnums=donate_argnums,
                         graph_key=graph_key, label=label, cache=cache)
