"""The persistent tier of the unified program cache.

One `ProgramCache` per process owns a writable cache *directory* (the
disk tier) plus any number of read-only *sources* (e.g. a checkpoint's
``programs/`` payload).  Entries are XLA serialized executables — the
output of ``jax.jit(...).lower().compile()`` run through
`jax.experimental.serialize_executable` — keyed by

    graph-hash x input signature (shapes/dtypes/pytree) x donation spec
    x device/mesh fingerprint x jax version x format version

so a second process that builds the same program loads the compiled
executable from disk instead of paying the XLA compile of the fused
train graphs again.

Entry files are corruption-safe by construction:

* written to a temp name and published with one atomic ``os.replace`` —
  a concurrent writer of the same key loses the race harmlessly (both
  wrote identical bytes) and a crash mid-write leaves only a temp file;
* framed as ``MAGIC | header-length | header-JSON | payload | CRC32``;
  a torn or bit-flipped entry fails the CRC (or the header parse) on
  load, is deleted, and the caller falls back to a fresh compile;
* self-describing: the header repeats the key ingredients, so an entry
  produced under a different jax version / backend / format is evicted
  instead of deserialized (versioned eviction).

The disk tier activates when a directory is configured
(``MXNET_PROGRAM_CACHE_DIR`` or `set_cache_dir`); without one the
unified cache still runs its memory tier (see program.py) and can
export entries on demand (checkpoint ``programs/`` payloads).
"""
from __future__ import annotations

import binascii
import json
import logging
import os
import pickle
import struct
import tempfile

from ..analysis import locks as _alocks

__all__ = ["ProgramCache", "device_fingerprint", "entry_key",
           "FORMAT_VERSION"]

_log = logging.getLogger(__name__)

FORMAT_VERSION = 1
_MAGIC = b"MXPROG01"
_SUFFIX = ".xprog"


def device_fingerprint():
    """Fingerprint of the device topology an executable is specialized
    to: platform, device kind, local/global device and process counts,
    the jax version, and the framework version (an op-implementation
    change across releases must not serve a stale executable through a
    symbol-JSON-keyed entry).  Serialized executables are only valid on
    an identical topology (the compiled program bakes in the mesh)."""
    import jax
    from ..libinfo import __version__ as _fw_version
    devs = jax.devices()
    return "|".join([
        jax.default_backend(),
        getattr(devs[0], "device_kind", "?"),
        "d%d" % len(devs),
        "p%d" % jax.process_count(),
        "jax=" + jax.__version__,
        "fw=" + _fw_version,
    ])


def entry_key(graph_key, signature, donation, fingerprint=None):
    """Content hash naming one cache entry file.

    `graph_key` is the caller's stable graph identity (symbol-JSON hash,
    sanitized jaxpr hash, ...), `signature` the abstract input signature
    (pytree structure + per-leaf shape/dtype), `donation` the
    donate_argnums spec.  A false hit on any ingredient would replay the
    wrong program, so ALL of them feed the hash."""
    import hashlib
    if fingerprint is None:
        fingerprint = device_fingerprint()
    blob = repr((FORMAT_VERSION, fingerprint, graph_key, signature,
                 tuple(donation or ()))).encode()
    return hashlib.sha256(blob).hexdigest()[:48]


def _frame(header, payload):
    head = json.dumps(header, sort_keys=True).encode()
    body = _MAGIC + struct.pack("<I", len(head)) + head + payload
    return body + struct.pack("<I", binascii.crc32(body) & 0xFFFFFFFF)


def _unframe(blob):
    """(header, payload) of a framed entry, or None when torn/corrupt."""
    if len(blob) < len(_MAGIC) + 8 or not blob.startswith(_MAGIC):
        return None
    body, (crc,) = blob[:-4], struct.unpack("<I", blob[-4:])
    if binascii.crc32(body) & 0xFFFFFFFF != crc:
        return None
    (hlen,) = struct.unpack("<I", body[len(_MAGIC):len(_MAGIC) + 4])
    hstart = len(_MAGIC) + 4
    if hstart + hlen > len(body):
        return None
    try:
        header = json.loads(body[hstart:hstart + hlen].decode())
    except ValueError:
        return None
    return header, body[hstart + hlen:]


class ProgramCache:
    """Disk tier + stats plane of the unified program cache.

    Thread-safe; all methods are best-effort — a cache failure degrades
    to a recompile, never to an error on the caller's path."""

    def __init__(self, directory=None, sources=(), limit_mb=None):
        self._lock = _alocks.make_lock("compile.cache")
        self.directory = None
        self.sources = []
        self._limit_mb = limit_mb
        self.counters = {"compiles": 0, "mem_hits": 0, "disk_hits": 0,
                         "disk_misses": 0, "live_hits": 0, "stores": 0,
                         "corrupt": 0, "evicted": 0, "errors": 0,
                         "fallbacks": 0, "lower_s_total": 0.0,
                         "compile_s_total": 0.0}
        self.events = []       # per-compile: {label, signature} (capped)
        self._programs = []    # weakrefs of live CachedPrograms
        # live tier: entry-key -> the loaded executable THIS process
        # already holds.  An in-process restart (fit failover, guardian
        # rollback, supervisor shrink-and-resume) rebuilds its fused
        # steps; without this tier the rebuilt wrapper would deserialize
        # a CLONE of an executable that is still alive in this process —
        # wasted work, and with the original alive the clone's teardown
        # double-frees runtime state on this jaxlib (observed glibc heap
        # corruption).  Bounded LRU; entries are dropped oldest-first.
        # It outlives the wrappers (a fit's module may be gone while its
        # executable is held here), so `op_scopes` reads it too.
        self._live = {}          # entry-key -> (executable, its label)
        self._live_cap = 64
        # keys whose entry was found corrupt/stale in a READ-ONLY source
        # (we cannot delete there): the next export of that key rewrites
        # instead of skipping the existing bad file
        self.corrupt_keys = set()
        # telemetry plane: hit/compile/eviction counters under the
        # stable 'cache' namespace (weakly held; newest cache answers)
        from ..obs import metrics as _obs_metrics
        _obs_metrics.register_producer("cache", self.stats)
        if directory:
            self.set_directory(directory)
        for s in sources:
            self.add_source(s)

    # -- configuration -------------------------------------------------------
    def _version_dir(self, root):
        return os.path.join(str(root), "v%d" % FORMAT_VERSION)

    def set_directory(self, directory):
        """Point the writable disk tier at `directory` (created on
        demand; entries live under a format-versioned subdirectory so a
        format bump orphans — and `prune` deletes — old entries)."""
        if not directory:
            self.directory = None
            return
        path = self._version_dir(directory)
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            _log.warning("program cache dir %r unusable (%s); disk tier "
                         "disabled", directory, e)
            self.directory = None
            return
        self.directory = path

    def add_source(self, directory):
        """Register a read-only entry location (a checkpoint's
        ``programs/`` payload, a warmed cache shipped with a container
        image).  Missing directories are accepted silently — payloads
        are optional by design."""
        if not directory:
            return
        for root in (self._version_dir(directory), str(directory)):
            if os.path.isdir(root) and root not in self.sources \
                    and root != self.directory:
                self.sources.append(root)
                return

    @property
    def limit_mb(self):
        if self._limit_mb is not None:
            return self._limit_mb
        from .. import config as _config
        return int(_config.get("MXNET_PROGRAM_CACHE_LIMIT_MB"))

    def enabled(self):
        return self.directory is not None or bool(self.sources)

    # -- live tier (in-process executables) ----------------------------------
    def live_get(self, key):
        """The already-loaded executable for `key`, if this process holds
        one (compiled or deserialized earlier) — the in-process restart
        fast path: no compile, no deserialize."""
        with self._lock:
            held = self._live.get(key)
            if held is None:
                return None
            self.counters["live_hits"] += 1
            # LRU touch
            self._live[key] = self._live.pop(key)
            return held[0]

    def live_put(self, key, exe, label=""):
        with self._lock:
            self._live[key] = (exe, label)
            while len(self._live) > self._live_cap:
                self._live.pop(next(iter(self._live)))

    def live_programs(self):
        """[(label, executable)] of the live tier, oldest use first."""
        with self._lock:
            return [(label, exe) for exe, label in self._live.values()]

    # -- lookup / store ------------------------------------------------------
    def _paths(self, key):
        fname = key + _SUFFIX
        if self.directory is not None:
            yield os.path.join(self.directory, fname)
        for src in self.sources:
            yield os.path.join(src, fname)

    def load(self, key, devices, expect_fingerprint=None):
        """Deserialize the entry for `key` onto `devices` (the device
        assignment it was compiled for — part of the key) -> loaded
        executable, or None.

        Corrupt entries are deleted (primary dir only); entries whose
        header disagrees with the current format/jax/device fingerprint
        are evicted rather than deserialized."""
        from jax.experimental import serialize_executable as _se
        fp = expect_fingerprint or device_fingerprint()
        for path in self._paths(key):
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError:
                continue
            got = _unframe(blob)
            if got is None:
                with self._lock:
                    self.counters["corrupt"] += 1
                    self.corrupt_keys.add(key)
                self._discard(path)
                continue
            header, payload = got
            if header.get("format") != FORMAT_VERSION or \
                    header.get("fingerprint") != fp:
                with self._lock:
                    self.counters["evicted"] += 1
                    self.corrupt_keys.add(key)
                self._discard(path)
                continue
            try:
                ser, in_tree, out_tree = pickle.loads(payload)
                exe = _se.deserialize_and_load(
                    ser, in_tree, out_tree,
                    execution_devices=list(devices))
            except Exception as e:
                _log.warning("program cache entry %s failed to "
                             "deserialize (%s); recompiling", path,
                             str(e)[:200])
                with self._lock:
                    self.counters["corrupt"] += 1
                    self.corrupt_keys.add(key)
                self._discard(path)
                continue
            try:  # LRU currency for the size-cap eviction
                os.utime(path, None)
            except OSError:
                pass
            with self._lock:
                self.counters["disk_hits"] += 1
            return exe
        return None

    def _discard(self, path):
        """Remove a bad/stale entry — only where we own the file."""
        if self.directory and path.startswith(self.directory):
            try:
                os.unlink(path)
            except OSError:
                pass

    def serialize_entry(self, compiled, header):
        """Frame one executable as entry bytes (shared by `store` and
        the checkpoint/export path, which writes into a payload dir)."""
        import pickle as _pickle
        from jax.experimental import serialize_executable as _se
        ser, in_tree, out_tree = _se.serialize(compiled)
        return _frame(header, _pickle.dumps((ser, in_tree, out_tree)))

    def write_entry(self, directory, key, blob, overwrite=False):
        """Atomically publish framed entry bytes under `directory`."""
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, key + _SUFFIX)
        if os.path.exists(path) and not overwrite:
            return path
        fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)   # atomic: readers see whole entries only
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    def store(self, key, compiled, meta=None):
        """Serialize + publish one compiled executable into the primary
        directory.  Returns the entry path, or None (disk tier off, or
        the backend cannot serialize this executable)."""
        if self.directory is None:
            return None
        header = dict(meta or {})
        header.update(format=FORMAT_VERSION,
                      fingerprint=device_fingerprint())
        try:
            blob = self.serialize_entry(compiled, header)
            path = self.write_entry(self.directory, key, blob)
        except Exception as e:
            with self._lock:
                self.counters["errors"] += 1
            _log.warning("program cache store failed for %s (%s)",
                         meta.get("label", key) if meta else key,
                         str(e)[:200])
            return None
        with self._lock:
            self.counters["stores"] += 1
        self._enforce_limit()
        return path

    # -- maintenance ---------------------------------------------------------
    def _entries(self):
        if self.directory is None:
            return []
        out = []
        try:
            for name in os.listdir(self.directory):
                if name.endswith(_SUFFIX):
                    path = os.path.join(self.directory, name)
                    try:
                        st = os.stat(path)
                        out.append((st.st_mtime, st.st_size, path))
                    except OSError:
                        pass
        except OSError:
            pass
        return out

    def _enforce_limit(self):
        """LRU size cap: drop the stalest entries past the MB budget."""
        limit = self.limit_mb * (1 << 20)
        entries = sorted(self._entries())
        total = sum(sz for _, sz, _ in entries)
        for mtime, sz, path in entries:
            if total <= limit:
                break
            self._discard(path)
            total -= sz
            with self._lock:
                self.counters["evicted"] += 1

    def prune(self):
        """Delete orphaned old-format version dirs + corrupt entries."""
        removed = 0
        if self.directory is None:
            return removed
        root = os.path.dirname(self.directory)
        import shutil
        try:
            for name in os.listdir(root):
                path = os.path.join(root, name)
                if name.startswith("v") and os.path.isdir(path) \
                        and path != self.directory:
                    shutil.rmtree(path, ignore_errors=True)
                    removed += 1
        except OSError:
            pass
        for _, _, path in self._entries():
            try:
                with open(path, "rb") as f:
                    if _unframe(f.read()) is None:
                        self._discard(path)
                        removed += 1
            except OSError:
                pass
        return removed

    # -- stats plane ---------------------------------------------------------
    def note_compile(self, label, sig_repr, lower_s=None, compile_s=None):
        """Record one cold compile.  ``lower_s``/``compile_s`` split the
        cold-start cost into trace->StableHLO and XLA-compile phases
        (CachedProgram._acquire times them); they accumulate into the
        ``compile_s_total``/``lower_s_total`` counters so mxtop's CACHE
        line can show the fleet's cold-compile debt in seconds, not
        just counts."""
        with self._lock:
            self.counters["compiles"] += 1
            if compile_s is not None:
                self.counters["compile_s_total"] = round(
                    self.counters.get("compile_s_total", 0.0) +
                    float(compile_s), 3)
            if lower_s is not None:
                self.counters["lower_s_total"] = round(
                    self.counters.get("lower_s_total", 0.0) +
                    float(lower_s), 3)
            if len(self.events) < 512:
                ev = {"label": label, "signature": sig_repr}
                if lower_s is not None:
                    ev["lower_s"] = round(float(lower_s), 4)
                if compile_s is not None:
                    ev["compile_s"] = round(float(compile_s), 4)
                self.events.append(ev)

    def bump(self, counter, n=1):
        with self._lock:
            self.counters[counter] = self.counters.get(counter, 0) + n

    def register_program(self, program):
        import weakref
        with self._lock:
            self._programs.append(weakref.ref(program))

    def programs(self):
        """Live CachedPrograms registered with this cache."""
        with self._lock:
            refs = list(self._programs)
        out = []
        for r in refs:
            p = r()
            if p is not None:
                out.append(p)
        return out

    def stats(self):
        """One dict: global counters + per-program signature/compile
        breakdown (the mxlint cache-report's and benchmark/'s currency).
        Memory-hit counts live on the programs (the warm dispatch path
        is lock-free) and are aggregated here."""
        with self._lock:
            counters = dict(self.counters)
            events = list(self.events)
        progs = []
        mem_hits = 0
        for p in self.programs():
            mem_hits += p.mem_hits
            progs.append({
                "label": p.label,
                "signatures": len(p.signatures()),
                "compiles": p.compile_count,
                "disk_hits": p.disk_hits,
                "disk_misses": getattr(p, "disk_misses", 0),
                "mem_hits": p.mem_hits,
                "lower_s": round(getattr(p, "lower_s_total", 0.0), 4),
                "compile_s": round(getattr(p, "compile_s_total", 0.0), 4),
            })
        counters["mem_hits"] = counters.get("mem_hits", 0) + mem_hits
        lookups = counters["compiles"] + counters["mem_hits"] + \
            counters["disk_hits"] + counters.get("live_hits", 0)
        return {
            "counters": counters,
            "hit_rate": round((counters["mem_hits"] +
                               counters["disk_hits"] +
                               counters.get("live_hits", 0)) / lookups, 4)
            if lookups else None,
            "disk_enabled": self.enabled(),
            "directory": self.directory,
            "programs": progs,
            "compile_events": events,
        }

    def write_stats(self, path=None):
        """Append this process's stats record to ``stats.json`` next to
        the entries (read-modify-write, atomic publish, capped history)
        so offline tools — mxlint --cache-report — can aggregate hit
        rates across runs."""
        if path is None:
            if self.directory is None:
                return None
            path = os.path.join(os.path.dirname(self.directory),
                                "stats.json")
        record = self.stats()
        record.pop("compile_events", None)
        record["events"] = [e for e in self.events][:256]
        import time
        record["time"] = int(time.time())
        runs = []
        try:
            with open(path) as f:
                runs = json.load(f).get("runs", [])
        except (OSError, ValueError):
            pass
        runs = (runs + [record])[-50:]
        tmp = path + ".tmp%d" % os.getpid()
        try:
            with open(tmp, "w") as f:
                json.dump({"runs": runs}, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        return path
