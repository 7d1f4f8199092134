"""Production data plane: the host→device staging ring.

Without it every batch pays a BLOCKING `device_put` on the training
thread, serialized against the step it is feeding.  This module is the
io tier that removes that serialization:

* `H2DRing` — a pinned-style, double-buffered **staging ring**: batches
  are assembled into REUSABLE preallocated host staging buffers (one
  `np.copyto` per input, which also applies the model's dtype cast — no
  per-batch allocator churn, and on hosts with pinned-memory transfer
  managers the stable buffers are what makes DMA engage), transferred
  to the device by a dedicated ``mx-io-h2d`` thread, and parked in a
  bounded **device-resident prefetch queue** (depth
  ``MXNET_IO_PREFETCH``, floor 2).  Batch k+1 decodes and transfers
  while batch k computes; the consumer never blocks on `device_put` —
  it pops an already-resident device batch.
* `DevicePrefetchIter` — wraps any `DataIter` with the ring.
  `Module.fit` wraps its training iterator automatically
  (``MXNET_IO_RING``, default on) and binds the fused train step's
  placement, so the batches the ring emits are EXACTLY the arrays the
  fused dispatch would have staged — `_stage_inputs` adopts them by
  sharding identity and the step program signature never moves (zero
  steady-state recompiles).  Checkpoint capture/seek, guardian
  quarantine and record-range attribution all delegate to the inner
  iterator, so resume and bad-data bookkeeping are unchanged.
* `DevicePrefetchLoader` — the same ring over a Gluon
  ``DataLoader``-style iterable of ``(data, label)`` pairs
  (`gluon.contrib.estimator.Estimator.fit` wraps with it).
* `auto_shard()` — per-host input sharding: resolves this process's
  ``(part_index, num_parts)`` from the supervisor/dist environment
  (``DMLC_RANK``/``DMLC_NUM_WORKER`` — rewritten by shrink-and-resume,
  so a re-shard lands at the next epoch fence) or the jax multi-process
  runtime.  `ImageRecordIter`/`ImageIter` accept ``num_parts='auto'``
  and re-resolve at every `reset()`.

Telemetry: the feeder's three stages run under three sibling leaf
spans per batch — ``io.source`` (the inner iterator's `next()`),
``io.stage`` (the cast and copy into the staging slot), ``io.h2d``
(`device_put` and the wait for it) — beside three always-on counters
of the same intervals (``source_s``, ``stage_s``, ``put_s``).  Spans
stand around WORK only: a wait during which the device may be idle (the
consumer's `get()` on an empty ring, the feeder's wait for a free slot)
is a counter (``stall_s``, ``stalls``) and never a span, because a
profile's reader names an idle gap by the span that overlaps it most,
and a span over a wait would cover every feed-bound gap and hide the
stage that caused it.  The ring registers its stats under the ``io.*``
dotted namespace in the obs MetricsRegistry — prefetch depth,
occupancy, stalls, bytes, decode-worker queue depth.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

import numpy as _np

from .base import MXNetError
from .analysis import locks as _alocks
from .io import DataBatch, DataIter
from .ndarray.ndarray import NDArray

__all__ = ["H2DRing", "RingPlacement", "DevicePrefetchIter",
           "DevicePrefetchLoader", "auto_shard", "stats"]


def auto_shard(part_index=None, num_parts=None):
    """Resolve this process's input shard as ``(part_index,
    num_parts)``.

    Explicit values win.  Otherwise the dist/supervisor environment
    (``DMLC_RANK``/``DMLC_NUM_WORKER`` — the variables shrink-and-resume
    rewrites when the pod loses a host, so readers that re-resolve at
    reset() re-shard on the epoch fence) is consulted first, then the
    jax multi-process runtime; a single-process run reads (0, 1)."""
    import os
    import sys
    if num_parts not in (None, 0, "auto"):
        return int(part_index or 0), int(num_parts)
    nw = os.environ.get("DMLC_NUM_WORKER")
    if nw and int(nw) > 1:
        return int(os.environ.get("DMLC_RANK", 0)), int(nw)
    if "jax" in sys.modules:
        try:
            import jax
            if jax.process_count() > 1:
                return int(jax.process_index()), int(jax.process_count())
        except Exception:
            pass
    return 0, 1


# ---------------------------------------------------------------------------
# io.* metrics (obs MetricsRegistry)
# ---------------------------------------------------------------------------

_rings = weakref.WeakSet()      # live rings (occupancy/depth at scrape)
_registered = False
# process-lifetime totals: a ring's counts must survive the ring (fit
# wrappers are released when fit returns; benchmark/ reads
# before/after deltas of these)
_TOTALS = {"stalls": 0, "stall_s": 0.0, "batches": 0, "bytes": 0,
           "h2d_s": 0.0, "source_s": 0.0, "stage_s": 0.0, "put_s": 0.0,
           "staging_copies": 0, "zero_copy": 0}
_totals_lock = None


def _totals_guard():
    global _totals_lock
    if _totals_lock is None:
        _totals_lock = _alocks.make_lock("io.totals")
    return _totals_lock


def _totals_add(**kw):
    with _totals_guard():
        for k, v in kw.items():
            _TOTALS[k] += v


def _metrics():
    from .obs import metrics as _m
    return _m


def _register_producer():
    """Register the ``io`` stats producer once (module-level function:
    the registry holds plain callables strongly, and the module never
    dies)."""
    global _registered
    if _registered:
        return
    _registered = True
    try:
        _metrics().register_producer("io", stats)
    except Exception:
        pass


def stats():
    """Io-tier stats (the ``io`` metrics producer): process-lifetime
    totals (stalls, batches, bytes, the feeder's seconds by stage,
    staging/zero-copy counts — these survive individual rings) plus the
    LIVE rings' count, configured prefetch depth, and current queue
    occupancy.

    The feeder's seconds: ``source_s`` in the inner iterator's `next()`,
    ``stage_s`` in the cast and copy into the staging slot, ``put_s`` in
    `device_put` and the wait for it; ``h2d_s`` is stage + put (with the
    adoption check between them), what one `H2DRing.put` costs once a
    slot is free.  ``stall_s`` is the CONSUMER's wait on an empty
    ring."""
    with _totals_guard():
        out = dict(_TOTALS)
    out.update({"rings": 0, "prefetch_depth": 0, "occupancy": 0})
    for ring in list(_rings):
        s = ring.ring_stats()
        out["rings"] += 1
        out["prefetch_depth"] = max(out["prefetch_depth"], s["depth"])
        out["occupancy"] += s["occupancy"]
    if out["h2d_s"] > 0:
        out["h2d_MBps"] = out["bytes"] / out["h2d_s"] / 1e6
    return out


# ---------------------------------------------------------------------------
# placement: where (and as what dtype) staged batches land
# ---------------------------------------------------------------------------

class RingPlacement:
    """Target of the ring's transfers: a jax sharding (or device) plus
    the per-input target dtypes.

    ``dtypes[i]`` of None keeps input i's dtype (labels — the fused
    step's `_stage_inputs` never casts label inputs, and the ring must
    land bit-identical arrays so the dispatch adopts them without a
    second transfer or a signature change)."""

    def __init__(self, sharding=None, dtypes=None, device=None):
        if sharding is None and device is None:
            from .context import current_context
            device = current_context().jax_device
        self.sharding = sharding if sharding is not None else device
        self.dtypes = list(dtypes) if dtypes is not None else None
        self._is_default = None   # resolved on first put()

    @classmethod
    def for_fused_step(cls, fs):
        """The fused train step's exact staging target: its data
        sharding and, per input, the bound argument's dtype (labels
        uncast) — what `_stage_inputs` would produce, computed once."""
        label_names = set(fs._mod._exec_group.label_names)
        dtypes = []
        for name in fs._input_names:
            if name in label_names:
                dtypes.append(None)
            else:
                dtypes.append(_np.dtype(fs._exec0.arg_dict[name].dtype))
        return cls(sharding=fs._data_sharding, dtypes=dtypes)

    def target_dtype(self, i, arr):
        if self.dtypes is None or i >= len(self.dtypes) or \
                self.dtypes[i] is None:
            return arr.dtype
        return self.dtypes[i]

    def put(self, host_arrays):
        """One batched transfer of every input to the target sharding.

        When the target is the process's plain default device the
        sharding argument is omitted: `device_put` may then ADOPT a
        suitably aligned host buffer zero-copy — the cheapest possible
        h2d, and safe because the ring retires adopted staging buffers
        from reuse (`H2DRing._adopted`)."""
        import jax
        tgt = self.sharding
        if self._is_default is None:
            from jax.sharding import SingleDeviceSharding
            try:
                dev = tgt.device if isinstance(tgt, SingleDeviceSharding) \
                    else tgt if not hasattr(tgt, "device_set") else None
                self._is_default = dev is not None and \
                    dev == jax.local_devices()[0]
            except Exception:
                self._is_default = False
        if self._is_default:
            return jax.device_put(list(host_arrays))
        return jax.device_put(list(host_arrays), tgt)


class _EndOfData:
    """Queue sentinel: the producer exhausted its source (or died with
    `exc`)."""

    __slots__ = ("exc",)

    def __init__(self, exc=None):
        self.exc = exc


class H2DRing:
    """The staging ring itself: reusable host staging slots, one
    transfer path, a bounded device-resident queue.

    The PRODUCER side (`put`) runs on the feeder thread: it assembles
    the batch into the next free staging-slot buffers (dtype cast
    included), issues ONE batched `device_put` to the placement, waits
    for the transfer (in the producer thread — the consumer never
    does), and enqueues the device arrays.  `put` blocks while the
    queue is full: bounded, backpressured — a slow consumer pauses
    decode instead of accumulating batches.  The CONSUMER side (`get`)
    pops a ready device batch; an empty queue is a counted **stall**
    (the pipeline failed to hide the input latency).
    """

    def __init__(self, placement, depth=None, staging=None, name="ring"):
        from . import config as _config
        if depth is None:
            depth = int(_config.get("MXNET_IO_PREFETCH"))
        self.depth = max(2, int(depth))   # device-resident prefetch >= 2
        if staging is None:
            staging = bool(_config.get("MXNET_IO_STAGING"))
        self._staging = staging
        self._placement = placement
        self.name = str(name)
        self._q = collections.deque()
        self._cond = _alocks.make_condition(name="io.ring")
        self._closed = False
        # single-producer serialization + epoch token: put() is
        # designed for one feeder, but a feeder whose join timed out
        # (wedged inner iterator) can wake AFTER a restart — the lock
        # keeps two producers out of the staging slots, and the token
        # (bumped by every reopen) makes the stale thread's put/put_end
        # a rejected no-op instead of a stale batch or premature EOF
        self._put_lock = _alocks.make_lock("io.ring.put")
        self._token = 0
        # double-buffered staging: two rotating buffer SETS — the set
        # filled for batch k+1 is never the one batch k's transfer just
        # drained (the transfer is awaited before enqueue, so two slots
        # are sufficient; the rotation keeps the contract explicit)
        self._slots = [dict(), dict()]
        self._slot_i = 0
        self._adopt_possible = None   # resolved on first transfer
        self._ended = None            # _EndOfData once the source dried
        self._stats = {"stalls": 0, "stall_s": 0.0, "batches": 0,
                       "bytes": 0, "h2d_s": 0.0, "source_s": 0.0,
                       "stage_s": 0.0, "put_s": 0.0, "staging_copies": 0,
                       "zero_copy": 0}
        self._stats_lock = _alocks.make_lock("io.ring.stats")
        _rings.add(self)
        _register_producer()

    # -- producer side -------------------------------------------------------
    def _may_adopt(self):
        """Whether this placement's backend can adopt host numpy
        memory zero-copy at all.  Only the CPU backend does (its device
        memory IS host memory); a DMA backend (real TPU/GPU) always
        copies — and there `np.asarray(shard)` would be a full
        device-to-host readback, so the per-buffer adoption check must
        never run.  Unknown platforms are treated as adopting
        (correctness over recycling: their buffers just never reuse)."""
        if self._adopt_possible is None:
            try:
                import jax
                tgt = self._placement.sharding
                devs = list(getattr(tgt, "device_set", None) or ())
                if not devs:
                    devs = [tgt if hasattr(tgt, "platform")
                            else getattr(tgt, "_device", None) or
                            jax.local_devices()[0]]
                self._adopt_possible = all(
                    getattr(d, "platform", "cpu") == "cpu" for d in devs)
            except Exception:
                self._adopt_possible = True
        return self._adopt_possible

    @staticmethod
    def _adopted(dev, buf):
        """True when the transfer ADOPTED `buf`'s memory zero-copy
        instead of copying it (the CPU backend does this for suitably
        aligned arrays, per shard).  An adopted buffer must never be
        refilled — the device array IS that memory.  Only called when
        `_may_adopt()` (np.asarray is then a zero-copy view, never a
        readback).  When aliasing cannot be disproven the buffer is
        treated as adopted (retired from reuse): correctness over
        recycling."""
        try:
            shards = getattr(dev, "addressable_shards", None) or ()
            views = [s.data for s in shards] or [dev]
            return any(_np.shares_memory(_np.asarray(v), buf)
                       for v in views)
        except Exception:
            return True

    def _assemble(self, arrays):
        """Host staging: copy (+cast) each input into this slot set's
        reusable buffer.  A changed shape/dtype (epoch-tail batch)
        reallocates that one buffer; a buffer the backend adopted
        zero-copy was retired by the previous transfer and is
        reallocated here too — on such backends the 'copyto + adopt'
        pair IS the whole h2d path (no second copy ever happens), while
        copying backends (a real TPU's DMA) keep recycling the same
        staging memory, pinned-style."""
        slot = self._slots[self._slot_i]
        self._slot_i = (self._slot_i + 1) % len(self._slots)
        staged = []
        copies = 0
        for j, a in enumerate(arrays):
            a = _np.asarray(a)
            tgt = _np.dtype(self._placement.target_dtype(j, a))
            if not self._staging:
                staged.append(a.astype(tgt) if a.dtype != tgt else a)
                continue
            buf = slot.get(j)
            if buf is None or buf.shape != a.shape or buf.dtype != tgt:
                buf = slot[j] = _np.empty(a.shape, tgt)
            _np.copyto(buf, a, casting="unsafe")
            copies += 1
            staged.append(buf)
        return staged, copies, slot

    def put(self, arrays, meta=None, token=None):
        """Stage + transfer one batch (producer thread).  Blocks while
        the queue is full (backpressure).  Returns False when the ring
        was closed under the wait — or when `token` no longer matches
        the ring's epoch (a stale feeder surviving a restart)."""
        import jax
        from .obs import trace as _trace
        with self._cond:
            self._cond.wait_for(
                lambda: self._closed or token not in (None, self._token)
                or len(self._q) < self.depth)
            if self._closed or token not in (None, self._token):
                return False
        with self._put_lock:
            t0 = time.perf_counter()
            with _trace.span("io.stage", cat="io", ring=self.name) as sp:
                staged, copies, slot = self._assemble(arrays)
                nbytes = sum(int(a.nbytes) for a in staged)
                sp.note(bytes=nbytes, copies=copies)
            t1 = time.perf_counter()
            with _trace.span("io.h2d", cat="io", ring=self.name,
                             bytes=nbytes):
                devs = self._placement.put(staged)
                # the wait lives HERE, on the io thread: the staging
                # slot is free for reuse the moment this returns, and
                # the consumer pops fully-resident arrays
                jax.block_until_ready(devs)
            t2 = time.perf_counter()
            if self._staging and self._may_adopt():
                # retire any buffer the backend adopted zero-copy: it
                # now BELONGS to the emitted device array and refilling
                # it would silently corrupt an in-flight batch
                for j, (d, b) in enumerate(zip(devs, staged)):
                    if slot.get(j) is b and self._adopted(d, b):
                        del slot[j]
                        with self._stats_lock:
                            self._stats["zero_copy"] += 1
                        _totals_add(zero_copy=1)
            dt = time.perf_counter() - t0
        with self._stats_lock:
            self._stats["batches"] += 1
            self._stats["bytes"] += nbytes
            self._stats["h2d_s"] += dt
            self._stats["stage_s"] += t1 - t0
            self._stats["put_s"] += t2 - t1
            self._stats["staging_copies"] += copies
        _totals_add(batches=1, bytes=nbytes, h2d_s=dt, stage_s=t1 - t0,
                    put_s=t2 - t1, staging_copies=copies)
        with self._cond:
            if self._closed or token not in (None, self._token):
                return False
            self._q.append((devs, meta))
            _metrics().gauge("io.ring.occupancy").set(len(self._q))
            self._cond.notify_all()
        return True

    def timed_source(self, fetch):
        """`fetch()`, the feeder's pull of one batch from its source,
        under the ``io.source`` span and the ``source_s`` counter (the
        ring never sees the source, so its feeders call through here)."""
        from .obs import trace as _trace
        t0 = time.perf_counter()
        try:
            with _trace.span("io.source", cat="io", ring=self.name):
                return fetch()
        finally:
            dt = time.perf_counter() - t0
            with self._stats_lock:
                self._stats["source_s"] += dt
            _totals_add(source_s=dt)

    def put_end(self, exc=None, token=None):
        """Mark the source exhausted (or broken): `get` drains the queue
        then surfaces the end/exception.  A stale feeder's token is
        rejected (its EOF must not truncate the restarted epoch)."""
        with self._cond:
            if token not in (None, self._token):
                return
            self._q.append(_EndOfData(exc))
            self._cond.notify_all()

    # -- consumer side -------------------------------------------------------
    def get(self):
        """Pop the oldest ready device batch as ``(device_arrays,
        meta)``; raises StopIteration at end of data — and KEEPS
        raising it on further calls (a drained ring must behave like an
        exhausted iterator, not hang waiting for a feeder that already
        exited).  An empty queue counts (and times) a stall."""
        t0 = None
        with self._cond:
            if not self._q and self._ended is not None:
                if self._ended.exc is not None:
                    raise self._ended.exc
                raise StopIteration
            if not self._q:
                t0 = time.perf_counter()
            self._cond.wait_for(lambda: self._q or self._closed)
            if not self._q and self._closed:
                raise StopIteration
            item = self._q.popleft()
            if isinstance(item, _EndOfData):
                self._ended = item
            _metrics().gauge("io.ring.occupancy").set(len(self._q))
            self._cond.notify_all()
        if t0 is not None and not isinstance(item, _EndOfData):
            # waiting for the end-of-epoch sentinel is not a pipeline
            # stall — only a wait for a REAL batch failed to overlap
            dt = time.perf_counter() - t0
            with self._stats_lock:
                self._stats["stalls"] += 1
                self._stats["stall_s"] += dt
            _totals_add(stalls=1, stall_s=dt)
        if isinstance(item, _EndOfData):
            if item.exc is not None:
                raise item.exc
            raise StopIteration
        return item

    def reopen(self):
        """Fresh epoch: clear state and return the new producer token
        (hand it to the feeder; a previous feeder's token is dead)."""
        with self._cond:
            self._closed = False
            self._ended = None
            self._q.clear()
            self._token += 1
            self._cond.notify_all()
            return self._token

    def close(self):
        with self._cond:
            self._closed = True
            self._q.clear()
            self._cond.notify_all()

    def ring_stats(self):
        with self._stats_lock:
            s = dict(self._stats)
        with self._cond:
            s["occupancy"] = sum(1 for it in self._q
                                 if not isinstance(it, _EndOfData))
        s["depth"] = self.depth
        return s


def _resolve_placement(placement):
    """Accept a RingPlacement, a callable producing one (lazy binding —
    the fused step may not exist until `init_optimizer`), or None (the
    current context's device, no cast)."""
    if callable(placement) and not isinstance(placement, RingPlacement):
        placement = placement()
    if placement is None:
        placement = RingPlacement()
    return placement


class DevicePrefetchIter(DataIter):
    """Wrap a `DataIter` with the staging ring: a named ``mx-io-h2d``
    feeder thread pulls batches from the inner iterator, stages them
    through `H2DRing`, and `next()` pops device-resident batches —
    `Module.fit` (and any consumer) never blocks on `device_put`.

    Delegation contract: `seek`/`checkpoint_state`/
    `set_checkpoint_state`/`record_range`/`set_quarantine`/
    `apply_quarantine` all route to the inner iterator (the feeder is
    paused around every such call), so elastic checkpointing, guardian
    quarantine, and shard attribution behave exactly as without the
    ring.  Read-ahead never leaks into checkpoint state: resume
    positioning is `seek(nbatch)`-based and the inner state the
    checkpoint captures is position-independent."""

    def __init__(self, data_iter, placement=None, depth=None,
                 staging=None, name="io"):
        super().__init__(getattr(data_iter, "batch_size", 0))
        self._inner = data_iter
        self._placement_src = placement
        self._ring = None
        self._thread = None
        self._stop = threading.Event()
        self._inner_lock = _alocks.make_lock("io.prefetch.inner")
        self._name = name
        self._started = False
        self._cached = None   # iter_next()'s buffered batch

    # -- delegation ----------------------------------------------------------
    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def record_range(self, nbatch):
        return self._inner.record_range(nbatch)

    def checkpoint_state(self):
        with self._inner_lock:
            return self._inner.checkpoint_state()

    def set_checkpoint_state(self, state, nbatch=0):
        self._pause()
        self._inner.set_checkpoint_state(state, nbatch)
        self._restart()

    def seek(self, nbatch):
        self._pause()
        self._inner.seek(nbatch)
        self._restart()

    def set_quarantine(self, log):
        if hasattr(self._inner, "set_quarantine"):
            self._inner.set_quarantine(log)

    def apply_quarantine(self, entries):
        if hasattr(self._inner, "apply_quarantine"):
            self._pause()
            self._inner.apply_quarantine(entries)
            self._restart()

    # -- the feeder thread ---------------------------------------------------
    def _feed(self, stop, token):
        """One epoch's producer.  EVERY failure — the inner iterator,
        staging, the transfer itself (device OOM) — lands in the ring
        as an end event so the consumer raises instead of waiting
        forever on a dead feeder.  `stop`/`token` are per-start: a
        feeder that outlived a timed-out join (wedged inner iterator)
        holds a dead token and cannot deliver stale batches or a
        premature EOF into the restarted epoch."""
        ring = self._ring
        try:
            while not stop.is_set():
                try:
                    with self._inner_lock:
                        batch = ring.timed_source(self._inner.next)
                except StopIteration:
                    ring.put_end(token=token)
                    return
                data = list(batch.data) + list(batch.label or [])
                arrays = [v._data if isinstance(v, NDArray) else
                          _np.asarray(v) for v in data]
                meta = (len(batch.data), batch.pad, batch.index,
                        batch.bucket_key)
                if not ring.put(arrays, meta, token=token):
                    return               # closed / restarted under us
        except Exception as e:           # surfaced on the consumer thread
            ring.put_end(e, token=token)

    def _start(self):
        if self._ring is None:
            self._ring = H2DRing(_resolve_placement(self._placement_src),
                                 name=self._name)
            from .obs import metrics as _m
            _m.registry().gauge("io.ring.depth").set(self._ring.depth)
        token = self._ring.reopen()
        self._stop = threading.Event()   # per-start: never shared with a
        self._cached = None              # possibly-wedged old feeder
        self._thread = threading.Thread(
            target=self._feed, args=(self._stop, token), daemon=True,
            name="mx-io-h2d")
        self._thread.start()
        self._started = True

    def _pause(self):
        """Stop the feeder and drop read-ahead (the inner iterator is
        about to be repositioned)."""
        if self._thread is None:
            self._started = False
            return
        self._stop.set()
        self._ring.close()
        from .analysis import tsan as _tsan
        _tsan.join_thread(self._thread, 10, owner=type(self).__name__)
        self._thread = None
        self._started = False

    def _restart(self):
        self._start()

    # -- DataIter surface ----------------------------------------------------
    def reset(self):
        self._pause()
        self._inner.reset()
        self._start()

    def next(self):
        cached = getattr(self, "_cached", None)
        if cached is not None:
            self._cached = None
            return cached
        if not self._started:
            self._start()
        devs, meta = self._ring.get()
        n_data, pad, index, bucket_key = meta
        from .context import current_context
        ctx = current_context()
        nds = [NDArray(d, ctx=ctx) for d in devs]
        return DataBatch(data=nds[:n_data], label=nds[n_data:] or None,
                         pad=pad, index=index, bucket_key=bucket_key,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def iter_next(self):
        """DataIter protocol: buffer the fetched batch so the paired
        `next()` returns it (not the one after)."""
        if getattr(self, "_cached", None) is not None:
            return True
        try:
            self._cached = self.next()
            return True
        except StopIteration:
            return False

    def close(self):
        self._pause()
        if self._ring is not None:
            self._ring.close()
        if hasattr(self._inner, "close"):
            try:
                self._inner.close()
            except Exception:
                pass

    def ring_stats(self):
        return self._ring.ring_stats() if self._ring is not None else {}

    def __del__(self):
        try:
            self._pause()
        except Exception:
            pass


class DevicePrefetchLoader:
    """The staging ring over a Gluon ``DataLoader``-style iterable of
    ``(data, label)`` pairs: iteration yields pairs whose arrays are
    already device-resident (NDArray-wrapped), fed by an ``mx-io-h2d``
    thread with bounded read-ahead.  `gluon.contrib.estimator.
    Estimator.fit` wraps its training loader with this when
    ``MXNET_IO_RING`` is on, so the fused Gluon step's `device_put`
    becomes an adoption of an already-placed buffer."""

    def __init__(self, loader, ctx=None, depth=None, name="io.gluon"):
        self._loader = loader
        self._ctx = ctx
        self._depth = depth
        self._name = name
        self._ring = None
        self._thread = None
        self._stop = threading.Event()

    def __len__(self):
        return len(self._loader)

    def _feed(self, it, stop, token):
        ring = self._ring
        try:
            while not stop.is_set():
                try:
                    pair = ring.timed_source(it.__next__)
                except StopIteration:
                    ring.put_end(token=token)
                    return
                arrays = [v._data if isinstance(v, NDArray) else
                          _np.asarray(v) for v in pair]
                if not ring.put(arrays, len(pair), token=token):
                    return
        except Exception as e:           # surfaced on the consumer side
            ring.put_end(e, token=token)

    def _stop_feeder(self):
        if self._thread is None:
            return
        self._stop.set()
        if self._ring is not None:
            self._ring.close()
        from .analysis import tsan as _tsan
        _tsan.join_thread(self._thread, 10, owner=type(self).__name__)
        self._thread = None

    close = _stop_feeder

    def __iter__(self):
        self._stop_feeder()
        if self._ring is None:
            device = self._ctx.jax_device if self._ctx is not None else None
            self._ring = H2DRing(RingPlacement(device=device),
                                 depth=self._depth, name=self._name)
        token = self._ring.reopen()
        self._stop = threading.Event()   # per-start (see DevicePrefetchIter)
        self._thread = threading.Thread(
            target=self._feed, args=(iter(self._loader), self._stop, token),
            daemon=True, name="mx-io-h2d")
        self._thread.start()
        ctx = self._ctx
        if ctx is None:
            from .context import current_context
            ctx = current_context()
        ring = self._ring
        def _gen():
            while True:
                try:
                    devs, _n = ring.get()
                except StopIteration:
                    return
                yield tuple(NDArray(d, ctx=ctx) for d in devs)
        return _gen()
