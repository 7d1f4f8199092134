"""Execution-engine semantics over JAX's async dispatch.

The reference's dependency engine (`src/engine/threaded_engine.cc`,
`include/mxnet/engine.h:116-315`) provides: (1) async op execution with
sequential consistency per variable, (2) `WaitForVar` / `WaitForAll` sync
points, (3) a serializing `NaiveEngine` debug mode, (4) bulk-execution fusion.

On TPU, XLA/PJRT already gives (1): `jax` dispatch is asynchronous and PJRT
buffer semantics preserve per-buffer ordering (read-after-write etc.), so we
do not rebuild a threaded scheduler for device compute.  What remains host-side
is bookkeeping for the sync points and the debug mode:

* every eagerly-dispatched output array is registered in a weak set so
  `waitall()` (reference `MXNDArrayWaitAll`) can block on everything in flight;
* ``MXNET_ENGINE_TYPE=NaiveEngine`` forces a block after every op, matching
  the reference's serializing debug engine (`src/engine/naive_engine.cc:50`);
* `bulk(size)` implements the reference's bulk-execution fusion
  (`include/mxnet/engine.h:308-313`) for the *host→device* direction: inside a
  bulk scope, pure creation ops (zeros/ones/initializers) stage numpy buffers
  host-side and the scope exit performs ONE batched `jax.device_put` per
  device instead of one dispatch per array: initializing a ResNet-50
  (~270 arrays) costs one transfer, not ~270 dispatches.
"""
from __future__ import annotations

import os
import weakref

from .analysis import locks as _alocks

__all__ = ["waitall", "wait_to_read", "bulk", "set_bulk_size", "engine_type",
           "bulk_active", "stage", "flush_staged"]

_lock = _alocks.make_lock("engine")
_in_flight = weakref.WeakSet()


def engine_type():
    return os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")


def _naive():
    return engine_type() == "NaiveEngine"


def track(jarr, op=None):
    """Register a dispatched jax.Array; block immediately under NaiveEngine.

    `op` names the originating operator: NaiveEngine exists to surface
    deferred errors AT the op that caused them, so its failure is chained
    into a contextful MXNetError naming that op instead of re-raising the
    bare XLA error with no attribution."""
    import jax.core as _jc
    if isinstance(jarr, _jc.Tracer):
        # abstract value inside a jax trace (fused train step / CachedOp):
        # nothing is in flight, and a leaked tracer in the wait-set would
        # outlive its trace
        return jarr
    if _naive():
        try:
            jarr.block_until_ready()
        except Exception as e:
            from .base import MXNetError
            raise MXNetError(
                f"NaiveEngine: operator '{op or '<unknown>'}' failed "
                f"during synchronous execution: {e}") from e
        return jarr
    try:
        with _lock:
            _in_flight.add(jarr)
    except TypeError:
        pass
    return jarr


def wait_to_read(jarr):
    """Block until an array's value is ready (reference `NDArray::WaitToRead`)."""
    block = getattr(jarr, "block_until_ready", None)
    if block is not None:  # host-staged numpy buffers are already "ready"
        block()


def waitall():
    """Block until all outstanding async work completes (reference
    `Engine::WaitForAll`, `mx.nd.waitall`)."""
    from .analysis import hostsync as _hostsync
    if _hostsync._active:
        _hostsync.note("waitall")
    with _lock:
        arrs = list(_in_flight)
        _in_flight.clear()
    for a in arrs:
        try:
            a.block_until_ready()
        except Exception:
            raise


_bulk_size = 0
_staging_depth = 0  # nesting depth of active bulk() scopes
_staged = []  # NDArrays whose _data is a host numpy buffer awaiting transfer
_staged_ids = set()


def set_bulk_size(size):
    """Reference `Engine::set_bulk_size` (`include/mxnet/engine.h:308-313`).

    Device-side op fusion is subsumed by whole-graph XLA compilation; the
    knob is kept for API parity.  Host-staging activates only inside the
    `bulk()` context manager (which guarantees a flush on exit).  Returns
    the previous value.
    """
    global _bulk_size
    prev, _bulk_size = _bulk_size, size
    return prev


def bulk_active():
    """True while inside a bulk scope (creation ops should host-stage)."""
    return _staging_depth > 0 and _bulk_size != 0


def stage(nd_obj):
    """Register a host-staged NDArray for the next `flush_staged()`."""
    if id(nd_obj) not in _staged_ids:
        _staged_ids.add(id(nd_obj))
        _staged.append(nd_obj)


def unstage(nd_obj):
    """Drop a staged NDArray (e.g. a scratch buffer that was copied away)."""
    if id(nd_obj) in _staged_ids:
        _staged_ids.discard(id(nd_obj))
        for i, a in enumerate(_staged):  # identity, not NDArray.__eq__
            if a is nd_obj:
                del _staged[i]
                break


def flush_staged():
    """Transfer all staged host buffers to their devices, one batched
    `jax.device_put` per target device."""
    import numpy as np
    if not _staged:
        return
    arrs = [a for a in _staged if isinstance(a._data, np.ndarray)]
    del _staged[:]
    _staged_ids.clear()
    if not arrs:
        return
    import jax
    by_dev = {}
    for a in arrs:
        by_dev.setdefault(a.context, []).append(a)
    for ctx, group in by_dev.items():
        bufs = jax.device_put([a._data for a in group], ctx.jax_device)
        for a, b in zip(group, bufs):
            a._data = b


class bulk:
    """Context manager `mx.engine.bulk(size)` (reference `python/mxnet/engine.py`).

    On exit of the outermost scope, staged host buffers are flushed to
    their devices in batched transfers.
    """

    def __init__(self, size):
        self.size = size
        self._prev = None

    def __enter__(self):
        global _staging_depth
        self._prev = set_bulk_size(self.size)
        _staging_depth += 1

    def __exit__(self, *args):
        global _staging_depth
        set_bulk_size(self._prev)
        _staging_depth -= 1
        if _staging_depth == 0:
            flush_staged()
