"""Device context abstraction.

Re-expression of the reference's `Context` (`include/mxnet/base.h:133-159`,
`python/mxnet/context.py`) for TPU: device types are {cpu, tpu} with `gpu`
kept as an alias for the accelerator so reference scripts written against
`mx.gpu()` run unmodified on TPU (`BASELINE.json` north star).  A Context maps
to a concrete `jax.Device`; NDArray buffers are committed to that device (HBM
via PJRT for tpu contexts).

`mx.tpu()` is a promise of an accelerator: with none attached it raises.
The one exception is a JAX platform set to ``cpu`` BY NAME
(``JAX_PLATFORMS=cpu`` — what `tests/conftest.py` does, with
``--xla_force_host_platform_device_count=N``): there `tpu(i)` is host
device *i*, so the multi-device and cross-backend consistency tests
(`test_utils.check_consistency`) run on the virtual CPU mesh.  A JAX that
merely *came up* on the CPU (libtpu failed to initialise) is not that.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """Device context (reference `python/mxnet/context.py:Context`).

    Parameters
    ----------
    device_type : {'cpu', 'tpu', 'gpu', 'cpu_pinned', 'cpu_shared'}
        'gpu' is accepted as an alias of 'tpu' (the accelerator).  The pinned /
        shared CPU types of the reference map to plain host memory under PJRT.
    device_id : int
    """

    # mirrors reference devtype2str / devstr2type tables
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    @property
    def is_accelerator(self):
        return self.device_type in ("gpu", "tpu")

    def __hash__(self):
        return hash((self.device_typeid if not self.is_accelerator else 2,
                     self.device_id))

    def __eq__(self, other):
        if not isinstance(other, Context):
            return False
        a = 2 if self.is_accelerator else self.device_typeid
        b = 2 if other.is_accelerator else other.device_typeid
        return a == b and self.device_id == other.device_id

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.stack.pop()

    # ---- JAX device resolution -------------------------------------------------
    @property
    def jax_device(self):
        """The concrete `jax.Device` backing this context."""
        return _resolve_device(self)

    def empty_cache(self):
        """Reference `Context.empty_cache` — PJRT owns pooling; no-op."""


def _accel_devices():
    import jax
    # LOCAL devices only: under jax.distributed, jax.devices() includes
    # other processes' (non-addressable) devices — a context must never
    # resolve to a device this process can't write
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    if devs:
        return devs
    if jax.config.jax_platforms == "cpu":
        return jax.local_devices()   # the CPU platform, chosen by name
    raise MXNetError(
        "mx.tpu()/mx.gpu() needs an accelerator and JAX found none "
        f"(default backend {jax.default_backend()!r}); set "
        "JAX_PLATFORMS=cpu to run accelerator contexts on host devices "
        "on purpose")


def _cpu_devices():
    import jax
    devs = [d for d in jax.local_devices() if d.platform == "cpu"]
    if devs:
        return devs
    # on an accelerator platform the host backend exists but is not among
    # local_devices(): ask for it by name, or cpu() would resolve to the
    # chip and every "host" array (decoded batches, staging buffers)
    # would live in HBM
    return jax.devices("cpu")


def _resolve_device(ctx):
    if not ctx.is_accelerator:
        devs = _cpu_devices()
        return devs[ctx.device_id % len(devs)]
    devs = _accel_devices()
    if not 0 <= ctx.device_id < len(devs):
        raise MXNetError(
            f"{ctx} does not exist: this process has {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs[ctx.device_id]


def cpu(device_id=0):
    """Host-memory context (reference `mx.cpu()`)."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """TPU context — the first-class accelerator (`BASELINE.json`: `mx.tpu()`)."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias for the accelerator so reference scripts run unmodified."""
    return Context("gpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_gpus():
    """Number of accelerator devices (reference `mx.context.num_gpus`)."""
    import jax
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_tpus():
    return num_gpus()


def current_context():
    """The default context (reference `python/mxnet/context.py:current_context`)."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return _default()


def _default():
    # TPU-first: if an accelerator is present, default remains cpu to match the
    # reference's semantics (mx.cpu() is the default ctx).
    return Context("cpu", 0)
