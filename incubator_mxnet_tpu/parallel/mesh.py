"""Device-mesh construction.

Replaces the reference's device topology machinery (`src/kvstore/
gpu_topology.h:491-782` PCIe/NVLink spanning trees): on TPU the physical
topology is the ICI torus and XLA's collective scheduler owns routing, so the
framework only chooses the *logical* mesh shape (dp/tp/pp/sp axes).
Multi-host: `jax.distributed.initialize` + `jax.devices()` spanning all hosts
gives a global mesh; DCN-vs-ICI placement follows axis order (outermost axes
land on DCN, reference scaling-book recipe).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError

DEFAULT_AXES = ("dp", "tp")


def make_mesh(shape=None, axis_names=None, devices=None):
    """Create a `jax.sharding.Mesh`.

    shape: dict axis->size (e.g. {'dp': 4, 'tp': 2}) or tuple of sizes.
    Unspecified → all devices on one 'dp' axis.
    """
    import jax
    from jax.sharding import Mesh

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        shape = {"dp": n}
    if isinstance(shape, dict):
        axis_names = tuple(shape.keys())
        sizes = tuple(shape.values())
    else:
        sizes = tuple(shape)
        axis_names = tuple(axis_names or DEFAULT_AXES[:len(sizes)])
    total = int(np.prod(sizes))
    if total != n:
        raise MXNetError(f"mesh shape {sizes} needs {total} devices, "
                         f"have {n}")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names)


# the accepted spec grammar, quoted by every parse error so a bad
# MXNET_MESH / Module.fit(mesh=) value is self-explaining
_SPEC_GRAMMAR = ("mesh spec grammar: comma-separated 'axis=size' "
                 "tokens, each axis a nonempty name and each size a "
                 "positive integer, e.g. 'dp=8' or 'dp=4,tp=2'")


def parse_spec(spec):
    """Parse a mesh spec string — ``'dp=8'``, ``'dp=4,tp=2'`` — into an
    ordered axis->size dict (the `MXNET_MESH` / ``Module.fit(mesh=)``
    currency).  Axis order is placement order: outermost axes land on
    DCN, innermost on ICI (scaling-book recipe).  A malformed spec
    raises `MXNetError` naming the offending token and the accepted
    grammar."""
    out = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise MXNetError(
                f"bad token {part!r} in mesh spec {spec!r}: missing "
                f"'='; {_SPEC_GRAMMAR}")
        k, v = part.split("=", 1)
        k, v = k.strip(), v.strip()
        if not k:
            raise MXNetError(
                f"bad token {part!r} in mesh spec {spec!r}: empty axis "
                f"name; {_SPEC_GRAMMAR}")
        try:
            size = int(v)
        except ValueError:
            raise MXNetError(
                f"bad token {part!r} in mesh spec {spec!r}: size {v!r} "
                f"is not an integer; {_SPEC_GRAMMAR}")
        if size <= 0:
            raise MXNetError(
                f"bad token {part!r} in mesh spec {spec!r}: size must "
                f"be a positive integer; {_SPEC_GRAMMAR}")
        if k in out:
            raise MXNetError(
                f"bad token {part!r} in mesh spec {spec!r}: axis {k!r} "
                f"appears twice; {_SPEC_GRAMMAR}")
        out[k] = size
    return out


def mesh_from_spec(spec=None, devices=None):
    """Build a Mesh from a spec (string or axis->size dict); with
    ``spec=None`` reads `MXNET_MESH`.  Returns None when nothing is
    configured — callers fall back to their default 1-D dp mesh."""
    if spec is None or spec == "":
        from .. import config as _config
        spec = _config.get("MXNET_MESH")
    if not spec:
        return None
    if isinstance(spec, str):
        spec = parse_spec(spec)
    if not spec:
        return None
    return make_mesh(spec, devices=devices)


def dp_axis_of(mesh):
    """The data-parallel axis of a composed mesh: 'dp' when present,
    else the first axis (the convention every consumer shares)."""
    names = tuple(mesh.axis_names)
    return "dp" if "dp" in names else names[0]


def local_mesh(n=None, axis_names=("dp",)):
    """Mesh over the first n local devices (testing convenience)."""
    import jax
    devs = jax.local_devices()
    n = n or len(devs)
    return make_mesh({axis_names[0]: n}, devices=devs[:n])


def mesh_axes(mesh):
    return tuple(mesh.axis_names)


def rebuild(axis_names=("dp",), per_host=None):
    """Rebuild the 1-axis data-parallel mesh over the CURRENT global
    device set — the shrink-and-resume step after a host loss: once the
    survivors have torn down and re-formed the process group
    (`dist.collective.shutdown()` + `init_process_group` at the smaller
    world size), `jax.devices()` spans only surviving hosts and every
    pre-shrink mesh is stale (it still holds the dead host's devices —
    dispatching on it hangs exactly like the collective being recovered
    from).  ``per_host`` optionally caps devices per process (testing
    convenience, mirrors `local_mesh`)."""
    import jax
    devices = jax.devices()
    if per_host is not None:
        by_proc = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        devices = [d for p in sorted(by_proc)
                   for d in by_proc[p][:int(per_host)]]
    return make_mesh({axis_names[0]: len(devices)}, devices=devices)


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None):
    """Multi-host bring-up (replaces ps-lite scheduler bootstrapping,
    reference `tools/launch.py` + DMLC_PS_ROOT_URI env wiring)."""
    import jax
    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
