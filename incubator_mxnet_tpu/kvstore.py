"""KVStore: data-parallel parameter synchronization.

Re-expression of `src/kvstore/` (reference: `kvstore_local.h` CPU reduce,
`comm.h` CommCPU/CommDevice P2P reduce, `kvstore_nccl.h`, ps-lite
`kvstore_dist.h`) for TPU.  The API (Init/Push/Pull/set_updater/
set_optimizer, `kvstore.py` python surface) is preserved; the transport
changes per the BASELINE north star:

* ``local``  — reduce on host (CommCPU analogue)
* ``device``/``nccl`` — reduce on the accelerator (CommDevice/NCCL analogue)
* ``tpu``   — reduce as an XLA `psum` over the ICI device mesh: pushed
  per-device shards are donated to one fused all-reduce computation
  (replaces NCCL rings / PCIe spanning trees — `gpu_topology.h` is subsumed
  by XLA's collective scheduling on the torus)
* ``dist_sync``/``dist_async``/``dist_device_sync`` — multi-host via
  `jax.distributed` when initialized (each host reduces its local devices,
  then a global collective); in single-process runs they behave as ``device``
  with dist bookkeeping (rank/num_workers), which is exactly how the
  reference's nightly tests run multi-worker on localhost.

Gradient compression (reference `gradient_compression.h:52-134` 2-bit with
error feedback) is implemented in the push path with per-key residuals.
"""
from __future__ import annotations

import pickle
import weakref

import numpy as _np

from .base import MXNetError
from .context import Context, cpu, tpu, num_gpus
from .ndarray.ndarray import NDArray
from . import optimizer as opt

__all__ = ["KVStore", "create", "live_stats", "findings"]

# live collective stores (weak): analysis.runtime_report() reads
# their stats() without holding the stores alive
_LIVE_STORES = weakref.WeakSet()


def live_stats():
    """stats() of every live collective (tpu/device) store —
    runtime_report's read path."""
    out = []
    for kv in list(_LIVE_STORES):
        try:
            out.append(kv.stats())
        except Exception:
            pass
    return out


def findings():
    """Bucketed-communication findings for `analysis.runtime_report()`:
    one HINT per live collective store summarizing its dispatch economy
    (collectives per push must be O(buckets), never O(params))."""
    from .analysis.findings import Finding, HINT
    out = []
    for st in live_stats():
        if not st.get("batched_pushes"):
            continue
        out.append(Finding(
            "kvstore.buckets", "summary", HINT,
            "kvstore='%s': %d batched pushes, %d allreduce dispatches "
            "(%.2f buckets/push, cap %d MB, avg fill %.0f%%, overlap "
            "%.0f%%), %.1f MB reduced"
            % (st["type"], st["batched_pushes"],
               st["allreduce_dispatches"],
               st["allreduce_dispatches"] / max(1, st["batched_pushes"]),
               st["bucket_cap_mb"], 100.0 * st["avg_bucket_fill"],
               100.0 * st["overlap_ratio"],
               st["bytes_reduced"] / (1 << 20)),
            location="kvstore"))
    return out


def _key(k):
    return str(k)


def plan_buckets(order, sizes, dtypes, cap_bytes):
    """THE bucket planning rule, shared by the kvstore scheduler
    (`KVStoreTPU._plan_buckets`) and the fused step's in-graph pod
    exchange (`fused._pod_bucket_psum`): pack the indices in `order`
    (already priority-sorted) into size-capped single-dtype buckets; an
    item larger than the cap gets a bucket of its own.  Deterministic —
    a pure function of (order, sizes, dtypes, cap), so two identical
    runs cut identical bucket boundaries, and the in-graph plan can
    never drift from the kvstore plan."""
    buckets, cur, cur_bytes, cur_dtype = [], [], 0, None
    for i in order:
        nb = sizes[i]
        if cur and (cur_bytes + nb > cap_bytes or dtypes[i] != cur_dtype):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_dtype = dtypes[i]
    if cur:
        buckets.append(cur)
    return buckets


def _split_closure(shapes):
    """The flatten-concat inverse: a closure slicing a 1-D bucket
    payload back into `shapes` (shared by the reduce and pull split
    programs, which differ only in their jit wrapper)."""
    import jax
    sizes = [int(_np.prod(s)) if s else 1 for s in shapes]
    offs = _np.cumsum([0] + sizes)

    def _split(buf, shapes=shapes, offs=offs, sizes=sizes):
        return tuple(
            jax.lax.dynamic_slice_in_dim(
                buf, int(offs[k]), sizes[k]).reshape(shapes[k])
            for k in range(len(shapes)))
    return _split


class KVStore:
    """Single-process key-value store (reference `include/mxnet/kvstore.h:59-310`)."""

    def __init__(self, kind="local"):
        self._kind = kind
        self._store = {}        # key -> NDArray (on store device)
        self._updater = None
        self._optimizer = None
        self._compression = None
        self._residuals = {}
        if kind in ("device", "nccl", "tpu") and num_gpus() > 0:
            self._store_ctx = tpu(0)
        else:
            self._store_ctx = cpu(0)

    # -- identity ------------------------------------------------------------
    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        return self._dist_rank() if "dist" in self._kind else 0

    @property
    def num_workers(self):
        return self._dist_size() if "dist" in self._kind else 1

    @staticmethod
    def _dist_rank():
        import jax
        try:
            return jax.process_index()
        except Exception:
            return 0

    @staticmethod
    def _dist_size():
        import jax
        try:
            return jax.process_count()
        except Exception:
            return 1

    # -- init/push/pull --------------------------------------------------------
    def init(self, key, value):
        """Reference `kvstore.py init`."""
        keys, values = _normalize(key, value)
        for k, v in zip(keys, values):
            if _key(k) in self._store:
                raise MXNetError(f"Key {k} already initialized")
            self._store[_key(k)] = v.copyto(self._store_ctx)

    def _commit(self, k, merged):
        """Apply a reduced value to the store: updater when installed,
        else overwrite (shared by per-key and batched push paths)."""
        sk = _key(k)
        if self._updater is not None:
            self._updater(_updater_key(k), merged, self._store[sk])
        else:
            self._store[sk]._set_data(
                merged.copyto(self._store_ctx)._data.astype(
                    self._store[sk].dtype))

    def push(self, key, value, priority=0):
        """Push values; multi-device lists are reduced (summed) first
        (reference `kvstore_local.h:184 PushImpl` → `comm.h Reduce`)."""
        keys, values = _normalize_push(key, value)
        for k, vals in zip(keys, values):
            sk = _key(k)
            if sk not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
            merged = self._reduce(vals)
            if self._compression is not None:
                merged = self._compress(sk, merged)
            self._commit(k, merged)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Broadcast stored value to out arrays (reference `comm.h:209 Broadcast`)."""
        if out is None:
            raise MXNetError("pull requires out=")
        keys, outs = _normalize_push(key, out)
        for k, tgt_list in zip(keys, outs):
            sk = _key(k)
            if sk not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
            src = self._store[sk]
            for tgt in tgt_list:
                src.copyto(tgt)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows in row_ids (reference `PullRowSparse`,
        `kvstore.py:314`).  Host-side gather (sparse is host-resident, see
        ndarray/sparse.py design note)."""
        if out is None or row_ids is None:
            raise MXNetError("row_sparse_pull requires out= and row_ids=")
        keys, outs = _normalize_push(key, out)
        if isinstance(row_ids, NDArray):
            row_ids = [row_ids]
        for k, tgt_list in zip(keys, outs):
            src = self._store[_key(k)]
            for tgt, rids in zip(tgt_list, row_ids * len(tgt_list)):
                rows = rids.asnumpy().astype("int64")
                vals = src.asnumpy()[rows]
                from .ndarray.sparse import RowSparseNDArray
                if isinstance(tgt, RowSparseNDArray):
                    tgt._np_data = vals
                    tgt._np_indices = rows
                else:
                    full = _np.zeros(src.shape, vals.dtype)
                    full[rows] = vals
                    tgt._set_data(tgt._data * 0 + full)

    def embedding(self, name, num_rows, dim, **kwargs):
        """A `embedding.ShardedEmbedding` table hosted on this store's
        parameter servers (dist stores only: the table's row shards live
        in the server processes, never densely on a worker).  Local
        stores have no server plane to shard onto."""
        raise MXNetError(
            f"kvstore type {self.type!r} has no parameter-server plane "
            "to host a sharded embedding — create the table against a "
            "'dist_async'/'dist_sync' store, or pass explicit server "
            "addresses to embedding.ShardedEmbedding")

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out=out, priority=priority)

    # -- reduction backends -----------------------------------------------------
    def _reduce(self, vals):
        if len(vals) == 1:
            return vals[0]
        import jax
        import jax.numpy as jnp
        if self._kind == "local":
            dev = cpu(0).jax_device
        else:
            dev = vals[0].context.jax_device
        acc = jax.device_put(vals[0]._data, dev)
        for v in vals[1:]:
            acc = acc + jax.device_put(v._data, dev)
        return NDArray(acc, ctx=vals[0].context if self._kind != "local" else cpu(0))

    # -- gradient compression ----------------------------------------------------
    def set_gradient_compression(self, compression_params):
        """2-bit compression with error feedback (reference
        `gradient_compression.h:52-134`).  None/empty clears it."""
        if not compression_params:
            self._compression = None
            self._residuals = {}
            return
        ctype = compression_params.get("type", "2bit")
        if ctype != "2bit":
            raise MXNetError("only 2bit gradient compression is supported "
                             "(as the reference)")
        self._compression = {
            "type": ctype,
            "threshold": float(compression_params.get("threshold", 0.5)),
        }

    def _compress(self, sk, merged):
        import jax
        import jax.numpy as jnp
        thr = self._compression["threshold"]
        resid = self._residuals.get(sk)
        g = merged._data
        if resid is not None:
            # the residual may have been written by the bucketed path on
            # a different device; device_put is a no-op when co-located
            if hasattr(resid, "devices") and hasattr(g, "devices") and \
                    resid.devices() != g.devices():
                resid = jax.device_put(resid, next(iter(g.devices())))
            g = g + resid
        q = jnp.where(g >= thr, thr, jnp.where(g <= -thr, -thr, 0.0)).astype(g.dtype)
        self._residuals[sk] = g - q
        return NDArray(q, ctx=merged.context)

    # -- optimizer integration ----------------------------------------------------
    def set_updater(self, updater):
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """Reference `kvstore.py set_optimizer`: in dist mode the reference
        pickles the optimizer to the servers; here the updater runs in-process
        on the reducing device."""
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    # -- server-state (de)serialization parity ------------------------------------
    def get_optimizer_states(self, dump_optimizer=False):
        """Optimizer slots as one bytes blob (the checkpoint plane's
        capture point; `dist/kvstore_dist.py` overrides to pull state back
        from the parameter servers)."""
        if self._updater is None:
            raise MXNetError("Cannot save states for distributed training")
        return self._updater.get_states(dump_optimizer)

    def set_optimizer_states(self, blob):
        if self._updater is None:
            raise MXNetError("Cannot load states for distributed training")
        self._updater.set_states(blob)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        with open(fname, "wb") as f:
            f.write(self.get_optimizer_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            self.set_optimizer_states(f.read())

    def _barrier(self):
        """Single-process stores have nothing to synchronize: engine order
        already serializes per-buffer access (WaitToRead semantics).  The
        distributed subclasses override this with a REAL rendezvous
        (`dist/kvstore_dist.py`); a single-process store is never a valid
        stand-in for one — assert loudly if someone treats it as such."""
        if self.num_workers != 1:
            raise MXNetError(
                f"{type(self).__name__} reports num_workers="
                f"{self.num_workers} but has no distributed barrier — use "
                "kv.create('dist_sync'/'dist_async')")

    def _send_command_to_servers(self, head, body):
        """No server processes exist for single-process stores; commands
        are meaningful only on the dist subclasses (which override)."""
        if self.num_workers != 1:
            raise MXNetError(
                "no servers to command on a single-process kvstore")


def _updater_key(k):
    return int(k) if isinstance(k, int) or (isinstance(k, str) and
                                            k.isdigit()) else k


class KVStoreTPU(KVStore):
    """`kvstore='tpu'` — push/pull as bucketed all-reduce over the device
    mesh (BASELINE north star; replaces `comm.h:451 CommDevice` /
    `kvstore_nccl.h:285-402`, bucket scheduling per the MLPerf-pods
    recipe: size-capped buckets, last-produced gradients first).

    Push: a multi-key push is packed into size-capped buckets
    (``MXNET_KVSTORE_BUCKET_MB``) in PRIORITY order — reversed key order,
    because backward materializes the LAST layer's gradients first — and
    each bucket's flatten+concat + `shard_map(psum)` + split programs are
    dispatched asynchronously as the bucket fills: bucket k's collective
    executes on the devices while the host is still assembling bucket
    k+1 (the dependency-engine overlap re-expressed as async XLA
    dispatch).  All three programs per bucket signature are compiled
    through the unified program cache, so steady state never recompiles.
    `push_part`/`end_push` expose the same machinery as a streaming API
    for callers whose gradients materialize one at a time.

    Pull: the stored values are broadcast with one `device_put` per
    bucket onto a replicated `NamedSharding` over the same mesh (XLA's
    broadcast collective), and each target takes its local shard — again
    O(buckets) collectives rather than N point-to-point copies.

    2-bit gradient compression composes with bucketing: the quantize
    (pack) + error-feedback residual update runs INSIDE the bucket
    program on the reduced payload, elementwise-identical to the
    reference's per-key path (`gradient_compression.h:52-134`).
    """

    def __init__(self, kind="tpu"):
        super().__init__(kind)
        self._meshes = {}        # tuple(device ids) -> Mesh
        self._allreduce_jit = {}  # tuple(device ids) -> jitted shard_map psum
        # last mesh a key was pushed over; lets pull() reuse the same devices
        self._key_mesh = {}
        self._concat_jit = None  # lazy shared flatten+concat program
        self._split_jit = {}     # (device ids, shapes) -> split program
        self._quant_jit = None   # 2-bit quantize+residual program
        self._stream = None      # pending streaming-push state
        self._last_bucket_out = None   # overlap probe (is_ready)
        self.allreduce_dispatches = 0   # tests assert one per step
        self._counters = {
            "pushes": 0, "batched_pushes": 0, "bytes_reduced": 0,
            "buckets": 0, "fill_sum": 0.0, "overlap_hits": 0,
            "overlap_eligible": 0, "pull_broadcasts": 0,
            "fallback_reduces": 0,
        }
        self._fill_hist = [0, 0, 0, 0]   # fill quartiles (<=25..<=100%)
        _LIVE_STORES.add(self)
        # telemetry plane: the communication-economy counters under the
        # stable 'kvstore' namespace (weakly held; the newest live
        # store answers scrapes)
        from .obs import metrics as _obs_metrics
        _obs_metrics.register_producer("kvstore", self.stats)

    @property
    def _bucket_cap_bytes(self):
        from . import config as _config
        # fractional MB are honored (tests force multi-bucket plans on
        # KB-sized tensors); floor of 1 byte keeps the planner sane
        return max(1, int(float(_config.get("MXNET_KVSTORE_BUCKET_MB"))
                          * (1 << 20)))

    @property
    def _overlap_enabled(self):
        from . import config as _config
        return bool(_config.get("MXNET_KVSTORE_OVERLAP"))

    def stats(self):
        """Communication-economy counters of this store: allreduce
        dispatches, bytes reduced, bucket count/fill, overlap ratio —
        surfaced through `analysis.runtime_report()` and held to the
        static plan byte for byte in tests/test_scaling.py."""
        self._release_guard()
        c = self._counters
        return {
            "type": self._kind,
            "pushes": c["pushes"],
            "batched_pushes": c["batched_pushes"],
            "allreduce_dispatches": self.allreduce_dispatches,
            "bytes_reduced": c["bytes_reduced"],
            "buckets": c["buckets"],
            "bucket_cap_mb": self._bucket_cap_bytes / (1 << 20),
            "bucket_fill_hist": {
                "<=25%": self._fill_hist[0], "<=50%": self._fill_hist[1],
                "<=75%": self._fill_hist[2], "<=100%": self._fill_hist[3]},
            "avg_bucket_fill": c["fill_sum"] / max(1, c["buckets"]),
            "overlap_ratio": c["overlap_hits"] / max(1,
                                                     c["overlap_eligible"]),
            "pull_broadcasts": c["pull_broadcasts"],
            "fallback_reduces": c["fallback_reduces"],
            "compression": None if self._compression is None
            else dict(self._compression),
        }

    def predicted_stats(self, shapes, dtypes=None, ndev=None):
        """Static mirror of one batched push's `stats()` counters —
        the plan-introspection hook the mxcost analyzer cross-checks
        against measured numbers: given the key shapes (and dtypes) a
        batched push would carry, derive the bucket plan with the SAME
        `plan_buckets` rule and priority order the scheduler uses and
        return the predicted allreduce dispatches / bytes reduced /
        bucket count.  `analysis.cost.enumerate_collectives` does the
        arithmetic; this method just binds this store's live bucket cap
        and device count to it."""
        from .analysis import cost as _cost
        if ndev is None:
            import jax
            ndev = len(jax.devices())
        stats = _cost.enumerate_collectives(
            shapes, dtypes=dtypes, dp=ndev,
            cap_bytes=self._bucket_cap_bytes,
            name=f"kvstore-{self._kind}")
        return {
            "type": self._kind,
            "allreduce_dispatches": stats["collectives_per_step"],
            "bytes_reduced": stats["bytes_per_step"],
            "buckets": stats["buckets"],
            "bucket_cap_mb": stats["bucket_cap_mb"],
            "dispatch_complexity": stats["dispatch_complexity"],
            "plan": stats["plan"],
        }

    def _mesh_for(self, devices):
        ids = tuple(d.id for d in devices)
        mesh = self._meshes.get(ids)
        if mesh is None:
            import numpy as np
            from jax.sharding import Mesh
            mesh = Mesh(np.asarray(devices), ("dev",))
            self._meshes[ids] = mesh
        return mesh

    def _allreduce(self, mesh):
        """One jitted all-reduce over the mesh: (N, *s) sharded on 'dev'
        → summed (*s), replicated on every participating device."""
        ids = tuple(d.id for d in mesh.devices.flat)
        fn = self._allreduce_jit.get(ids)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as P

            def _psum(shards):           # shards: (1, *s) local block
                return jax.lax.psum(shards[0], "dev")

            fn = jax.jit(jax.shard_map(_psum, mesh=mesh,
                                       in_specs=P("dev"), out_specs=P()))
            self._allreduce_jit[ids] = fn
        return fn

    def _reduce(self, vals):
        if len(vals) == 1:
            return vals[0]
        import jax
        devices = [v.context.jax_device for v in vals]
        if len({d.id for d in devices}) != len(devices):
            # duplicate devices (e.g. all values on one chip): plain sum
            acc = vals[0]._data
            for v in vals[1:]:
                acc = acc + jax.device_put(v._data, devices[0])
            return NDArray(acc, ctx=vals[0].context)
        mesh = self._mesh_for(devices)
        shape = tuple(vals[0].shape)
        return NDArray(
            self._mesh_allreduce(mesh, shape,
                                 [v._data for v in vals],
                                 vals[0].context.jax_device.id),
            ctx=vals[0].context)

    def _mesh_allreduce(self, mesh, shape, shards, lead_id):
        """Assemble per-device shards into one mesh array, psum with ONE
        collective, return the lead device's replicated shard (downstream
        single-device math sees an ordinary committed array; the pull path
        re-broadcasts with one collective)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        global_arr = jax.make_array_from_single_device_arrays(
            (len(shards),) + shape, NamedSharding(mesh, P("dev")),
            [b.reshape((1,) + shape) for b in shards])
        self.allreduce_dispatches += 1
        summed = self._allreduce(mesh)(global_arr)
        return next(s.data for s in summed.addressable_shards
                    if s.device.id == lead_id)

    def _record_key_mesh(self, sk, vals):
        """Remember the device set a key was pushed over so pull() can use
        the one-collective broadcast instead of per-target copies."""
        if len(vals) > 1:
            devs = [v.context.jax_device for v in vals]
            if len({d.id for d in devs}) == len(devs):
                self._key_mesh[sk] = self._mesh_for(devs)

    @property
    def prefers_batched_push(self):
        """Multi-key push/pull should arrive as one call: the whole key
        list reduces in O(buckets) collectives (`_reduce_many`) instead
        of one per parameter (the reference's batched NCCL push,
        `model.py:125`)."""
        return True

    # -- bucket planning -------------------------------------------------------
    @staticmethod
    def _nbytes(v):
        size = int(_np.prod(v.shape)) if v.shape else 1
        return size * _np.dtype(v.dtype).itemsize

    def _plan_buckets(self, order, values):
        """Pack the key indices in `order` (already priority-sorted:
        batched pushes reverse the key list because backward materializes
        the LAST layer's gradients first; streaming pushes arrive in
        production order) into size-capped single-dtype buckets.  A key
        larger than the cap gets a bucket of its own.  Deterministic:
        the plan is a pure function of (order, shapes, dtypes, cap), so
        two identical runs cut identical bucket boundaries."""
        return plan_buckets(
            order, [self._nbytes(v[0]) for v in values],
            [v[0].dtype for v in values], self._bucket_cap_bytes)

    # -- cached bucket programs ------------------------------------------------
    def _concat_prog(self, dev_id=None):
        if self._concat_jit is None:
            self._concat_jit = {}
        prog = self._concat_jit.get(dev_id)
        if prog is None:
            import jax.numpy as jnp
            from .compile import cached_jit
            # one shape-agnostic program PER DEVICE (an AOT executable
            # validates the input placement, so each device's flatten+
            # concat is its own cache entry); the per-signature cache
            # specializes per bucket signature (unified program cache —
            # steady state never recompiles)
            self._concat_jit[dev_id] = prog = cached_jit(
                lambda *xs: jnp.concatenate([x.reshape(-1) for x in xs]),
                graph_key=("kvstore-concat", dev_id),
                label="kvstore/concat")
        return prog

    def _split_prog(self, ids0, shapes):
        from .compile import cached_jit
        split = self._split_jit.get((ids0, shapes))
        if split is None:
            split = cached_jit(_split_closure(shapes),
                              graph_key=("kvstore-split", ids0, shapes),
                              label="kvstore/split")
            self._split_jit[(ids0, shapes)] = split
        return split

    def _pull_split(self, shapes):
        """Split program for the pull broadcast's per-device local
        shards: plain jit (its cache keys on the committed device, so
        the SAME shapes on 8 devices are 8 silent specializations —
        an AOT entry would reject 7 of them)."""
        import jax
        split = self._split_jit.get(("pull", shapes))
        if split is None:
            split = jax.jit(_split_closure(shapes))
            self._split_jit[("pull", shapes)] = split
        return split

    def _quant_prog(self):
        """2-bit quantize + error-feedback residual as ONE program on the
        reduced bucket payload (reference `gradient_compression.h:52-134`
        — elementwise, so the bucketed result is bit-identical to the
        per-key path).  The threshold rides as a traced scalar so
        changing it never recompiles."""
        if self._quant_jit is None:
            import jax.numpy as jnp
            from .compile import cached_jit

            def quant(g, resid, thr):
                t = jnp.asarray(thr, g.dtype)
                x = g + resid
                q = jnp.where(x >= t, t,
                              jnp.where(x <= -t, -t,
                                        jnp.zeros((), g.dtype)))
                return q, x - q
            self._quant_jit = cached_jit(quant,
                                         graph_key=("kvstore-2bit",),
                                         label="kvstore/2bit")
        return self._quant_jit

    # -- bucketed reduce -------------------------------------------------------
    def _reduce_bucket(self, idxs, keys, values, mesh, lead_id, ids0):
        """Reduce one bucket: per-device flatten+concat, ONE psum over
        the mesh, optional in-bucket 2-bit quantize, split back.  Every
        program dispatch here is ASYNC — the collective executes while
        the host assembles the next bucket (the overlap probe counts how
        often that actually happened, without ever blocking)."""
        import jax
        ndev = len(values[idxs[0]])
        shapes = tuple(tuple(values[i][0].shape) for i in idxs)
        dtype = values[idxs[0]][0].dtype
        total = int(sum(int(_np.prod(s)) if s else 1 for s in shapes))
        per_dev = [
            self._concat_prog(ids0[d])(*[values[i][d]._data for i in idxs])
            for d in range(ndev)]
        prev = self._last_bucket_out
        if prev is not None:
            self._counters["overlap_eligible"] += 1
            try:
                if not prev.is_ready():
                    self._counters["overlap_hits"] += 1
            except Exception:
                pass
            if mesh.devices.flat[0].platform == "cpu":
                # depth-1 collective pipeline on CPU hosts: XLA-CPU
                # collectives rendezvous on HOST threads, so two
                # all-reduce rounds in flight can interleave their
                # participants across a core-limited pool and deadlock
                # (each round holding threads the other needs).  Bucket
                # k+1's assembly above still overlapped bucket k's
                # collective; we just never keep TWO collectives queued.
                # On TPU the collective runs on device hardware and the
                # full pipeline depth stays async.
                jax.block_until_ready(prev)
        local = self._mesh_allreduce(mesh, (total,), per_dev, lead_id)
        nbytes = total * _np.dtype(dtype).itemsize
        self._counters["bytes_reduced"] += nbytes
        self._counters["buckets"] += 1
        fill = min(1.0, nbytes / self._bucket_cap_bytes)
        self._counters["fill_sum"] += fill
        self._fill_hist[min(3, max(0, int(_np.ceil(fill * 4)) - 1))] += 1
        if self._compression is not None:
            # the error-feedback residual lives PER KEY in the same
            # `_residuals` map the per-key fallback path uses (quantize
            # is elementwise, so the bucket residual is exactly the
            # concat of per-key residuals) — a mid-run switch between
            # the bucketed and fallback reduce paths keeps every key's
            # accumulated quantization error intact
            import jax.numpy as jnp
            dev = next(iter(local.devices()))
            parts = []
            for i, s in zip(idxs, shapes):
                r = self._residuals.get(_key(keys[i]))
                if r is None:
                    n = int(_np.prod(s)) if s else 1
                    parts.append(jnp.zeros((n,), dtype))
                else:
                    parts.append(jax.device_put(r, dev).reshape(-1))
            resid = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            thr = _np.asarray(self._compression["threshold"], dtype)
            local, new_resid = self._quant_prog()(local, resid, thr)
            for i, p in zip(idxs, self._pull_split(shapes)(new_resid)):
                self._residuals[_key(keys[i])] = p
        self._last_bucket_out = local
        if not self._overlap_enabled:
            jax.block_until_ready(local)
        pieces = self._split_prog(ids0, shapes)(local)
        ctx0 = values[idxs[0]][0].context
        return {i: NDArray(p, ctx=ctx0) for i, p in zip(idxs, pieces)}

    def _bucket_eligible(self, values):
        first_devs = [v.context.jax_device for v in values[0]]
        ids0 = tuple(d.id for d in first_devs)
        same = all(tuple(v.context.jax_device.id for v in vals) == ids0
                   for vals in values)
        if not same or len(first_devs) == 1 or len(set(ids0)) != len(ids0):
            return None
        return first_devs, ids0

    def _reduce_ordered(self, order, keys, values):
        """Bucketed reduce of `values` in the given priority order;
        returns merged NDArrays aligned with `keys`.  Falls back to
        per-key reduction (with per-key compression) when the key list
        does not share one clean device mesh."""
        placed = self._bucket_eligible(values)
        if placed is None:
            self._counters["fallback_reduces"] += 1
            return [self._reduce_compress(keys[k], vals)
                    for k, vals in enumerate(values)]
        first_devs, ids0 = placed
        mesh = self._mesh_for(first_devs)
        self._counters["batched_pushes"] += 1
        results = {}
        # NOTE: _last_bucket_out deliberately carries over from the
        # previous push — the depth-1 CPU collective pipeline guard in
        # _reduce_bucket must also cover back-to-back pushes (push k's
        # final collective may still be in flight when push k+1
        # dispatches its first bucket)
        bytes_before = self._counters["bytes_reduced"]
        plan = self._plan_buckets(order, values)
        for bucket in plan:
            results.update(self._reduce_bucket(
                bucket, keys, values, mesh, first_devs[0].id, ids0))
        from . import profiler as _profiler
        _profiler.record_kvstore(
            "bucketed_push", keys=len(keys), buckets=len(plan),
            bytes=self._counters["bytes_reduced"] - bytes_before)
        return [results[i] for i in range(len(values))]

    def _reduce_compress(self, k, vals):
        merged = self._reduce(vals)
        if self._compression is not None:
            merged = self._compress(_key(k), merged)
        return merged

    def _reduce_many(self, values, keys=None):
        """Bucketed multi-key reduce (batched push): priority order is
        REVERSED key order — backward produces the last layer's
        gradients first, so their buckets dispatch first."""
        keys = list(keys) if keys is not None else list(range(len(values)))
        return self._reduce_ordered(list(reversed(range(len(values)))),
                                    keys, values)

    # -- streaming push: dispatch buckets as gradients materialize ------------
    def begin_push(self):
        """Open a streaming push: gradients arrive one key at a time
        (`push_part`) in production order as backward materializes them;
        every time the pending set reaches the bucket cap its reduce
        dispatches IMMEDIATELY, overlapping the rest of backward.
        `end_push` flushes the tail and closes the stream."""
        if self._stream is not None:
            raise MXNetError("begin_push: a streaming push is already open")
        self._stream = {"keys": [], "values": [], "bytes": 0}
        # _last_bucket_out carries over (see _reduce_ordered): the CPU
        # depth-1 pipeline guard spans push boundaries too

    def push_part(self, key, value, priority=0):
        """Add one (or more) keys' per-device gradients to the open
        streaming push; dispatches a bucket when the cap fills."""
        st = self._stream
        if st is None:
            raise MXNetError("push_part outside begin_push/end_push")
        keys, values = _normalize_push(key, value)
        for k, vals in zip(keys, values):
            sk = _key(k)
            if sk not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
            self._record_key_mesh(sk, vals)
            st["keys"].append(k)
            st["values"].append(vals)
            st["bytes"] += self._nbytes(vals[0])
        if st["bytes"] >= self._bucket_cap_bytes:
            self._flush_stream()

    def _flush_stream(self):
        st = self._stream
        keys, values = st["keys"], st["values"]
        if not keys:
            return
        st["keys"], st["values"], st["bytes"] = [], [], 0
        if all(len(vals) > 1 for vals in values):
            merged = self._reduce_ordered(list(range(len(keys))), keys,
                                          values)
        else:
            merged = [self._reduce_compress(k, vals)
                      for k, vals in zip(keys, values)]
        for k, m in zip(keys, merged):
            self._commit(k, m)

    def end_push(self):
        """Flush the pending tail of a streaming push and close it."""
        if self._stream is None:
            raise MXNetError("end_push without begin_push")
        try:
            self._flush_stream()
        finally:
            self._stream = None

    def push(self, key, value, priority=0):
        keys, values = _normalize_push(key, value)
        self._counters["pushes"] += 1
        for k, vals in zip(keys, values):
            self._record_key_mesh(_key(k), vals)
        if len(keys) > 1 and all(len(vals) > 1 for vals in values):
            for k in keys:
                if _key(k) not in self._store:
                    raise MXNetError(f"Key {k} has not been initialized")
            from .obs import trace as _obs_trace
            with _obs_trace.span("kvstore.push", cat="kvstore",
                                 keys=len(keys)):
                merged = self._reduce_many(values, keys)
                for k, m in zip(keys, merged):
                    self._commit(k, m)
            return
        super().push(key, value, priority)

    def set_gradient_compression(self, compression_params):
        """2-bit compression on the collective store COMPOSES with
        bucketing (quantize + error-feedback residual inside the bucket
        program); anything else is a structured unsupported error — the
        base-class stub would otherwise half-apply it silently.
        None/empty clears compression (handled by the base class)."""
        if not compression_params:
            return super().set_gradient_compression(compression_params)
        ctype = compression_params.get("type", "2bit")
        if ctype != "2bit":
            raise MXNetError(
                f"kvstore='{self._kind}': gradient compression type "
                f"{ctype!r} is unsupported on the collective store — only "
                "'2bit' (in-bucket quantize with error feedback) composes "
                "with bucketed all-reduce")
        super().set_gradient_compression(compression_params)

    def _release_guard(self):
        """Drop the pipeline-guard reference once its collective has
        finished: a completed bucket can never be the second-in-flight
        collective the depth-1 CPU guard exists to prevent, and holding
        it longer pins a bucket-sized device buffer for no reason."""
        prev = self._last_bucket_out
        if prev is not None:
            try:
                if prev.is_ready():
                    self._last_bucket_out = None
            except Exception:
                self._last_bucket_out = None

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if out is None:
            raise MXNetError("pull requires out=")
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._release_guard()
        keys, outs = _normalize_push(key, out)
        for k in keys:
            if _key(k) not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
        # bucketed broadcast: the multi-key pull mirroring a bucketed
        # push rides O(buckets) broadcast collectives (concat the stored
        # values, ONE device_put onto the replicated mesh sharding per
        # bucket, split each device's local shard) instead of one
        # transfer per key
        remaining = list(range(len(keys)))
        if len(keys) > 1:
            remaining = self._pull_buckets(keys, outs)
        for i in remaining:
            k, tgt_list = keys[i], outs[i]
            sk = _key(k)
            src = self._store[sk]
            mesh = self._key_mesh.get(sk)
            tgt_devs = {t.context.jax_device.id for t in tgt_list}
            mesh_devs = ({d.id for d in mesh.devices.flat}
                         if mesh is not None else set())
            if mesh is not None and len(tgt_list) > 1 and \
                    tgt_devs <= mesh_devs:
                # one broadcast collective over the mesh, then local shards
                rep = jax.device_put(src._data, NamedSharding(mesh, P()))
                self._counters["pull_broadcasts"] += 1
                by_dev = {s.device.id: s.data for s in rep.addressable_shards}
                for tgt in tgt_list:
                    tgt._set_data(by_dev[tgt.context.jax_device.id]
                                  .astype(tgt.dtype))
            else:
                for tgt in tgt_list:
                    src.copyto(tgt)

    def _pull_buckets(self, keys, outs):
        """Broadcast every eligible key in size-capped buckets; returns
        the indices the caller must still pull per-key.  Eligible: >1
        targets, every key on ONE shared recorded mesh, targets within
        it, store values and targets dtype-consistent per bucket."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        cand = {}   # key index -> its recorded mesh
        for i, (k, tgt_list) in enumerate(zip(keys, outs)):
            m = self._key_mesh.get(_key(k))
            if m is not None and len(tgt_list) >= 2:
                cand[i] = m
        if not cand:
            return list(range(len(keys)))
        # the MAJORITY mesh keeps the O(buckets) economy even when one
        # leading key was recorded on a different (minority) mesh — that
        # key just falls to the per-key path below
        counts = {}
        for m in cand.values():
            counts[id(m)] = counts.get(id(m), 0) + 1
        mesh = max(cand.values(), key=lambda m: counts[id(m)])
        mesh_devs = {d.id for d in mesh.devices.flat}
        elig = []
        for i, m in cand.items():
            sk = _key(keys[i])
            if m is mesh and \
                    {t.context.jax_device.id for t in outs[i]} <= \
                    mesh_devs and \
                    all(t.dtype == self._store[sk].dtype
                        for t in outs[i]):
                elig.append(i)
        if len(elig) < 2:
            return list(range(len(keys)))
        values = [[self._store[_key(keys[i])]] for i in elig]
        cat = self._concat_prog(self._store_ctx.jax_device.id)
        rep_sharding = NamedSharding(mesh, P())
        for bucket in self._plan_buckets(range(len(elig)), values):
            idxs = [elig[j] for j in bucket]
            shapes = tuple(tuple(self._store[_key(keys[i])].shape)
                           for i in idxs)
            buf = cat(*[self._store[_key(keys[i])]._data for i in idxs])
            rep = jax.device_put(buf, rep_sharding)
            self._counters["pull_broadcasts"] += 1
            split = self._pull_split(shapes)
            by_dev = {s.device.id: split(s.data)
                      for s in rep.addressable_shards}
            for j, i in enumerate(idxs):
                for tgt in outs[i]:
                    tgt._set_data(
                        by_dev[tgt.context.jax_device.id][j])
        return [i for i in range(len(keys)) if i not in set(elig)]


def _normalize(key, value):
    if isinstance(key, (int, str)):
        keys = [key]
        values = [value if isinstance(value, NDArray) else value]
    else:
        keys = list(key)
        values = list(value)
    return keys, values


def _normalize_push(key, value):
    """Returns keys + list-of-lists of arrays."""
    if isinstance(key, (int, str)):
        if isinstance(value, NDArray):
            return [key], [[value]]
        if isinstance(value, (list, tuple)) and value and isinstance(
                value[0], NDArray):
            return [key], [list(value)]
        raise MXNetError("invalid push/pull value")
    keys = list(key)
    out = []
    for v in value:
        if isinstance(v, NDArray):
            out.append([v])
        else:
            out.append(list(v))
    return keys, out


def create(name="local"):
    """Factory (reference `src/kvstore/kvstore.cc:48-64` type dispatch)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if name == "tpu":
        return KVStoreTPU()
    if name in ("device", "nccl", "local_allreduce_device"):
        # device-side reduce: same single-collective engine as 'tpu'
        # (reference comm.h CommDevice / kvstore_nccl.h both lower to one
        # all-reduce; so do we)
        return KVStoreTPU("device")
    if name in ("local", "local_allreduce_cpu"):
        return KVStore("local")
    if name in ("dist_sync", "dist_async", "dist_device_sync",
                "dist_sync_device", "dist"):
        import os
        role = os.environ.get("DMLC_ROLE")
        if role == "server":
            # the reference runs the same user script on server hosts; the
            # process becomes the server and never returns to user code
            # (python/mxnet/kvstore_server.py _init_kvstore_server_module).
            # Constraint vs the reference: one server, colocated with the
            # root URI host (gradient traffic rides the TPU mesh, the
            # server is control-plane only).
            import sys
            from .dist.server import ParameterServer
            ParameterServer(
                host=os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
                port=int(os.environ.get("DMLC_PS_ROOT_PORT", 9091)),
            ).serve_forever()
            sys.exit(0)
        if role == "scheduler":
            # no scheduler in this architecture (no rendezvous needed: the
            # single server's address is static); exit cleanly so external
            # trackers that spawn one are satisfied
            import sys
            sys.exit(0)
        if os.environ.get("DMLC_PS_ROOT_URI") or role == "worker":
            from .dist.kvstore_dist import KVStoreDist
            return KVStoreDist(name)
        # no tracker env: single-process stand-in with dist bookkeeping
        # (how the reference's unit tests run dist kvstores too)
        return KVStore(name)
    raise MXNetError(f"Unknown KVStore type {name}")
