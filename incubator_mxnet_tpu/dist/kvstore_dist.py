"""Worker-side distributed KVStore.

The `src/kvstore/kvstore_dist.h:44-412` role: locally reduce the per-device
gradient shards (one XLA all-reduce over the chip mesh — KVStoreTPU's
engine), then exchange ONE merged array per key with the parameter server
over the socket transport.  `dist_sync` aggregates a round across all
workers before anyone observes it; `dist_async` applies pushes immediately.

The reference encodes worker identity via the dmlc tracker env
(DMLC_RANK/DMLC_NUM_WORKER etc.); the same names are honored here so
`tools/launch.py` and existing cluster scripts port directly.
"""
from __future__ import annotations

import os
import pickle

from ..base import MXNetError
from ..kvstore import (KVStoreTPU, _normalize, _normalize_push, _key,
                       _updater_key)
from ..resilience import CircuitBreaker, ServerLostError, faults as _faults
from .transport import Channel


class KVStoreDist(KVStoreTPU):
    def __init__(self, kind="dist_sync"):
        super().__init__(kind)
        self._sync = "async" not in kind
        host = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        port = int(os.environ.get("DMLC_PS_ROOT_PORT", 9091))
        self._chan = Channel(host, port)
        env_rank = os.environ.get("DMLC_RANK")
        from .. import config as _config
        # membership epoch fence: a worker restarted by shrink-and-resume
        # carries the post-shrink epoch (MXNET_SUPERVISOR_EPOCH); a stale
        # host registering with an old epoch is refused by the server
        self._epoch = int(_config.get("MXNET_SUPERVISOR_EPOCH"))
        reply = _check(self._chan.request(
            {"cmd": "register", "role": "worker", "epoch": self._epoch,
             "rank": int(env_rank) if env_rank is not None else None}))
        self._rank = reply["rank"]
        self._num_workers = reply["num_workers"]
        # key-range sharding over N servers (reference kvstore_dist.h:44 +
        # docs/faq/distributed_training.md:50-53): whole small keys land
        # on one server by stable hash; arrays over
        # MXNET_KVSTORE_BIGARRAY_BOUND flat-split into one contiguous
        # range per server, each stored under the TRUE key (every server
        # only ever holds its own slice, exactly ps-lite's value ranges)
        self._num_servers = int(reply.get("num_servers", 1))
        self._chans = [self._chan]
        if self._num_servers > 1:
            srv = _check(self._chan.request({"cmd": "server_list"}))
            self._chans += [Channel(h, p) for h, p in srv["servers"]]
        from .. import config as _config
        # per-server health: a consecutive-failure circuit breaker per
        # channel; a tripped breaker is the permanent-death diagnosis that
        # becomes a structured ServerLostError (failover semantics)
        self._breakers = [
            CircuitBreaker(
                failure_threshold=int(_config.get(
                    "MXNET_PS_BREAKER_THRESHOLD")),
                reset_timeout=float(_config.get("MXNET_PS_BREAKER_RESET_S")))
            for _ in self._chans]
        # a reconnected root channel re-handshakes (re-registers under the
        # SAME rank) before the retried request is resent
        rank = self._rank

        def _rehandshake(chan, _rank=rank, _epoch=self._epoch):
            chan.bare_request({"cmd": "register", "role": "worker",
                               "rank": _rank, "epoch": _epoch})
        self._chan.on_reconnect = _rehandshake
        self._bigarray_bound = int(_config.get(
            "MXNET_KVSTORE_BIGARRAY_BOUND"))
        self._push_count = {}    # (srv, key) -> completed sync pushes
        self._update_on_kvstore = False
        # route profiler(profile_process='server') commands through us
        from .. import profiler as _profiler
        _profiler.set_kvstore_handle(self)
        # telemetry plane: the dist retry/failover counters under their
        # own namespace (the base class's bucketed counters stay under
        # 'kvstore' via super().__init__'s registration)
        from ..obs import metrics as _obs_metrics
        _obs_metrics.register_producer("kvstore.dist", self.stats)
        # collective data plane: gradients all-reduce over the global device
        # mesh (ICI/DCN via XLA collectives — the reference's NCCL/ps-lite
        # data role done the TPU way, SURVEY §2.4); the socket server is
        # then control plane only (registration, init, barriers).  sync
        # mode only: async semantics need a mailbox, which is the server.
        self._collective = None
        if self._sync and self._num_workers > 1 and \
                os.environ.get("MXNET_KVSTORE_COLLECTIVE", "1") != "0":
            try:
                self._collective = _CollectivePlane(self._rank,
                                                    self._num_workers)
            except Exception as e:
                import logging
                logging.getLogger(__name__).warning(
                    "collective data plane unavailable (%s); gradients go "
                    "through the parameter server", str(e)[:200])
                self._collective = None

    def _request(self, srv, msg):
        """One control-channel round trip with failover semantics.

        The channel itself retries transient failures (backoff, reconnect,
        idempotent resend — transport.Channel).  This layer tracks
        per-server HEALTH: each exhausted channel-level attempt counts
        against the server's circuit breaker; when the breaker trips the
        server is diagnosed permanently dead and a structured
        `ServerLostError` names the server, its address, and the keys
        whose ranges it owned.  A server that answers but has LOST its
        store (restarted empty) gets the same diagnosis — its state is
        unrecoverable without a checkpoint resume either way."""
        chan = self._chans[srv]
        breaker = self._breakers[srv]
        addr = f"{chan.host}:{chan.port}"
        if not breaker.allow():
            raise ServerLostError(
                srv, addr, keys=self._keys_on(srv),
                reason=f"circuit breaker is {breaker.state} after "
                       f"{breaker.failure_threshold} consecutive failures")
        last = None
        framed = False
        while True:
            try:
                # retries resend the SAME frame (same seq) so a server
                # that already applied it replays its cached reply
                reply = chan.resend_last() if framed else chan.request(msg)
                break
            except TimeoutError as e:
                # slow or wedged, not provably dead: the channel stayed
                # consistent (stale reply discarded by seq).  Resend the
                # SAME frame (the server's dedup/inflight shell absorbs
                # it) until the breaker declares the server unresponsive
                # — a partition with no RST must still reach failover.
                last = e
                framed = True
                if breaker.record_failure():
                    raise ServerLostError(
                        srv, addr, keys=self._keys_on(srv),
                        reason=f"unresponsive during {msg.get('cmd')!r}: "
                               f"{breaker.failure_threshold} consecutive "
                               f"timeouts ({e})") from e
                _faults.note("retry", site="kvstore", server=srv,
                             cmd=msg.get("cmd"), error="timeout")
            except (ConnectionError, EOFError, OSError) as e:
                last = e
                framed = True
                if breaker.record_failure():
                    raise ServerLostError(
                        srv, addr, keys=self._keys_on(srv),
                        reason=f"unreachable during {msg.get('cmd')!r} "
                               f"after {breaker.failure_threshold} "
                               f"consecutive failures "
                               f"({type(last).__name__}: {last})") from last
                _faults.note("reconnect", site="kvstore", server=srv,
                             cmd=msg.get("cmd"))
        if "error" in reply:
            err = reply["error"]
            if "epoch fenced" in err:
                # a shrink committed while this request waited (our own
                # watchdog had not fired yet): surface the recoverable
                # signal, not a generic error — fit's restart loop then
                # drives this worker through the shrink/fence path
                from ..resilience.supervisor import CollectiveTimeoutError
                breaker.record_success()   # the server is alive and sane
                raise CollectiveTimeoutError(
                    f"kvstore.{msg.get('cmd')}", axis="workers",
                    detail=err)
            k = msg.get("key")
            if "has not been initialized" in err and k is not None \
                    and k in self._store:
                # the server answered but forgot a key this worker DID
                # initialize: it restarted empty — its range is gone
                breaker.record_failure()
                raise ServerLostError(
                    srv, addr, keys=self._keys_on(srv),
                    reason=f"server restarted without state ({err})")
            # an application-level error over a WORKING transport still
            # proves the server alive — close any half-open probe
            breaker.record_success()
            raise MXNetError(err)
        breaker.record_success()
        return reply

    def _supervised(self, name, fn):
        """Route a blocking cross-host exchange through the active
        `JobSupervisor`'s hung-collective watchdog (plain call when no
        supervisor is active).  A sync push/pull that a dead host's
        missing contribution can stall forever becomes a structured
        `CollectiveTimeoutError` naming the absent hosts instead."""
        from ..resilience.supervisor import supervised
        return supervised(name, fn, axis="workers")

    def server_addresses(self):
        """Every parameter server's (host, port), root first — the
        shard-server set a `ShardedEmbedding` table partitions over."""
        return [(c.host, c.port) for c in self._chans]

    def embedding(self, name, num_rows, dim, **kwargs):
        """A `ShardedEmbedding` row-sharded over THIS store's servers:
        each server hosts one row shard next to the dense key ranges it
        already owns, so `set_optimizer` / checkpoint state capture
        cover both planes in one place."""
        from ..embedding import ShardedEmbedding
        return ShardedEmbedding(name, num_rows, dim,
                                self.server_addresses(), **kwargs)

    def stats(self):
        """PR 5 retry/failover counters, one dict — exported through
        `JobSupervisor.stats()` into the chaos artifacts: per-channel idempotent resends, stale replies
        discarded by sequence number, and every per-server breaker's
        state."""
        return {
            "resends": sum(c.resends for c in self._chans),
            "discarded_stale": sum(c.discarded_stale for c in self._chans),
            "breakers": [
                {"server": i, "addr": f"{c.host}:{c.port}",
                 "state": b.state,
                 "consecutive_failures": b.consecutive_failures}
                for i, (c, b) in enumerate(zip(self._chans,
                                               self._breakers))],
        }

    def _keys_on(self, srv):
        """Keys whose shard routing places a range on server `srv`
        (ServerLostError evidence: what data the lost server owned)."""
        import numpy as _np
        out = []
        for sk, v in self._store.items():
            size = int(_np.prod(v.shape)) if v.shape else 1
            if any(s == srv for s, _ in self._shards(sk, size)):
                out.append(sk)
        return out

    # -- checkpoint plane ------------------------------------------------------
    def get_optimizer_states(self, dump_optimizer=False):
        """Optimizer slots as one bytes blob for the checkpoint plane.

        Server-side optimizer (socket data plane): each server owns the
        slots for ITS key ranges — pull every server's states back through
        the control channel and wrap them per-server, the
        rank-0-writes-params layout's single blob.  Collective mode: the
        optimizer ran worker-side (replicated), so the local updater is
        authoritative."""
        if self._updater is not None:
            return self._updater.get_states(dump_optimizer=dump_optimizer)
        blobs = {}
        for srv in range(len(self._chans)):
            reply = self._request(srv, {"cmd": "get_optimizer_states",
                                        "dump_optimizer": dump_optimizer})
            blobs[srv] = reply.get("states")
        if all(b is None for b in blobs.values()):
            raise MXNetError(
                "get_optimizer_states: no optimizer is installed on any "
                "parameter server (call set_optimizer first)")
        return pickle.dumps({"dist_server_states": blobs}, protocol=4)

    def set_optimizer_states(self, blob):
        """Restore a `get_optimizer_states` blob.  Per-server blobs go
        back to the server that owns each key range (rank 0 pushes, then
        everyone barriers so no worker trains against half-restored
        slots); a worker-side blob loads into the local updater."""
        payload = pickle.loads(blob) if isinstance(blob, bytes) else blob
        if isinstance(payload, dict) and "dist_server_states" in payload:
            if self._rank == 0:
                for srv, states in payload["dist_server_states"].items():
                    if states is None:
                        continue
                    self._request(int(srv), {"cmd": "set_optimizer_states",
                                             "states": states})
            self._barrier()
            return
        if self._updater is None:
            raise MXNetError(
                "set_optimizer_states: blob holds worker-side updater "
                "state but this store has no local updater (collective "
                "mode not engaged?)")
        self._updater.set_states(blob)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        with open(fname, "wb") as f:
            f.write(self.get_optimizer_states(dump_optimizer=dump_optimizer))

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            self.set_optimizer_states(f.read())

    def server_profiler_command(self, action, **kw):
        """Drive every parameter server's profiler (reference
        `mx.profiler.set_config/set_state/dump(profile_process='server')`
        forwarded through MXKVStoreSendCommmandToServers).  Every server
        is attempted; failures are aggregated so a bad first server
        cannot leave the rest silently unconfigured."""
        errors = []
        for i, chan in enumerate(self._chans):
            try:
                _check(chan.request(dict({"cmd": "profiler",
                                          "action": action}, **kw)))
            except Exception as e:
                errors.append(f"server {i}: {e}")
        if errors:
            raise MXNetError("server profiler command failed on: " +
                             "; ".join(errors))

    @property
    def prefers_batched_push(self):
        """Training glue should hand push/pull the full key list at once so
        the whole step rides one fused collective (see
        `_collective_push_batch`)."""
        return self._collective is not None

    # -- identity ------------------------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    # -- data plane ----------------------------------------------------------
    def _shards(self, sk, size):
        """Route a key's flat value by ELEMENT COUNT: [(server_idx,
        slice)] — one slice on one hashed server for small keys, one
        contiguous range per server above the bigarray bound."""
        n = len(self._chans)
        if n == 1 or size <= self._bigarray_bound:
            if str(sk).isdigit():
                srv = int(sk) % n
            else:
                import zlib
                srv = zlib.crc32(str(sk).encode()) % n
            return [(srv, slice(0, size))]
        bounds = [size * i // n for i in range(n + 1)]
        return [(i, slice(bounds[i], bounds[i + 1])) for i in range(n)]

    def init(self, key, value):
        """Rank 0 ships initial weights to the owning server(s); everyone
        barriers so no worker pulls before the key exists (reference
        `kvstore_dist.h` InitImpl pushes only on worker 0, then Barrier)."""
        keys, values = _normalize(key, value)
        if self._rank == 0:
            for k, v in zip(keys, values):
                sk = _key(k)
                flat = v.asnumpy().reshape(-1)
                for srv, sl in self._shards(sk, flat.size):
                    self._request(srv, {"cmd": "init", "keys": [sk],
                                        "values": [flat[sl]]})
        self._barrier()
        # keep a local copy so pull() can place results on local devices
        for k, v in zip(keys, values):
            if self._collective is not None:
                # broadcast rank 0's init over the mesh so every worker's
                # local copy is IDENTICAL (the socket path trusts each
                # worker to have initialized equally; the collective path
                # enforces it)
                import jax.numpy as jnp
                src = v._data if self._rank == 0 else \
                    jnp.zeros(v.shape, v.dtype)
                from ..ndarray.ndarray import NDArray
                summed = self._collective.allreduce(src)
                self._store[_key(k)] = NDArray(summed, ctx=self._store_ctx)
            else:
                self._store[_key(k)] = v.copyto(self._store_ctx)

    def _wire_dtype(self, merged_dtype):
        """Wire dtype for compressed-gradient collectives.  Quantized
        terms are {-t, 0, +t}; partial sums are k*t with |k| <= workers.
        bf16 (8 significand bits) keeps every k*t EXACT only when t's
        significand is a single bit (power of two) AND k <= 256 — e.g.
        t=0.3 already rounds 5t below ten workers.  Outside that envelope
        the half-width wire would silently diverge from the reference
        server path's exact accumulation, so it keeps the merged dtype."""
        import math
        import jax.numpy as jnp
        thr = float(self._compression.get("threshold", 0.5))
        frac = math.frexp(abs(thr))[0] if thr else 0.5
        if self._num_workers <= 256 and frac == 0.5:
            return jnp.bfloat16
        return merged_dtype

    def _collective_push(self, sk, vals):
        """Sync push over XLA collectives: local chip reduce, then ONE
        global all-reduce; optimizer (if shipped) applies identically on
        every worker; zero gradient bytes on the socket."""
        from ..ndarray.ndarray import NDArray
        merged = self._reduce(vals)
        if self._compression is not None:
            # error-feedback quantization BEFORE the collective: summing
            # quantized terms matches the server-side accumulate semantics.
            # The collective then rides the interconnect at HALF width —
            # quantized grads are in {-t, 0, +t} — the collective-mode
            # reading of the reference's wire compression
            # (`gradient_compression.h:52-134` saves PS bytes; this saves
            # ICI/DCN bytes).
            merged = self._compress(sk, merged)
            wire = self._wire_dtype(merged._data.dtype)
            summed = self._collective.allreduce(
                merged._data.astype(wire)).astype(merged._data.dtype)
        else:
            # allreduce returns a fresh worker-local array; wrap without
            # another device copy
            summed = self._collective.allreduce(merged._data)
        summed_nd = NDArray(summed, ctx=self._store_ctx)
        if self._updater is not None:
            self._updater(_updater_key(sk), summed_nd, self._store[sk])
        else:
            self._store[sk] = summed_nd
        self._record_key_mesh(sk, vals)

    def push(self, key, value, priority=0):
        keys, values = _normalize_push(key, value)
        if self._collective is not None:
            if len(keys) > 1:
                self._supervised(
                    "kvstore.push",
                    lambda: self._collective_push_batch(keys, values))
                return

            def _push_each():
                for k, vals in zip(keys, values):
                    sk = _key(k)
                    if sk not in self._store:
                        raise MXNetError(
                            f"Key {k} has not been initialized")
                    self._collective_push(sk, vals)
            self._supervised("kvstore.push", _push_each)
            return
        self._supervised("kvstore.push",
                         lambda: self._socket_push(keys, values))

    def _collective_push_batch(self, keys, values):
        """Batched sync push: local reduce per key, then ONE fused global
        all-reduce over the flattened bucket of every key — ~1 collective
        dispatch per training step instead of one per parameter (the
        reference batches NCCL pushes the same way, `model.py:125`)."""
        from ..ndarray.ndarray import NDArray
        import jax.numpy as jnp
        sks, merged, dtypes = [], [], []
        for k, vals in zip(keys, values):
            sk = _key(k)
            if sk not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
            m = self._reduce(vals)
            if self._compression is not None:
                # quantize + halve the wire width (see _collective_push)
                m = self._compress(sk, m)
                dtypes.append(m._data.dtype)
                merged.append(m._data.astype(
                    self._wire_dtype(m._data.dtype)))
            else:
                dtypes.append(None)
                merged.append(m._data)
            sks.append(sk)
            self._record_key_mesh(sk, vals)
        summed = self._collective.allreduce_many(merged)
        for sk, s, dt in zip(sks, summed, dtypes):
            if dt is not None:
                s = s.astype(dt)
            s_nd = NDArray(s, ctx=self._store_ctx)
            if self._updater is not None:
                self._updater(_updater_key(sk), s_nd, self._store[sk])
            else:
                self._store[sk] = s_nd

    def _socket_push(self, keys, values):
        from .compression import pack_2bit
        for k, vals in zip(keys, values):
            sk = _key(k)
            if sk not in self._store:
                raise MXNetError(f"Key {k} has not been initialized")
            merged = self._reduce(vals)      # one collective over local chips
            if self._compression is not None:
                # quantize device-side (error feedback stays on device);
                # each shard packs 4 codes/byte for its wire — 16x fewer
                # bytes than fp32 (reference gradient_compression.h)
                merged = self._compress(sk, merged)
            flat = merged.asnumpy().reshape(-1)
            for srv, sl in self._shards(sk, flat.size):
                part = flat[sl]
                if self._compression is not None:
                    wire_value = pack_2bit(part,
                                           self._compression["threshold"])
                else:
                    wire_value = part
                self._request(srv, {"cmd": "push", "key": sk,
                                    "value": wire_value,
                                    "sync": self._sync, "rank": self._rank})
                if self._sync:
                    ck = (srv, sk)
                    self._push_count[ck] = self._push_count.get(ck, 0) + 1
            self._record_key_mesh(sk, vals)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if out is None:
            raise MXNetError("pull requires out=")
        # the sync pull is the step's rendezvous: it waits for every
        # worker's round contribution, so a dead host stalls it — run it
        # under the supervisor watchdog when one is active
        self._supervised("kvstore.pull",
                         lambda: self._pull_impl(key, out, ignore_sparse))

    def _pull_impl(self, key, out, ignore_sparse=True):
        keys, outs = _normalize_push(key, out)
        if self._collective is not None:
            # the all-reduce left an identical fresh value on every worker;
            # fan out locally, no socket round trip
            for k, tgt_list in zip(keys, outs):
                super().pull(k, out=tgt_list)
            return
        import numpy as _np
        for k, tgt_list in zip(keys, outs):
            sk = _key(k)
            src = self._store.get(sk)
            if src is None:
                # without the local shape the shard routing cannot be
                # reconstructed — and init() populates the local copy on
                # EVERY worker, so this is a protocol violation, not a
                # recoverable state
                raise MXNetError(
                    f"pull({k}): key was never initialized on this worker")
            shape = src.shape
            size = int(_np.prod(shape)) if shape else 1
            parts = []
            for srv, sl in self._shards(sk, size):
                reply = self._request(
                    srv, {"cmd": "pull", "key": sk,
                          "min_version": self._push_count.get((srv, sk), 0)})
                parts.append(_np.asarray(reply["value"]).reshape(-1))
            value = _np.concatenate(parts) if len(parts) > 1 else parts[0]
            if value.size != size:
                raise MXNetError(
                    f"pull({k}): servers returned {value.size} elements, "
                    f"local copy has {size} — worker/server shapes "
                    "disagree (inconsistent init?)")
            value = value.reshape(shape)
            src._set_data(src._data * 0 + value.astype(src.dtype))
            # local fan-out reuses the single-collective broadcast engine
            super().pull(k, out=tgt_list)

    # -- control plane -------------------------------------------------------
    def set_optimizer(self, optimizer):
        """Ship the optimizer to the server (reference pickles it through
        MXKVStoreSendCommmandToServers, `python/mxnet/kvstore.py:535`).

        Collective mode: the server never sees gradients, so the optimizer
        runs worker-side instead — every worker applies the identical
        update to the identical all-reduced gradient (the 'sharded server'
        role collapses into replicated local application; ZeRO-style
        sharded application lives in `parallel/zero.py`)."""
        self._optimizer = optimizer
        self._update_on_kvstore = True
        if self._collective is not None:
            from .. import optimizer as _opt
            self._updater = _opt.get_updater(optimizer)
            self._barrier()
            return
        if self._rank == 0:
            blob = pickle.dumps(optimizer)
            for chan in self._chans:
                _check(chan.request({"cmd": "set_optimizer",
                                     "optimizer": blob}))
        self._barrier()

    def _barrier(self):
        self._supervised(
            "kvstore.barrier",
            lambda: _check(self._chan.request({"cmd": "barrier"})))

    def close(self, send_stop=True):
        """Close every server channel.  ``send_stop=False`` skips the
        protocol 'stop' — the failover teardown path, where counting
        this worker as stopped would shut down HEALTHY servers running
        `serve_forever` out from under the restarted run."""
        from .. import profiler as _profiler
        if _profiler._kvstore_handle[0] is self:
            _profiler.set_kvstore_handle(None)
        for chan in getattr(self, "_chans", [self._chan]):
            if send_stop:
                try:
                    # best-effort, fail-fast: no reconnect/retry cycle
                    # against a server that may already be dead
                    chan.bare_request({"cmd": "stop"})
                except Exception:
                    pass
            try:
                chan.close()
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _check(reply):
    if "error" in reply:
        raise MXNetError(reply["error"])
    return reply


class _CollectivePlane:
    """Global all-reduce over one representative device per worker process.

    Bootstraps `jax.distributed` (dist/collective.py) and builds a 1-D
    mesh with one device column per worker; `allreduce` sums each worker's
    contribution with ONE XLA collective riding ICI/DCN (Gloo on the CPU
    test mesh).  This is the data plane the reference implements with
    range-sharded ps-lite servers (`kvstore_dist.h:44-412`) — on TPU the
    wires are the interconnect and the server keeps only control duties.
    """

    def __init__(self, rank, num_workers):
        import jax
        import numpy as np
        from . import collective
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        collective.init_process_group(num_processes=num_workers,
                                      process_id=rank)
        if jax.process_count() != num_workers:
            raise RuntimeError(
                f"jax process_count {jax.process_count()} != "
                f"num_workers {num_workers}")
        reps = []
        for p in range(num_workers):
            devs = [d for d in jax.devices() if d.process_index == p]
            if not devs:
                raise RuntimeError(f"no devices visible for process {p}")
            reps.append(devs[0])
        self._mesh = Mesh(np.array(reps), ("workers",))
        self._local_dev = reps[jax.process_index()]
        self._in_sharding = NamedSharding(self._mesh, P("workers"))
        self._out_sharding = NamedSharding(self._mesh, P())
        self._sum = jax.jit(lambda x: x.sum(axis=0),
                            out_shardings=self._out_sharding)
        self._concat_jit = {}    # signature -> flatten+concat program
        self._split_jit = {}     # signature -> split+reshape program
        # global collective dispatches issued (tests assert one per step,
        # not one per key)
        self.dispatch_count = 0

    def allreduce(self, arr):
        """Sum `arr` across all workers; returns the replicated result's
        local view (a jax array on this worker's device)."""
        import jax
        local = jax.device_put(arr, self._local_dev)[None]
        garr = jax.make_array_from_single_device_arrays(
            (self._mesh.size,) + tuple(local.shape[1:]),
            self._in_sharding, [local])
        self.dispatch_count += 1
        out = self._sum(garr)
        return [s.data for s in out.addressable_shards][0]

    def allreduce_many(self, arrs):
        """Sum a LIST of arrays across workers with ONE collective per
        dtype bucket: flatten+concat locally, all-reduce the bucket, split
        back.  The reference batches NCCL pushes the same way
        (`python/mxnet/model.py:125`); key-range splitting
        (MXNET_KVSTORE_BIGARRAY_BOUND) has no role here because there is
        no server to shard over — the interconnect carries one fused
        payload."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if len(arrs) == 1:
            return [self.allreduce(arrs[0])]
        by_dtype = {}
        for i, a in enumerate(arrs):
            by_dtype.setdefault(np.dtype(a.dtype), []).append(i)
        out = [None] * len(arrs)
        for dt, idxs in by_dtype.items():
            group = [arrs[i] for i in idxs]
            sig = (dt,) + tuple(tuple(a.shape) for a in group)
            cat = self._concat_jit.get(sig)
            if cat is None:
                cat = jax.jit(lambda *xs: jnp.concatenate(
                    [x.reshape(-1) for x in xs]))
                self._concat_jit[sig] = cat
            local = [jax.device_put(a, self._local_dev) for a in group]
            bucket = cat(*local)
            summed = self.allreduce(bucket)
            split = self._split_jit.get(sig)
            if split is None:
                shapes = [tuple(a.shape) for a in group]
                offs = np.cumsum([0] + [int(np.prod(s)) for s in shapes])

                def _split(buf, shapes=shapes, offs=offs):
                    return tuple(
                        jax.lax.dynamic_slice_in_dim(
                            buf, int(offs[k]),
                            int(offs[k + 1] - offs[k])).reshape(shapes[k])
                        for k in range(len(shapes)))
                split = jax.jit(_split)
                self._split_jit[sig] = split
            for i, piece in zip(idxs, split(summed)):
                out[i] = piece
        return out
