"""Replica handles: the units the `ReplicaRouter` spreads requests over.

A replica is one independently-failing copy of a served model.  Two
concrete kinds share the `Replica` contract:

* `LocalReplica` — an in-process `ServedModel` + `MicroBatcher` pair
  (its own parameter copy, its own breaker-visible failure domain).
  N local replicas of one symbol share the unified program cache —
  the graph hash is identical — so replicas 2..N warm with ZERO XLA
  compiles.
* `RemoteReplica` — a subprocess worker (`serving.worker`) driven over
  the sequence-numbered `dist.transport` frames.  The process boundary
  makes SIGKILL-grade death real: the router's failover path is tested
  against actual dead processes, not simulations.  Requests carry the
  router's request id and the worker deduplicates on it, so a resend
  after a torn connection can never execute twice on that worker.

The contract the router relies on:

* ``submit(inputs, timeout_ms, rid)`` returns a Future; the future
  fails with `ReplicaLostError` when the replica dies before resolving
  it (the router's failover trigger — anything else is a caller error
  that would fail identically on every replica).
* ``heartbeat()`` is a cheap liveness check; ``probe()`` is the
  deepcheck — a real bucket-1 inference through the compiled ladder.
* ``swap(...)`` replaces the parameter set in place (same shapes, same
  programs: the program cache is untouched, so a swap costs zero XLA
  compiles).  ``version`` counts committed swaps.
* ``outstanding()`` / ``estimated_wait_s()`` drive least-loaded
  dispatch and priority shedding.
"""
from __future__ import annotations

import os
import queue as _queue
import subprocess
import sys
import threading
import time

from concurrent.futures import Future

import numpy as _np

from ..analysis import locks as _locks
from ..base import MXNetError

__all__ = ["Replica", "LocalReplica", "RemoteReplica", "ReplicaLostError",
           "worker_argv", "launch_worker"]


class ReplicaLostError(MXNetError):
    """The replica died (process killed, batcher torn down, transport
    gone) before this request resolved.  Structured so the router can
    distinguish "this replica is gone — fail over" from "this request
    is bad — fail it everywhere": `replica_id` names the dead replica,
    `rid` the in-flight request."""

    def __init__(self, replica_id, rid=None, reason=""):
        self.replica_id = str(replica_id)
        self.rid = rid
        super().__init__(
            f"replica '{replica_id}' lost"
            + (f" with request {rid} in flight" if rid else "")
            + (f": {reason}" if reason else "")
            + " — the router fails over to a surviving replica")


class Replica:
    """Shared contract; see the module docstring."""

    replica_id = "?"
    version = 0          # committed weight-swap count

    def submit(self, inputs, timeout_ms=None, rid=None, priority=1):
        raise NotImplementedError

    def heartbeat(self):
        raise NotImplementedError

    def probe(self):
        raise NotImplementedError

    def swap(self, arg_params=None, aux_params=None, checkpoint_dir=None):
        raise NotImplementedError

    def outstanding(self):
        raise NotImplementedError

    def estimated_wait_s(self):
        return None

    # protocol stub: concrete replicas surface through the router's
    # 'router' producer and each ServingMetrics' 'serving.<id>' one
    def stats(self):   # mxlint: disable=untracked-stats
        return {}

    def close(self, drain=True):
        pass


def _load_checkpoint_params(checkpoint_dir):
    """(arg_params, aux_params) from the newest VALID elastic checkpoint
    under `checkpoint_dir` (torn checkpoints are never selected) —
    the swap source shared by both replica kinds."""
    from ..checkpoint import load as _load, latest as _latest
    from ..checkpoint.state import split_params
    path = checkpoint_dir
    if not os.path.exists(os.path.join(path, "manifest.json")):
        found = _latest(path)
        if found is None:
            raise MXNetError(
                f"replica swap: no valid checkpoint under "
                f"{checkpoint_dir!r} (torn checkpoints are never selected)")
        path = found
    data = _load(path)
    return split_params(data.arrays)


class LocalReplica(Replica):
    """In-process replica: one `ServedModel` (its own parameter copy)
    behind its own `MicroBatcher`."""

    def __init__(self, model, replica_id=None, max_batch_size=None,
                 max_queue_latency_ms=2.0, max_queue=256, **batcher_knobs):
        from .batcher import MicroBatcher
        from .metrics import ServingMetrics
        self._model = model
        self.replica_id = str(replica_id if replica_id is not None
                              else f"local/{model.name}")
        self.metrics = ServingMetrics(self.replica_id)
        if not model.warmed:
            model.warmup()
        self._batcher = MicroBatcher(
            model, self.metrics, max_batch_size=max_batch_size,
            max_queue_latency_ms=max_queue_latency_ms, max_queue=max_queue,
            **batcher_knobs)
        self._dead = False
        self._last_reply_t = None   # when a response last resolved

    # -- request path --------------------------------------------------------
    def submit(self, inputs, timeout_ms=None, rid=None, priority=1):
        if self._dead:
            raise ReplicaLostError(self.replica_id, rid,
                                   "replica was killed")
        try:
            inner = self._batcher.submit(inputs, timeout_ms=timeout_ms,
                                         priority=priority)
        except MXNetError as exc:
            if self._dead or "draining" in str(exc):
                raise ReplicaLostError(self.replica_id, rid,
                                       str(exc)) from exc
            raise
        # surface the batcher's shutdown sweep as REPLICA LOSS: a killed
        # replica fails its queued requests with a shutdown error, and
        # the router must read that as "this replica is gone, fail the
        # request over", not "this request is bad"
        out = Future()
        out.request_id = rid

        def _chain(f, out=out, rid=rid):
            self._last_reply_t = time.monotonic()
            try:
                res = f.result()
            except MXNetError as exc:
                s = str(exc)
                lost = self._dead and ("shut down" in s or "draining" in s)
                try:
                    out.set_exception(
                        ReplicaLostError(self.replica_id, rid, s)
                        if lost else exc)
                except Exception:
                    pass
                return
            except Exception as exc:
                try:
                    out.set_exception(exc)
                except Exception:
                    pass
                return
            try:
                out.set_result(res)
            except Exception:
                pass

        inner.add_done_callback(_chain)
        return out

    # -- health --------------------------------------------------------------
    def heartbeat(self):
        if self._dead or not self._batcher._thread.is_alive():
            raise ReplicaLostError(self.replica_id,
                                   reason="batcher worker is gone")
        return {"outstanding": self.outstanding(), "version": self.version}

    def probe(self):
        """Deepcheck: a real inference through the smallest bucket."""
        self.heartbeat()
        model = self._model
        inputs = [_np.zeros((1,) + model._sample_shapes[n], model._dtype)
                  for n in model.data_names]
        model.infer(inputs)
        return {"programs": model.program_count(), "version": self.version}

    # -- swap ----------------------------------------------------------------
    def swap(self, arg_params=None, aux_params=None, checkpoint_dir=None):
        if checkpoint_dir is not None:
            arg_params, aux_params = _load_checkpoint_params(checkpoint_dir)
        self._model.set_params(arg_params, aux_params)
        self.version += 1
        return self.version

    # -- load ----------------------------------------------------------------
    def outstanding(self):
        return self._batcher._outstanding

    def estimated_wait_s(self):
        """What a new request would wait here: the batcher's queue-model
        estimate, floored by the observed response-latency EWMA — the
        queue model alone is blind to host scheduling overhead, which
        dominates exactly when the fleet is overloaded.  On an EMPTY
        replica the floor decays with the age of the last response: the
        EWMA cannot decay on its own (it only updates on responses),
        and holding it would wedge the fleet autoscaler's idle
        detection forever after an overload burst."""
        est = self._batcher.estimated_wait_s()
        lat = self.metrics.avg_latency_s()
        if lat is not None and self.outstanding() == 0:
            # empty replica: decay the floor with the age of the last
            # response (1s half-life, same as RemoteReplica) — an
            # abrupt drop would collapse the fleet admission signal on
            # momentary empty instants mid-flood, while no decay at
            # all wedges idle detection forever
            last = self._last_reply_t
            age = 0.0 if last is None else time.monotonic() - last
            lat = lat * 0.5 ** age
        if est is None:
            return lat
        return est if lat is None else max(est, lat)

    # registered by this replica's ServingMetrics ('serving.<id>')
    def stats(self):   # mxlint: disable=untracked-stats
        snap = self.metrics.snapshot()
        snap["version"] = self.version
        return snap

    def close(self, drain=True):
        self._dead = True
        self._batcher.close(drain=drain)

    def kill(self):
        """Abrupt death (tests/chaos): queued requests fail with the
        shutdown error — the router reads it as replica loss and fails
        them over.  A batch already executing completes (its requesters
        were served before the death)."""
        self._dead = True
        try:
            self._batcher.kill()
        except MXNetError:
            pass


def worker_argv(*, prefix=None, epoch=0, symbol_file=None,
                checkpoint_dir=None, data_shapes, buckets=(1, 2, 4, 8),
                name="model", host="127.0.0.1", port=0):
    """The `serving.worker` command line for one replica — the single
    place the worker CLI contract is spelled, shared by
    `RemoteReplica.spawn` (local subprocess) and the fleet host daemon
    (`serving.hostd`, spawning on ITS host)."""
    shapes = ";".join("%s=%s" % (n, ",".join(str(d) for d in s))
                      for n, s in data_shapes)
    cmd = [sys.executable, "-m", "incubator_mxnet_tpu.serving.worker",
           "--name", str(name), "--data-shapes", shapes,
           "--buckets", ",".join(str(b) for b in buckets),
           "--host", str(host), "--port", str(int(port))]
    if prefix is not None:
        cmd += ["--prefix", prefix, "--epoch", str(epoch)]
    if symbol_file is not None:
        cmd += ["--symbol-file", symbol_file]
    if checkpoint_dir is not None:
        cmd += ["--checkpoint-dir", checkpoint_dir]
    return cmd


def launch_worker(cmd, *, env=None, name="model", ready_timeout=240.0,
                  launch=None, tag=None, port_prefix="REPLICA_PORT",
                  ready_prefix="REPLICA_READY", start_new_session=False,
                  thread_prefix="mx-replica"):
    """Run one worker argv and wait for its readiness handshake.
    Returns ``(proc, port, ready_info)`` where ``ready_info`` is the
    parsed ``REPLICA_READY`` evidence (programs / compiles / disk_hits
    — the zero-compile spin-up cert chaos and the fleet
    autoscaler read).  ``launch(cmd, env) -> Popen`` overrides the
    default local `subprocess.Popen` (remote-exec hook).  The line
    prefixes are parameters so the fleet host daemon's handshake
    (``HOSTD_PORT`` / ``HOSTD_READY``) shares this one implementation;
    ``start_new_session`` puts the child in its own process group (the
    daemon + its workers die together under a group SIGKILL).

    ``ready_timeout`` is enforced even when the child stays alive but
    SILENT (wedged on a hung checkpoint read): a deadline timer kills
    it, which unblocks the pipe read."""
    full_env = dict(os.environ, **(env or {}))
    if launch is not None:
        proc = launch(cmd, full_env)
    else:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                env=full_env,
                                start_new_session=start_new_session)
    port = None
    ready_info = {}
    timed_out = threading.Event()

    def _deadline_kill():
        timed_out.set()
        proc.kill()

    timer = threading.Timer(float(ready_timeout), _deadline_kill)
    timer.daemon = True
    timer.start()
    try:
        while True:
            line = proc.stdout.readline()
            if not line:
                if timed_out.is_set():
                    break
                raise MXNetError(
                    f"worker '{name}' exited during startup "
                    f"(rc={proc.poll()})")
            if line.startswith(port_prefix + " "):
                port = int(line.split()[1])
            elif line.startswith(ready_prefix):
                # "REPLICA_READY programs=N compiles=K disk_hits=D":
                # the zero-compile spin-up evidence (chaos reads it)
                for tok in line.split()[1:]:
                    k, _, v = tok.partition("=")
                    if v.isdigit():
                        ready_info[k] = int(v)
                break
    finally:
        timer.cancel()
    if port is None or timed_out.is_set():
        proc.kill()
        raise MXNetError(
            f"worker '{name}' did not complete its readiness handshake "
            f"within {ready_timeout:g}s")
    # drain the pipe in the background or the worker blocks on a
    # full stdout once it starts logging
    threading.Thread(target=lambda: proc.stdout.read(),
                     daemon=True,
                     name=f"{thread_prefix}-{tag or name}-stdout").start()
    return proc, port, ready_info


class RemoteReplica(Replica):
    """Subprocess replica over the seq-numbered dist transport.

    ``concurrency`` dispatch threads each own one `Channel` (channels
    are serial by design), so up to that many requests are on the wire
    at once; the rest wait in a bounded local queue.  The worker side
    coalesces nothing — each request is one device dispatch — so the
    local queue length drives the load estimate."""

    def __init__(self, host, port, replica_id=None, process=None,
                 concurrency=2, max_queue=256, timeout=None,
                 control_timeout=5.0):
        self.replica_id = str(replica_id if replica_id is not None
                              else f"remote/{host}:{port}")
        self.host, self.port = host, int(port)
        self.process = process       # Popen when spawn()ed (chaos kills it)
        self._q = _queue.PriorityQueue(maxsize=int(max_queue))
        self._seq_counter = 0
        self._lost = threading.Event()
        self._inflight = {}          # rid -> _Pending (on the wire)
        self._lock = _locks.make_lock("serving.replica")
        self._ewma_s = None          # recent per-request round-trip
        self._last_reply_t = None    # when the EWMA last saw a response
        self._chans = []
        self._threads = []
        # the control channel answers in microseconds or the worker is
        # in trouble: a SHORT timeout keeps one wedged (but connected)
        # worker from pinning the router's health loop for minutes —
        # the slow probe surfaces as suspicion, not a long stall
        self._control = self._make_channel(control_timeout)
        for i in range(int(concurrency)):
            chan = self._make_channel(timeout)
            self._chans.append(chan)
            t = threading.Thread(target=self._dispatch_loop, args=(chan,),
                                 daemon=True,
                                 name=f"mx-replica-{self.replica_id}-{i}")
            t.start()
            self._threads.append(t)

    def _make_channel(self, timeout):
        from ..dist.transport import Channel
        from ..resilience import RetryPolicy
        # short reconnect budget: a dead worker should be DIAGNOSED in
        # ~a second so failover starts, not nursed for minutes — the
        # router's re-dispatch is the real retry (worker-side rid dedup
        # keeps a transport-level resend from executing twice)
        return Channel(self.host, self.port, timeout=timeout,
                       connect_wait=10.0,
                       retry=RetryPolicy(max_attempts=2, base_delay=0.05,
                                         max_delay=0.2))

    @classmethod
    def spawn(cls, *, prefix=None, epoch=0, symbol_file=None,
              checkpoint_dir=None, data_shapes, buckets=(1, 2, 4, 8),
              name="model", replica_id=None, env=None, concurrency=2,
              ready_timeout=240.0, host="127.0.0.1", launch=None):
        """Launch a `serving.worker` subprocess and connect to it.  The
        worker inherits ``MXNET_PROGRAM_CACHE_DIR`` (when set), so every
        replica after the first warms from the shared disk tier with
        zero XLA compiles.

        ``host`` is the address the worker binds AND the address this
        handle connects to (default localhost, so existing callers and
        artifacts are unchanged).  ``launch`` is the launch-command hook
        for remote execution: a callable ``launch(cmd, env) -> Popen``
        (text mode, stdout piped) that runs the worker argv on the
        target host — e.g. by prefixing an ssh invocation — instead of
        the default local ``subprocess.Popen``.  Cross-host *fleets*
        should prefer `serving.fleet.AgentHost`, which delegates the
        spawn to a host daemon and reuses this module's launch helper
        on the far side."""
        cmd = worker_argv(prefix=prefix, epoch=epoch,
                          symbol_file=symbol_file,
                          checkpoint_dir=checkpoint_dir,
                          data_shapes=data_shapes, buckets=buckets,
                          name=name, host=host)
        proc, port, ready_info = launch_worker(
            cmd, env=env, name=name, ready_timeout=ready_timeout,
            launch=launch, tag=replica_id or name)
        self = cls(host, port, replica_id=replica_id, process=proc,
                   concurrency=concurrency)
        self.ready_info = ready_info
        return self

    # -- request path --------------------------------------------------------
    class _Pending:
        __slots__ = ("msg", "future", "rid", "t_enqueue")

        def __init__(self, msg, rid):
            self.msg = msg
            self.rid = rid
            self.future = Future()
            self.t_enqueue = time.monotonic()

    def submit(self, inputs, timeout_ms=None, rid=None, priority=1):
        if self._lost.is_set():
            raise ReplicaLostError(self.replica_id, rid)
        # host-normalize so only numpy crosses the transport
        to_np = lambda v: v.asnumpy() if hasattr(v, "asnumpy") \
            else _np.asarray(v)
        arrs = {k: to_np(v) for k, v in inputs.items()} \
            if isinstance(inputs, dict) else [to_np(v) for v in inputs]
        msg = {"cmd": "infer", "rid": rid, "inputs": arrs,
               "timeout_ms": timeout_ms}
        from ..obs import trace as _obs_trace
        tr = _obs_trace.current_frame()
        if tr is not None:
            # captured on the SUBMITTING thread: the dispatch loop that
            # puts this frame on the wire runs where contextvars are
            # blind — the channel's rpc span parents to this instead
            msg["tr"] = tr
        pend = self._Pending(msg, rid)
        with self._lock:
            self._seq_counter += 1
            seq = self._seq_counter
        try:
            # same dispatch-rank ordering as the batcher: interactive
            # work never waits behind an admitted best-effort burst
            self._q.put_nowait((int(priority), seq, pend))
        except _queue.Full:
            raise MXNetError(
                f"replica '{self.replica_id}' queue is full — "
                "backpressure, retry later") from None
        return pend.future

    def _dispatch_loop(self, chan):
        while not self._lost.is_set():
            try:
                pend = self._q.get(timeout=0.05)[2]
            except _queue.Empty:
                continue
            if pend.future.cancelled() or \
                    not pend.future.set_running_or_notify_cancel():
                continue
            with self._lock:
                self._inflight[pend.rid] = pend
            try:
                reply = chan.request(pend.msg)
            except Exception as exc:
                # fail THIS pend explicitly first: a concurrent
                # dispatch thread may already have run _mark_lost (its
                # sweep could miss a pend between queue-pop and
                # _inflight insert), and _mark_lost early-returns once
                # _lost is set — the current request must never be
                # left unresolved
                reason = f"{type(exc).__name__}: {exc}"
                with self._lock:
                    self._inflight.pop(pend.rid, None)
                try:
                    pend.future.set_exception(
                        ReplicaLostError(self.replica_id, pend.rid,
                                         reason))
                except Exception:
                    pass
                self._mark_lost(reason)
                return
            with self._lock:
                self._inflight.pop(pend.rid, None)
            rt = time.monotonic() - pend.t_enqueue
            self._ewma_s = rt if self._ewma_s is None \
                else 0.8 * self._ewma_s + 0.2 * rt
            self._last_reply_t = time.monotonic()
            try:
                if "error" in reply:
                    pend.future.set_exception(MXNetError(reply["error"]))
                else:
                    from ..ndarray.ndarray import NDArray
                    pend.future.set_result(
                        [NDArray(_np.asarray(o)) for o in reply["outs"]])
            except Exception:
                pass   # caller cancelled meanwhile

    def _mark_lost(self, reason):
        """Transport-level death: fail everything this replica holds so
        the router's failover callbacks fire at once."""
        if self._lost.is_set():
            return
        self._lost.set()
        with self._lock:
            inflight, self._inflight = dict(self._inflight), {}
        for rid, pend in inflight.items():
            try:
                pend.future.set_exception(
                    ReplicaLostError(self.replica_id, rid, reason))
            except Exception:
                pass
        while True:
            try:
                pend = self._q.get_nowait()[2]
            except _queue.Empty:
                break
            try:
                pend.future.set_exception(
                    ReplicaLostError(self.replica_id, pend.rid, reason))
            except Exception:
                pass

    # -- health --------------------------------------------------------------
    def _control_request(self, msg):
        if self._lost.is_set():
            raise ReplicaLostError(self.replica_id)
        try:
            reply = self._control.request(msg)
        except TimeoutError:
            # slow-but-connected is SUSPICION evidence, not death: the
            # health loop degrades the replica's preference and only
            # the liveness deadline (continued silence) evicts it
            raise
        except Exception as exc:
            raise ReplicaLostError(
                self.replica_id,
                reason=f"{type(exc).__name__}: {exc}") from exc
        if "error" in reply:
            raise MXNetError(reply["error"])
        return reply

    def heartbeat(self):
        return self._control_request({"cmd": "hb"})

    def probe(self):
        return self._control_request({"cmd": "probe"})

    def swap(self, arg_params=None, aux_params=None, checkpoint_dir=None):
        if checkpoint_dir is None:
            raise MXNetError(
                f"replica '{self.replica_id}': remote swap needs a "
                "checkpoint_dir the worker can read (shipping raw param "
                "tensors over the control channel is not supported)")
        reply = self._control_request({"cmd": "swap",
                                       "checkpoint_dir": checkpoint_dir})
        self.version = int(reply["version"])
        return self.version

    # -- load ----------------------------------------------------------------
    def outstanding(self):
        with self._lock:
            return self._q.qsize() + len(self._inflight)

    def estimated_wait_s(self):
        if self._ewma_s is None:
            return None
        outstanding = self.outstanding()
        if outstanding == 0:
            # same wedge as LocalReplica's EWMA floor: the round-trip
            # EWMA is measured from enqueue (it INCLUDES queue wait)
            # and only updates on responses, so on an EMPTY replica it
            # is a memory of traffic that already ended and would hold
            # a remembered overload forever, blocking the fleet
            # autoscaler's idle detection.  The VIEW decays with the
            # age of the last response (1s half-life, no mutation —
            # a read-rate-dependent decay would collapse the shared
            # measurement admission shedding floors on): a momentary
            # empty instant mid-flood reads essentially the full
            # floor, real silence reaches any idle threshold within
            # seconds.
            last = self._last_reply_t
            age = 0.0 if last is None else time.monotonic() - last
            return self._ewma_s * 0.5 ** age
        return self._ewma_s * (outstanding + 1) / max(
            len(self._chans), 1)

    # a remote fetch, not a local producer: the worker process's own
    # registry answers its scrapes (see scrape() below)
    def stats(self):   # mxlint: disable=untracked-stats
        try:
            return self._control_request({"cmd": "stats"})
        except (ReplicaLostError, MXNetError):
            return {"lost": True}

    def scrape(self):
        """The worker process's telemetry snapshot ({"values", "prom"})
        over the control channel — the fleet's per-replica scrape leg."""
        reply = self._control_request({"cmd": "metrics"})
        return {"values": dict(reply.get("values") or {}),
                "prom": reply.get("prom", "")}

    def close(self, drain=True):
        if not self._lost.is_set() and drain:
            deadline = time.monotonic() + 30
            while self.outstanding() and time.monotonic() < deadline:
                time.sleep(0.01)
        try:
            if not self._lost.is_set():
                self._control.bare_request({"cmd": "stop"})
        except Exception:
            pass
        self._mark_lost("replica closed")
        for chan in self._chans + [self._control]:
            try:
                chan.close()
            except Exception:
                pass
        if self.process is not None:
            try:
                self.process.wait(timeout=10)
            except Exception:
                self.process.kill()

    def kill(self):
        """SIGKILL the worker process (chaos): no flush, no unwinding."""
        if self.process is not None:
            self.process.kill()
