"""Cross-host serving fleet: placement, SLO autoscaling, host-loss survival.

The router (router.py) made the REPLICA the unit of redundancy; this
module makes the HOST one.  Before it, every `RemoteReplica` lived on
localhost — one dead machine took the whole fleet down, and a traffic
ramp had no way to recruit capacity.  `FleetManager` composes the
repo's existing ingredients into the fleet layer both reference papers
describe (the TensorFlow paper's production serving story; the MLPerf
pods paper's host-level liveness, already reproduced for *training* in
`dist/membership.py`):

* **host-aware placement** — replicas are spawned across a registry of
  `FleetHost` handles with anti-affinity: each new replica lands on the
  live host carrying the fewest of this model's replicas, so one host
  death costs 1/H of capacity, never all of it.  A host whose spawns
  keep failing trips its per-host `CircuitBreaker` and placement skips
  it while it cools off.

* **host liveness via `dist.membership`** — the fleet heartbeats every
  host agent on an interval and feeds the SAME `MembershipTable` the
  elastic trainer uses; a host whose beats go silent past the deadline
  is dead in the next view.  A dead host marks ALL its replicas dead at
  once (`router.declare_lost`), so in-flight requests fail over
  immediately instead of waiting out each replica's own probe silence,
  and the fleet re-places the lost capacity on survivors (backfill —
  its latency is a stat, not a hope).

* **SLO-driven autoscaling** — the `Autoscaler` watches the SAME
  queue-model signal the admission controller sheds on
  (`router.estimated_wait_s()`): sustained est-wait above the SLO
  spawns a replica (warm spinup — with a shared program-cache dir the
  worker certifies ZERO XLA compiles in its READY line, and a compiling
  spinup is a WARN finding); sustained idle retires one through the
  router's drain path.  Hysteresis (a dead band between the breach and
  idle thresholds), a cooldown after every action, and a min/max
  replica budget make the loop flap-proof: an oscillating signal resets
  the streaks and can never thrash the fleet.

* **graceful degradation** — capacity loss raises est-wait, the
  router's admission controller sheds best_effort FIRST (unchanged
  policy, same signal), interactive p99 rides inside its SLO band while
  the autoscaler backfills; `tools/run_chaos.py --fleet` certifies the
  whole story against a real SIGKILLed host.

Fault sites (`resilience.faults`): ``fleet.spawn`` (per replica spawn,
names host + replica) and ``host.down`` (per host probe — a ``drop``
clause simulates host silence without killing anything).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from ..analysis import locks as _locks
from ..analysis import tsan as _tsan
from ..base import MXNetError
from ..dist.membership import MembershipTable
from ..obs import metrics as _obs_metrics
from ..resilience import CircuitBreaker, faults as _faults

__all__ = ["FleetManager", "Autoscaler", "ReplicaSpec", "FleetHost",
           "InProcessHost", "AgentHost"]

# module-level fleet event log for analysis.runtime_report(): every
# scale/host event from every live FleetManager, bounded
_EVENTS = collections.deque(maxlen=512)
_EVENTS_LOCK = _locks.make_lock("serving.fleet.events")


def _note_event(fleet, action, **ctx):
    entry = {"fleet": fleet, "action": action, **ctx}
    with _EVENTS_LOCK:
        _EVENTS.append(entry)
    from .. import profiler as _profiler
    _profiler.record_serving(f"fleet:{fleet}", 0.0, event=action,
                             **{k: v for k, v in ctx.items()
                                if isinstance(v, (str, int, float, bool))})
    return entry


def findings():
    """Fleet findings for `analysis.runtime_report()`: host losses and
    backfills as WARNs (capacity events someone should know about), a
    WARN for any scale-up that compiled XLA programs (the warm-spinup
    contract is ZERO — warm the shared program cache), and one HINT
    summarizing scale traffic per fleet."""
    from ..analysis.findings import Finding, HINT, WARN
    with _EVENTS_LOCK:
        events = list(_EVENTS)
    out = []
    per_fleet = collections.Counter()
    for e in events:
        per_fleet[e["fleet"]] += 1
        if e["action"] == "host_down":
            out.append(Finding(
                "serving.fleet", "host-lost", WARN,
                "fleet '%s': host '%s' declared dead (%s) — %d replica(s) "
                "failed over and re-placed on survivors"
                % (e["fleet"], e.get("host"), e.get("reason", "?"),
                   e.get("replicas", 0)),
                location="serving.fleet"))
        elif e["action"] == "backfill_complete":
            out.append(Finding(
                "serving.fleet", "backfill", WARN,
                "fleet '%s': backfilled to target %d in %.2fs after "
                "capacity loss"
                % (e["fleet"], e.get("target", 0),
                   e.get("latency_s", 0.0)),
                location="serving.fleet"))
        elif e["action"] == "scale_up" and e.get("spinup_compiles"):
            out.append(Finding(
                "serving.fleet", "cold-spinup", WARN,
                "fleet '%s': scale-up of '%s' on host '%s' compiled %d "
                "XLA program(s) — warm spinup should be ZERO-compile; "
                "share MXNET_PROGRAM_CACHE_DIR across the fleet"
                % (e["fleet"], e.get("replica"), e.get("host"),
                   e.get("spinup_compiles")),
                location="serving.fleet"))
    for fleet, n in sorted(per_fleet.items()):
        ups = sum(1 for e in events
                  if e["fleet"] == fleet and e["action"] == "scale_up")
        downs = sum(1 for e in events
                    if e["fleet"] == fleet and e["action"] == "scale_down")
        out.append(Finding(
            "serving.fleet", "summary", HINT,
            "fleet '%s': %d event(s) — %d scale-up, %d scale-down"
            % (fleet, n, ups, downs), location="serving.fleet"))
    return out


def reset_findings():
    with _EVENTS_LOCK:
        _EVENTS.clear()


class ReplicaSpec:
    """What to spawn: one served model's worker recipe, JSON-able so a
    host agent on another machine can execute it (`to_msg`/`from_msg`
    round-trip over the transport frames)."""

    __slots__ = ("name", "prefix", "epoch", "symbol_file",
                 "checkpoint_dir", "data_shapes", "buckets", "env",
                 "concurrency")

    def __init__(self, *, data_shapes, name="model", prefix=None, epoch=0,
                 symbol_file=None, checkpoint_dir=None,
                 buckets=(1, 2, 4, 8), env=None, concurrency=2):
        self.name = str(name)
        self.prefix = prefix
        self.epoch = int(epoch)
        self.symbol_file = symbol_file
        self.checkpoint_dir = checkpoint_dir
        self.data_shapes = [(str(n), tuple(int(d) for d in s))
                            for n, s in data_shapes]
        self.buckets = tuple(int(b) for b in buckets)
        self.env = dict(env or {})
        self.concurrency = int(concurrency)

    def to_msg(self):
        return {"name": self.name, "prefix": self.prefix,
                "epoch": self.epoch, "symbol_file": self.symbol_file,
                "checkpoint_dir": self.checkpoint_dir,
                "data_shapes": [[n, list(s)] for n, s in self.data_shapes],
                "buckets": list(self.buckets), "env": dict(self.env),
                "concurrency": self.concurrency}

    @classmethod
    def from_msg(cls, msg):
        return cls(data_shapes=[(n, tuple(s))
                                for n, s in msg["data_shapes"]],
                   name=msg.get("name", "model"),
                   prefix=msg.get("prefix"),
                   epoch=msg.get("epoch", 0),
                   symbol_file=msg.get("symbol_file"),
                   checkpoint_dir=msg.get("checkpoint_dir"),
                   buckets=msg.get("buckets", (1, 2, 4, 8)),
                   env=msg.get("env"),
                   concurrency=msg.get("concurrency", 2))


class FleetHost:
    """One serving host the fleet can place replicas on.

    The contract: ``heartbeat()`` raises when the host is unreachable
    (the membership deadline turns sustained failure into death);
    ``spawn_replica(spec, replica_id)`` starts one worker THERE and
    returns the router-side `Replica` handle."""

    host_id = "?"

    def heartbeat(self):
        raise NotImplementedError

    def spawn_replica(self, spec, replica_id):
        raise NotImplementedError

    def scrape(self):
        """The host's telemetry snapshot ({"values", "prom"}), or None
        when this host kind has no scrape leg (in-process hosts share
        the manager's own registry)."""
        return None

    def close(self):
        pass


class InProcessHost(FleetHost):
    """A logical host inside this process: ``spawn`` is a caller-supplied
    factory (tests hand it a `LocalReplica` builder), and
    liveness is a flag tests flip.  The autoscaler/placement logic is
    identical to the cross-host path — only the actuation is local."""

    def __init__(self, host_id, spawn=None):
        self.host_id = str(host_id)
        self._spawn = spawn
        self._down = False

    def heartbeat(self):
        if self._down:
            raise MXNetError(f"host '{self.host_id}' is down")
        return {"ok": True, "host_id": self.host_id}

    def spawn_replica(self, spec, replica_id):
        if self._down:
            raise MXNetError(f"host '{self.host_id}' is down")
        if self._spawn is None:
            raise MXNetError(
                f"host '{self.host_id}': no spawn factory configured")
        return self._spawn(spec, replica_id)

    def fail(self):
        """Simulate host death (tests): heartbeats fail from now on."""
        self._down = True

    def recover(self):
        self._down = False


class AgentHost(FleetHost):
    """A host fronted by its `serving.hostd` agent daemon.

    Two serial channels: a short-timeout control channel (heartbeats
    answer in microseconds or the host is in trouble) and a separate
    long-timeout spawn channel (a cold worker warmup legitimately takes
    a while; it must not block the next heartbeat)."""

    def __init__(self, host_id, host, port, process=None,
                 control_timeout=5.0, spawn_timeout=300.0):
        self.host_id = str(host_id)
        self.host, self.port = str(host), int(port)
        self.process = process       # Popen when launch_local()ed
        self._control = self._make_channel(control_timeout)
        self._spawn_chan = self._make_channel(spawn_timeout)

    def _make_channel(self, timeout):
        from ..dist.transport import Channel
        from ..resilience import RetryPolicy
        # short connect window: a dead host should be DIAGNOSED in ~a
        # couple of seconds so the membership deadline can act, not
        # nursed through a long reconnect budget
        return Channel(self.host, self.port, timeout=timeout,
                       connect_wait=2.0,
                       retry=RetryPolicy(max_attempts=2, base_delay=0.05,
                                         max_delay=0.2))

    @classmethod
    def connect(cls, host_id, endpoint, **kw):
        """Attach to an ALREADY-RUNNING host daemon by endpoint —
        ``"host:port"`` / ``":port"`` / ``"port"``
        (`dist.transport.parse_endpoint` spellings).  The production
        cross-host path: an operator starts ``python -m
        incubator_mxnet_tpu.serving.hostd`` on each machine and hands
        the fleet the endpoints; `launch_local` is the single-machine
        convenience around the same protocol."""
        from ..dist.transport import parse_endpoint
        host, port = parse_endpoint(endpoint)
        return cls(host_id, host, port, **kw)

    @classmethod
    def launch_local(cls, host_id, bind_host="127.0.0.1", env=None,
                     ready_timeout=60.0, launch=None):
        """Start a host daemon — locally by default, or anywhere via the
        ``launch(cmd, env) -> Popen`` hook (ssh wrapper, container exec).
        The daemon and every worker it spawns share one process group
        (``start_new_session``), so a SIGKILL of the group is a faithful
        whole-host power-off (the chaos schedule's weapon).  The
        launch-and-handshake loop is `replica.launch_worker` — one
        implementation for workers AND daemons."""
        import sys
        from .replica import launch_worker
        cmd = [sys.executable, "-m", "incubator_mxnet_tpu.serving.hostd",
               "--host-id", str(host_id), "--host", bind_host]
        proc, port, _ready = launch_worker(
            cmd, env=env, name=f"hostd '{host_id}'",
            ready_timeout=ready_timeout, launch=launch, tag=host_id,
            port_prefix="HOSTD_PORT", ready_prefix="HOSTD_READY",
            start_new_session=True, thread_prefix="mx-hostd")
        return cls(host_id, bind_host, port, process=proc)

    def _request(self, chan, msg):
        reply = chan.request(msg)
        if "error" in reply:
            raise MXNetError(reply["error"])
        return reply

    def heartbeat(self):
        return self._request(self._control, {"cmd": "hb"})

    def scrape(self):
        """The daemon process's registry snapshot over the control
        channel (the fleet-wide scrape's per-host leg)."""
        reply = self._request(self._control, {"cmd": "metrics"})
        return {"values": dict(reply.get("values") or {}),
                "prom": reply.get("prom", "")}

    def spawn_replica(self, spec, replica_id):
        from .replica import RemoteReplica
        reply = self._request(self._spawn_chan,
                              {"cmd": "spawn", "spec": spec.to_msg(),
                               "replica_id": replica_id})
        rep = RemoteReplica(self.host, int(reply["port"]),
                            replica_id=replica_id,
                            concurrency=spec.concurrency)
        rep.ready_info = dict(reply.get("ready", {}))
        return rep

    def close(self):
        try:
            self._control.bare_request({"cmd": "stop"})
        except Exception:
            pass
        for chan in (self._control, self._spawn_chan):
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
        """SIGKILL the whole host process group (chaos): the daemon AND
        every worker it spawned die with no flush, no unwinding."""
        import os
        import signal
        if self.process is not None:
            try:
                os.killpg(self.process.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                self.process.kill()


class Autoscaler:
    """The scale decision, isolated from actuation so seeded est-wait
    traces drive it deterministically in tests (injectable clock, no
    threads, no subprocesses).

    ``observe(est_wait_ms, live, busy)`` returns ``(action, reason)``
    where action is "up", "down", or None:

    * est-wait above ``slo_ms`` (or None — no live capacity at all)
      starts/extends the BREACH streak; sustained past ``up_after_s``
      and outside the cooldown -> "up" (clamped at ``max_replicas``).
    * est-wait below ``idle_fraction * slo_ms`` with nothing in flight
      starts/extends the IDLE streak; sustained past ``down_after_s``
      and outside the cooldown -> "down" (clamped at ``min_replicas``).
    * anything between the two thresholds is the HYSTERESIS dead band:
      both streaks reset, so a signal oscillating around the SLO can
      never accumulate a decision — and every action arms the cooldown,
      so even a pathological square-wave signal is rate-limited to one
      scale event per ``cooldown_s``.
    """

    def __init__(self, slo_ms, *, up_after_s, down_after_s, cooldown_s,
                 min_replicas, max_replicas, idle_fraction=0.1,
                 clock=time.monotonic):
        if int(min_replicas) < 0 or int(max_replicas) < int(min_replicas):
            raise MXNetError(
                f"autoscaler: invalid replica budget "
                f"[{min_replicas}, {max_replicas}]")
        self.slo_ms = float(slo_ms)
        self.up_after_s = float(up_after_s)
        self.down_after_s = float(down_after_s)
        self.cooldown_s = float(cooldown_s)
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.idle_fraction = float(idle_fraction)
        self._clock = clock
        self._breach_since = None
        self._idle_since = None
        self._cooldown_until = 0.0
        self.clamped_at_max = 0
        self.clamped_at_min = 0

    def cooldown_remaining_s(self):
        return max(self._cooldown_until - self._clock(), 0.0)

    def streaks(self):
        now = self._clock()
        return {
            "breach_s": (now - self._breach_since
                         if self._breach_since is not None else 0.0),
            "idle_s": (now - self._idle_since
                       if self._idle_since is not None else 0.0)}

    def observe(self, est_wait_ms, live, busy):
        now = self._clock()
        breach = est_wait_ms is None or est_wait_ms > self.slo_ms
        idle = (not breach and not busy
                and est_wait_ms <= self.idle_fraction * self.slo_ms)
        if breach:
            self._idle_since = None
            if self._breach_since is None:
                self._breach_since = now
            sustained = now - self._breach_since
            if sustained >= self.up_after_s and now >= self._cooldown_until:
                if live >= self.max_replicas:
                    # count EPISODES (one per sustain window), not
                    # ticks: resetting the streak means a continuous
                    # clamped breach increments once per up_after_s,
                    # independent of the caller's tick rate
                    self.clamped_at_max += 1
                    self._breach_since = None
                    return None, None
                self._breach_since = None
                self._cooldown_until = now + self.cooldown_s
                wait = ("no live capacity" if est_wait_ms is None
                        else f"est-wait {est_wait_ms:.0f} ms > SLO "
                             f"{self.slo_ms:g} ms")
                return "up", f"{wait} sustained {sustained:.1f}s"
        elif idle:
            self._breach_since = None
            if self._idle_since is None:
                self._idle_since = now
            sustained = now - self._idle_since
            if sustained >= self.down_after_s \
                    and now >= self._cooldown_until:
                if live <= self.min_replicas:
                    self.clamped_at_min += 1
                    self._idle_since = None    # episode, not tick, count
                    return None, None
                self._idle_since = None
                self._cooldown_until = now + self.cooldown_s
                return "down", (
                    f"est-wait {est_wait_ms:.1f} ms < "
                    f"{self.idle_fraction * self.slo_ms:g} ms idle "
                    f"threshold sustained {sustained:.1f}s")
        else:
            # the dead band: neither overloaded nor provably idle
            self._breach_since = None
            self._idle_since = None
        return None, None


class _HostState:
    """Fleet-side bookkeeping for one host."""

    def __init__(self, rank, handle, breaker):
        self.rank = rank             # membership-table rank
        self.handle = handle
        self.breaker = breaker       # trips on consecutive spawn failures
        self.alive = True
        self.beats = 0
        self.hb_failures = 0         # consecutive


class FleetManager:
    """The fleet control loop over a `ReplicaRouter` (module docstring).

    ``hosts`` is the host registry (`FleetHost` handles); ``spec`` is
    the one model this fleet scales (multi-model fleets run one manager
    per model — placement is per-model anti-affinity by definition).
    The manager owns placement, host liveness, and the autoscaler; the
    router keeps owning dispatch, replica health, failover, and
    admission shedding — both act on the same est-wait signal.
    """

    def __init__(self, hosts, spec, router=None, name="fleet",
                 target_replicas=None, min_replicas=None,
                 max_replicas=None, slo_ms=None, tick_s=None,
                 up_after_s=None, down_after_s=None, cooldown_s=None,
                 idle_fraction=None, host_heartbeat_s=None,
                 host_deadline_s=None, clock=time.monotonic, start=True):
        from .. import config as _config
        from .router import ReplicaRouter
        if not hosts:
            raise MXNetError("fleet: at least one host is required")
        ids = [h.host_id for h in hosts]
        if len(set(ids)) != len(ids):
            raise MXNetError(f"fleet: duplicate host ids in {ids}")
        self.name = str(name)
        self.spec = spec
        self._clock = clock
        self.router = router if router is not None \
            else ReplicaRouter(name=f"{self.name}-router")
        self._owns_router = router is None

        def knob(value, key):
            return value if value is not None else _config.get(key)

        self.tick_s = float(knob(tick_s, "MXNET_FLEET_TICK_S"))
        self.host_heartbeat_s = float(
            knob(host_heartbeat_s, "MXNET_FLEET_HOST_HEARTBEAT_S"))
        self.host_deadline_s = float(
            knob(host_deadline_s, "MXNET_FLEET_HOST_DEADLINE_S"))
        min_r = int(knob(min_replicas, "MXNET_FLEET_MIN_REPLICAS"))
        max_r = int(knob(max_replicas, "MXNET_FLEET_MAX_REPLICAS"))
        self.autoscaler = Autoscaler(
            float(knob(slo_ms, "MXNET_FLEET_SLO_MS")),
            up_after_s=float(knob(up_after_s, "MXNET_FLEET_UP_AFTER_S")),
            down_after_s=float(
                knob(down_after_s, "MXNET_FLEET_DOWN_AFTER_S")),
            cooldown_s=float(knob(cooldown_s, "MXNET_FLEET_COOLDOWN_S")),
            min_replicas=min_r, max_replicas=max_r,
            idle_fraction=float(
                knob(idle_fraction, "MXNET_FLEET_IDLE_FRACTION")),
            clock=clock)
        self.target = int(target_replicas if target_replicas is not None
                          else max(min_r, 1))
        if not min_r <= self.target <= max_r:
            raise MXNetError(
                f"fleet '{self.name}': target {self.target} outside the "
                f"replica budget [{min_r}, {max_r}]")
        self._lock = _locks.make_lock("serving.fleet")
        _tsan.instrument(self, f"serving.fleet[{self.name}]")
        _obs_metrics.register_producer(
            "fleet" if self.name == "fleet" else f"fleet.{self.name}",
            self.stats)
        self._placement = {}          # replica_id -> host_id
        self._rid_seq = itertools.count(1)
        # host liveness rides the SAME MembershipTable the elastic
        # trainer uses: rank = registry index, deadline = host death
        self.membership = MembershipTable(len(hosts),
                                          self.host_deadline_s,
                                          clock=clock)
        self._hosts = {}
        for rank, handle in enumerate(hosts):
            breaker = CircuitBreaker(
                failure_threshold=int(
                    _config.get("MXNET_SERVING_BREAKER_THRESHOLD")),
                reset_timeout=float(
                    _config.get("MXNET_SERVING_BREAKER_RESET_S")))
            self._hosts[handle.host_id] = _HostState(rank, handle, breaker)
            # optimistic initial beat: a host that NEVER answers must
            # still age into the dead list (the table only judges hosts
            # it has seen)
            self.membership.heartbeat(rank, self.membership.epoch,
                                      label=handle.host_id)
        # counters / events
        self.scale_ups = 0
        self.scale_downs = 0
        self.hosts_lost = 0
        self.backfills = 0
        self.spawn_failures = 0
        self.last_backfill_s = None
        self._backfill_started = None   # capacity-loss timestamp
        self._scale_reason = None       # last autoscale decision's why
        self._events = collections.deque(maxlen=256)
        self._last_signal_ms = None
        self._closed = threading.Event()
        self._thread = None
        self._placer = None
        self._probers = []
        if start:
            self.start()

    # -- lifecycle ------------------------------------------------------------
    def start(self):
        """Place the initial fleet and start the control loops: ONE
        prober thread per host (a dead host's blocking connect attempts
        must never starve another host's membership beats — probing
        serially is how a single dead machine gets every healthy host
        falsely declared dead), the WATCH loop (liveness + autoscale
        decisions, never blocks on actuation), and the PLACER loop
        (spawns/retires toward target — a cold spawn can take minutes,
        and a second host dying during it must still be declared dead
        by the watch loop immediately, not after the spawn returns)."""
        if self._thread is not None:
            return self
        # probers BEFORE placement: the initial spawns can take seconds
        # (a cold ladder compile), and the constructor's seed beats must
        # not age past the deadline while they run
        self._probers = []
        for hs in self._hosts.values():
            t = threading.Thread(
                target=self._probe_loop, args=(hs,), daemon=True,
                name=f"mx-fleet-{self.name}-hb-{hs.handle.host_id}")
            t.start()
            self._probers.append(t)
        self._reconcile("initial placement")
        self._thread = threading.Thread(
            target=self._watch_loop, daemon=True,
            name=f"mx-fleet-{self.name}")
        self._thread.start()
        self._placer = threading.Thread(
            target=self._place_loop, daemon=True,
            name=f"mx-fleet-{self.name}-placer")
        self._placer.start()
        return self

    def shutdown(self, drain=True, close_hosts=False):
        self._closed.set()
        if self._thread is not None:
            _tsan.join_thread(self._thread, 30,
                              owner=f"FleetManager[{self.name}]")
            _tsan.join_thread(self._placer, 30,
                              owner=f"FleetManager[{self.name}]")
            for t in self._probers:
                _tsan.join_thread(t, 15,
                                  owner=f"FleetManager[{self.name}]")
        if self._owns_router:
            self.router.shutdown(drain=drain)
        if close_hosts:
            for hs in list(self._hosts.values()):
                try:
                    hs.handle.close()
                except Exception:
                    pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=True)

    # -- placement ------------------------------------------------------------
    def _live_hosts(self):
        with self._lock:
            return [hs for hs in self._hosts.values() if hs.alive]

    def _placed_on(self, host_id):
        with self._lock:
            return [rid for rid, hid in self._placement.items()
                    if hid == host_id]

    def _pick_host(self):
        """Anti-affinity: the live host (breaker permitting) carrying
        the fewest of this fleet's replicas; registry order breaks
        ties.  None when no host can take work."""
        with self._lock:
            crowd = collections.Counter(self._placement.values())
            cands = [hs for hs in self._hosts.values()
                     if hs.alive and hs.breaker.state != "open"]
        cands.sort(key=lambda hs: (crowd[hs.handle.host_id], hs.rank))
        for hs in cands:
            if hs.breaker.allow():
                return hs
        return None

    def _spawn_one(self, reason):
        hs = self._pick_host()
        if hs is None:
            states = {h.handle.host_id: ("alive" if h.alive else "dead",
                                         h.breaker.state)
                      for h in self._hosts.values()}
            raise MXNetError(
                f"fleet '{self.name}': no live host can take a replica "
                f"(hosts: {states})")
        host_id = hs.handle.host_id
        rid = f"{self.spec.name}@{host_id}/{next(self._rid_seq)}"
        t0 = self._clock()
        try:
            _faults.fire("fleet.spawn", host=host_id, replica=rid)
            replica = hs.handle.spawn_replica(self.spec, rid)
        except Exception as exc:
            hs.breaker.record_failure()
            with self._lock:
                self.spawn_failures += 1
            self._event("spawn_failed", host=host_id, replica=rid,
                        reason=f"{type(exc).__name__}: {exc}")
            raise MXNetError(
                f"fleet '{self.name}': spawning {rid} on host "
                f"'{host_id}' failed: {exc}") from exc
        hs.breaker.record_success()
        self.router.add_replica(replica)
        ready = dict(getattr(replica, "ready_info", None) or {})
        with self._lock:
            self._placement[rid] = host_id
        self._event("scale_up", host=host_id, replica=rid, reason=reason,
                    duration_s=round(self._clock() - t0, 3),
                    spinup_compiles=ready.get("compiles"),
                    spinup_disk_hits=ready.get("disk_hits"))
        with self._lock:
            self.scale_ups += 1
        return rid

    def _retire_one(self, reason):
        """Scale-down through the router's drain path: pick a replica on
        the MOST crowded host (re-balancing toward anti-affinity), the
        one with the least outstanding work."""
        with self._lock:
            placement = dict(self._placement)
        if not placement:
            return None
        crowd = collections.Counter(placement.values())
        slots = self._router_slots()

        def key(rid):
            slot = slots.get(rid)
            out = slot.replica.outstanding() if slot is not None else 0
            return (-crowd[placement[rid]], out)

        rid = sorted(placement, key=key)[0]
        host_id = placement[rid]
        t0 = self._clock()
        # placement out FIRST (the fleet's source of truth), actuation
        # after: during the drain the router still holds the slot, and
        # _sync_placement seeing a placement entry with no slot would
        # misread this deliberate retire as a replica loss and re-arm
        # the backfill clock
        with self._lock:
            self._placement.pop(rid, None)
            self.scale_downs += 1
        try:
            self.router.remove_replica(rid, drain=True)
        except MXNetError:
            pass   # already gone (raced a death) — the sync tick cleans up
        self._event("scale_down", host=host_id, replica=rid, reason=reason,
                    duration_s=round(self._clock() - t0, 3))
        return rid

    def _router_slots(self):
        with self.router._lock:
            return dict(self.router._slots)

    def _live_replicas(self):
        """Replicas this fleet placed that the router still serves."""
        from .router import DEAD
        slots = self._router_slots()
        with self._lock:
            placement = dict(self._placement)
        return [rid for rid in placement
                if rid in slots and slots[rid].state != DEAD]

    def _spawn_reason(self):
        """Why the next placer spawn happens: a pending backfill wins
        (capacity loss is the louder story), else the autoscaler's last
        decision."""
        with self._lock:
            if self._backfill_started is not None:
                return "backfill after capacity loss"
            return self._scale_reason or "reconcile to target"

    def _reconcile(self, reason=None):
        """Spawn until the live count meets the target (initial
        placement and post-loss backfill share this one path)."""
        guard = 0
        while not self._closed.is_set():
            live = len(self._live_replicas())
            if live >= self.target:
                break
            if reason is None:
                reason = self._spawn_reason()
            guard += 1
            if guard > 2 * self.autoscaler.max_replicas + 4:
                break   # spawns keep dying — breakers/events tell why
            try:
                self._spawn_one(reason)
            except MXNetError:
                if not self._live_hosts():
                    break
                self._closed.wait(min(self.tick_s, 0.2))
        live_now = len(self._live_replicas())
        with self._lock:
            # one lock hold for the whole completion decision: a
            # concurrent scale-down cancels the measurement by nulling
            # _backfill_started together with lowering target, and a
            # split read could pair the stale start with the shrunken
            # target and report a backfill that never happened
            started = self._backfill_started
            if started is None or live_now < self.target:
                return
            latency = self._clock() - started
            self._backfill_started = None
            self.backfills += 1
            self.last_backfill_s = round(latency, 3)
        self._event("backfill_complete", target=self.target,
                    latency_s=round(latency, 3))

    # -- host liveness --------------------------------------------------------
    def _probe_loop(self, hs):
        """One host's heartbeat thread: its beats feed the membership
        table regardless of how long any OTHER host's failing probe
        blocks.  The probe itself never judges death — only silence in
        the table past the deadline does (`_check_hosts`, on the
        control loop)."""
        host_id = hs.handle.host_id
        while not self._closed.wait(self.host_heartbeat_s):
            try:
                _faults.fire("host.down", host=host_id)
                hs.handle.heartbeat()
            except Exception:
                with self._lock:
                    hs.hb_failures += 1
                continue
            # membership beat BEFORE flipping alive: the watch loop
            # judges by (alive AND rank-in-dead-view), and alive=True
            # against a still-stale view would let _on_host_down
            # re-fire on a rejoining host (double-counted hosts_lost,
            # a phantom instant backfill)
            self.membership.heartbeat(hs.rank, self.membership.epoch,
                                      label=host_id)
            with self._lock:
                hs.beats += 1
                hs.hb_failures = 0
                was_dead = not hs.alive
                hs.alive = True
            if was_dead:
                self._event("host_rejoined", host=host_id)

    def _check_hosts(self):
        view = self.membership.view()
        with self._lock:
            hosts = list(self._hosts.values())
        for hs in hosts:
            if hs.rank in view["dead"] and hs.alive:
                self._on_host_down(hs, view["age"].get(hs.rank))

    def _on_host_down(self, hs, age_s):
        """A dead HOST kills all its replicas at once: fail them over
        immediately, drop them from the fleet, and backfill on the
        survivors.  The placement drop is ATOMIC (one lock hold for
        every replica on the host): the placer runs concurrently, and a
        one-at-a-time sweep would let it observe a live count that
        still includes a not-yet-removed dead replica — enough to
        declare a backfill complete that hasn't happened."""
        host_id = hs.handle.host_id
        # re-read the CURRENT view: _check_hosts judged from a
        # snapshot, and a rejoining host beats the table BEFORE its
        # alive flag flips — so a host that is alive again by now is
        # out of the fresh dead list and must not be re-declared
        if hs.rank not in self.membership.view()["dead"]:
            return
        with self._lock:
            if not hs.alive:
                return
            hs.alive = False
            self.hosts_lost += 1
            if self._backfill_started is None:
                self._backfill_started = self._clock()
            lost = [rid for rid, hid in self._placement.items()
                    if hid == host_id]
            for rid in lost:
                self._placement.pop(rid, None)
        # event BEFORE the router sweep: the declaration is the fact,
        # the removals its consequence — and the placer can finish the
        # whole backfill while the sweep runs, so anyone observing
        # backfills >= 1 must already see the host_down that caused it
        reason = (f"heartbeat silence {age_s:.1f}s > deadline "
                  f"{self.host_deadline_s:g}s"
                  if age_s is not None else "heartbeat silence")
        self._event("host_down", host=host_id, reason=reason,
                    replicas=len(lost))
        _faults.note("host_lost", site="host.down", host=host_id,
                     replicas=len(lost))
        for rid in lost:
            self.router.declare_lost(rid)
            try:
                self.router.remove_replica(rid, drain=False)
            except MXNetError:
                pass

    def _sync_placement(self):
        """Garbage-collect replicas the router declared dead on its own
        (individual replica death, not host death) so the live count —
        and therefore backfill — sees the capacity loss."""
        from .router import DEAD
        slots = self._router_slots()
        with self._lock:
            placement = dict(self._placement)
        for rid, host_id in placement.items():
            slot = slots.get(rid)
            if slot is not None and slot.state != DEAD:
                continue
            if slot is not None:
                try:
                    self.router.remove_replica(rid, drain=False)
                except MXNetError:
                    pass
            with self._lock:
                self._placement.pop(rid, None)
                if self._backfill_started is None:
                    self._backfill_started = self._clock()
            self._event("replica_lost", host=host_id, replica=rid)

    # -- the control loops ----------------------------------------------------
    def _watch_loop(self):
        """Liveness + autoscale DECISIONS only — never blocks on a
        spawn or a drain, so a host death is declared (and its replicas
        failed over at once) even while the placer is minutes deep in a
        cold spawn."""
        while not self._closed.wait(self.tick_s):
            try:
                self._check_hosts()
                self._sync_placement()
                self._autoscale_tick()
            except Exception as exc:   # the loop must outlive any tick
                self._event("tick_error",
                            reason=f"{type(exc).__name__}: {exc}")

    def _place_loop(self):
        """Actuation: reconcile the fleet toward target (spawns for
        initial placement growth and backfill, retires for surplus)."""
        while not self._closed.wait(self.tick_s):
            try:
                self._retire_surplus()
                self._reconcile()
            except Exception as exc:
                self._event("tick_error",
                            reason=f"{type(exc).__name__}: {exc}")

    def _retire_surplus(self):
        with self._lock:
            reason = self._scale_reason
        while not self._closed.is_set():
            if len(self._live_replicas()) <= self.target:
                break
            if self._retire_one(reason or "scale-down") is None:
                break

    def _autoscale_tick(self):
        wait_s = self.router.estimated_wait_s()
        est_ms = None if wait_s is None else wait_s * 1e3
        with self._lock:
            self._last_signal_ms = est_ms
        live = self._live_replicas()
        slots = self._router_slots()
        busy = any(slots[rid].replica.outstanding() > 0
                   for rid in live if rid in slots)
        action, reason = self.autoscaler.observe(est_ms, len(live), busy)
        if action == "up":
            # grow to at least live+1 but NEVER below the current
            # target: mid-backfill (live transiently under target after
            # a host loss) a scale-up must not shrink the backfill goal.
            # The PLACER does the spawning — a decision is instant, an
            # actuation can block for minutes.
            with self._lock:
                self.target = min(max(self.target, len(live) + 1),
                                  self.autoscaler.max_replicas)
                self._scale_reason = reason
        elif action == "down":
            with self._lock:
                self.target = max(len(live) - 1,
                                  self.autoscaler.min_replicas)
                self._scale_reason = reason
                # an intervening scale-down invalidates a pending
                # backfill measurement: without this, target meeting
                # the SHRUNKEN live count would report a successful
                # "backfill" (with idle-period latency) that never
                # happened
                self._backfill_started = None

    # -- observability --------------------------------------------------------
    def _event(self, action, **ctx):
        entry = _note_event(self.name, action,
                            t=round(self._clock(), 3), **ctx)
        with self._lock:
            self._events.append(entry)

    def stats(self):
        """Fleet snapshot: per-host replica counts + liveness, the
        placement map, scale events with reasons, backfill latency, and
        the autoscaler's live signal/streaks — the KVStore/router
        stats() convention."""
        view = self.membership.view()
        with self._lock:
            placement = dict(self._placement)
            events = list(self._events)
            snap = {
                "fleet": self.name,
                "target": self.target,
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
                "hosts_lost": self.hosts_lost,
                "backfills": self.backfills,
                "spawn_failures": self.spawn_failures,
                "backfill_latency_s": self.last_backfill_s,
                "signal": {
                    "est_wait_ms": self._last_signal_ms,
                    "slo_ms": self.autoscaler.slo_ms,
                    "clamped_at_max": self.autoscaler.clamped_at_max,
                    "clamped_at_min": self.autoscaler.clamped_at_min,
                    "cooldown_remaining_s": round(
                        self.autoscaler.cooldown_remaining_s(), 3),
                    **{k: round(v, 3)
                       for k, v in self.autoscaler.streaks().items()},
                },
            }
            hosts = {}
            for hid, hs in self._hosts.items():
                hosts[hid] = {
                    "alive": hs.alive,
                    "replicas": sum(1 for h in placement.values()
                                    if h == hid),
                    "beats": hs.beats,
                    "hb_failures": hs.hb_failures,
                    "age_s": view["age"].get(hs.rank),
                    "spawn_breaker": hs.breaker.state,
                }
        snap["live_replicas"] = len(self._live_replicas())
        snap["hosts"] = hosts
        snap["placement"] = placement
        snap["events"] = events[-32:]
        return snap

    def scrape(self):
        """The fleet-wide telemetry aggregate: this process's registry
        (router, fleet, serving.* producers), every live host daemon's
        snapshot, and every placed remote replica's worker snapshot —
        one call, the whole fleet.  Dead or unreachable legs are
        recorded under ``unreachable`` instead of failing the scrape
        (a half-dead fleet is exactly when you need the numbers)."""
        from ..obs.scrape import metrics_reply
        local = metrics_reply()
        out = {"fleet": self.name,
               "local": {"values": local["values"],
                         "prom": local["prom"]},
               "hosts": {}, "replicas": {}, "unreachable": []}
        with self._lock:
            hosts = {hid: hs.handle for hid, hs in self._hosts.items()}
        for hid, handle in hosts.items():
            try:
                snap = handle.scrape()
            except Exception:
                out["unreachable"].append(f"host:{hid}")
                continue
            if snap is not None:
                out["hosts"][hid] = snap
        for rid, slot in self._router_slots().items():
            scrape_fn = getattr(slot.replica, "scrape", None)
            if scrape_fn is None:
                continue
            try:
                out["replicas"][rid] = scrape_fn()
            except Exception:
                out["unreachable"].append(f"replica:{rid}")
        return out
