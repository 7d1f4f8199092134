"""Cross-process distributed tracing: trace-id/span-id context over
the transport frames, spans as shared-file JSONL.

The profiler's chrome trace answers "what did THIS process spend time
on"; it cannot answer "where did this request/step spend its time
ACROSS processes" — a routed request crosses router -> transport ->
subprocess worker -> batcher -> fused execute, and a training step
crosses fit -> kvstore push -> parameter server.  This module adds the
missing correlation:

* a **span** is one timed operation with a ``trace`` id (the whole
  request/step), its own ``span`` id, and a ``parent`` span id — ids
  are ``pid``-prefixed counters, unique across every process of a run
  with zero coordination;
* the current span rides a ``contextvars`` context; `span()` opens a
  child of whatever is current (or a new root);
* **propagation**: the dist transport injects the current span as a
  ``tr`` frame field on every request (`rpc_span`), and every server
  handler (replica worker, host daemon, parameter server) adopts it
  (`server_span`) — so the worker-side execute span is a CHILD of the
  router-side dispatch span, in another process;
* finished spans append to a **shared JSONL file** (`obs.jsonl_sink`
  — O_APPEND line-atomic, pid/thread-stamped), one line per span, so
  every process of a run writes the same file and
  ``tools/mxtrace.py`` merges them into one Perfetto-loadable chrome
  trace where a single request reads as one connected tree with flow
  arrows across process lanes.

Enabled by pointing ``MXNET_OBS_TRACE`` at the shared span file (the
env propagates to spawned workers/daemons) or `enable(path)`.  Off,
every hook is a single global read returning a shared no-op span.

**Phases.**  A `phase` is a span that is also tallied: with tracing on
it is exactly a `span` (one record, one annotation), and on or off it
adds its wall seconds and a count to a process-wide tally that
`phases()` returns and the ``phase`` metrics namespace renders.  Set-up
(the package import, a `fit`'s bind and init, the trace, lower and
compile of a program) and a `fit`'s epoch end are phases, so a job
with tracing off still knows where its start went.  JAX's own compile
events (`watch_jax`) are put down to the innermost phase open on the
calling thread, or to ``""`` where none is.  `reset()` leaves the
tally; `reset_phases()` clears it.

**One clock.**  A live span also holds a `jax.profiler.TraceAnnotation`
of its name for as long as it is open, so a profile taken by anyone —
a benchmark's traced run, an operator's `jax.profiler.trace` — shows
``io.stage``, ``fit.callbacks`` ... on the host plane against the
device's ops, on the profile's own clock.  With no profile running the
annotation is a check of one flag; `record_span` (already timed,
post hoc) cannot hold one.  The
in-memory buffer is bounded (``MXNET_OBS_TRACE_BUFFER``, drop-oldest
with a ``dropped`` counter surfaced as a metric); it auto-flushes
every ``_FLUSH_EVERY`` spans and at exit, and explicitly via
`flush()`.
"""
from __future__ import annotations

import atexit
import contextlib
import contextvars
import itertools
import os
import threading
import time

from . import jsonl_sink as _jsonl

__all__ = ["enabled", "enable", "disable", "flush", "stats",
           "span", "start_span", "record_span", "current_frame",
           "activate", "rpc_span", "server_span", "NULL_SPAN",
           "phase", "record_phase", "phases", "reset_phases", "watch_jax"]

_ctx = contextvars.ContextVar("mx_obs_trace", default=None)

_FLUSH_EVERY = 512

_lock = threading.Lock()
_enabled = None            # tri-state: None = read MXNET_OBS_TRACE lazily
_path = None
_buffer = []
_cap = None
_dropped = 0
_flushed = 0
_ended = 0
_atexit_armed = False
_flush_event = threading.Event()
_flusher = [None]
# observability of the observability: nanoseconds the background
# flusher spent serializing + writing spans (the increment races are
# benign — it is a counter).  Exposed as 'trace.self_time_ms' in the
# metrics scrape.
_self_ns = [0]
# pid-prefixed ids: unique across processes with zero coordination (the
# pid is cached — a syscall per span id would tax the hot path — and
# refreshed after fork so a forked child's ids diverge)
_ids = itertools.count(1)
_PID = [os.getpid()]
_id_prefix = ["%x-" % _PID[0]]


def _refresh_pid():
    _PID[0] = os.getpid()
    _id_prefix[0] = "%x-" % _PID[0]


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_refresh_pid)


def _id(kind):
    return kind + _id_prefix[0] + str(next(_ids))


# span timestamps are wall-clock us (time.time_ns() // 1000), not
# perf_counter: spans from DIFFERENT processes must land on one
# comparable timeline in the merged trace


def enabled():
    global _enabled, _path, _cap
    if _enabled is None:
        with _lock:
            if _enabled is None:
                from .. import config as _config
                path = str(_config.get("MXNET_OBS_TRACE") or "")
                _path = path or None
                _cap = max(int(_config.get("MXNET_OBS_TRACE_BUFFER")), 16)
                _enabled = bool(path)
        if _enabled:
            _arm_atexit()
            _ensure_flusher()
    return _enabled


def enable(path=None):
    """Turn tracing on programmatically; `path` (optional) is the
    shared span JSONL file — without one, spans stay in the bounded
    in-memory buffer (tests read them via `buffered()`)."""
    global _enabled, _path, _cap
    enabled()   # resolve knobs first so this override wins
    with _lock:
        _enabled = True
        if path is not None:
            _path = str(path)
        has_path = _path is not None
    _arm_atexit()
    if has_path:
        _ensure_flusher()


def disable():
    global _enabled
    enabled()
    with _lock:
        _enabled = False


def _arm_atexit():
    global _atexit_armed
    if _atexit_armed:
        return
    _atexit_armed = True
    atexit.register(flush)
    # the span plane's own counters join the scrape ('trace.dropped'
    # is how silent span loss becomes visible)
    from . import metrics as _metrics
    _metrics.register_producer("trace", stats)


def stats():
    """Span-plane counters (registered as the ``trace`` metrics
    namespace when tracing is enabled)."""
    with _lock:
        return {"buffered": len(_buffer), "dropped": _dropped,
                "flushed": _flushed, "ended": _ended,
                "self_time_ms": _self_ns[0] / 1e6,
                "enabled": bool(_enabled)}


def self_time_ns():
    """Nanoseconds the flusher spent serializing + writing spans."""
    return _self_ns[0]


def _as_dict(rec):
    tr, sp, pa, name, cat, ts, dur, args, thread = rec
    return {"k": "span", "tr": tr, "sp": sp, "pa": pa, "name": name,
            "cat": cat, "ts": ts, "dur": dur, "args": args,
            "thread": thread, "pid": _PID[0]}


def buffered():
    """Unflushed span records as dicts (tests; file-less mode)."""
    with _lock:
        return [_as_dict(r) for r in _buffer[:len(_buffer)]]


_SAFE_DUMPS = _jsonl._dumps


def _render(rec):
    """One span tuple -> its JSONL line.  Hand-rendered: the generic
    json encoder costs ~4us per span dict at flush rate, which the
    calibrated overhead gate charges straight to the hot path.  Ids,
    cats, and our span names are controlled identifiers (no escaping);
    anything potentially carrying quotes (args values, thread names,
    caller-supplied names) goes through the real encoder."""
    tr, sp, pa, name, cat, ts, dur, args, thread = rec
    return (
        '{"k":"span","tr":"%s","sp":"%s","pa":%s,"name":%s,"cat":"%s",'
        '"ts":%d,"dur":%d,"pid":%d,"thread":%s,"args":%s}'
        % (tr, sp,
           '"%s"' % pa if pa else "null",
           '"%s"' % name if '"' not in name and "\\" not in name
           else _SAFE_DUMPS(name),
           cat, ts, dur, _PID[0],
           '"%s"' % thread if '"' not in thread and "\\" not in thread
           else _SAFE_DUMPS(thread),
           _SAFE_DUMPS(args) if args else "{}"))


def reset():
    """Drop buffered spans and counters; keep enablement (tests)."""
    global _dropped, _flushed, _ended
    with _lock:
        _buffer.clear()
        _dropped = _flushed = _ended = 0
        _self_ns[0] = 0


def flush():
    """Write every buffered span to the shared file, one line each.
    The lock serializes FLUSHERS only — recorders append lock-free
    (GIL-atomic), and taking the first n elements then deleting them
    cannot race appends, which only ever extend the tail."""
    global _flushed
    t0 = time.perf_counter_ns()
    with _lock:
        n = len(_buffer)
        path = _path
        if not n or path is None:
            return 0
        batch = _buffer[:n]
        del _buffer[:n]
    lines = []
    for rec in batch:
        try:
            lines.append(_render(rec))
        except (TypeError, ValueError):
            continue   # unserializable args: drop the span, not the run
    _jsonl.sink(path).write_rendered(lines)
    _flushed += n
    _self_ns[0] += time.perf_counter_ns() - t0
    return n


def _flush_loop():
    """The background flusher: serialization + the write syscall are
    paid HERE, never on the traced hot path (`_record` only appends to
    the in-memory buffer).  Wakes on the threshold signal or every
    0.5s, whichever first; the atexit flush drains the tail."""
    while True:
        _flush_event.wait(timeout=0.5)
        _flush_event.clear()
        try:
            flush()
        except Exception:
            pass    # the flusher must never die mid-run


def _ensure_flusher():
    t = _flusher[0]
    if t is not None and t.is_alive():
        return
    t = threading.Thread(target=_flush_loop, daemon=True,
                         name="mx-obs-trace-flush")
    _flusher[0] = t
    t.start()


def _record(tr, sp, pa, name, cat, ts, dur, args):
    """Buffer one finished span as a TUPLE (rendered to JSON by the
    flusher).  LOCK-FREE on the hot path: a list append is atomic
    under the GIL, and a contended lock here costs a futex syscall per
    span across every serving/dispatch thread (measured ~3x the span's
    own cost).  The cap trim takes the lock only when actually over
    cap (file-less buffering — the flusher normally drains long
    before).  The emitting thread is captured HERE: stamping at flush
    time would attribute every span to the flusher thread."""
    global _dropped, _ended
    _buffer.append((tr, sp, pa, name, cat, ts, dur, args,
                    threading.current_thread().name))
    _ended += 1                      # benign race: it is a counter
    n = len(_buffer)
    cap = _cap or 65536
    if n > cap:
        with _lock:
            while len(_buffer) > cap:
                _buffer.pop(0)
                _dropped += 1
    elif n >= _FLUSH_EVERY and _path is not None \
            and not _flush_event.is_set():
        _flush_event.set()


_TraceAnnotation = []     # [class] once resolved; [None] without jax


def _annotate(name):
    """An entered `jax.profiler.TraceAnnotation` of `name`, or None
    where the profiler cannot be had."""
    if not _TraceAnnotation:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = None
        _TraceAnnotation.append(TraceAnnotation)
    if _TraceAnnotation[0] is None:
        return None
    annotation = _TraceAnnotation[0](name)
    annotation.__enter__()
    return annotation


class SpanHandle:
    """One live span; `end()` exactly once buffers the record."""

    __slots__ = ("trace", "span", "parent", "name", "cat", "t0", "args",
                 "_done", "_annotation")

    def __init__(self, name, trace, parent, cat, args):
        self.name = name
        self.trace = trace
        self.span = _id("s")
        self.parent = parent
        self.cat = cat
        self.args = args
        self._done = False
        # the same span on the profiler's clock (module docstring)
        self._annotation = _annotate(name)
        self.t0 = time.time_ns() // 1000

    def frame(self):
        """The wire form carried in a transport frame's ``tr`` field."""
        return {"t": self.trace, "s": self.span}

    def note(self, **args):
        self.args.update(args)
        return self

    def end(self, **args):
        if self._done:
            return
        self._done = True
        dur = time.time_ns() // 1000 - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if args:
            self.args.update(args)
        _record(self.trace, self.span, self.parent, self.name, self.cat,
                self.t0, dur, self.args)


class _NullSpan:
    """The shared off-switch: every hook returns this when tracing is
    disabled — no allocation, no time reads."""

    __slots__ = ()
    trace = span = parent = None

    def frame(self):
        return None

    def note(self, **args):
        return self

    def end(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


NULL_SPAN = _NullSpan()


def current_frame():
    """The current span's wire form ({"t","s"}) or None."""
    return _ctx.get()


def start_span(name, parent=None, cat="span", **args):
    """Open a span (manual end).  ``parent`` is a wire frame
    ({"t","s"}) — defaults to the current context; None there starts a
    new trace.  Does NOT touch the context (async owners like the
    router hold the handle and `activate()` it where child work
    happens)."""
    if not enabled():
        return NULL_SPAN
    if parent is None:
        parent = _ctx.get()
    if parent:
        return SpanHandle(name, parent["t"], parent["s"], cat, args)
    return SpanHandle(name, _id("t"), None, cat, args)


def record_span(name, ts_us, dur_us, parent=None, cat="span", **args):
    """Buffer an already-timed span (post-hoc instrumentation sites)."""
    if not enabled():
        return
    if parent is None:
        parent = _ctx.get()
    trace = parent["t"] if parent else _id("t")
    _record(trace, _id("s"), parent["s"] if parent else None, str(name),
            cat, int(ts_us), int(dur_us), args)


class _Activation:
    """Tiny context manager making a frame current (class-based: this
    sits on the router dispatch hot path, where a contextlib generator
    costs real microseconds under the GIL)."""

    __slots__ = ("_frame", "_token")

    def __init__(self, frame):
        self._frame = frame
        self._token = None

    def __enter__(self):
        if self._frame is not None:
            self._token = _ctx.set(self._frame)
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            _ctx.reset(self._token)


def activate(handle_or_frame):
    """Make a span (or wire frame) the current context for the body —
    children opened inside parent to it, transport requests inject it."""
    frame = handle_or_frame.frame() \
        if isinstance(handle_or_frame, (SpanHandle, _NullSpan)) \
        else handle_or_frame
    return _Activation(frame)


@contextlib.contextmanager
def span(name, cat="span", parent=None, **args):
    """Timed child span of the current context, active for the body."""
    if not enabled():
        yield NULL_SPAN
        return
    sp = start_span(name, parent=parent, cat=cat, **args)
    token = _ctx.set(sp.frame())
    try:
        yield sp
    finally:
        _ctx.reset(token)
        sp.end()


def rpc_span(msg, peer):
    """Transport-client hook (`dist.transport.Channel`): open a span
    for this request and inject its context as the frame's ``tr``
    field.  An explicit ``tr`` already on the message (a submit-time
    capture from another thread, e.g. `RemoteReplica`) becomes the
    PARENT — the rpc span slots under the request that queued it."""
    if not enabled():
        return NULL_SPAN
    parent = msg.get("tr") or _ctx.get()
    sp = start_span(f"rpc.{msg.get('cmd')}", parent=parent, cat="rpc",
                    peer=str(peer))
    msg["tr"] = sp.frame()
    return sp


@contextlib.contextmanager
def server_span(msg, name, cat="server", **args):
    """Server-handler hook: adopt the frame's ``tr`` as parent, open
    the handling span, and keep it current for the body — the
    cross-process edge of the span tree."""
    if not enabled():
        yield NULL_SPAN
        return
    parent = msg.get("tr") if isinstance(msg, dict) else None
    sp = start_span(name, parent=parent, cat=cat, **args)
    token = _ctx.set(sp.frame())
    try:
        yield sp
    finally:
        _ctx.reset(token)
        sp.end()


# -- phases: spans that are also tallied (module docstring) -----------------

_tally = {}               # name -> [n, seconds, {jax event: [n, seconds]}]
_tally_lock = threading.Lock()
_open = threading.local()  # .stack: names of the phases open on a thread

# JAX's compile events (jax._src.dispatch / compiler / compilation_cache)
# under the names the tally gives them.  A persistent-cache hit is a
# `compile` event too: its time holds the `cache_load`.
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


def _entry(name):
    entry = _tally.get(name)
    if entry is None:
        entry = _tally[name] = [0, 0.0, {}]
    return entry


def _tally_add(name, seconds):
    with _tally_lock:
        entry = _entry(name)
        entry[0] += 1
        entry[1] += seconds


class Phase:
    """One open phase: the span it is (or `NULL_SPAN`), and after the body
    its wall seconds `s`."""

    __slots__ = ("name", "s", "_cat", "_args", "_sp", "_token", "_t0")

    def __init__(self, name, cat, args):
        self.name = name
        self.s = None
        self._cat = cat
        self._args = args

    def __enter__(self):
        sp = self._sp = start_span(self.name, cat=self._cat, **self._args)
        self._token = None if sp is NULL_SPAN else _ctx.set(sp.frame())
        try:
            _open.stack.append(self.name)
        except AttributeError:
            _open.stack = [self.name]
        self._t0 = time.perf_counter()
        return self

    def note(self, **args):
        self._sp.note(**args)
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self._t0
        _open.stack.pop()
        _tally_add(self.name, self.s)
        if self._token is not None:
            _ctx.reset(self._token)
        self._sp.end()


def phase(name, cat="span", **args):
    """``with phase(name) as ph:`` -- a `span` that is also tallied
    (`phases()`); ``ph.note(...)`` adds to the span's args, ``ph.s`` is
    the body's wall seconds once it has run."""
    return Phase(name, cat, args)


def record_phase(name, ts_us, dur_us, cat="span", **args):
    """Tally an already-timed phase (and record its span where tracing is
    on): the package's own import, which no phase can be open around."""
    _tally_add(name, dur_us / 1e6)
    record_span(name, ts_us, dur_us, cat=cat, **args)


def phases():
    """``{name: {"n", "s", "jax": {event: {"n", "s"}}}}`` since the process
    began (or `reset_phases()`); ``""`` holds JAX's events that no phase
    was open around."""
    with _tally_lock:
        return {name: {"n": n, "s": s,
                       "jax": {k: {"n": e[0], "s": e[1]}
                               for k, e in events.items()}}
                for name, (n, s, events) in _tally.items()}


def reset_phases():
    """Clear the tally (tests; `reset()` leaves it)."""
    with _tally_lock:
        _tally.clear()


def _on_jax_start(event, value, **kw):
    # JAX marks the start of a timed event with a scalar: an event inside
    # another of its kind (a jit traced inside a jit's trace) is part of
    # the outer one's time, and is not counted again
    if event in _JAX_EVENTS:
        depth = getattr(_open, "depth", None)
        if depth is None:
            depth = _open.depth = {}
        depth[event] = depth.get(event, 0) + 1


def _on_jax_event(event, duration=0.0, **kw):
    key = _JAX_EVENTS.get(event)
    if key is None:
        return
    depth = getattr(_open, "depth", None)
    if depth and depth.get(event):
        depth[event] -= 1
        if depth[event]:
            return
    stack = getattr(_open, "stack", None)
    with _tally_lock:
        ev = _entry(stack[-1] if stack else "")[2].setdefault(key, [0, 0.0])
        ev[0] += 1
        ev[1] += duration


def _phase_stats():
    """The ``phase`` metrics namespace: the tally, ``""`` as ``outside``."""
    return {name or "outside": entry for name, entry in phases().items()}


_watching = []


def watch_jax():
    """Put JAX's compile events down to phases, and the tally in the
    scrape (once a process: the package's import calls this)."""
    if _watching:
        return
    _watching.append(True)
    import jax.monitoring
    jax.monitoring.register_scalar_listener(_on_jax_start)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    jax.monitoring.register_event_listener(_on_jax_event)
    from . import metrics as _metrics
    _metrics.register_producer("phase", _phase_stats)
