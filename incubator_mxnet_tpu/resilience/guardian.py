"""Training guardian: in-graph numerical-health monitoring with a
skip / rollback / quarantine policy ladder.

Infra faults are covered elsewhere (fault injection + retry, host loss,
replica loss); this module defends the training loop against the
*silent* failures — a NaN gradient, a loss spike, a corrupt input
record — that either crash `Module.fit` mid-epoch or quietly poison the
parameters that checkpointing then faithfully preserves.

Three layers:

* **in-graph health word** — the fused train step (fused.py), when a
  guardian is attached, computes an all-finite reduction over the
  step's gradients, floating outputs and applied update, plus the
  per-step parameter-displacement ratio ||new_w - w|| / ||w|| (the
  training signal the spike detector watches) INSIDE the compiled
  program, and conditionally applies the update: a non-finite step's
  weight / optimizer-state / aux / metric updates are `where`-selected
  away (**skip-batch**) while the RNG key and update counts advance
  unconditionally, so a skipped step is deterministic and reproducible.
  The health word is returned as two device scalars per step — the host
  does NOT block on them; `maybe_poll` materializes the accumulated
  tokens every ``MXNET_GUARDIAN_INTERVAL`` steps (one gather), so
  steady-state overhead is a fused reduction per step and one small
  device->host read per interval (`guardian_device_ms` and
  `guardian_poll_wait_ms` in benchmark/ read them).  An unforced
  poll LAGS by one dispatch: it never gathers the health word of the
  newest dispatch (the block, or the single step, the device is still
  running), so the fit loop waits for work the device has finished or
  is finishing, with the next dispatch already queued behind it — the
  device never drains for a poll.  A step is therefore diagnosed at
  most ``interval`` + one dispatch's steps after it ran; a FORCED poll
  (epoch end, every checkpoint and preemption snapshot, so the end of
  `fit` too) gathers everything, so a manifest is never stamped
  healthy on evidence older than its own step.

* **policy ladder** (this module) — on each poll:

  - a **non-finite step** (already skipped in-graph) is counted,
    quarantined by stream position, and reported
    (`analysis.runtime_report()` + profiler + faults JSONL);
  - a **loss spike** — log(signal) above ``MXNET_GUARDIAN_SPIKE_K``
    EW standard deviations (sigma banded to [0.25, 1.25] log units)
    over the log-space EWMA after a ``MXNET_GUARDIAN_SPIKE_WINDOW``-step
    warmup, AND past the absolute displacement gate (the step moved the
    parameters by a damaging fraction of their norm — a lone relative
    outlier whose absolute displacement is harmless is a hard batch,
    not divergence) — already *applied* its damage, so the guardian
    requests **rollback-to-last-good**:
    `Module.fit` restores the newest checkpoint whose manifest carries
    a healthy ``health`` stamp at a step at or before the last in-bounds
    signal, replays the intervening good batches bit-identically
    (full-state restore: optimizer slots, update counts, RNG streams,
    iterator position), and skips the quarantined spike window;
  - **consecutive failures** past ``MXNET_GUARDIAN_MAX_FAILURES`` (or
    rollbacks past ``MXNET_GUARDIAN_MAX_ROLLBACKS``) escalate to a
    structured `TrainingDivergedError` naming the step, the signal
    value, and the offending data shard.

* **bad-data quarantine** — every skipped / rolled-back position (and
  every corrupt record the io layer detects) is appended as one JSON
  line to a quarantine file (``<checkpoint_dir>/quarantine.jsonl`` by
  default); a resumed run loads it and skips the same positions, so a
  poisonous batch is consumed exactly zero times after diagnosis.

Multi-worker: health bits are all-reduced through the kvstore (inside
the supervisor's watchdog fence when one is active) so every worker
takes the same skip/rollback decision; a worker whose local shard
produced the bad batch propagates its verdict to workers that saw a
clean step.  Degrades to local decisions (with a counted warning) when
the store cannot reduce.

Fault sites: ``grad.nonfinite`` (an ``error`` clause poisons that
step's gradients with NaN in-graph), ``loss.spike`` (scales the step's
gradients by 1e6 — a detectable, damaging spike), ``io.corrupt_record`` (the
`faults.mutate` payload hook; a ``corrupt`` clause bit-flips record
bytes) — all deterministic, exercised end-to-end by
``tools/run_chaos.py --train``.
"""
from __future__ import annotations

import json
import math
import os
import time

import numpy as _np

from ..analysis import locks as _locks
from ..base import MXNetError
from ..obs import trace as _obs_trace
from . import faults as _faults

__all__ = ["TrainingGuardian", "TrainingDivergedError", "RollbackRequested",
           "QuarantineLog", "findings", "reset_findings"]

_SPIKE_SCALE = 1.0e6   # gradient multiplier an injected loss.spike applies
_LOG_FLOOR = 1.0e-30   # signal floor before taking logs (all-zero grads)
# log-space sigma band bounds: the detector works on log(signal), where
# training's exponential decay is a drift the EW variance absorbs.  The
# lower bound keeps a near-constant signal's vanishing sigma from
# flagging noise (k*0.25 ~ a 4.5x jump at k=6); the upper bound keeps a
# fast-decaying warmup's huge variance from hiding real spikes (k*1.25
# ~ a 1800x jump at k=6 — the injected scale clears it with headroom).
_SIGMA_LO, _SIGMA_HI = 0.25, 1.25
# absolute spike gate on the signal itself: the signal is the
# parameter-DISPLACEMENT ratio ||new_w - w|| / ||w|| per step, so a
# spike must ALSO have moved the parameters by a damaging fraction of
# their norm.  A converged model's gradient noise spans decades — a
# relative jump whose absolute displacement is harmless (1e-5 of the
# weights) is a hard batch, never a rollback.
_SPIKE_MIN_DISPLACEMENT = 0.25


class TrainingDivergedError(MXNetError):
    """Training health is unrecoverable by the guardian's ladder: too
    many consecutive non-finite/spiking steps (or too many rollbacks).
    Structured: `step`, `signal` (the gradient-norm training signal at
    the failing step, NaN for a non-finite step), `shard` (offending
    data source/range when the iterator could attribute it), `reason`.
    """

    def __init__(self, step, signal=None, shard=None, reason=""):
        self.step = int(step)
        self.signal = None if signal is None else float(signal)
        self.shard = shard
        sig = "non-finite" if self.signal is None or \
            not math.isfinite(self.signal) else f"{self.signal:.6g}"
        where = f" (offending data: {shard})" if shard else ""
        super().__init__(
            f"training diverged at step {self.step}: health signal "
            f"{sig}{where}"
            + (f" — {reason}" if reason else "")
            + "; the guardian's skip/rollback budget is exhausted — "
              "inspect the quarantine log, the data shard, and the "
              "learning-rate schedule before resuming")


class RollbackRequested(MXNetError):
    """Internal control-flow signal: the guardian diagnosed a loss spike
    whose update was already applied and wants `Module.fit` to restore
    the newest healthy checkpoint at or before `last_good_step` and skip
    the quarantined window.  Caught by the fit restart loop — user code
    only ever sees `TrainingDivergedError` when the budget runs out."""

    def __init__(self, step, last_good_step, signal, quarantined=()):
        self.step = int(step)
        self.last_good_step = int(last_good_step)
        self.signal = float(signal)
        self.quarantined = list(quarantined)
        super().__init__(
            f"loss spike at step {self.step} (signal {self.signal:.6g}); "
            f"rolling back to the newest healthy checkpoint at step <= "
            f"{self.last_good_step} and skipping "
            f"{len(self.quarantined)} quarantined batch position(s)")


# -- findings (analysis.runtime_report) ---------------------------------------
_lock = _locks.make_lock("guardian.findings")
_findings = []


def findings():
    """Guardian findings (skips, rollbacks, quarantines, divergence) for
    `analysis.runtime_report()`."""
    with _lock:
        return list(_findings)


def reset_findings():
    with _lock:
        _findings.clear()


def _add_finding(code, message, key, severity=None):
    from ..analysis.findings import Finding, WARN
    with _lock:
        for f in _findings:
            if f.code == code and f.node == key:
                f.count += 1
                return
        _findings.append(Finding("guardian." + code.split("-")[0], code,
                                 severity or WARN, message, node=key))


def _record_event(event, **args):
    """One guardian event into every observability plane: the faults
    JSONL trace (chaos artifacts), the profiler (step-aligned chrome
    trace with a thread lane), and the findings list."""
    _faults.note(event, site="guardian", **args)
    try:
        from .. import profiler as _profiler
        _profiler.record_guardian(event, **args)
    except Exception:
        pass


class QuarantineLog:
    """Append-only JSONL quarantine file shared by every process of a
    run — written through the one tested sink (`obs.jsonl_sink`:
    O_APPEND line-atomic appends, pid/rank/thread stamping).  Each
    entry is one poisoned unit: a batch position ({'epoch','nbatch'})
    or a record ({'source','record'})."""

    def __init__(self, path):
        from ..obs import jsonl_sink as _jsonl
        self.path = str(path)
        self._jsonl = _jsonl
        self._sink = _jsonl.sink(self.path)

    def append(self, **entry):
        self._sink.write(entry)

    def load(self):
        """Every entry written so far (any process), oldest first."""
        return self._jsonl.read_jsonl(self.path)

    def batch_positions(self):
        """{(epoch, nbatch)} of every quarantined stream position."""
        return {(int(e["epoch"]), int(e["nbatch"])) for e in self.load()
                if "nbatch" in e and "epoch" in e}

    def records(self, source=None):
        """{record_id} quarantined for `source` (or any source)."""
        return {int(e["record"]) for e in self.load()
                if "record" in e and
                (source is None or e.get("source") == source)}

    def close(self):
        self._sink.close()


class TrainingGuardian:
    """Per-fit training health guardian (see module docstring).

    Lifecycle: `Module.fit` builds one per fit() call
    (`TrainingGuardian.maybe_create`), `attach()`es it to the bound
    module after `init_optimizer` (wires the fused step's in-graph
    health word, the kvstore reduction, and the iterator's quarantine),
    then calls `tag()` + `maybe_poll()` per processed block (the poll
    lags by one dispatch), `maybe_poll(force=True)` before every
    snapshot and at the epoch's end, and `health_stamp()` at every
    checkpoint snapshot."""

    @classmethod
    def maybe_create(cls, checkpoint_dir=None, logger=None):
        from .. import config as _config
        if not _config.get("MXNET_GUARDIAN"):
            return None
        return cls(checkpoint_dir=checkpoint_dir, logger=logger)

    def __init__(self, checkpoint_dir=None, interval=None, window=None,
                 spike_k=None, max_failures=None, max_rollbacks=None,
                 quarantine_path=None, logger=None):
        from .. import config as _config
        self.checkpoint_dir = checkpoint_dir
        self.interval = max(1, int(
            interval if interval is not None
            else _config.get("MXNET_GUARDIAN_INTERVAL")))
        self.window = max(2, int(
            window if window is not None
            else _config.get("MXNET_GUARDIAN_SPIKE_WINDOW")))
        self.spike_k = float(
            spike_k if spike_k is not None
            else _config.get("MXNET_GUARDIAN_SPIKE_K"))
        self.max_failures = int(
            max_failures if max_failures is not None
            else _config.get("MXNET_GUARDIAN_MAX_FAILURES"))
        self.max_rollbacks = int(
            max_rollbacks if max_rollbacks is not None
            else _config.get("MXNET_GUARDIAN_MAX_ROLLBACKS"))
        if quarantine_path is None:
            quarantine_path = str(
                _config.get("MXNET_GUARDIAN_QUARANTINE") or "")
            if not quarantine_path and checkpoint_dir is not None:
                os.makedirs(str(checkpoint_dir), exist_ok=True)
                quarantine_path = os.path.join(str(checkpoint_dir),
                                               "quarantine.jsonl")
        self.quarantine = QuarantineLog(quarantine_path) \
            if quarantine_path else None
        self._skip_positions = self.quarantine.batch_positions() \
            if self.quarantine is not None else set()
        self._logger = logger
        self.can_rollback = checkpoint_dir is not None
        self.in_graph = True     # fused step arms the health word on this
        # pending health tokens: [{'ok','sig','pos','k'}] — device arrays
        # until a poll materializes them (no per-step host sync)
        self._pending = []
        self._untagged = 0       # trailing pending entries without a pos
        self._gstep = 0          # trained-step counter (mirrors fit's)
        # spike detector state: EWMA + EW variance over LOG(signal) —
        # training signals decay exponentially, so a linear EWMA lags
        # orders of magnitude above the current level and hides real
        # spikes; in log space the decay is drift the variance absorbs
        self._ewma = None        # EWMA of log(signal)
        self._ewvar = 0.0        # EW variance of log(signal)
        self._history = 0        # finite signals folded in so far
        self._last_good_step = 0
        # policy state
        self._consecutive_failures = 0
        self._rollbacks = 0
        self.pending_rollback_step = None   # armed between request+restore
        # (lo, hi) gstep window the newest rollback disowned — consumed
        # by CheckpointPublisher to fence those versions out of the
        # model registry (loop/publisher.py)
        self.last_rollback_window = None
        self._shard_info = None  # last batch attribution (source, lo, hi)
        self._iterator = None
        self._allreduce = None   # kvstore reduction (multi-worker)
        self._kv_seen = _np.zeros(3, _np.float64)  # cumulative pulled
        self._sync_errors = 0
        # poll_wait_s: seconds the polls stood in their device gather
        # (the fit loop waits there for the dispatch BEFORE the newest);
        # polls_lagged: polls that left a dispatch in flight
        self._stats = {"steps_observed": 0, "polls": 0, "poll_wait_s": 0.0,
                       "polls_lagged": 0, "steps_in_flight_max": 0,
                       "skips": 0, "spikes": 0, "rollbacks": 0,
                       "quarantined": 0,
                       "sync_degraded": 0, "injected_nonfinite": 0,
                       "injected_spike": 0}
        # telemetry plane: skip/rollback/quarantine counters under the
        # stable 'guardian' namespace (weakly held — dies with the fit)
        from ..obs import metrics as _obs_metrics
        _obs_metrics.register_producer("guardian", self.stats)

    # -- wiring ---------------------------------------------------------------
    def attach(self, module):
        """Wire this guardian into a bound+optimized Module: the fused
        step computes the in-graph health word and conditional update;
        a multi-worker kvstore becomes the decision all-reduce.  Safe to
        call again after a restart rebuilds either."""
        fs = getattr(module, "_fused_step", None)
        if fs is not None and hasattr(fs, "attach_guardian"):
            fs.attach_guardian(self)
        kv = getattr(module, "_kvstore", None)
        if kv is not None and getattr(kv, "num_workers", 1) > 1:
            self._wire_kvstore(kv)

    def attach_iterator(self, data_iter):
        """Give the iterator the quarantine log (it appends corrupt
        records it detects) and apply already-quarantined records so a
        resumed run never re-reads a poisoned record."""
        self._iterator = data_iter
        if self.quarantine is None:
            return
        if hasattr(data_iter, "set_quarantine"):
            data_iter.set_quarantine(self.quarantine)
        if hasattr(data_iter, "apply_quarantine"):
            data_iter.apply_quarantine(self.quarantine.load())

    def _wire_kvstore(self, kv):
        """Health-bit all-reduce over the kvstore: every worker pushes
        its cumulative counters on a reserved key and pulls the sum, so
        one worker's local verdict (its shard fed it the bad batch)
        becomes everyone's decision.  Runs inside the supervisor's
        watchdog when one is active (`supervised`), so a dead worker
        surfaces as a CollectiveTimeoutError, not a hang."""
        from . import supervisor as _sup
        state = {"inited": False}
        key = "__guardian_health__"

        def allreduce(vec):
            from .. import nd

            def exchange():
                if not state["inited"]:
                    kv.init(key, nd.zeros(len(vec)))
                    state["inited"] = True
                kv.push(key, nd.array(_np.asarray(vec, _np.float32)))
                out = nd.zeros(len(vec))
                kv.pull(key, out)
                return out.asnumpy()

            return _sup.supervised("guardian.sync", exchange)

        self._allreduce = allreduce

    # -- fused-step side ------------------------------------------------------
    def step_multipliers(self, k):
        """One gradient multiplier per step of the upcoming block: 1.0
        normally; NaN when an injected ``grad.nonfinite`` clause fires
        for that step (the in-graph skip path's deterministic trigger);
        ``_SPIKE_SCALE`` when a ``loss.spike`` clause fires."""
        out = []
        for _ in range(k):
            self._gstep += 1
            gm = 1.0
            try:
                _faults.fire("grad.nonfinite", step=self._gstep)
            except Exception:
                gm = float("nan")
                self._stats["injected_nonfinite"] += 1
            try:
                _faults.fire("loss.spike", step=self._gstep)
            except Exception:
                gm = _SPIKE_SCALE
                self._stats["injected_spike"] += 1
            out.append(_np.float32(gm))
        return out

    def record_health(self, k, ok, sig):
        """Health word of the last dispatch: `ok`/`sig` are device
        scalars (k==1) or stacked device vectors (a K-step block).  No
        host sync here — `maybe_poll` materializes them in one gather,
        and an unforced one only once a newer dispatch stands behind
        this entry."""
        self._pending.append({"ok": ok, "sig": sig, "k": int(k),
                              "pos": None})
        self._untagged += 1
        self._stats["steps_observed"] += int(k)
        if len(self._pending) > 1024:
            # a fused step driven outside the fit loop (no polls): cap
            # the token backlog instead of pinning device buffers forever
            drop = len(self._pending) - 1024
            self._pending = self._pending[drop:]
            self._untagged = min(self._untagged, len(self._pending))

    # -- fit-loop side --------------------------------------------------------
    def tag(self, epoch, nbatch0, data_iter=None):
        """Attach stream positions (epoch, first nbatch) to the health
        tokens the fused step recorded since the last tag — the fit loop
        calls this right after each processed block, so a later poll can
        quarantine a bad step by position."""
        first_nbatch = int(nbatch0)
        if self._untagged:
            for entry in self._pending[-self._untagged:]:
                entry["pos"] = (int(epoch), int(nbatch0))
                nbatch0 += entry["k"]
            self._untagged = 0
        it = data_iter if data_iter is not None else self._iterator
        if it is not None and hasattr(it, "record_range"):
            try:
                self._shard_info = it.record_range(first_nbatch)
            except Exception:
                pass

    def should_skip(self, epoch, nbatch):
        """Whether this stream position is quarantined (skip without
        training; positions still advance so resume bookkeeping stays
        aligned with the run that wrote the quarantine)."""
        return (int(epoch), int(nbatch)) in self._skip_positions

    def note_skipped(self, epoch, nbatch):
        _record_event("quarantine-skip", epoch=int(epoch),
                      nbatch=int(nbatch))

    def maybe_poll(self, gstep, force=False):
        """Materialize pending health tokens and run the policy ladder.
        Unforced (the fit loop, after every dispatch): the newest
        dispatch stays in flight, and the OLDER tokens are gathered once
        they cover ``interval`` trained steps — the loop waits for the
        dispatch before the one it just queued, never for that one.
        On `force` (checkpoint boundaries, epoch ends): everything, the
        dispatch in flight included.  Raises `RollbackRequested` on a
        diagnosed spike, `TrainingDivergedError` past the budget; a
        dispatch still in flight is then never judged
        (`rollback_committed` drops it): the restore discards its
        update, its positions are not quarantined, and the replay trains
        on them again."""
        due = self._pending if force else self._pending[:-1]
        due_steps = sum(e["k"] for e in due)
        if not due or (not force and due_steps < self.interval):
            return
        in_flight = sum(e["k"] for e in self._pending[len(due):])
        self._stats["polls"] += 1
        if in_flight:
            self._stats["polls_lagged"] += 1
            self._stats["steps_in_flight_max"] = max(
                self._stats["steps_in_flight_max"], in_flight)
        # a span around the poll's WORK; the wait for the device inside
        # it (the gather) is no work and is told apart as `wait_us`
        sp = _obs_trace.start_span("fit.guardian", cat="train",
                                   steps=due_steps, in_flight=in_flight,
                                   gstep=int(gstep))
        wait0 = self._stats["poll_wait_s"]
        try:
            tokens = self._classify(self._materialize(len(due)))
            local = self._ladder_inputs(tokens)
            agreed = self._agree(local)
            self._apply_ladder(agreed, tokens, gstep)
        finally:
            sp.end(wait_us=int(
                (self._stats["poll_wait_s"] - wait0) * 1e6))

    def _materialize(self, n):
        """One blocking gather of the `n` oldest pending device tokens
        -> [(pos, step_offset, ok, sig)] flattened per step; the newer
        ones stay pending."""
        import jax
        # pending tokens are exactly the last sum(k) dispatched steps,
        # those left in flight included, ending at the fused step's
        # counter (_gstep) — rollback-safe
        base_step = self._gstep - sum(e["k"] for e in self._pending)
        pending, self._pending = self._pending[:n], self._pending[n:]
        self._untagged = min(self._untagged, len(self._pending))
        leaves = []
        for e in pending:
            leaves.append(e["ok"])
            leaves.append(e["sig"])
        t0 = time.perf_counter()
        host = jax.device_get(leaves)
        self._stats["poll_wait_s"] += time.perf_counter() - t0
        out = []
        consumed = 0
        for i, e in enumerate(pending):
            ok = _np.atleast_1d(_np.asarray(host[2 * i]))
            sig = _np.atleast_1d(_np.asarray(host[2 * i + 1]))
            for j in range(e["k"]):
                pos = None
                if e["pos"] is not None:
                    pos = (e["pos"][0], e["pos"][1] + j)
                out.append((pos, base_step + consumed + 1,
                            float(ok[j]), float(sig[j])))
                consumed += 1
        return out

    def _classify(self, raw):
        """Classify each materialized token ONCE against the detector
        state as it stood when the token's step ran (folding in-bounds
        signals as it walks) -> [(pos, step, ok, sig, is_spike)]."""
        out = []
        contaminated = False
        for pos, step, ok, sig in raw:
            spike = False
            if ok >= 0.5 and not contaminated:
                spike = self._is_spike(sig)
                if not spike:
                    self._fold(sig)
                    self._last_good_step = max(self._last_good_step, step)
            # once a spike appears, the later steps of this window
            # trained on contaminated parameters: they must neither
            # advance last_good nor feed the EWMA.  A non-finite step
            # does NOT contaminate — its update was refused in-graph.
            if spike:
                contaminated = True
            out.append((pos, step, ok, sig, spike))
        return out

    def _ladder_inputs(self, tokens):
        """Local health bits: [n_bad, n_spike, first_spike_step]."""
        n_bad = sum(1 for _, _, ok, _, _ in tokens if ok < 0.5)
        n_spike = sum(1 for *_, spike in tokens if spike)
        spike_step = next((step for _, step, _, _, spike in tokens
                           if spike), 0)
        return _np.asarray([n_bad, n_spike, spike_step], _np.float64)

    def _is_spike(self, sig):
        """Spike test: a k-sigma relative jump of log(signal) over its
        EWMA AND an absolute displacement past
        ``_SPIKE_MIN_DISPLACEMENT`` — the signal is the per-step
        parameter-displacement ratio, so the absolute gate means the
        step genuinely moved the parameters by a damaging fraction."""
        if self._history < self.window or self._ewma is None:
            return False
        if sig <= _SPIKE_MIN_DISPLACEMENT:
            return False
        logsig = math.log(max(sig, _LOG_FLOOR))
        sigma = min(max(math.sqrt(max(self._ewvar, 0.0)), _SIGMA_LO),
                    _SIGMA_HI)
        return logsig - self._ewma > self.spike_k * sigma

    def _fold(self, sig):
        """Fold one in-bounds signal into the log-space EWMA/variance."""
        logsig = math.log(max(sig, _LOG_FLOOR))
        if self._ewma is None:
            self._ewma = logsig
            self._ewvar = 0.0
        else:
            alpha = 2.0 / (self.window + 1.0)
            delta = logsig - self._ewma
            self._ewma += alpha * delta
            self._ewvar = (1.0 - alpha) * (self._ewvar
                                           + alpha * delta * delta)
        self._history += 1

    def _ewma_linear(self):
        """The EWMA back in signal units (for stamps/messages/stats)."""
        return None if self._ewma is None else math.exp(self._ewma)

    def _agree(self, local):
        """All-reduce the local health bits so every worker takes the
        same decision.  In synchronous data-parallel training every
        worker observes the identical health word, so the sum is n x the
        local value; the reduction matters for the asymmetric case — one
        worker's shard fed it the bad batch — where the OR of the flags
        (sum > 0) propagates the verdict.  Degrades to the local bits
        (counted) when the store cannot reduce."""
        if self._allreduce is None:
            return local
        try:
            pulled = _np.asarray(self._allreduce(list(local)), _np.float64)
            # the store SUMS every worker's pushes across polls: this
            # poll's verdict is the delta against what was already seen
            total = pulled - self._kv_seen
            self._kv_seen = pulled
            if total[1] > 0 and local[1] == 0:
                # a peer diagnosed the spike: adopt its step (mean of the
                # diagnosing workers — identical when symmetric)
                total[2] = total[2] / max(round(total[1]), 1)
            elif local[1] > 0:
                total[2] = local[2]
            return total
        except Exception as e:
            self._sync_errors += 1
            self._stats["sync_degraded"] += 1
            if self._logger is not None:
                self._logger.warning(
                    "guardian: health-bit reduction unavailable (%s); "
                    "falling back to local decisions", str(e)[:200])
            return local

    def _apply_ladder(self, agreed, tokens, gstep):
        n_bad, n_spike = int(round(agreed[0])), int(round(agreed[1]))
        spike_step = int(round(agreed[2]))
        # the failure BUDGET counts steps, not worker-copies of a step:
        # in synchronous data-parallel training every worker reports the
        # same bad step, so the agreed sum is world_size x the step
        # count — budget on the LOCAL count (floored at 1 when only a
        # peer saw the bad step, so the verdict still registers)
        local_bad = sum(1 for _, _, ok, _, _ in tokens if ok < 0.5)
        budget_bad = max(local_bad, 1 if n_bad else 0)
        # rung 1: skip-batch — the in-graph select already refused the
        # update; here the skipped positions are quarantined and counted
        if n_bad:
            for pos, step, ok, sig, _ in tokens:
                if ok >= 0.5:
                    continue
                self._quarantine(pos, step, "nonfinite", sig)
                self._stats["skips"] += 1
                _record_event("skip-batch", step=step,
                              epoch=pos[0] if pos else -1,
                              nbatch=pos[1] if pos else -1)
                _add_finding(
                    "skip-batch",
                    f"non-finite gradients at step {step} — the update "
                    "was not applied (in-graph skip); the batch position "
                    "is quarantined", f"step{step}")
            self._consecutive_failures += budget_bad
        # rung 2: rollback — a spiking update was already applied
        if n_spike:
            self._stats["spikes"] += 1
            self._consecutive_failures += 1
            sig = next((s for *_, s, spike in tokens if spike),
                       float("nan"))
            self._check_budget(spike_step or gstep, sig)
            quarantined = []
            for pos, step, ok, s, spike in tokens:
                # the spike window: the diagnosed step and everything
                # after it in this poll (updates already contaminated)
                if ok >= 0.5 and (spike or (spike_step and
                                            step >= spike_step)):
                    self._quarantine(pos, step, "loss-spike", s)
                    if pos is not None:
                        quarantined.append(pos)
            if self.can_rollback:
                self._rollbacks += 1
                self._stats["rollbacks"] += 1
                if self._rollbacks > self.max_rollbacks:
                    raise TrainingDivergedError(
                        spike_step or gstep, signal=sig,
                        shard=self._shard_desc(),
                        reason=f"{self._rollbacks - 1} rollback(s) already "
                               "spent (MXNET_GUARDIAN_MAX_ROLLBACKS)")
                self.pending_rollback_step = self._last_good_step
                self.last_rollback_window = (
                    self._last_good_step + 1, int(spike_step or gstep))
                _record_event("rollback", step=spike_step or gstep,
                              last_good_step=self._last_good_step)
                # the EWMA may be unset when a PEER diagnosed the spike
                # (fresh detector after rollback_committed, late joiner)
                ew = self._ewma_linear()
                _add_finding(
                    "rollback",
                    f"loss spike at step {spike_step or gstep} (signal "
                    f"{sig:.6g} vs EWMA "
                    f"{'?' if ew is None else format(ew, '.6g')}) — "
                    "rolling back to the newest healthy checkpoint at "
                    f"step <= {self._last_good_step}", f"step{spike_step}")
                raise RollbackRequested(spike_step or gstep,
                                        self._last_good_step, sig,
                                        quarantined)
            _add_finding(
                "spike-unrecoverable",
                f"loss spike at step {spike_step or gstep} (signal "
                f"{sig:.6g}) but no checkpoint_dir to roll back to — "
                "training continues on the spiked parameters; pass "
                "checkpoint_dir= to Module.fit to arm rollback",
                f"step{spike_step}")
        if not n_bad and not n_spike:
            self._consecutive_failures = 0
        else:
            bad_step = next((st for _, st, ok, _, _ in tokens
                             if ok < 0.5), gstep)
            self._check_budget(bad_step, float("nan") if n_bad else None)

    def _check_budget(self, step, signal):
        if self._consecutive_failures > self.max_failures:
            _record_event("diverged", step=int(step))
            raise TrainingDivergedError(
                step, signal=signal, shard=self._shard_desc(),
                reason=f"{self._consecutive_failures} consecutive "
                       "unhealthy step(s) (MXNET_GUARDIAN_MAX_FAILURES="
                       f"{self.max_failures})")

    def _quarantine(self, pos, step, reason, signal):
        if pos is not None:
            self._skip_positions.add(pos)
        self._stats["quarantined"] += 1
        _record_event("quarantine", step=int(step), reason=reason)
        if self.quarantine is None:
            return
        entry = {"reason": reason, "step": int(step),
                 "signal": None if signal is None or
                 not math.isfinite(signal) else float(signal)}
        if pos is not None:
            entry["epoch"], entry["nbatch"] = int(pos[0]), int(pos[1])
        shard = self._shard_desc()
        if shard:
            entry["shard"] = shard
        self.quarantine.append(**entry)

    def _shard_desc(self):
        info = self._shard_info
        if not info:
            return None
        try:
            source, lo, hi = info
            return f"{source}[{lo}:{hi}]"
        except Exception:
            return str(info)

    # -- checkpoint side ------------------------------------------------------
    def health_stamp(self):
        """The ``health`` block a checkpoint manifest carries: rollback
        selects only checkpoints stamped healthy (an unstamped manifest
        — pre-guardian — counts as healthy for compatibility)."""
        status = "healthy" if self._consecutive_failures == 0 and \
            self.pending_rollback_step is None else "suspect"
        stamp = {"status": status,
                 "signal_ewma": self._ewma_linear(),
                 "skips": self._stats["skips"],
                 "rollbacks": self._rollbacks}
        return stamp

    def rollback_committed(self, step):
        """A rollback restore landed: clear the pending request and the
        spike detector's history (the replayed window re-folds fresh) —
        the failure counter survives, so thrashing rollbacks still
        escalate to TrainingDivergedError."""
        self.pending_rollback_step = None
        self._ewma = None
        self._ewvar = 0.0
        self._history = 0
        self._pending = []
        self._untagged = 0
        self._last_good_step = int(step)
        self._gstep = int(step)
        _record_event("rollback-committed", step=int(step))

    def stats(self):
        out = dict(self._stats)
        out.update(consecutive_failures=self._consecutive_failures,
                   signal_ewma=self._ewma_linear(),
                   quarantine_path=self.quarantine.path
                   if self.quarantine is not None else None,
                   pending_rollback_step=self.pending_rollback_step)
        return out

    def close(self):
        if self.quarantine is not None:
            self.quarantine.close()
