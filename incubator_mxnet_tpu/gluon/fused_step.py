"""The Gluon training step as ONE donated XLA program.

`Module.fit` got its single-program hot loop in `fused.FusedTrainStep`;
this is the same treatment for the Gluon side, reachable from the public
`gluon.contrib.estimator.Estimator.fit` loop (the reference's Estimator,
`python/mxnet/gluon/contrib/estimator/estimator.py`).  The eager pattern

    with autograd.record():
        out = net(data); loss = loss_fn(out, label)
    loss.backward(); trainer.step(batch)

costs a dispatch for the CachedOp forward, one for the (fused) tape
backward — which must RECOMPUTE the forward for its residuals — and one
for the optimizer apply.  Here the whole thing traces once per input
signature into a single program: the net and loss blocks run their nd ops
on traced shells (every registered op is jax-traceable), `jax.vjp` takes
the gradients with the forward residuals shared (no recompute), the
PUBLIC optimizer applies via `fused._apply_traced`, BatchNorm aux states
and the metric accumulate in-graph, and every persistent buffer is a
donated carry.

Block mode (`call_block`, driven by Estimator.fit +
MXNET_FUSED_STEP_BLOCK): K batches run as ONE `lax.scan` program per
dispatch, amortizing host dispatch and write-back Python across K steps —
the Gluon analogue of `fused.FusedTrainStep`'s scan blocks.  The
framework trace runs once into a closed jaxpr shared by the 1-step and
every K-step program.

Eligibility (checked at build, with transparent fallback to the eager
loop): single-context trainer, no ZeRO/TP sharding, no RNG-consuming ops
(dropout nets fall back), metrics with `device_update`.
"""
from __future__ import annotations

import logging
import threading as _threading

import numpy as _np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import autograd as _autograd
from .parameter import DeferredInitializationError
from ..fused import (_apply_traced, _no_rng, _untraceable, _state_data,
                     _state_write_back, _raise_if_unrecoverable,
                     _TracedCore, _one_step_jit, _scan_block_jit,
                     _BlockMetricView)

__all__ = ["GluonFusedStep"]

_log = logging.getLogger(__name__)


class _SwapParams:
    """Temporarily repoint Parameters' storage at traced shells."""

    def __init__(self, params, shells):
        self._params = params
        self._shells = shells
        self._saved = None

    def __enter__(self):
        self._saved = [p._data for p in self._params]
        for p, s in zip(self._params, self._shells):
            p._data = [s]

    def __exit__(self, *exc):
        for p, d in zip(self._params, self._saved):
            p._data = d


_SCAN_TRACE = _threading.local()


class _ScanLowering:
    """Arms scan-over-layers for the duration of the fused core's
    forward trace (MXNET_FUSED_SCAN): `HybridSequential` lowers runs of
    structurally identical children to ONE `lax.scan` body over stacked
    per-layer parameters instead of N inlined copies, so XLA compiles
    the layer body once.  Scoped to the trace — eager user forwards
    never pay the detection walk."""

    def __enter__(self):
        from .. import config as _cfg
        self._on = bool(_cfg.get("MXNET_FUSED_SCAN"))
        if self._on:
            _SCAN_TRACE.depth = getattr(_SCAN_TRACE, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        if self._on:
            _SCAN_TRACE.depth -= 1


def scan_lowering_active():
    """True while a fused gluon core trace wants scan-over-layers
    (checked by `HybridSequential.hybrid_forward`)."""
    return getattr(_SCAN_TRACE, "depth", 0) > 0


class GluonFusedStep:
    """One donated program for Estimator's train step (K per dispatch in
    block mode)."""

    @classmethod
    def try_build(cls, net, loss_fn, trainer, metrics):
        """Returns an instance or None when the configuration cannot fuse
        (the caller keeps the reference eager loop)."""
        try:
            if trainer is None or len(trainer._contexts) != 1:
                return None
            if getattr(trainer, "_zero", None) is not None:
                return None
            # every net parameter must be trainer-owned: anything outside
            # trainer._params would trace as a CONSTANT, silently ignoring
            # later set_data/load_parameters on e.g. frozen layers
            owned = {p.name for p in trainer._params}
            net_params = set(net.collect_params().keys()) \
                if hasattr(net, "collect_params") else owned
            if not net_params <= owned:
                return None
            for m in metrics:
                if getattr(m, "device_update", None) is None:
                    return None
            return cls(net, loss_fn, trainer, metrics)
        except Exception as e:
            _log.warning("gluon fused step unavailable (%s)", str(e)[:200])
            return None

    def __init__(self, net, loss_fn, trainer, metrics):
        self._net = net
        self._loss_fn = loss_fn
        self._trainer = trainer
        self._metrics = list(metrics)
        self._ctx = trainer._contexts[0]
        all_params = list(trainer._params)
        self._train_params = [p for p in all_params
                              if p.grad_req not in (None, "null")]
        self._aux_params = [p for p in all_params
                            if p.grad_req in (None, "null")]
        self._indices = [trainer._param2idx[p.name]
                         for p in self._train_params]
        self._opt = trainer._optimizer
        self._updater = trainer._updaters[0]
        self._jit = None
        self._jit_block = {}
        self._core_closed = None
        self._core_sig = None     # input signature the core was traced for
        self._core_cache = {}     # in_sig -> traced program set
        self.broken = False
        self._carry = None
        self._t_vec = None
        self._block_view = None   # per-step metric exposure for bursts
        self.last_loss = None
        self.last_outputs = None
        GluonFusedStep._seq = getattr(GluonFusedStep, "_seq", 0) + 1
        self._audit_key = f"GluonFusedStep#{GluonFusedStep._seq}"
        self._step_no = 0   # donation-tracker step counter

    def _donation_groups(self, ws, ss, auxs):
        """(owner_name, pytree) pairs for the donated carries — naming
        source for the donation tracker and unrecoverable errors."""
        groups = [(p.name, w) for p, w in zip(self._train_params, ws)]
        groups += [(p.name + ".state", s)
                   for p, s in zip(self._train_params, ss)]
        groups += [(p.name, a) for p, a in zip(self._aux_params, auxs)]
        return groups

    # -- build ---------------------------------------------------------------
    def _build_core(self):
        """The one-step train function over raw arrays; traced exactly once
        under `make_jaxpr` (the trace runs the whole net's Python)."""
        import jax
        import jax.numpy as jnp

        net, loss_fn = self._net, self._loss_fn
        tparams, aparams = self._train_params, self._aux_params
        metrics = self._metrics
        opt, indices, ctx = self._opt, self._indices, self._ctx

        def core(inner, x, rescale):
            ws, auxs, ss, mcarry, t_vec = inner
            data, label, lr_vec, wd_vec = x
            t_vec = t_vec + jnp.float32(1.0)

            def forward(pws):
                shells = [NDArray(w, ctx=ctx) for w in pws]
                aux_shells = [NDArray(a, ctx=ctx) for a in auxs]
                with _SwapParams(tparams, shells), \
                        _SwapParams(aparams, aux_shells), \
                        _autograd.pause(train_mode=True):
                    with _ScanLowering():
                        out = net(NDArray(data, ctx=ctx))
                    losses = loss_fn(out, NDArray(label, ctx=ctx))
                # BatchNorm-style aux updates landed in-place on the shells
                new_aux = tuple(s._data for s in aux_shells)
                return jnp.sum(losses._data), (out._data, losses._data,
                                               new_aux)

            loss_sum, vjp, (out, losses, new_aux) = \
                jax.vjp(forward, list(ws), has_aux=True)
            # scan carries must keep invariant dtypes: a bf16-cast net's
            # BN aux update may compute fp32 running stats — land them
            # back in the stored aux dtype (the 1-step jit tolerated the
            # widening; lax.scan correctly refuses)
            new_aux = tuple(
                na.astype(a.dtype) if na.dtype != a.dtype else na
                for na, a in zip(new_aux, auxs))
            (grads,) = vjp(jnp.ones((), loss_sum.dtype))
            new_ws, new_ss = _apply_traced(opt, indices, ws, grads, ss, ctx,
                                           lr_vec, wd_vec, t_vec, rescale)
            new_mcarry = []
            for m, (msum, mnum) in zip(metrics, mcarry):
                dsum, dnum = m.device_update([label], [out])
                new_mcarry.append((msum + jnp.asarray(dsum, jnp.float32),
                                   mnum + jnp.asarray(dnum, jnp.int32)))
            mean_loss = loss_sum / losses.size
            new_inner = (tuple(new_ws), tuple(new_aux), tuple(new_ss),
                         tuple(new_mcarry), t_vec)
            return new_inner, (mean_loss, out)

        return core

    def _trace_core(self, core, example):
        """Run the net's framework trace ONCE (fused._TracedCore); every
        program — 1-step jit, each K-step scan — replays the jaxpr."""
        self._core_closed = _TracedCore(core, example)

    def _build1(self):
        self._jit = _one_step_jit(self._core_closed, label=self._audit_key)

    def _buildk(self, k):
        # mcarry_index=3: the metric accumulator's slot in the gluon
        # inner carry (ws, auxs, ss, mcarry, t_vec) — stacked per step
        # so the handler burst can observe per-batch metric state
        jitk = self._scan_jit if getattr(self, "_scan_jit", None) is not None \
            else _scan_block_jit(self._core_closed, mcarry_index=3,
                                 label=self._audit_key)
        self._scan_jit = jitk
        self._jit_block[k] = jitk
        return jitk

    # -- per step ------------------------------------------------------------
    def _ensure_states(self):
        upd = self._updater
        need = [(i, p) for i, p in zip(self._indices, self._train_params)
                if i not in upd.states]
        if not need:
            return
        # ONE compiled program creates every state (fused.py helper); the
        # per-parameter eager path costs a round trip per op on a remote
        # device and dominated Estimator's time-to-first-batch
        from ..fused import create_states_on_device
        states = create_states_on_device(
            self._opt, [i for i, _ in need],
            [p.data()._data for _, p in need], self._ctx)
        if states is not None:
            for (i, _), s in zip(need, states):
                upd.states[i] = s
                upd.states_synced[i] = True
            return
        for i, p in need:
            upd.states[i] = \
                self._opt.create_state_multi_precision(i, p.data())
            upd.states_synced[i] = True

    def __call__(self, data, label, batch_size):
        """Run one fused Gluon step; returns True when handled (params,
        optimizer state, aux and metrics all updated)."""
        return self._dispatch([(data, label)], batch_size)

    def call_block(self, pairs, batch_size):
        """Run len(pairs) steps as ONE `lax.scan` dispatch."""
        return self._dispatch(list(pairs), batch_size)

    def _dispatch(self, pairs, batch_size):
        if self.broken:
            return False
        import jax
        k = len(pairs)

        trainer = self._trainer
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._kvstore is not None:
            return False   # multi-device/dist reductions: eager loop
        if self._opt is not trainer._optimizer or \
                self._updater is not trainer._updaters[0]:
            # load_states() replaces the updater's optimizer (and the
            # states dict): rebuild around the restored objects
            self._opt = trainer._optimizer
            self._updater = trainer._updaters[0]
            self._jit = None
            self._jit_block = {}
            self._core_closed = None
            self._core_sig = None
            self._core_cache = {}   # cached programs trace the OLD optimizer
            self._carry = None
            self._t_vec = None
        opt = self._opt
        opt.rescale_grad = trainer._scale / batch_size
        try:
            self._ensure_states()
        except DeferredInitializationError:
            # the eager loop's first forward materializes the parameters;
            # retry fusing from the next batch
            return False

        # eligibility BEFORE any transfer: a rejected block must not cost
        # K device_puts (the eager fallback would re-upload the batches)
        sig0 = None
        for data, label in pairs:
            if not isinstance(data, NDArray) or not isinstance(label, NDArray):
                return False
            s = (tuple(data.shape), str(data.dtype),
                 tuple(label.shape), str(label.dtype))
            if sig0 is None:
                sig0 = s
            elif s != sig0:
                return False   # ragged block cannot share one program
        in_sig = sig0
        from .. import analysis as _analysis
        _analysis.recompile.note(
            self._audit_key, ("data", "label"),
            ((sig0[0], sig0[1]), (sig0[2], sig0[3])))
        dev = self._ctx.jax_device
        staged = [(jax.device_put(d._data, dev), jax.device_put(l._data, dev))
                  for d, l in pairs]

        carry = self._carry if self._carry is not None and \
            getattr(self, "_carry_sdict", None) is self._updater.states and \
            getattr(self, "_carry_sig", None) == in_sig and \
            all(p._data[0]._data is w
                for p, w in zip(self._train_params, self._carry[0])) and \
            all(p._data[0]._data is a
                for p, a in zip(self._aux_params, self._carry[1])) \
            else None

        states = [self._updater.states[i] for i in self._indices]
        if carry is not None:
            ws, auxs, ss = carry
        else:
            ws = [p._data[0]._data for p in self._train_params]
            auxs = tuple(p._data[0]._data for p in self._aux_params)
            ss = tuple(_state_data(s) for s in states)
            # cold dispatch: params/states may be externally staged
            # (initialize, load_parameters, trainer-state restore) —
            # donated host-staged buffers corrupt under the AOT path;
            # re-own through one XLA copy (fused.reown_for_donation)
            from ..fused import reown_for_donation
            ws, auxs, ss = reown_for_donation((ws, auxs, ss))

        mcarry = []
        for m in self._metrics:
            pend = getattr(m, "_device_totals", None)
            if pend is None:
                import jax.numpy as jnp
                pend = (jax.device_put(jnp.zeros((), jnp.float32), dev),
                        jax.device_put(jnp.zeros((), jnp.int32), dev))
            mcarry.append(tuple(pend))

        counts_before = dict(opt._index_update_count)
        num_update_before = opt.num_update
        from ..fused import advance_hyper_rows
        rows, rescale_dev = advance_hyper_rows(opt, self._indices, k, self,
                                               dev)
        t_vec = self._t_vec if carry is not None else None
        if t_vec is None:
            from ..fused import reown_for_donation
            t_vec = reown_for_donation(jax.device_put(_np.asarray(
                [opt._index_update_count[i] - k for i in self._indices],
                _np.float32), dev))

        inner = (tuple(ws), tuple(auxs), ss, tuple(mcarry), t_vec)
        xs = [(dval, lval, lr_j, wd_j)
              for (dval, lval), (lr_j, wd_j) in zip(staged, rows)]

        if _analysis.enabled():
            self._step_no += k
            _analysis.donation.record(
                f"{self._audit_key} step {self._step_no}",
                self._donation_groups(ws, ss, auxs))

        if self._core_closed is not None and in_sig != self._core_sig:
            # signature changed: the traced core jaxpr is shape-
            # specialized — swap in the cached program set for this
            # signature or re-trace (churn recorded by the auditor above);
            # a ragged tail batch must not permanently break the fast path
            cached = self._core_cache.get(in_sig)
            if cached is not None:
                (self._core_closed, self._jit, self._scan_jit,
                 self._jit_block) = cached
            else:
                self._core_closed = None

        try:
            with _no_rng():
                if self._core_closed is None:
                    core = self._build_core()
                    self._trace_core(core, (inner, xs[0], rescale_dev))
                    self._jit = None
                    self._jit_block = {}
                    self._scan_jit = None
                if k == 1:
                    if self._jit is None:
                        self._build1()
                    new_inner, (mean_loss, out) = self._jit(
                        inner, xs[0], rescale_dev)
                    mys = None
                else:
                    jitk = self._jit_block.get(k) or self._buildk(k)
                    # ys (all K steps' losses/outputs) are available from
                    # the scan; handlers only read the latest, so expose
                    # the in-program last slice — mys (per-step metric
                    # carries) feeds the per-batch handler burst
                    new_inner, _ys, mys, (mean_loss, out) = jitk(
                        inner, tuple(xs), rescale_dev)
        except Exception as e:
            opt._index_update_count = counts_before
            opt.num_update = num_update_before
            self._carry = None
            self._t_vec = None
            self._block_view = None
            self.broken = True
            if isinstance(e, _untraceable()):
                # selection: the net's, loss's or optimizer's Python
                # cannot run under a trace (a dropout net draws host RNG)
                _log.warning("gluon fused step not traceable (%s); "
                             "Estimator uses the eager loop", str(e)[:300])
                return False
            # the selected step failed on its device: raised with its
            # cause (naming the donated buffers it consumed, if any),
            # never replaced by the eager loop
            _raise_if_unrecoverable("gluon fused step", e,
                                    self._donation_groups(ws, ss, auxs))
            raise MXNetError(
                f"gluon fused step failed to trace, lower, compile or run "
                f"({type(e).__name__}: {str(e)[:300]})") from e

        new_ws, new_aux, new_ss, new_mcarry, new_t = new_inner
        # write back (params/aux/optimizer state are shared with the eager
        # path so the two stay interchangeable)
        for p, nw in zip(self._train_params, new_ws):
            p._data[0]._set_data(nw)
        for p, na in zip(self._aux_params, new_aux):
            p._data[0]._set_data(na)
        for s, ns in zip(states, new_ss):
            _state_write_back(s, ns)
        finals = []
        for m, pend in zip(self._metrics, new_mcarry):
            t = tuple(pend)
            m._device_totals = t
            finals.append(t)
        if mys is not None:
            # per-step metric exposure for the Estimator handler burst
            self._block_view = _BlockMetricView(self._metrics, mys, finals)
            self._block_view.arm()
        else:
            self._block_view = None
        self._t_vec = new_t
        self.last_loss = NDArray(mean_loss, ctx=self._ctx)
        self.last_outputs = NDArray(out, ctx=self._ctx)
        self._carry = ([p._data[0]._data for p in self._train_params],
                       tuple(p._data[0]._data for p in self._aux_params),
                       tuple(_state_data(s) for s in states))
        self._carry_sig = in_sig
        self._carry_sdict = self._updater.states
        self._core_sig = in_sig
        if len(self._core_cache) < 8 or in_sig in self._core_cache:
            self._core_cache[in_sig] = (self._core_closed, self._jit,
                                        self._scan_jit, self._jit_block)
        return True

    def set_block_cursor(self, j):
        """Expose logical step j's metric state to the Estimator's
        batch-j handler burst (per-step semantics for K>1 blocks)."""
        if self._block_view is not None:
            self._block_view.expose(j)

    def cached_programs(self):
        """Live CachedPrograms across every cached signature set."""
        progs = {}
        for p in (self._jit, getattr(self, "_scan_jit", None)):
            if p is not None and hasattr(p, "export_to"):
                progs[id(p)] = p
        for entry in self._core_cache.values():
            for p in entry[1:3]:
                if p is not None and hasattr(p, "export_to"):
                    progs[id(p)] = p
        return list(progs.values())

    def export_programs(self, directory):
        """Serialize compiled executables into `directory` (checkpoint
        ``programs/`` payload); returns entries written."""
        return sum(p.export_to(directory) for p in self.cached_programs())

    def compile_phase_stats(self):
        """Cold-start phase breakdown — the same artifact shape as
        `fused.FusedTrainStep.compile_phase_stats`, which only touches
        the attributes both step classes share (traced core, scan runs,
        unified-cache program wrappers)."""
        from ..fused import FusedTrainStep
        return FusedTrainStep.compile_phase_stats(self)
