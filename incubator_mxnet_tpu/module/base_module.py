"""BaseModule with the classic fit/score/predict training loop
(reference `python/mxnet/module/base_module.py`, fit at :409,
train loop :515-560)."""
from __future__ import annotations

import logging
import statistics
import time

import numpy as _np

from ..base import MXNetError
from .. import metric as _metric
from .. import io as _io
from ..model import BatchEndParam
from ..ndarray.ndarray import NDArray
from ..obs import trace as _obs_trace


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0
        self._supervisor = None   # JobSupervisor of the last dist fit
        self._guardian = None     # TrainingGuardian of the current fit

    # -- high-level API --------------------------------------------------------
    def forward_backward(self, data_batch):
        """Reference `base_module.py:193 forward_backward`."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def fit_step(self, data_batch, eval_metric):
        """One training step plus metric update.  Subclasses may override
        with a fused single-program implementation (Module does on TPU)."""
        self.forward_backward(data_batch)
        self.update()
        self.update_metric(eval_metric, data_batch.label)

    def _fit_block_k(self):
        """How many batches `fit` may hand to `fit_block` per dispatch.
        1 = classic per-batch stepping; Module returns K>1 when the fused
        K-step scan program is available (MXNET_FUSED_STEP_BLOCK)."""
        return 1

    def fit_block(self, data_batches, eval_metric):
        """Run a block of train steps in one dispatch when the subclass
        can (Module: `lax.scan` over K stacked batches).  Returns True when
        handled; False -> `fit` falls back to per-batch `fit_step`."""
        return False

    def _fit_block_cursor(self, j):
        """Hook: `fit` is about to fire batch j's callbacks for the last
        processed block (subclasses point per-batch output views at j)."""

    def check(self, hints=True):
        """Run the `mxlint` static graph passes over this module's Symbol
        (analysis/graph_passes.py) — duplicate names, dead outputs, aux
        races, f64 promotion, unbound inputs, TPU tile-alignment hints —
        seeded with the bound data/label shapes when available.  Returns
        an `analysis.Report`; raises nothing."""
        from .. import analysis as _analysis
        if self._symbol is None:
            return _analysis.Report(target=type(self).__name__)
        shapes = {}
        for desc in list(getattr(self, "_data_shapes", None) or []) + \
                list(getattr(self, "_label_shapes", None) or []):
            if hasattr(desc, "name"):
                shapes[desc.name] = tuple(desc.shape)
            else:
                shapes[desc[0]] = tuple(desc[1])
        return _analysis.check(self._symbol, shapes=shapes or None,
                               hints=hints,
                               target=self._symbol.name or "symbol")

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Reference `base_module.py score`."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for cb in _as_list(score_end_callback):
                cb(params)
        return eval_metric.get_name_value()

    def _infer_buckets(self, eval_data):
        """The shape buckets inference batches pad up to: the iterator's
        batch size (plus any bound data shape, which warmup compiled)."""
        buckets = set()
        bs = getattr(eval_data, "batch_size", 0) or 0
        if bs:
            buckets.add(int(bs))
        if self.binded and getattr(self, "_data_shapes", None):
            shape = self._data_shapes[0][1] if not hasattr(
                self._data_shapes[0], "shape") else self._data_shapes[0].shape
            if shape:
                buckets.add(int(shape[0]))
        return sorted(buckets)

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        buckets = self._infer_buckets(eval_data)
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            if buckets:
                # a ragged tail batch would force a fresh XLA compile
                # (analysis/recompile.py's shape-churn hazard); pad it to
                # the compiled bucket and slice the pad rows back off
                eval_batch = _io.pad_to_bucket(eval_batch, buckets)
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)]
                       for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False,
                sparse_row_id_fn=None):
        """Reference `base_module.py predict`."""
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (NDArray, _np.ndarray)):
            if isinstance(eval_data, _np.ndarray):
                from ..ndarray import array
                eval_data = array(eval_data)
            self.forward(_io.DataBatch([eval_data]))
            return self.get_outputs()[0]
        if reset:
            eval_data.reset()
        buckets = self._infer_buckets(eval_data)
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            if buckets:
                # pad the ragged tail to a compiled bucket instead of
                # recompiling for it (see iter_predict)
                eval_batch = _io.pad_to_bucket(eval_batch, buckets)
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches, as num of outputs "
                                     "is not the same in mini-batches.")
            from ..ndarray import concatenate
            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, checkpoint_dir=None,
            checkpoint_period=100, checkpoint_keep_last=5, resume=False,
            max_restarts=None, mesh=None):
        """THE classic training loop (reference `base_module.py:409 fit`).

        Elastic checkpointing (no reference analogue): with
        ``checkpoint_dir`` set, every `checkpoint_period` processed
        batches an async snapshot of the FULL training state — params,
        optimizer slots, update counts, iterator position, RNG streams —
        is staged to pooled host buffers and committed atomically by a
        background thread while training continues; ``resume=True``
        restores the newest valid checkpoint and continues mid-epoch
        (train-metric accumulation restarts at the resumed batch).  A
        SIGTERM during fit triggers one final synchronous snapshot before
        exiting (checkpoint/manager.py preemption hook).

        Failover (resilience layer): when a distributed run loses a
        parameter server permanently (`ServerLostError` — crashed,
        partitioned past the retry budget, or restarted empty) and a
        ``checkpoint_dir`` is set, fit tears down the kvstore and
        restarts from the last committed checkpoint instead of dying, up
        to ``max_restarts`` times (default: MXNET_FIT_MAX_RESTARTS).  A
        replacement server must be reachable at the configured address —
        the restarted fit re-registers, re-pushes the checkpointed
        params, and re-ships the optimizer exactly like a fresh launch.
        The budget covers failures during the restart's own re-init too
        (the replacement server dying mid-handshake consumes a restart,
        not the whole run).

        Elastic supervision (resilience/supervisor.py): a multi-worker
        dist fit runs under a per-host `JobSupervisor` (MXNET_SUPERVISOR)
        — heartbeats to the coordinator, a watchdog around every sync
        push/pull/barrier, straggler findings.  A HOST loss then surfaces
        as a `CollectiveTimeoutError` naming the absent hosts instead of
        an indefinite hang, and with a ``checkpoint_dir`` set fit drives
        **shrink-and-resume**: the survivors agree on the new world size
        via the epoch-fenced shrink barrier, this worker adopts its new
        (dense) rank, and the run restarts from the last committed
        checkpoint at the smaller world size — a fenced-out stale host
        can never rejoin and corrupt the shrunk pod.

        Training guardian (resilience/guardian.py, MXNET_GUARDIAN): the
        fused step computes an in-graph health word (all-finite + grad
        norm) and refuses non-finite updates (**skip-batch**, positions
        quarantined); a diagnosed loss spike triggers
        **rollback-to-last-good** — the newest checkpoint whose manifest
        carries a healthy ``health`` stamp at or before the last
        in-bounds step is restored, the intervening good batches replay
        bit-identically, and the quarantined spike window is skipped;
        past the failure/rollback budget a structured
        `TrainingDivergedError` names the step, signal, and data shard.
        """
        import os as _os
        from ..resilience import ServerLostError, CollectiveTimeoutError
        from ..resilience import guardian as _guardian_mod
        if max_restarts is None:
            from .. import config as _config
            max_restarts = int(_config.get("MXNET_FIT_MAX_RESTARTS"))
        failed_over = False
        self._guardian = _guardian_mod.TrainingGuardian.maybe_create(
            checkpoint_dir, logger=self.logger)
        # every attempt gets the same fixed arguments; the restart loop
        # below only flips resume/force flags (one dict, not a second
        # copy of the parameter list to keep in sync)
        fixed = dict(
            eval_data=eval_data, eval_metric=eval_metric,
            epoch_end_callback=epoch_end_callback,
            batch_end_callback=batch_end_callback, kvstore=kvstore,
            optimizer=optimizer, optimizer_params=optimizer_params,
            eval_end_callback=eval_end_callback,
            eval_batch_end_callback=eval_batch_end_callback,
            initializer=initializer, arg_params=arg_params,
            aux_params=aux_params, allow_missing=allow_missing,
            begin_epoch=begin_epoch, num_epoch=num_epoch,
            validation_metric=validation_metric, monitor=monitor,
            sparse_row_id_fn=sparse_row_id_fn,
            checkpoint_dir=checkpoint_dir,
            checkpoint_period=checkpoint_period,
            checkpoint_keep_last=checkpoint_keep_last, mesh=mesh)
        while True:
            try:
                return self._fit_attempt(
                    train_data, force_rebind=force_rebind,
                    force_init=force_init, resume=resume, **fixed)
            except _guardian_mod.RollbackRequested as e:
                # the guardian diagnosed a loss spike whose update was
                # already applied: restore the newest HEALTHY checkpoint
                # at or before the last in-bounds step (the guardian's
                # pending_rollback_step bounds the resume selection) and
                # replay — the spike window itself is quarantined, so
                # the resumed run skips it.  Budgeted inside the
                # guardian: past MXNET_GUARDIAN_MAX_ROLLBACKS the spike
                # escalates to TrainingDivergedError instead.
                if checkpoint_dir is None or self._guardian is None:
                    raise
                self.logger.warning(
                    "fit: %s — restarting from the last healthy "
                    "checkpoint in %r", e, checkpoint_dir)
                self._teardown_kvstore()
                resume = True
                force_rebind = True
                force_init = True
            except (ServerLostError, CollectiveTimeoutError,
                    ConnectionError, EOFError, TimeoutError) as e:
                # raw connection/timeout errors are recoverable only on a
                # RESTART attempt's re-init (handshake against the
                # replacement server, before per-server breakers exist) —
                # on a first attempt they are real configuration errors
                if not isinstance(e, (ServerLostError,
                                      CollectiveTimeoutError)) \
                        and not failed_over:
                    raise
                if checkpoint_dir is None or max_restarts <= 0:
                    raise
                if not isinstance(kvstore, str):
                    # a caller-provided kvstore INSTANCE cannot be
                    # rebuilt; restarting would loop on its closed
                    # channels — surface the loss instead
                    raise
                if isinstance(e, CollectiveTimeoutError):
                    # a HOST (not a server) is gone: before restarting,
                    # the survivors must agree on the smaller world —
                    # the epoch-fenced shrink barrier.  This worker then
                    # adopts its new dense rank and the post-shrink
                    # membership epoch; the coordinator reset the kvstore
                    # state at commit, so the resumed attempt re-inits it
                    # from the checkpoint exactly like a fresh launch.
                    if self._supervisor is None:
                        raise
                    try:
                        shrink = self._supervisor.shrink(reason=str(e))
                    except Exception as shrink_exc:
                        self.logger.error(
                            "fit: shrink barrier failed (%s) after %s",
                            shrink_exc, e)
                        raise e from shrink_exc
                    self.logger.warning(
                        "fit: %s — pod shrunk to world_size=%d at epoch "
                        "%d (this worker: rank %d -> %d)", e,
                        shrink.world_size, shrink.epoch,
                        self._supervisor.rank, shrink.rank)
                    _os.environ["DMLC_RANK"] = str(shrink.rank)
                    _os.environ["DMLC_NUM_WORKER"] = str(shrink.world_size)
                    _os.environ["MXNET_SUPERVISOR_EPOCH"] = \
                        str(shrink.epoch)
                    self._supervisor = None
                    # the pre-shrink jax.distributed group still spans
                    # the dead host: tear it down so the restarted
                    # kvstore's collective plane re-initializes (and
                    # re-derives its worker mesh) at the surviving world
                    # size instead of failing against the stale group
                    # and silently degrading to the socket data plane.
                    # User code holding its own dp meshes re-derives
                    # them with parallel.mesh.rebuild().
                    try:
                        from ..dist import collective as _collective
                        _collective.shutdown()
                    except Exception:
                        pass
                max_restarts -= 1
                failed_over = True
                self.logger.warning(
                    "fit: %s — restarting from the last checkpoint in %r "
                    "(%d restart(s) remaining)", e, checkpoint_dir,
                    max_restarts)
                self._teardown_kvstore()
                # the next attempt resumes the checkpoints THIS run wrote
                # (when one exists, its params override everything);
                # caller-supplied arg_params stay in place as the
                # fallback — a crash BEFORE the first commit must restart
                # from the caller's (e.g. pretrained) weights, not from a
                # fresh initializer draw
                resume = True
                force_rebind = True
                force_init = True

    def _fit_attempt(self, train_data, eval_data=None, eval_metric="acc",
                     epoch_end_callback=None, batch_end_callback=None,
                     kvstore="local", optimizer="sgd",
                     optimizer_params=(("learning_rate", 0.01),),
                     eval_end_callback=None, eval_batch_end_callback=None,
                     initializer=None, arg_params=None, aux_params=None,
                     allow_missing=False, force_rebind=False,
                     force_init=False, begin_epoch=0, num_epoch=None,
                     validation_metric=None, monitor=None,
                     sparse_row_id_fn=None, checkpoint_dir=None,
                     checkpoint_period=100, checkpoint_keep_last=5,
                     resume=False, mesh=None):
        """One fit attempt; `ServerLostError` propagates to `fit`'s
        restart loop with the checkpoint manager already flushed/closed."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        if initializer is None:
            initializer = Uniform(0.01)

        ckpt_mgr = None
        ckpt_resume = None
        resume_nbatch = 0
        gstep = 0
        if checkpoint_dir is not None:
            from .. import checkpoint as _ckpt
            from .. import config as _config
            if _config.get("MXNET_PROGRAM_CACHE"):
                # a prior run's programs/ payload: compiled executables
                # this attempt can load instead of recompiling (the
                # cold-start half of elastic restart; compile/ subsystem)
                import os as _os
                from .. import compile as _compile
                _compile.add_source(_os.path.join(checkpoint_dir,
                                                  "programs"))
            if resume:
                # read-only: the manager (writer, retention, rank layout)
                # is built AFTER init_optimizer, when the kvstore — and
                # with it this process's rank — is known
                g = getattr(self, "_guardian", None)
                if g is not None and g.pending_rollback_step is not None:
                    # rollback-to-last-good: newer checkpoints may carry
                    # the spike's damage — select by health stamp AND
                    # the guardian's last in-bounds step
                    path = _ckpt.latest_healthy(
                        checkpoint_dir, max_step=g.pending_rollback_step)
                else:
                    path = _ckpt.latest(checkpoint_dir)
                ckpt_resume = _ckpt.load(path) if path is not None else None
            elif _ckpt.latest(checkpoint_dir, deep=False,
                              include_rejected=True) is not None:
                # include_rejected: even a directory holding ONLY
                # canary-rejected checkpoints belongs to some other run
                # a fresh run must not share a directory with an old run's
                # checkpoints: the old run's higher step numbers would win
                # `latest()` after this run's first crash and resume would
                # silently continue the ABANDONED run
                raise MXNetError(
                    f"checkpoint_dir {checkpoint_dir!r} already holds "
                    "checkpoints from a previous run; pass resume=True to "
                    "continue it, or point a fresh run at a fresh "
                    "directory (or delete the old checkpoints)")
            if ckpt_resume is not None:
                self.logger.info("resuming from %s (step %d, epoch %d, "
                                 "batch %d)", ckpt_resume.path,
                                 ckpt_resume.step, ckpt_resume.epoch,
                                 ckpt_resume.nbatch)
                arg_params, aux_params = _ckpt.state.split_params(
                    ckpt_resume.arrays)
                allow_missing = False
                force_init = True
                begin_epoch = ckpt_resume.epoch
                resume_nbatch = ckpt_resume.nbatch
                gstep = ckpt_resume.step

        with _obs_trace.phase("fit.bind", cat="train"):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with _obs_trace.phase("fit.init_params", cat="train"):
            self.init_params(initializer=initializer, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with _obs_trace.phase("fit.init_optimizer", cat="train"):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params, mesh=mesh)
        sup = self._start_supervisor()
        # h2d staging ring (io_plane.py, MXNET_IO_RING): wrap the
        # training iterator so batches decode, stage into reusable host
        # buffers, and transfer on the mx-io-h2d thread WHILE the
        # current step computes — the fit loop pops device-resident
        # batches and never blocks on device_put.  Wrapped here (after
        # init_optimizer) so the fused step's exact staging target —
        # data sharding + per-input dtypes — binds the ring; checkpoint
        # capture, guardian quarantine and seek all delegate through.
        train_data, io_ring = self._wrap_io_ring(train_data)
        if checkpoint_dir is not None:
            from .. import checkpoint as _ckpt
            # dist layout: the resolved kvstore names this process's rank —
            # rank 0 owns params/manifest/retention, other ranks publish
            # side shards only (checkpoint/manager.py dist layout)
            kv = getattr(self, "_kvstore", None)
            rank = getattr(kv, "rank", 0) if kv is not None else 0
            num_ranks = getattr(kv, "num_workers", 1) if kv is not None \
                else 1
            ckpt_mgr = _ckpt.CheckpointManager(
                checkpoint_dir, keep_last=checkpoint_keep_last,
                rank=rank, num_ranks=num_ranks)
            if ckpt_resume is not None and rank != 0:
                # this worker's rank-local state (its own iterator
                # position/permutation, RNG streams) lives in ITS shard;
                # rank 0's blobs must not stand in for it — absent a shard
                # (lagging rank at commit time) fall back to position-only
                # resume via the manifest's nbatch
                ckpt_resume.blobs.pop(_ckpt.state.ITERATOR_BLOB, None)
                ckpt_resume.rng = None
                shard = ckpt_resume.rank_shard(rank)
                if shard is not None:
                    ckpt_resume.blobs.update(shard.get("blobs") or {})
                    ckpt_resume.rng = shard.get("rng")
        if ckpt_resume is not None:
            from .. import checkpoint as _ckpt
            _ckpt.state.restore_module_optimizer(
                self, ckpt_resume.blobs.get(_ckpt.state.OPTIMIZER_BLOB))
            _ckpt.state.restore_rng(ckpt_resume.rng)
        guardian = getattr(self, "_guardian", None)
        if guardian is not None:
            if guardian.pending_rollback_step is not None:
                # the restore landed (or no healthy checkpoint existed
                # and this attempt restarts from the caller's params) —
                # either way the rollback is committed and the spike
                # detector's history starts fresh
                guardian.rollback_committed(
                    ckpt_resume.step if ckpt_resume is not None else 0)
            # attach AFTER every fused-step rebuild path (init_optimizer
            # and the optimizer-state restore both construct fresh ones)
            guardian.attach(self)
            guardian.attach_iterator(train_data)
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        last_snap_step = gstep
        if ckpt_mgr is not None:
            ckpt_mgr.install_preemption_hook()
        from .. import analysis as _analysis
        from ..resilience import CollectiveTimeoutError, ServerLostError
        server_lost = False
        try:
            with _analysis.hostsync.hot_loop("Module.fit"):
                self._fit_epochs(
                    train_data, eval_data, eval_metric, validation_metric,
                    epoch_end_callback, batch_end_callback,
                    eval_end_callback, eval_batch_end_callback, monitor,
                    sparse_row_id_fn, begin_epoch, num_epoch, ckpt_mgr,
                    ckpt_resume, resume_nbatch, gstep, last_snap_step,
                    checkpoint_period)
        except (ServerLostError, CollectiveTimeoutError):
            server_lost = True   # either failover signal must not be
            raise                # masked by a deferred flush error
        finally:
            if io_ring is not None:
                # stop the feeder thread and drop read-ahead; the INNER
                # iterator stays usable for the caller/restart loop
                try:
                    io_ring._pause()
                except Exception:
                    pass
            if sup is not None:
                # stop the heartbeat loop but KEEP self._supervisor: the
                # restart loop's shrink barrier still needs its identity
                # and membership view (the shrink request rides a fresh
                # channel, not the stopped heartbeat one)
                from ..resilience import supervisor as _sup_mod
                _sup_mod.deactivate(sup)
                try:
                    sup.stop()
                except Exception:
                    pass
            if ckpt_mgr is not None:
                try:
                    ckpt_mgr.flush()
                except MXNetError:
                    # a deferred background-write error must not mask the
                    # failover signal the restart loop keys on
                    if not server_lost:
                        raise
                finally:
                    ckpt_mgr.close()

    def _wrap_io_ring(self, train_data):
        """Wrap the training iterator with the h2d staging ring
        (io_plane.DevicePrefetchIter) when MXNET_IO_RING is on and a
        fused train step is live to provide the placement.  Returns
        ``(iterator, ring_or_None)``; the caller closes the ring when
        the attempt ends."""
        from .. import config as _config
        fs = getattr(self, "_fused_step", None)
        if fs is None or getattr(fs, "broken", False) or \
                not _config.get("MXNET_IO_RING"):
            return train_data, None
        from .. import io_plane as _io_plane
        if isinstance(train_data, _io_plane.DevicePrefetchIter):
            return train_data, None
        if not hasattr(train_data, "next") or \
                not hasattr(train_data, "reset"):
            return train_data, None   # a bare iterable: leave it alone
        wrapped = _io_plane.DevicePrefetchIter(
            train_data, placement=fs.ring_placement, name="fit")
        return wrapped, wrapped

    def _start_supervisor(self):
        """Attach a `JobSupervisor` to a multi-worker dist fit: heartbeat
        this host into the coordinator's membership table and arm the
        hung-collective watchdog around the kvstore's sync exchanges.
        Returns the started supervisor (also kept on `self._supervisor`
        for the restart loop's shrink barrier) or None — single-process
        and non-dist runs never pay for supervision, and a supervisor
        bring-up failure degrades to the unsupervised PR 5 behavior
        instead of blocking training."""
        self._supervisor = None
        kv = getattr(self, "_kvstore", None)
        if kv is None or getattr(kv, "num_workers", 1) <= 1 or \
                not hasattr(kv, "_chan"):
            return None
        from .. import config as _config
        if not _config.get("MXNET_SUPERVISOR"):
            return None
        from ..resilience import supervisor as _sup_mod
        try:
            sup = _sup_mod.JobSupervisor.for_kvstore(kv).start()
        except Exception as e:
            self.logger.warning(
                "supervisor unavailable (%s); continuing unsupervised",
                str(e)[:200])
            return None
        _sup_mod.activate(sup)
        self._supervisor = sup
        return sup

    def _teardown_kvstore(self):
        """Drop the current kvstore connection so the next
        `init_optimizer` builds a fresh one (the failover restart path).
        No protocol 'stop' is sent: this worker is RESTARTING, not
        leaving — a 'stop' would count toward the surviving servers'
        shutdown quorum and take them down under the resumed run."""
        kv = getattr(self, "_kvstore", None)
        if kv is not None:
            try:
                if getattr(kv, "_chans", None) is not None:
                    kv.close(send_stop=False)
                else:
                    kv.close()
            except Exception:
                pass
        self._kvstore = None
        self.optimizer_initialized = False

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, sparse_row_id_fn,
                    begin_epoch, num_epoch, ckpt_mgr, ckpt_resume,
                    resume_nbatch, gstep, last_snap_step, checkpoint_period):
        from ..resilience import faults as _faults
        guardian = getattr(self, "_guardian", None)
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            if ckpt_resume is not None and epoch == begin_epoch:
                # continue mid-epoch: native iterator restore (shuffle
                # permutation + position) where supported, reset+skip
                # otherwise; metric accumulation restarts here
                from .. import checkpoint as _ckpt
                _ckpt.state.restore_iterator(
                    train_data,
                    ckpt_resume.blobs.get(_ckpt.state.ITERATOR_BLOB),
                    resume_nbatch)
                nbatch = resume_nbatch
            data_iter = iter(train_data)
            end_of_batch = False
            try:
                next_data_batch = next(data_iter)
            except StopIteration:
                end_of_batch = True
                next_data_batch = None
            while not end_of_batch:
                if guardian is not None and \
                        guardian.should_skip(epoch, nbatch):
                    # quarantined stream position: consume it, never
                    # train on it — the position still advances so
                    # resume bookkeeping stays aligned with the run
                    # that wrote the quarantine entry
                    guardian.note_skipped(epoch, nbatch)
                    nbatch += 1
                    try:
                        next_data_batch = next(data_iter)
                    except StopIteration:
                        end_of_batch = True
                    continue
                # pod chaos site: a `kill` here is a whole-host SIGKILL
                # at a step boundary (the membership deadline detects it,
                # the survivors' watchdogs convert the stalled round)
                _faults.fire("host.step", nbatch=nbatch, epoch=epoch)
                step_tic = time.time()
                data_batch = next_data_batch
                nbatch_at_entry = nbatch
                # block mode: collect K batches and let the subclass run
                # them as ONE dispatch (Module: lax.scan over K stacked
                # batches — host bookkeeping amortizes across the block).
                # Callbacks still fire once per batch, in bursts of K.
                block = [data_batch]
                block_k = 1 if monitor is not None else self._fit_block_k()
                while len(block) < block_k and not end_of_batch:
                    if guardian is not None and guardian.should_skip(
                            epoch, nbatch_at_entry + len(block)):
                        # a quarantined position mid-block: stop the
                        # block before it (it becomes the next head and
                        # the loop-top skip consumes it)
                        break
                    try:
                        block.append(next(data_iter))
                    except StopIteration:
                        end_of_batch = True
                burst = ()
                if monitor is not None:
                    # monitoring needs per-pass intermediate values: use the
                    # unfused forward/backward so the hooks can observe them
                    monitor.tic()
                    self.forward_backward(data_batch)
                    self.update()
                    burst = block   # single batch; callback fires below
                elif len(block) == block_k and block_k > 1 and \
                        self.fit_block(block, eval_metric):
                    burst = block   # one scan dispatch; callbacks burst
                else:
                    # classic per-batch stepping with classic callback
                    # timing (the tail of an epoch, or a block the fused
                    # path rejected — e.g. a host-side metric, where a
                    # deferred burst would hand batch-j callbacks block-
                    # final metric/output state for no fusion benefit)
                    for b in block:
                        self.fit_step(b, eval_metric)
                        if batch_end_callback is not None:
                            with _obs_trace.span("fit.callbacks",
                                                 cat="train", epoch=epoch,
                                                 nbatch=nbatch):
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals())
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        nbatch += 1
                if not end_of_batch:
                    try:
                        next_data_batch = next(data_iter)
                        self.prepare(next_data_batch,
                                     sparse_row_id_fn=sparse_row_id_fn)
                    except StopIteration:
                        end_of_batch = True
                if monitor is not None:
                    self.update_metric(eval_metric, data_batch.label)
                    monitor.toc_print()
                if burst:
                    # the burst of K cursor moves and callbacks: host
                    # work between two blocks, under one span
                    with _obs_trace.span("fit.callbacks", cat="train",
                                         epoch=epoch, nbatch=nbatch,
                                         k=len(burst)) as sp:
                        moves = []
                        for _bi, _b in enumerate(burst):
                            move_tic = time.perf_counter()
                            self._fit_block_cursor(_bi)
                            moves.append(time.perf_counter() - move_tic)
                            if batch_end_callback is not None:
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals())
                                for callback in _as_list(
                                        batch_end_callback):
                                    callback(batch_end_params)
                            nbatch += 1
                        # a move enqueues a few tiny programs on the
                        # block just dispatched, the same host work K
                        # times.  The runtime takes only so many
                        # programs in flight (32 on the v5e): with the
                        # block before still running and that block's
                        # own burst queued behind it, one enqueue of
                        # this burst stands until that block ends.  What
                        # a move took beyond the median move is that
                        # wait FOR the device, not work
                        typical = statistics.median_low(moves)
                        sp.note(wait_us=int(sum(
                            max(0.0, m - typical) for m in moves) * 1e6))

                gstep += nbatch - nbatch_at_entry
                if guardian is not None and nbatch > nbatch_at_entry:
                    # pair the block's health tokens with their stream
                    # positions, then run the policy ladder every
                    # MXNET_GUARDIAN_INTERVAL steps (one device gather;
                    # raises RollbackRequested / TrainingDivergedError).
                    # The poll lags by one dispatch: it waits for the
                    # block BEFORE the one dispatched above, which stays
                    # queued on the device, so the device goes from one
                    # block to the next with no host in between and this
                    # loop collects the next block's batches under it
                    guardian.tag(epoch, nbatch_at_entry, train_data)
                    guardian.maybe_poll(gstep)
                if self._supervisor is not None and nbatch > nbatch_at_entry:
                    # per-step wall time feeds the heartbeat EWMA the
                    # coordinator's straggler detection compares across
                    # the pod; the step counter keys lag detection
                    self._supervisor.record_step(
                        (time.time() - step_tic) /
                        (nbatch - nbatch_at_entry))
                if ckpt_mgr is not None and nbatch > nbatch_at_entry:
                    # batch boundary: params and (epoch, nbatch, step)
                    # agree — the only place a snapshot may be taken
                    ckpt_mgr.honor_preemption(
                        lambda: self._elastic_snapshot(
                            ckpt_mgr, train_data, epoch, nbatch, gstep,
                            sync=True, meta={"preempted": True}))
                    if gstep - last_snap_step >= checkpoint_period:
                        self._elastic_snapshot(ckpt_mgr, train_data, epoch,
                                               nbatch, gstep)
                        last_snap_step = gstep

            if guardian is not None:
                # drain the tail of the epoch's health tokens before the
                # boundary snapshot stamps its manifest
                guardian.maybe_poll(gstep, force=True)
            # epoch boundary: eval scoring, param syncs, callbacks and
            # snapshots legitimately block once per epoch — not hot-loop
            # host-sync hazards (analysis.hostsync would misattribute)
            from .. import analysis as _analysis
            with _analysis.hostsync.paused():
                with _obs_trace.phase("fit.epoch_end", cat="train",
                                      epoch=epoch, nbatch=nbatch) as sp:
                    # the metric read is where the loop first needs the
                    # device's results: its time is the wait for the
                    # last block (not work), told apart as `wait_us`
                    wait_tic = time.perf_counter()
                    name_values = eval_metric.get_name_value()
                    sp.note(wait_us=int(
                        (time.perf_counter() - wait_tic) * 1e6))
                    for name, val in name_values:
                        self.logger.info("Epoch[%d] Train-%s=%f", epoch,
                                         name, val)
                    toc = time.time()
                    self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                     (toc - tic))

                    # every parameter to the host and back: `bytes` each
                    # way where tracing is on (PERF.md section 3)
                    with _obs_trace.phase("fit.get_params",
                                          cat="train") as ph:
                        arg_params_, aux_params_ = self.get_params()
                        moved = _params_nbytes(arg_params_, aux_params_) \
                            if _obs_trace.enabled() else None
                        ph.note(bytes=moved)
                    with _obs_trace.phase("fit.set_params", cat="train",
                                          bytes=moved):
                        self.set_params(arg_params_, aux_params_)
                    with _obs_trace.phase("fit.op_counters", cat="train"):
                        self._note_op_counters(aux_params_)

                    if epoch_end_callback is not None:
                        for callback in _as_list(epoch_end_callback):
                            callback(epoch, self.symbol, arg_params_,
                                     aux_params_)

                if eval_data:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
                if ckpt_mgr is not None:
                    # epoch-boundary snapshot AFTER the reset so the fresh
                    # shuffle permutation travels with it: resume starts
                    # the next epoch exactly as this run would have
                    self._elastic_snapshot(ckpt_mgr, train_data, epoch + 1,
                                           0, gstep)
                    last_snap_step = gstep
                    ckpt_mgr.honor_preemption(
                        lambda: self._elastic_snapshot(
                            ckpt_mgr, train_data, epoch + 1, 0, gstep,
                            sync=True, meta={"preempted": True}))

    def _note_op_counters(self, aux_params):
        """Leave in `mx.obs` what the graph's counting operators have added
        to their auxiliary states since the last look (`OpDef.counters`
        says which span, counters and gauges: `RoutedExperts`' routing load
        is `moe.load`), read from the parameters the epoch's end has just
        synchronised (no read of its own, none inside the block loop)."""
        if self.symbol is None:     # a container of modules has no graph
            return
        nodes = self.__dict__.get("_counter_nodes")
        if nodes is None:
            # the auxiliary states are an op's last inputs
            nodes = self._counter_nodes = [
                (n.op, n.attrs, [(slot, var.name) for slot, (var, _) in list(
                    zip(n.op.list_input_names(n.attrs),
                        n.inputs))[-n.op.num_aux(n.attrs):]])
                for n in self.symbol._topo()
                if not n.is_variable and n.op.counters is not None]
        seen = self.__dict__.setdefault("_counters_seen", {})

        def since(name):
            now = aux_params[name].asnumpy().astype(_np.float64)
            before = seen.get(name, 0.0)
            seen[name] = now
            # counters that fell were set anew (`fit(aux_params=...)`)
            return now if _np.any(now < before) else now - before

        deltas = {}
        for op, params, slots in nodes:
            deltas.setdefault(op, []).append(dict(
                {slot: since(name) for slot, name in slots}, params=params))
        from ..obs import metrics as _obs_metrics
        for op, per_node in deltas.items():
            note = op.counters(per_node)
            with _obs_trace.span(note["span"], cat="train", **note["args"]):
                pass
            for key, by in note["counters"].items():
                _obs_metrics.counter(key).inc(by)
            for key, value in note["gauges"].items():
                _obs_metrics.gauge(key).set(value)

    def _elastic_snapshot(self, mgr, train_data, epoch, nbatch, step,
                          sync=False, meta=None):
        """Stage one elastic checkpoint: sync device->pooled-host gather,
        background serialization + atomic commit (checkpoint/)."""
        from .. import analysis as _analysis
        with _analysis.hostsync.paused():
            return self._elastic_snapshot_impl(mgr, train_data, epoch,
                                               nbatch, step, sync=sync,
                                               meta=meta)

    def _elastic_snapshot_impl(self, mgr, train_data, epoch, nbatch, step,
                               sync=False, meta=None):
        """Checkpoint gathers block by design — not hot-loop host syncs
        (hence the `paused()` wrapper above)."""
        from .. import checkpoint as _ckpt
        guardian = getattr(self, "_guardian", None)
        if guardian is not None:
            # drain pending health tokens FIRST: a snapshot must never
            # stamp itself healthy on stale evidence (an undetected
            # spike raises here and the snapshot is not taken at all)
            guardian.maybe_poll(step, force=True)
            meta = dict(meta or {}, health=guardian.health_stamp())
        if mgr.rank != 0:
            # non-primary ranks publish ONLY rank-local state (this
            # worker's iterator position/permutation; its updater slots
            # when the optimizer runs worker-side) as a side shard —
            # params are identical across ranks and a server-side
            # optimizer's slots are rank 0's to pull, so gathering either
            # here would multiply checkpoint cost by the worker count for
            # bytes that are thrown away
            blobs = {}
            if self.optimizer_initialized and \
                    not getattr(self, "_update_on_kvstore", False) and \
                    getattr(self, "_updater", None) is not None:
                blobs[_ckpt.state.OPTIMIZER_BLOB] = \
                    self._updater.get_states(dump_optimizer=True)
            it_blob = _ckpt.state.capture_iterator(train_data)
            if it_blob is not None:
                blobs[_ckpt.state.ITERATOR_BLOB] = it_blob
            mgr.snapshot(arrays={}, blobs=blobs, step=step, epoch=epoch,
                         nbatch=nbatch, sync=sync, meta=meta)
            return
        arrays, blobs = _ckpt.state.capture_module(self, train_data)
        meta = dict(meta or {})
        optimizer = getattr(self, "_optimizer", None)
        if optimizer is not None:
            # scalar optimizer position in the manifest (human-inspectable
            # evidence; the authoritative tensors ride the optimizer blob)
            meta["optimizer"] = optimizer.state_dict()
        mgr.snapshot(arrays=arrays, blobs=blobs, step=step, epoch=epoch,
                     nbatch=nbatch, sync=sync, meta=meta)
        self._export_checkpoint_programs(mgr)

    def _export_checkpoint_programs(self, mgr):
        """Ship the fused step's compiled executables as a ``programs/``
        payload next to the checkpoints, so a resumed (or freshly
        served) process loads programs from disk instead of recompiling
        (compile/ subsystem).  Entries are individually CRC'd and
        atomically published — a torn payload degrades to a recompile,
        never to a bad resume — and already-exported entries are
        skipped, so the steady-state cost is a directory stat."""
        from .. import config as _config
        if not _config.get("MXNET_PROGRAM_CACHE") or \
                not _config.get("MXNET_PROGRAM_CACHE_CHECKPOINT"):
            return
        fs = getattr(self, "_fused_step", None)
        if fs is None or getattr(fs, "broken", False):
            return
        import os
        try:
            fs.export_programs(os.path.join(mgr.directory, "programs"))
        except Exception as e:
            # payload is an optimization, never worth failing a snapshot
            self.logger.debug("program payload export skipped (%s)",
                              str(e)[:200])

    # -- properties / abstract -------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        from ..ndarray import save
        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load
        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)

    def install_monitor(self, mon):
        raise NotImplementedError()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False, mesh=None):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


def _params_nbytes(*dicts):
    """Bytes of the arrays in the given name -> NDArray dicts."""
    return sum(a.size * a.dtype.itemsize
               for d in dicts for a in (d or {}).values())
