"""Module: symbolic training on one or more devices
(reference `python/mxnet/module/module.py` — bind:364, forward:573,
backward:627, update:644)."""
from __future__ import annotations

import logging

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..initializer import Uniform, InitDesc
from .. import optimizer as opt
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray.ndarray import NDArray
from .. import ndarray as nd
from ..obs import trace as _obs_trace
from .base_module import BaseModule, _as_list
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list or [1] * len(context)

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused_step = None
        self._mesh = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Reference `module.py load`."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Reference `module.py save_checkpoint`."""
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, self._arg_params,
                        self._aux_params)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)

    # -- properties ------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        # inferred, so that a container can wire the next module to them
        # before anything has run (the executors have no outputs until then)
        shapes = {d[0]: tuple(d[1]) for d in
                  list(self._data_shapes) + list(self._label_shapes or [])}
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names, out_shapes))

    # -- params ----------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        self._flush_fused()
        if initializer is None:
            initializer = Uniform(0.01)

        from .. import engine as _engine
        # bulk scope: parameter drafts and initializer writes host-stage and
        # flush as batched transfers — per-array device round trips dominate
        # init on a remote chip otherwise (reference analogue: deferred
        # alloc + engine bulk, `include/mxnet/engine.h:308`)
        with _engine.bulk(1 << 16):
            if self._arg_params is None:
                self._arg_params = {
                    name: nd.zeros(
                        self._exec_group.execs[0].arg_dict[name].shape,
                        dtype=self._exec_group.execs[0].arg_dict[name].dtype)
                    for name in self._param_names}
            if self._aux_params is None:
                self._aux_params = {
                    name: nd.zeros(
                        self._exec_group.execs[0].aux_dict[name].shape,
                        dtype=self._exec_group.execs[0].aux_dict[name].dtype)
                    for name in self._aux_names}

        def _impl(desc, arr, cache):
            # desc carries the variable's attr dict (__init__ etc.) — the
            # initializer dispatches on it, so it must not be rebuilt bare
            if cache is not None:
                if str(desc) in cache:
                    cache_arr = cache[str(desc)]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError(f"{desc} is not presented")
                    if initializer is not None:
                        initializer(desc, arr)
            else:
                if initializer is not None:
                    initializer(desc, arr)

        attrs = self._symbol.attr_dict()
        with _engine.bulk(1 << 16):
            for name, arr in sorted(self._arg_params.items()):
                desc = InitDesc(name, attrs.get(name, None))
                _impl(desc, arr, arg_params)
            for name, arr in sorted(self._aux_params.items()):
                desc = InitDesc(name, attrs.get(name, None))
                _impl(desc, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self._flush_fused()
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            return
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty = True
        self.params_initialized = True

    # -- bind ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        self._data_shapes = data_shapes
        self._label_shapes = label_shapes

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused_step = None

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes
        self.binded = False
        self.bind(data_shapes, label_shapes, self.for_training,
                  self.inputs_need_grad, force_rebind=True)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # -- optimizer -------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False, mesh=None):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        # composed device mesh for the fused step: a jax Mesh, a spec
        # string/dict ('dp=4,tp=2'), or None (MXNET_MESH env spec, else
        # the default 1-D dp mesh over the contexts)
        if mesh is not None and not hasattr(mesh, "axis_names"):
            from ..parallel.mesh import mesh_from_spec
            mesh = mesh_from_spec(
                mesh, devices=[c.jax_device for c in self._context])
        self._mesh = mesh

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            # sync distributed training averages over the global batch
            # (reference module.py:504)
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        # TPU fast path eligibility must be decided BEFORE the kvstore /
        # updater wiring: when the fused step will own the optimizer, the
        # kvstore must never get an optimizer installed (a later unfused
        # update() would then apply it to its own weight copies and pull
        # weights back as gradients) and idx2name must use the per-device
        # layout the local updater / fused indices share
        fusable = self._fusable(kvstore)
        if fusable:
            update_on_kvstore = False

        idx2name = {}
        if update_on_kvstore:
            idx2name.update(enumerate(self._exec_group.param_names))
        else:
            for k in range(len(self._context)):
                idx2name.update({i * len(self._context) + k: n
                                 for i, n in
                                 enumerate(self._exec_group.param_names)})
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    f"is not normalized to 1.0/batch_size/num_workers "
                    f"({optimizer.rescale_grad} vs. {rescale_grad}). Is this "
                    "intended?")

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        # TPU fast path: compile forward+backward+optimizer+metric into ONE
        # donated XLA program per signature (fused.FusedTrainStep) — the
        # public equivalent of the reference's bulk-exec segments + fused
        # update ops (`graph_executor.cc:1194-1316`, `optimizer_op.cc`).
        # Optimizer state lives in self._updater either way, so the
        # fused path and the unfused fallback share one state store.
        self._fused_step = None
        if fusable:
            from .. import fused as _fused
            self._fused_step = _fused.FusedTrainStep(self, self._updater)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _fusable(self, kvstore):
        """Whether fit can run the single-program fused train step."""
        from .. import config as _config
        if not _config.get("MXNET_FUSED_TRAIN_STEP"):
            return False
        if self._state_names or self.inputs_need_grad or not self.for_training:
            return False
        if self._compression_params:
            return False
        if any(v not in ("write", "null")
               for v in self._exec_group.grad_req.values()):
            return False
        if kvstore is not None and \
                getattr(kvstore, "type", "") not in ("local", "device", "tpu"):
            return False
        ndev = len(self._context)
        if ndev > 1:
            if len({c.device_type for c in self._context}) > 1:
                return False
            bs = self._exec_group.batch_size
            if bs % ndev or any(
                    (s.stop - s.start) != bs // ndev
                    for s in self._exec_group.slices):
                return False
            # loss heads with batch/valid normalization divide the gradient
            # by the batch they SEE: the fused single program sees the
            # global batch, the unfused per-device path normalizes by the
            # device slice and sums — a factor-ndev difference.  Keep such
            # graphs on the unfused (reference-semantics) path.
            for n in self._symbol._topo():
                if not n.is_variable and \
                        n.attrs.get("normalization") in ("batch", "valid"):
                    return False
        return True

    def fit_step(self, data_batch, eval_metric):
        """One train step + metric update; fused single-program when
        available (see init_optimizer), reference semantics otherwise.
        Traced as one span — the kvstore push/pull rpc spans it issues
        parent into it, so a training step reads as one connected tree
        across worker and server processes in the merged trace."""
        with _obs_trace.span("fit.step", cat="train"):
            if self._fused_step is not None and \
                    self._fused_step(data_batch, eval_metric):
                return
            self.forward_backward(data_batch)
            self.update()
            self.update_metric(eval_metric, data_batch.label)

    def _fit_block_k(self):
        """K batches per `fit` dispatch: when the fused step is live, one
        `lax.scan` program runs K steps per dispatch (the reference's
        bulk-exec-segment idea, `graph_executor.cc:1194-1316`, taken to
        its XLA-native conclusion)."""
        fs = self._fused_step
        if fs is None or fs.broken:
            return 1
        from .. import config as _config
        return max(int(_config.get("MXNET_FUSED_STEP_BLOCK")), 1)

    def fit_block(self, data_batches, eval_metric):
        """Run a block of batches as ONE fused scan dispatch.  On False the
        fit loop runs the block per-batch (fused 1-step or unfused); the
        pre-dispatch eligibility checks are cheap, so blocks keep being
        attempted — a later block may fuse (e.g. after deferred state
        materializes)."""
        fs = self._fused_step
        if fs is None:
            return False
        with _obs_trace.span("fit.step_block", cat="train",
                             k=len(data_batches)) as sp:
            ran = fs.call_block(data_batches, eval_metric)
            sp.note(fused=bool(ran))
        return ran

    def _fit_block_cursor(self, j):
        """Point get_outputs() AND the in-graph metric totals at batch j
        of the last block while the fit loop fires that batch's
        callbacks (per-logical-step callback semantics for K>1)."""
        fs = self._fused_step
        if fs is not None:
            fs.set_block_cursor(j)

    # -- forward/backward ------------------------------------------------------
    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Pre-stage the upcoming batch's device transfer while the
        current step computes (reference `PrefetcherIter`'s H2D role)."""
        super().prepare(data_batch, sparse_row_id_fn=sparse_row_id_fn)
        fs = self._fused_step
        if fs is not None and not fs.broken and fs._carry is not None:
            # only while the fused path is ACTIVE (a step has run and the
            # carry is armed): otherwise the eager path would transfer the
            # batch a second time
            fs.prestage(data_batch)

    def _flush_fused(self):
        """Deferred fused-step write-backs must land before anything reads
        the public param/state/aux NDArrays (see fused.FusedTrainStep.flush)."""
        if self._fused_step is not None:
            self._fused_step.flush()

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if self._fused_step is not None:
            self._fused_step.clear_outputs()
            self._fused_step.flush()
        self._exec_group.forward(data_batch, is_train)

    def forward_backward(self, data_batch):
        """Fused train step (one XLA program per device)."""
        assert self.binded and self.params_initialized
        self._flush_fused()
        self._exec_group.forward_backward(data_batch)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer using accumulated gradients
        (reference `module.py:644 update`)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._flush_fused()
        self._params_dirty = True
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore,
                                      self._exec_group.param_names)
        else:
            if self._fused_step is not None and len(self._context) > 1:
                self._seed_fallback_states()
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._exec_group.param_names)

    def _seed_fallback_states(self):
        """The fused step keeps optimizer state under device-0 indices
        (i*ndev) only; the unfused per-device update uses i*ndev+k.  A
        mid-training fallback batch must not start devices k>=1 from
        freshly zeroed state — seed them with copies of the fused state so
        the per-device weight copies stay in lockstep."""
        from ..ndarray.ndarray import NDArray

        def _copy_state(s):
            if s is None:
                return None
            if isinstance(s, NDArray):
                return s.copy()
            if isinstance(s, (tuple, list)):
                return tuple(_copy_state(x) for x in s)
            return s

        ndev = len(self._context)
        upd = self._updater
        for i in range(len(self._exec_group.param_names)):
            base = i * ndev
            if base not in upd.states:
                continue
            for k in range(1, ndev):
                if base + k not in upd.states:
                    upd.states[base + k] = _copy_state(upd.states[base])
                    upd.states_synced[base + k] = True

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_step is not None:
            outs = self._fused_step.current_outputs()
            if outs is not None:
                # last step ran fused: outputs are the global-batch arrays
                # (in block mode, the view follows the callback cursor so a
                # batch-j callback reads batch j's outputs)
                return outs
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def _sync_params_from_devices(self):
        if self._exec_group is None or not self._params_dirty:
            return
        self._flush_fused()
        if self._arg_params is None:
            self._arg_params = {}
        if self._aux_params is None:
            self._aux_params = {}
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._kvstore and self._update_on_kvstore:
            for param_name, param_val in sorted(self._arg_params.items()):
                self._kvstore.pull(param_name, param_val)
        self._params_dirty = False

    def get_optimizer_states_blob(self):
        """Full optimizer state as one bytes blob (the checkpoint plane's
        capture point): local updater slots + the pickled optimizer
        (num_update / LR-scheduler position travel along); with a
        server-side optimizer (`update_on_kvstore` on a dist store) the
        slots are pulled back through the kvstore control channel."""
        assert self.optimizer_initialized
        self._flush_fused()
        if self._update_on_kvstore:
            getter = getattr(self._kvstore, "get_optimizer_states", None)
            if getter is None:
                raise MXNetError(
                    f"kvstore {self._kvstore.type!r} runs the optimizer "
                    "server-side but cannot export its state")
            return getter(dump_optimizer=True)
        return self._updater.get_states(dump_optimizer=True)

    def set_optimizer_states_blob(self, blob):
        assert self.optimizer_initialized
        self._flush_fused()  # stale pending state must not clobber the load
        if self._update_on_kvstore:
            setter = getattr(self._kvstore, "set_optimizer_states", None)
            if setter is None:
                raise MXNetError(
                    f"kvstore {self._kvstore.type!r} runs the optimizer "
                    "server-side but cannot restore its state")
            setter(blob)
            return
        self._updater.set_states(blob)
        # a resumed optimizer must keep counting updates where it left off:
        # when the blob carried the pickled optimizer, adopt it as THE
        # optimizer so Module and Updater agree on num_update
        restored = getattr(self._updater, "optimizer", None)
        if isinstance(restored, opt.Optimizer):
            self._optimizer = restored
            if self._fused_step is not None:
                # the fused program captured the PRE-restore optimizer at
                # construction (FusedTrainStep.__init__ caches
                # updater.optimizer); rebuild it or every fused step would
                # keep advancing the stale instance from num_update=0
                # while the restored one stays frozen
                try:
                    from .. import fused as _fused
                    self._fused_step = _fused.FusedTrainStep(self,
                                                             self._updater)
                except Exception as e:
                    self.logger.warning(
                        "fused train step unavailable after optimizer "
                        "state restore (%s); falling back to "
                        "forward_backward+update", str(e)[:200])
                    self._fused_step = None

    def save_optimizer_states(self, fname):
        with open(fname, "wb") as fout:
            fout.write(self.get_optimizer_states_blob())

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as fin:
            self.set_optimizer_states_blob(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        for exe in self._exec_group.execs:
            mon.install(exe)
