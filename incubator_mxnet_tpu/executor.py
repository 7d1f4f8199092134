"""Executor: a bound Symbol compiled to single XLA computations.

Re-expression of `src/executor/graph_executor.cc` (Bind/SimpleBind at
:1575/1606, Forward :63, Backward :76) for TPU.  Where the reference builds
per-node engine ops with a memory plan (`PlanMemory`) and fuses bulk segments,
here the *whole graph* is one `jax.jit`-compiled XLA program per
(train-mode, input-signature) — memory planning, fusion, and scheduling are
delegated to XLA (SURVEY.md §7 design stance).  The Forward/Backward split is
preserved: Forward runs the forward executable; Backward runs a combined
forward+vjp executable reusing the SAME rng key so stochastic ops (Dropout)
see identical masks in both passes, matching the reference's stored-mask
semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray.ndarray import NDArray
from .symbol.symbol import Symbol, graph_eval_fn

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, arg_arrays, grad_arrays, grad_req,
                 aux_arrays):
        self._symbol = symbol
        self._ctx = ctx
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self.arg_arrays = list(arg_arrays)
        self.grad_arrays = list(grad_arrays)
        self.aux_arrays = list(aux_arrays)
        self.arg_dict = dict(zip(arg_names, self.arg_arrays))
        self.grad_dict = dict(zip(arg_names, self.grad_arrays))
        self.aux_dict = dict(zip(aux_names, self.aux_arrays))
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in arg_names}
        self.outputs = []
        self._monitor_callback = None

        self._fns = {}      # is_train -> python graph fn
        self._fwd_jit = {}  # is_train -> jitted forward
        self._bwd_jit = None
        self._n_rng = 0
        self._last_key = None
        self._last_is_train = False

    # -- compilation ---------------------------------------------------------
    def _graph_fn(self, is_train):
        if is_train not in self._fns:
            fn, arg_nodes, aux_nodes, n_rng = graph_eval_fn(self._symbol,
                                                            is_train)
            self._n_rng = n_rng
            self._fns[is_train] = fn
        return self._fns[is_train]

    def _forward_jit(self, is_train):
        if is_train not in self._fwd_jit:
            fn = self._graph_fn(is_train)
            self._fwd_jit[is_train] = jax.jit(
                lambda args, aux, key: fn(args, aux, key))
        return self._fwd_jit[is_train]

    def _forward_res_jit(self):
        """Training forward that ALSO returns the vjp residuals, so
        `backward()` replays only the linearized backward pass — the
        reference reuses forward activations the same way
        (`graph_executor.cc:63,76` Forward stashes, Backward consumes).
        `jax.vjp`'s function is a `Partial` pytree whose leaves are the
        residual arrays: a jit can return it, and `_vjp_apply_jit`
        consumes it in a second program with no forward recompute."""
        if getattr(self, "_fwd_res", None) is None:
            fn = self._graph_fn(True)
            wrt_idx = [i for i, n in enumerate(self._symbol.list_arguments())
                       if self._grad_req.get(n, "null") != "null"]

            def run(args, aux, key):
                args = list(args)

                def f(wrt_vals):
                    for i, v in zip(wrt_idx, wrt_vals):
                        args[i] = v
                    outs, new_aux = fn(tuple(args), aux, key)
                    return outs, new_aux

                outs, vjp, new_aux = jax.vjp(
                    f, tuple(args[i] for i in wrt_idx), has_aux=True)
                return outs, new_aux, vjp

            self._fwd_res = jax.jit(run)
            self._bwd_wrt_idx = wrt_idx

            def apply(vjp, cts):
                (grads,) = vjp(cts)
                return grads

            self._vjp_apply_jit = jax.jit(apply)
        return self._fwd_res

    def _backward_jit(self):
        if self._bwd_jit is None:
            fn = self._graph_fn(True)
            wrt_idx = [i for i, n in enumerate(self._symbol.list_arguments())
                       if self._grad_req.get(n, "null") != "null"]

            def run(args, aux, key, out_grads):
                args = list(args)

                def f(wrt_vals):
                    for i, v in zip(wrt_idx, wrt_vals):
                        args[i] = v
                    outs, new_aux = fn(tuple(args), aux, key)
                    return outs, new_aux

                outs, vjp, new_aux = jax.vjp(f, tuple(args[i] for i in wrt_idx),
                                             has_aux=True)
                cts = tuple(
                    og if og is not None else jnp.ones_like(o)
                    for o, og in zip(outs, out_grads))
                (grads,) = vjp(cts)
                return outs, grads, new_aux

            self._bwd_jit = jax.jit(run)
            self._bwd_wrt_idx = wrt_idx
        return self._bwd_jit

    def _store_grad(self, tgt, g, req):
        """Write a gradient back honoring the grad array's OWN device
        (group2ctx grads live with their parameters)."""
        g = g.astype(tgt.dtype)
        if tgt.context.jax_device != self._ctx.jax_device:
            g = jax.device_put(g, tgt.context.jax_device)
        tgt._data = (tgt._data + g) if req == "add" else g

    def _gather_args(self, arrays):
        """Array values for the jitted program, streaming any that reside
        on another device (group2ctx parameter placement) onto the compute
        ctx — one program, per-step transfers at the group boundary."""
        dev = self._ctx.jax_device
        out = []
        for a in arrays:
            v = a._data
            if hasattr(v, "devices") and v.devices() != {dev}:
                v = jax.device_put(v, dev)
            out.append(v)
        return tuple(out)

    # -- API -----------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Run forward (reference `executor.py:114 forward` → `MXExecutorForward`)."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"Unknown argument {k}")
            tgt = self.arg_dict[k]
            src = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            if src.dtype != tgt.dtype:
                src = src.astype(tgt.dtype)
            tgt._data = jax.device_put(src, self._ctx.jax_device)
        from . import random as _random
        key = _random.next_key() if self._n_rng else jax.random.PRNGKey(0)
        self._last_key = key
        self._last_is_train = is_train
        args = self._gather_args(self.arg_arrays)
        aux = self._gather_args(self.aux_arrays)
        self._exec_count = getattr(self, "_exec_count", 0) + 1
        trains = bool(is_train) and any(
            r != "null" for r in self._grad_req.values())
        if trains:
            # stash the vjp residuals: backward() replays ONLY the
            # linearized backward pass (no second forward)
            fwd = self._forward_res_jit()
            outs, new_aux, self._stashed_vjp = fwd(args, aux, key)
        else:
            self._stashed_vjp = None
            fwd = self._forward_jit(bool(is_train))
            outs, new_aux = fwd(args, aux, key)
        if is_train:
            for a, v in zip(self.aux_arrays, new_aux):
                a._data = v
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, arr in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, arr)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        """Run backward (reference `graph_executor.cc:76 Backward`).  When
        `forward(is_train=True)` stashed vjp residuals, ONLY the
        linearized backward program runs (the reference reuses forward
        activations identically); without a prior training forward it
        falls back to the combined forward+vjp program with the stashed
        rng key."""
        n_out = len(self._symbol._entries)
        if out_grads is None:
            ogs = tuple([None] * n_out)
        elif isinstance(out_grads, NDArray):
            ogs = (out_grads._data,) + tuple([None] * (n_out - 1))
        else:
            ogs = tuple(g._data if isinstance(g, NDArray) else g
                        for g in out_grads)
        stashed = getattr(self, "_stashed_vjp", None)
        if stashed is not None:
            # cotangent defaults come from the LIVE outputs (no eval_shape
            # re-trace needed)
            ogs = tuple(
                jnp.ones(o._data.shape, o._data.dtype) if g is None else g
                for g, o in zip(ogs, self.outputs))
            self._exec_count = getattr(self, "_exec_count", 0) + 1
            grads = self._vjp_apply_jit(stashed, ogs)
            # residuals pin the forward activations in device memory —
            # release them now that they are consumed (a repeated bare
            # backward() falls back to the combined program)
            self._stashed_vjp = None
        else:
            run = self._backward_jit()
            args = self._gather_args(self.arg_arrays)
            aux = self._gather_args(self.aux_arrays)
            key = self._last_key if self._last_key is not None \
                else jax.random.PRNGKey(0)
            if any(g is None for g in ogs):
                # cheap eval_shape once per signature for output shapes
                fwd = self._forward_jit(True)
                outs, _ = jax.eval_shape(fwd, args, aux, key)
                ogs = tuple(jnp.ones(o.shape, o.dtype) if g is None else g
                            for g, o in zip(ogs, outs))
            self._exec_count = getattr(self, "_exec_count", 0) + 1
            outs, grads, new_aux = run(args, aux, key, ogs)
        arg_names = self._symbol.list_arguments()
        for i, g in zip(self._bwd_wrt_idx, grads):
            tgt = self.grad_arrays[i]
            if tgt is None:
                continue
            self._store_grad(tgt, g, self._grad_req.get(arg_names[i]))
        return [NDArray(g, ctx=self._ctx) for g in grads]

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused train step (one XLA program; used by Module for performance)."""
        for k, v in kwargs.items():
            tgt = self.arg_dict[k]
            src = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            if src.dtype != tgt.dtype:
                src = src.astype(tgt.dtype)
            tgt._data = jax.device_put(src, self._ctx.jax_device)
        from . import random as _random
        key = _random.next_key() if self._n_rng else jax.random.PRNGKey(0)
        self._last_key = key
        # a residual stash from an earlier forward(is_train=True) is now
        # stale; a later bare backward() must fall back to the combined
        # program, not linearize at the OLD inputs
        self._stashed_vjp = None
        run = self._backward_jit()
        args = self._gather_args(self.arg_arrays)
        aux = self._gather_args(self.aux_arrays)
        n_out = len(self._symbol._entries)
        fwd = self._forward_jit(True)
        outs_s, _ = jax.eval_shape(fwd, args, aux, key)
        ogs = tuple(jnp.ones(o.shape, o.dtype) for o in outs_s)
        if out_grads is not None:
            ogs = tuple(g._data if g is not None else d
                        for g, d in zip(out_grads, ogs))
        outs, grads, new_aux = run(args, aux, key, ogs)
        for a, v in zip(self.aux_arrays, new_aux):
            a._data = v
        arg_names = self._symbol.list_arguments()
        for i, g in zip(self._bwd_wrt_idx, grads):
            tgt = self.grad_arrays[i]
            if tgt is None:
                continue
            self._store_grad(tgt, g, self._grad_req.get(arg_names[i]))
        self.outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        return self.outputs

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """Reference `executor.py copy_params_from`.  Each array keeps ITS
        OWN context: group2ctx-placed parameters stay on their group's
        device (that residency is the point of the feature).  All values
        move in ONE batched transfer — per-param round trips dominate on a
        remote chip."""
        plan = []   # (target NDArray, host/src value)

        def gather(params, table, what):
            for k, v in params.items():
                if k in table:
                    tgt = table[k]
                    src = v._data if isinstance(v, NDArray) else v
                    if hasattr(src, "astype") and src.dtype != tgt.dtype:
                        src = src.astype(tgt.dtype)
                    plan.append((tgt, src))
                elif not allow_extra_params:
                    raise MXNetError(f"Found name {k} not in {what}")

        gather(arg_params, self.arg_dict, "arguments")
        if aux_params:
            gather(aux_params, self.aux_dict, "aux states")
        if plan:
            moved = jax.device_put(
                [_np.asarray(s) if isinstance(s, (list, tuple)) else s
                 for _, s in plan],
                [t.context.jax_device for t, _ in plan])
            for (tgt, _), v in zip(plan, moved):
                tgt._data = v

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (reference `executor.py reshape`); jit
        re-specializes per signature so this only reallocates buffers."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        arg_names = self._symbol.list_arguments()
        aux_names = self._symbol.list_auxiliary_states()
        new_args = []
        new_grads = []
        for name, sh, old, g in zip(arg_names, arg_shapes, self.arg_arrays,
                                    self.grad_arrays):
            if sh != old.shape:
                new_args.append(NDArray(jnp.zeros(sh, old.dtype), ctx=self._ctx))
                new_grads.append(None if g is None else
                                 NDArray(jnp.zeros(sh, old.dtype), ctx=self._ctx))
            else:
                new_args.append(old)
                new_grads.append(g)
        new_aux = []
        for sh, old in zip(aux_shapes, self.aux_arrays):
            new_aux.append(old if sh == old.shape else
                           NDArray(jnp.zeros(sh, old.dtype), ctx=self._ctx))
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Reference `MXExecutorSetMonitorCallback` (per-output monitoring)."""
        self._monitor_callback = callback

    def debug_str(self):
        lines = [f"Symbol outputs: {self._symbol.list_outputs()}"]
        for n in self._symbol._topo():
            kind = "var" if n.is_variable else n.op.name
            lines.append(f"  {kind} {n.name}")
        return "\n".join(lines)

    # -- construction --------------------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     group2ctx=None):
        from .symbol.symbol import check_unique_names
        check_unique_names(symbol)  # shadowed names would train wrong arrays
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shape_kwargs)
        if arg_shapes is None:
            raise MXNetError("simple_bind: shape inference failed")
        type_dict = type_dict or {}

        # reference group2ctx (`graph_executor.cc` ctx assignment from
        # __ctx_group__ attrs): parameter arrays RESIDE on their group's
        # device — the memory-placement half of legacy model parallelism.
        # Compute still runs as one XLA program on the bound ctx (inputs
        # stream in per step); per-group COMPUTE placement is the job of
        # the sharding layer (`parallel.group2ctx_shardings` bridges this
        # API to mesh shardings for true SPMD model parallel).
        var_group = {}
        if group2ctx:
            for node in symbol._topo():
                if node.is_variable:
                    g = node._extra_attrs.get("__ctx_group__")
                    if g is not None and g in group2ctx:
                        var_group[node.name] = group2ctx[g]

        # allocate every array in ONE batched transfer: per-array
        # device_put costs a host<->device round trip each — ~300 arrays
        # over a remote-chip link dominates bind time otherwise
        plan = []   # (host_buffer, device) in creation order

        # a parameter DECLARED float16/bfloat16 (`Variable(dtype=...)`, a
        # gluon parameter's dtype) is bound in that type where the caller's
        # type_dict says nothing of it: a language model's token ids cannot
        # be bound in a low-precision type, so its data input cannot carry
        # the low-precision lane the way an image batch does
        declared = {}
        for node in symbol._topo():
            low = str(node._extra_attrs.get("__dtype__", "")) \
                if node.is_variable else ""
            if low in ("float16", "bfloat16"):
                declared[node.name] = low

        def make(shape, name):
            dt = np_dtype(type_dict.get(name) or declared.get(name)
                          or _np.float32)
            dev_ctx = var_group.get(name, ctx)
            plan.append((_np.zeros(shape, dt), dev_ctx.jax_device))
            return dev_ctx

        arg_ctxs = [make(s, n) for n, s in zip(arg_names, arg_shapes)]
        if isinstance(grad_req, str):
            reqs = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            reqs = dict(zip(arg_names, grad_req))
        else:
            reqs = {n: grad_req.get(n, "null") for n in arg_names}
        grad_ctxs = [make(s, n) if reqs.get(n, "null") != "null" else None
                     for n, s in zip(arg_names, arg_shapes)]
        aux_ctxs = [make(s, n) for n, s in zip(aux_names, aux_shapes)]

        bufs = jax.device_put([b for b, _ in plan], [d for _, d in plan])
        it = iter(bufs)
        args = [NDArray(next(it), ctx=c) for c in arg_ctxs]
        grads = [NDArray(next(it), ctx=c) if c is not None else None
                 for c in grad_ctxs]
        auxs = [NDArray(next(it), ctx=c) for c in aux_ctxs]
        return Executor(symbol, ctx, args, grads, reqs, auxs)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states):
        from .symbol.symbol import check_unique_names
        check_unique_names(symbol)  # shadowed names would train wrong arrays
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        ctx = ctx or current_context()

        def to_list(d, names, what):
            if d is None:
                return [None] * len(names)
            if isinstance(d, dict):
                return [d.get(n) for n in names]
            if len(d) != len(names):
                raise MXNetError(f"Length of {what} does not match number of "
                                 f"{what} names")
            return list(d)

        arg_arrays = to_list(args, arg_names, "arguments")
        missing = [n for n, a in zip(arg_names, arg_arrays) if a is None]
        if missing:
            raise MXNetError(f"bind: missing arguments {missing}")
        grad_arrays = to_list(args_grad, arg_names, "gradients")
        aux_arrays = to_list(aux_states, aux_names, "aux states")
        aux_arrays = [a if a is not None else
                      NDArray(jnp.zeros((1,), _np.float32), ctx=ctx)
                      for a in aux_arrays]
        if args_grad is None:
            grad_req = "null"
            grad_arrays = [None] * len(arg_names)
        return Executor(symbol, ctx, arg_arrays, grad_arrays, grad_req,
                        aux_arrays)
