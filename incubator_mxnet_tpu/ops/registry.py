"""Central operator registry.

TPU-native equivalent of the reference's NNVM op registry
(`NNVM_REGISTER_OP` + string-keyed attribute maps, `include/mxnet/op_attr_types.h:66-271`,
example registration `src/operator/nn/fully_connected.cc:239-328`).

Design: one registry entry per operator.  Instead of separate
`FCompute<cpu>` / `FCompute<gpu>` kernels plus hand-written `FInferShape` /
`FInferType` / `FGradient` tables, each op provides a single **pure,
jax-traceable compute function** ``fn(params, *arrays) -> array | tuple``:

* eager dispatch jit-compiles it per (op, static-params) — XLA generates the
  TPU kernel (the `FCompute<tpu>` equivalent);
* shape/type inference is `jax.eval_shape` of the same function (replaces the
  InferAttr fixpoint, `src/executor/infer_graph_attr_pass.cc:73`);
* gradients come from `jax.vjp` of the same function (replaces `FGradient`);
  ops with non-autodiff gradients (e.g. SoftmaxOutput's implicit CE loss grad,
  `src/operator/softmax_output.cc`) wrap themselves in `jax.custom_vjp`;
* the symbolic executor composes these functions into one XLA computation
  (replaces `GraphExecutor` + bulk segments, `src/executor/graph_executor.cc`).

The Python frontends are *generated* from this registry
(`ndarray/register.py`, `symbol/register.py`) exactly like the reference
generates them from `MXSymbolListAtomicSymbolCreators`
(`python/mxnet/ndarray/register.py:30-169`).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

from ..base import MXNetError, py_literal

__all__ = ["OpDef", "register", "get", "list_ops", "REQUIRED", "eager_call",
           "vjp_call", "eval_shape", "SCAN_KEPT", "scan_kept"]


class _Required:
    def __repr__(self):
        return "REQUIRED"


REQUIRED = _Required()

_REGISTRY: dict[str, "OpDef"] = {}

# The one name under which an operator's custom-VJP forward rule declares
# what a re-materialised scanned layer keeps for it (`OpDef.scan_remat`).
SCAN_KEPT = "scan_kept"


def scan_kept(x):
    """`x`, named as a value that a re-materialised scanned layer body
    keeps rather than computes again (`symbol.graph_eval_fn`: its
    `jax.checkpoint` saves the values of this name and nothing else).  For
    a custom-VJP forward rule to pass its kernel's OUTPUTS through, where
    the written backward pass reads them: the kernel then runs once a
    layer, not twice.  An identity anywhere else (an inlined layer, a
    forward-only program: the name lowers to nothing)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, SCAN_KEPT)


class OpDef:
    """A registered operator.

    Attributes
    ----------
    name : canonical op name (reference ops keep their MXNet names so that
        generated frontends and saved Symbol JSON stay compatible).
    fn : ``fn(params: dict, *arrays) -> jnp.ndarray | tuple`` pure function.
    nin : number of tensor inputs; -1 = variadic (count from ``variadic_param``).
    nout : number of outputs, or callable ``(params) -> int``.
    naux : trailing inputs that are auxiliary states (e.g. BatchNorm running
        stats); in train mode ``fn`` returns ``nout`` outputs followed by
        ``naux`` updated aux values which the caller writes back in place.
    params : dict name -> default (REQUIRED for mandatory params).
    param_types : optional dict name -> converter applied after coercion.
    needs_rng : op consumes a PRNG key; dispatch appends a key array input.
    mode_dependent : op behaves differently in train vs predict mode; dispatch
        injects boolean param ``_train``.
    stop_grad : do not record on the autograd tape (BlockGrad & friends).
    aliases : alternative registered names (reference keeps e.g. both
        ``Flatten`` and ``flatten``).
    """

    __slots__ = ("name", "fn", "nin", "nout", "naux", "params", "param_types",
                 "needs_rng", "mode_dependent", "stop_grad", "aliases",
                 "variadic_param", "dynamic_params", "input_names", "doc",
                 "cache_key", "cost_meta", "scan_remat", "counters")

    def __init__(self, name, fn, nin=1, nout=1, naux=0, params=None,
                 param_types=None, needs_rng=False, mode_dependent=False,
                 stop_grad=False, aliases=(), variadic_param=None,
                 dynamic_params=(), input_names=None, doc=None,
                 cache_key=None, cost_meta=None, scan_remat=False,
                 counters=None):
        self.name = name
        self.fn = fn
        self.nin = nin
        self.nout = nout
        self.naux = naux
        self.params = dict(params or {})
        self.param_types = dict(param_types or {})
        self.needs_rng = needs_rng
        self.mode_dependent = mode_dependent
        self.stop_grad = stop_grad
        self.aliases = tuple(aliases)
        self.variadic_param = variadic_param
        # dynamic_params: params passed as traced scalar inputs (appended after
        # tensor inputs, before the rng key) so e.g. a changing learning rate
        # does not retrigger XLA compilation.
        self.dynamic_params = tuple(dynamic_params)
        # input_names: static list or callable(params)->list of input slot
        # names; the symbolic frontend auto-creates Variables for trailing
        # missing inputs (reference ListArguments + auto-var creation in
        # Symbol composition, e.g. fc1_weight/fc1_bias)
        self.input_names = input_names
        self.doc = doc or (fn.__doc__ if fn else None)
        # cache_key: a process-stable graph identity (e.g. a symbol-JSON
        # hash for CachedOp graphs) routing this op's eager dispatch
        # through the unified program cache's disk tier; None (all
        # primitive ops) keeps the plain per-(op, params) jit — tiny
        # programs that are not worth a disk round trip.
        self.cache_key = cache_key
        # cost_meta: static metadata for the mxcost analyzer
        # (analysis/cost.py).  Keys: "flops" — fn(params, in_avals,
        # out_avals) -> float overriding the analyzer's per-op-name
        # rule; "compute_dtype" — the dtype the op's arithmetic ACTUALLY
        # runs in, when it differs from what the graph dtypes suggest
        # (the quantized ops declare "float32" here: that declaration IS
        # the int8-slower-than-fp32 defect's static signature);
        # "quantized" — marks an int8-family op for the dtype-flow pass.
        self.cost_meta = dict(cost_meta) if cost_meta else None
        # scan_remat: the op keeps more for its backward pass than a
        # layer's stacked activations have room for (a chunked recurrence,
        # whose written backward pass keeps a float32 state per chunk;
        # routed experts, whose written backward pass keeps the router's
        # float32 probabilities, the plan and the rows' pre-activations),
        # so a scanned layer holding it recomputes its activations in the
        # backward pass instead of stacking them (`symbol.graph_eval_fn`),
        # all but the values that an operator of the layer names
        # `scan_kept`: those are stacked, and what made them (a Pallas
        # forward kernel) does not run again.  An operator names a value
        # where the benchmark's cell of its kind has paid for the bytes on
        # the chip (PERF.md section 6, PR 37)
        self.scan_remat = bool(scan_remat)
        # counters: the op's auxiliary states are counters it adds to in
        # every training step.  `counters(deltas)`, `deltas` one {aux slot
        # name: what was added since the last look (numpy), "params": the
        # node's own} per node of this op in a graph, gives {"span": name,
        # "args": {...}, "counters": {name: increment}, "gauges": {name:
        # value}}, which
        # `BaseModule.fit` leaves in `mx.obs` where it synchronises the
        # parameters anyway, at the epoch's end
        self.counters = counters

    # -- parameter handling ---------------------------------------------------
    def canonicalize_params(self, kwargs):
        """Coerce/validate kwargs against the param table; returns plain dict."""
        out = {}
        for k, default in self.params.items():
            if k in kwargs and kwargs[k] is not None:
                v = py_literal(kwargs[k])
                conv = self.param_types.get(k)
                if conv is not None:
                    v = conv(v)
                out[k] = _hashable(v)
            elif default is REQUIRED:
                raise MXNetError(
                    f"Operator {self.name}: required parameter '{k}' missing")
            else:
                out[k] = _hashable(default)
        unknown = set(kwargs) - set(self.params) - {"name", "out", "ctx", "attr", "__layout__", "lr_mult", "wd_mult"}
        if unknown:
            raise MXNetError(f"Operator {self.name}: unknown parameters {sorted(unknown)}")
        return out

    def num_outputs(self, params):
        return self.nout(params) if callable(self.nout) else self.nout

    def num_aux(self, params):
        return self.naux(params) if callable(self.naux) else self.naux

    def list_input_names(self, params):
        if self.input_names is None:
            return None
        if callable(self.input_names):
            return list(self.input_names(params))
        return list(self.input_names)

    def num_inputs(self, params):
        if self.nin >= 0:
            return self.nin
        if self.variadic_param and self.variadic_param in params:
            return int(params[self.variadic_param])
        return -1

    def __repr__(self):
        return f"OpDef({self.name})"


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def register(name, **kwargs):
    """Decorator registering a compute function as operator ``name``.

    Mirrors `NNVM_REGISTER_OP(name).set_attr<FCompute>(...)` — but there is a
    single backend (XLA) so one function covers cpu+tpu.
    """
    def deco(fn):
        op = OpDef(name, fn, **kwargs)
        if name in _REGISTRY:
            raise MXNetError(f"Operator {name} registered twice")
        _REGISTRY[name] = op
        for alias in op.aliases:
            if alias in _REGISTRY:
                raise MXNetError(f"Operator alias {alias} registered twice")
            _REGISTRY[alias] = op
        return fn
    return deco


def register_opdef(op):
    """Register a dynamically-created OpDef (CachedOp graphs)."""
    _REGISTRY[op.name] = op
    return op


def get(name) -> OpDef:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError(f"Operator {name} is not registered") from None


def maybe_get(name) -> Optional[OpDef]:
    return _REGISTRY.get(name)


def list_ops():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Eager dispatch: jit-per-(op, params) cache.  The analogue of the reference's
# imperative PushFCompute (`src/imperative/imperative_utils.h:361-410`): one
# cached XLA executable per (op, static attrs, input signature) — jax.jit
# handles the per-signature level.
# ---------------------------------------------------------------------------

def _freeze(v):
    """Hashable stand-in for a param value: the jit caches key on frozen
    params, and basic-index keys carry `slice` objects, which are
    unhashable before Python 3.12."""
    if isinstance(v, slice):
        return ("__slice__", v.start, v.stop, v.step)
    if isinstance(v, tuple):
        return tuple(_freeze(x) for x in v)
    return v


def _thaw(v):
    if isinstance(v, tuple):
        if len(v) == 4 and v[0] == "__slice__":
            return slice(v[1], v[2], v[3])
        return tuple(_thaw(x) for x in v)
    return v


def _freeze_params(params):
    return tuple(sorted((k, _freeze(v)) for k, v in params.items()))


@functools.lru_cache(maxsize=None)
def _jitted(op_name, frozen_params):
    import jax
    op = _REGISTRY[op_name]
    params = {k: _thaw(v) for k, v in frozen_params}

    def run(*arrays):
        return op.fn(params, *arrays)

    if op.cache_key is not None:
        # whole-graph ops (Gluon CachedOp) compile through the unified
        # program cache: a fresh process loads the serialized executable
        # from the disk tier instead of re-paying the XLA compile
        from ..compile import cached_jit
        return cached_jit(run,
                          graph_key=("cachedop", op.cache_key,
                                     frozen_params),
                          label="cachedop/" + op_name)
    return jax.jit(run)


def eager_call(op: OpDef, params: dict, arrays):
    """Execute an op eagerly; returns tuple of jax arrays (outputs then aux).

    Inside an outer jax trace (fused train step / CachedOp), the compute
    function is called directly: nesting a jit per op would bloat the outer
    program with hundreds of call-ops and multiply compile time.
    """
    import jax
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        out = op.fn(dict(params), *arrays)
    else:
        out = _jitted(op.name, _freeze_params(params))(*arrays)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _jitted_vjp(op_name, frozen_params):
    import jax
    op = _REGISTRY[op_name]
    params = {k: _thaw(v) for k, v in frozen_params}

    def run(arrays, cotangents):
        import jax.numpy as jnp

        def fwd(*xs):
            out = op.fn(params, *xs)
            return out if isinstance(out, tuple) else (out,)
        primals, vjp = jax.vjp(fwd, *arrays)
        # ops may emit trailing aux-state outputs (e.g. BatchNorm running
        # stats in train mode) that carry no gradient: pad with zeros
        cts = tuple(cotangents) + tuple(
            jnp.zeros_like(p) for p in primals[len(cotangents):])
        return vjp(cts)

    return jax.jit(run)


def vjp_call(op: OpDef, params: dict, arrays, cotangents):
    """Input gradients of an op at ``arrays`` given output ``cotangents``.

    The `FGradient` equivalent (`include/mxnet/op_attr_types.h` FGradient):
    computed from the same compute function via jax.vjp, compiled and cached.
    """
    return _jitted_vjp(op.name, _freeze_params(params))(tuple(arrays),
                                                        tuple(cotangents))


def eval_shape(op: OpDef, params: dict, avals):
    """Shape/dtype inference (replaces InferShape/InferType fixpoint,
    `src/executor/infer_graph_attr_pass.cc:35-262`) via jax.eval_shape."""
    import jax

    def run(*xs):
        out = op.fn(params, *xs)
        return out if isinstance(out, tuple) else (out,)

    return jax.eval_shape(run, *avals)
