"""Fused public training paths — the whole train step as ONE donated XLA
program.

The reference keeps per-step dispatch cheap with bulk-exec segments
(`src/executor/graph_executor.cc:1194-1316`) and fused optimizer kernels
(`src/operator/optimizer_op.cc`), so `Module.fit`'s forward → backward →
kvstore push/pull → per-parameter-update loop costs little on GPU.  On TPU
every dispatch is a host→device round trip; the TPU-native answer is to
compile the ENTIRE train step — forward, backward, gradient reduction
(data parallel), optimizer for all parameters, BatchNorm aux updates,
metric accumulation, RNG key advance — into one donated XLA program per
input signature, reachable from the public `Module.fit` /
`gluon.Trainer.step` APIs.

Two layers:

* `FusedOptimizer` — applies `Optimizer.update_multi_precision` for every
  parameter in one jitted donated program.  The *public* optimizer objects
  are traced directly (their nd-op math is jax underneath), so every
  registered optimizer keeps its exact semantics — including lr/wd
  multipliers, schedulers, and multi-precision fp32 master weights.
  Hyperparameters that change per step (lr, wd, update count t,
  rescale_grad) are injected as traced scalars so schedules never
  retrigger compilation.  Optimizers whose update cannot trace (e.g. ones
  drawing host RNG) fall back to the per-parameter eager path
  automatically.

* `FusedTrainStep` — used by `Module` (`module/module.py`): whole-graph
  forward+vjp (the Symbol is already one XLA computation) composed with
  the `FusedOptimizer` trace plus aux/metric/key carries.  For multiple
  devices the inputs are sharded over a 1-D `jax.sharding.Mesh` data axis
  with parameters replicated: XLA inserts the gradient all-reduce (the
  `kvstore='device'/'tpu'` reduce becomes a collective inside the
  program) and BatchNorm statistics become global-batch statistics
  (sync-BN semantics, the stronger form of the reference's per-device
  stats).
"""
from __future__ import annotations

import contextlib
import logging

import numpy as _np

from .base import MXNetError
from .ndarray.ndarray import NDArray
from .obs import trace as _obs_trace

__all__ = ["FusedOptimizer", "FusedTrainStep", "FusedInference"]

_log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# pytree helpers over optimizer states (None | NDArray | nested tuples)
# ---------------------------------------------------------------------------

def _state_data(s):
    """NDArray-state pytree -> raw jax-array pytree."""
    if s is None:
        return None
    if isinstance(s, NDArray):
        return s._data
    if isinstance(s, (tuple, list)):
        return tuple(_state_data(x) for x in s)
    return s


def _state_wrap(values, ctx):
    """Raw-array pytree -> fresh NDArray shells (used inside the trace so
    the public optimizer's in-place writes land on throwaway wrappers)."""
    import jax
    if values is None:
        return None
    if isinstance(values, (tuple, list)):
        return tuple(_state_wrap(v, ctx) for v in values)
    if isinstance(values, jax.Array) or hasattr(values, "dtype"):
        return NDArray(values, ctx=ctx)
    return values


def _state_write_back(dst, new_values):
    """Write updated raw arrays into the persistent NDArray state pytree."""
    if dst is None:
        return
    if isinstance(dst, NDArray):
        dst._set_data(new_values)
        return
    if isinstance(dst, (tuple, list)):
        for d, v in zip(dst, new_values):
            _state_write_back(d, v)


class _TMap(dict):
    """Stand-in for `Optimizer._index_update_count` during tracing: returns
    the traced per-parameter step count (as an NDArray scalar so optimizer
    float math like ``beta ** t`` stays inside the graph)."""

    def __init__(self, t_vec, pos, ctx):
        super().__init__()
        self._t_vec = t_vec
        self._pos = pos
        self._ctx = ctx

    def __getitem__(self, index):
        return NDArray(self._t_vec[self._pos[index]], ctx=self._ctx)


def _constrain_like(value, sharding):
    """Pin a traced output (pytree) to the input arrays' NamedShardings so
    a donated update hands back buffers with the SAME layout (GSPMD would
    otherwise pick its own, silently re-laying-out TP/ZeRO-sharded
    tensors)."""
    import jax
    from jax.sharding import NamedSharding
    if value is None:
        return None
    if isinstance(value, (tuple, list)):
        return tuple(_constrain_like(v, s)
                     for v, s in zip(value, sharding))
    if isinstance(sharding, NamedSharding):
        return jax.lax.with_sharding_constraint(value, sharding)
    return value


def _sharding_tree(x):
    """Mirror an NDArray-state pytree with each leaf's current sharding."""
    if x is None:
        return None
    if isinstance(x, (tuple, list)):
        return tuple(_sharding_tree(v) for v in x)
    data = getattr(x, "_data", x)
    return getattr(data, "sharding", None)


def _apply_traced(opt, indices, ws, gs, ss, ctx, lr_vec, wd_vec, t_vec,
                  rescale):
    """Trace the PUBLIC optimizer over all parameters at once.

    Runs inside a jax trace: `opt`'s lr/wd/t/rescale lookups are patched to
    return traced scalars, then `update_multi_precision` is called per
    parameter on NDArray shells wrapping the traced arrays.  The patches
    are removed before returning (they only matter at trace time;
    compiled executions never re-enter this Python).
    """
    pos = {i: k for k, i in enumerate(indices)}
    saved = dict(vars(opt))
    try:
        opt._get_lr = lambda i: NDArray(lr_vec[pos[i]], ctx=ctx)
        opt._get_wd = lambda i: NDArray(wd_vec[pos[i]], ctx=ctx)
        opt._update_count = lambda i: None  # host-side, done by the caller
        opt._index_update_count = _TMap(t_vec, pos, ctx)
        opt.rescale_grad = NDArray(rescale, ctx=ctx)
        new_ws, new_ss = [], []
        for k, i in enumerate(indices):
            w = NDArray(ws[k], ctx=ctx)
            g = NDArray(gs[k], ctx=ctx)
            s = _state_wrap(ss[k], ctx)
            opt.update_multi_precision(i, w, g, s)
            new_ws.append(w._data)
            new_ss.append(_state_data(s))
        return new_ws, tuple(new_ss)
    finally:
        for k in list(vars(opt)):
            if k not in saved:
                delattr(opt, k)
        opt.__dict__.update(saved)


def reown_for_donation(tree):
    """Re-materialize every array leaf of `tree` through one jitted XLA
    copy, so the returned buffers are exclusively owned by this
    process's XLA computations.

    Why: a donated dispatch through an AOT executable (the unified
    program cache's `jit.lower().compile()` path, or an executable
    deserialized from the disk tier) silently corrupts buffers that
    came from `jax.device_put` of HOST memory — checkpoint restores,
    external `set_params`, epoch-boundary param syncs all stage arrays
    that way.  The plain `jax.jit` dispatch path defensively copies
    such inputs; the AOT call path does not, and XLA's in-place reuse
    of the donated buffer then races whatever still aliases the staged
    host copy (observed: nondeterministically wrong resumed-training
    params at ~30-50%, and glibc heap corruption for the in-process
    deserialize variant).  Fused steps call this on every COLD dispatch
    — the only time externally-staged buffers can enter the donated
    carry; the steady-state fast path (our own previous outputs) never
    pays it.  The copy is one fused program per signature (jax.jit's
    own cache), not a per-leaf dispatch."""
    import jax
    import jax.numpy as jnp

    def copy_leaf(x):
        if not hasattr(x, "dtype"):
            return x
        if x.dtype == jnp.bool_:
            return jnp.logical_or(x, False)
        # multiply by one: bitwise identity for every float/int/uint
        # dtype, and inside a non-donating jit the output is a FRESH
        # buffer (a bare identity could be forwarded/aliased by XLA)
        return x * jnp.ones((), x.dtype)

    global _REOWN_JIT
    if _REOWN_JIT is None:
        _REOWN_JIT = jax.jit(
            lambda t: jax.tree_util.tree_map(copy_leaf, t))
    return _REOWN_JIT(tree)


_REOWN_JIT = None
# a carry larger than this is re-owned leaf by leaf, in place
# (`FusedTrainStep._reown_in_place`); up to it, in one program, because a
# program a leaf costs a carry of many small leaves more than the second
# copy does (ResNet-50's several hundred leaves on the v5e: 4 s of set-up
# and 0.1 s of every cold dispatch; PERF.md section 6, PR 30)
REOWN_IN_PLACE_BYTES = 1 << 30


def _tree_nbytes(tree):
    import jax
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


# NOTE on donation safety (formerly a _AotCall pre-validation wrapper):
# donation consumes the caller's persistent buffers only when the compiled
# executable actually RUNS — a failed trace or compile raises before
# execution with every buffer intact, and callers triage post-dispatch
# failures with _raise_if_unrecoverable (is_deleted on the inputs).  A
# `jit.lower(*args)` pre-validation pass would re-trace the whole
# multi-thousand-op graph and double first-step latency for no safety.


@contextlib.contextmanager
def _quiet_donation():
    """Warning scope for an auto-donating dispatch: jax warns when a
    donated buffer cannot alias any program output, and for donated
    batch INPUTS that is the common case (the step's outputs are small)
    — the donation still lets the runtime release the staged buffer at
    dispatch instead of holding it across the step.  Expected, not
    actionable; silence exactly that message."""
    import warnings
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _maybe_scan_plan(symbol):
    """The symbol's scan-over-layers plan when MXNET_FUSED_SCAN is on and
    the graph has at least one eligible run, else None.  Never raises —
    a failed detection pass just means the inlined lowering."""
    from . import config as _config
    if not bool(_config.get("MXNET_FUSED_SCAN")):
        return None
    try:
        from .analysis.graph_passes import scan_plan
        plan = scan_plan(symbol)
        return plan if plan.get("runs") else None
    except Exception as e:
        _log.debug("scan-over-layers detection failed (%s); using the "
                   "inlined lowering", str(e)[:200])
        return None


def _donated_invalidated(*trees):
    """True when any jax-array leaf in the given pytrees was deleted by a
    donating dispatch (promoted into `analysis.donation.any_deleted`; kept
    as the historical name for callers of the probe)."""
    from .analysis import donation as _donation
    return _donation.any_deleted(*trees)


def _opt_param_names(opt, indices):
    """Best-effort human names for optimizer parameter indices (Module
    installs `idx2name`; the gluon Trainer installs `param_dict`) — the
    names the donation tracker and unrecoverable-failure errors report."""
    i2n = getattr(opt, "idx2name", None) or {}
    pd = getattr(opt, "param_dict", None) or {}
    out = []
    for i in indices:
        if i in i2n:
            out.append(str(i2n[i]))
        elif i in pd and getattr(pd[i], "name", None):
            out.append(str(pd[i].name))
        else:
            out.append(f"param[{i}]")
    return out


def _param_dict_mults(opt, indices):
    """Per-parameter lr/wd multipliers from the optimizer's param_dict
    (consulted FIRST by _get_lr/_get_wd — gluon Trainer populates it), as
    a hashable tuple for the hyper-vector cache key: freezing a layer
    mid-training via `param.lr_mult = 0` must invalidate the cache."""
    pd = getattr(opt, "param_dict", None) or {}
    if not pd:
        return ()
    return tuple(
        (getattr(pd[i], "lr_mult", None), getattr(pd[i], "wd_mult", None))
        if i in pd else None for i in indices)


def _raise_if_unrecoverable(kind, exc, named_trees):
    """Shared post-dispatch failure triage for every fused path: when the
    donating dispatch already consumed the persistent buffers, falling
    back would replay onto deleted arrays — raise an `MXNetError` NAMING
    the consumed parameters instead (analysis.donation).  `named_trees`
    is an iterable of (owner_name, pytree).  Returns when a fallback is
    safe (buffers intact)."""
    from .analysis import donation as _donation
    _donation.raise_if_consumed(kind, exc, named_trees)


class HostRNGInTrace(RuntimeError):
    """The traced Python drew a host RNG key (see `_no_rng`)."""


def _untraceable():
    """The exception types that mean "this Python cannot run under a JAX
    trace": a host RNG draw, or a tracer forced to a concrete value
    (`asnumpy()`, `float()`, `if x:`, a boolean-mask index).  Code that
    raises one of these was never eligible for a fused program, and the
    caller SELECTS its eager path.  Anything else raised while a fused
    program is traced, lowered, compiled or run — a mesh or sharding
    mismatch, a Mosaic refusal, RESOURCE_EXHAUSTED — is the selected
    program failing and is raised, never replaced by another path."""
    import jax
    return (HostRNGInTrace,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError,
            jax.errors.NonConcreteBooleanIndexError)


def _no_rng():
    """Context forbidding host RNG draws during a fused trace: a key drawn
    at trace time would bake the SAME randomness into every compiled step."""
    import contextlib
    from . import random as _random

    @contextlib.contextmanager
    def guard():
        orig = _random.next_key

        def blocked():
            raise HostRNGInTrace(
                "optimizer draws host RNG; not fusable")

        _random.next_key = blocked
        try:
            yield
        finally:
            _random.next_key = orig
    return guard()


# ---------------------------------------------------------------------------
# once-traced cores: the expensive framework trace captured as a closed
# jaxpr, replayed cheaply by every program built over it (shared by the
# Module and Gluon fused steps)
# ---------------------------------------------------------------------------

class _TracedCore:
    """`core(inner, x, *extras) -> (new_inner, step_out)` traced ONCE under
    `make_jaxpr` (this runs the whole framework graph's Python); calling
    the instance replays the jaxpr in jaxpr-eval time, so the 1-step jit
    and each K-step scan body re-trace for pennies instead of re-running
    framework op dispatch."""

    def __init__(self, core, example_args):
        import jax
        flat, in_tree = jax.tree_util.tree_flatten(tuple(example_args))

        def flat_core(*leaves):
            return core(*jax.tree_util.tree_unflatten(in_tree, leaves))

        with _obs_trace.phase("fused.trace", cat="compile") as ph:
            closed, out_shape = jax.make_jaxpr(
                flat_core, return_shape=True)(*flat)
        self.trace_s = ph.s
        self._closed = closed
        self._in_tree = in_tree
        self._out_tree = jax.tree_util.tree_structure(out_shape)
        self.out_shape = out_shape   # (inner, step_out) ShapeDtypeStructs
        self._graph_hash = None

    def num_eqns(self):
        """Total equation count of the traced step, recursing into
        nested jaxprs (scan/cond/pjit bodies) — the graph-size number
        the cold-start work scales with.  A scan-deduped graph counts
        ONE layer body where the inlined lowering counts N."""
        def subs(v):
            vals = v if isinstance(v, (tuple, list)) else (v,)
            out = []
            for x in vals:
                inner = getattr(x, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    out.append(inner)
                elif hasattr(x, "eqns"):
                    out.append(x)
            return out

        def count(jaxpr):
            n = len(jaxpr.eqns)
            for eqn in jaxpr.eqns:
                for v in eqn.params.values():
                    for sub in subs(v):
                        n += count(sub)
            return n

        return count(self._closed.jaxpr)

    @property
    def graph_hash(self):
        """Stable identity of the traced step for the program cache's
        disk tier (the jaxpr print with addresses scrubbed — shapes,
        dtypes, optimizer math and metric set are all in it)."""
        if self._graph_hash is None:
            from .compile import graph_hash_of_jaxpr
            self._graph_hash = graph_hash_of_jaxpr(self._closed)
        return self._graph_hash

    def __call__(self, *args):
        import jax
        from jax.extend.core import jaxpr_as_fun
        leaves, tree = jax.tree_util.tree_flatten(tuple(args))
        if tree != self._in_tree:
            raise TypeError("fused-core signature changed under trace")
        out = jaxpr_as_fun(self._closed)(*leaves)
        return jax.tree_util.tree_unflatten(self._out_tree, out)


def advance_hyper_rows(opt, indices, k, owner, placement):
    """Advance the optimizer's update counts k steps and collect the k
    per-step (lr_vec, wd_vec) device rows plus the rescale scalar.

    The per-parameter vectors are base * static multipliers, so they are
    re-uploaded only when the BASE values move (scheduler step,
    set_learning_rate, rescale change) — cached on `owner._hyper_base` /
    `owner._hyper_dev`.  The base is evaluated once PER STEP (counts
    advance between evaluations), so an lr schedule stepping mid-block
    still lands exact per-step rows.  Shared by the Module and Gluon
    fused steps."""
    import jax
    rows = []
    for _ in range(k):
        for i in indices:
            opt._update_count(i)
        sched = getattr(opt, "lr_scheduler", None)
        base_lr = sched(opt.num_update) if sched is not None else opt.lr
        base = (float(base_lr), float(opt.wd), float(opt.rescale_grad),
                tuple(sorted(getattr(opt, "lr_mult", {}).items())),
                tuple(sorted(getattr(opt, "wd_mult", {}).items())),
                _param_dict_mults(opt, indices))
        if getattr(owner, "_hyper_base", None) != base:
            lrs = [float(opt._get_lr(i)) for i in indices]
            wds = [float(opt._get_wd(i)) for i in indices]
            owner._hyper_dev = jax.device_put(
                [_np.asarray(lrs, _np.float32),
                 _np.asarray(wds, _np.float32),
                 _np.float32(opt.rescale_grad)], placement)
            owner._hyper_base = base
        rows.append((owner._hyper_dev[0], owner._hyper_dev[1]))
    return rows, owner._hyper_dev[2]


def create_states_on_device(opt, indices, weights_raw, ctx):
    """Create optimizer state for every (index, raw device array) pair in
    ONE compiled program — the public optimizer's create_state traced over
    NDArray shells, so fp32 masters are in-program casts and momenta are
    in-program zeros.  Returns a list of NDArray-state pytrees, or None
    when the optimizer's create_state cannot trace (`_untraceable`; the
    caller then selects its eager/host path — a compile or device error
    propagates).  The per-parameter eager path costs a dispatch per op;
    this costs one dispatch total."""
    import jax

    def create(ws_in):
        return tuple(
            _state_data(opt.create_state_multi_precision(
                i, NDArray(w, ctx=ctx)))
            for i, w in zip(indices, ws_in))

    try:
        with _no_rng():
            vals = jax.jit(create)(list(weights_raw))
    except _untraceable() as e:
        _log.info("optimizer create_state does not trace (%s); states are "
                  "created eagerly", str(e)[:200])
        return None
    return [_state_wrap(v, ctx) for v in vals]


def _pod_bucket_psum(grads, axis, cap_bytes, extras=()):
    """Exchange every gradient in O(buckets) psum collectives: pack the
    (trace-time-static) gradient list into size-capped same-dtype
    buckets — the kvstore scheduler's planning rule AND priority order
    (reversed parameter order), applied INSIDE the step program —
    flatten-concat each bucket and exchange it in its OWN `lax.psum`
    bind over the dp axis.  Backward materializes the LAST layer's
    gradients first, so the first-planned bucket's all-reduce depends
    only on ITS layers' VJP chain: the scheduler starts that collective
    while earlier layers' backward is still computing — the
    dependency-engine overlap, expressed as dataflow instead of
    host-side async dispatch.  One extra psum carries the
    small per-shard partial sums (metric deltas, BN aux moments, the
    guardian's health bit).  Returns (summed grads, bucket plan, summed
    extras, psum binds actually dispatched — the extras fold into the
    first f32 bucket when one exists and otherwise cost one extra
    bind).  The psum of per-shard gradients is the reference kvstore's
    cross-device sum."""
    import jax
    import jax.numpy as jnp
    from .kvstore import plan_buckets
    sizes = [int(_np.prod(g.shape)) * g.dtype.itemsize if g.shape
             else g.dtype.itemsize for g in grads]
    # the kvstore scheduler's EXACT plan, including its priority order:
    # reversed parameter order, so the last layers' gradients — the ones
    # backward's VJP chain produces first — form the first buckets
    plan = plan_buckets(reversed(range(len(grads))), sizes,
                        [g.dtype for g in grads], cap_bytes)
    flats = []
    for bucket in plan:
        if len(bucket) == 1:
            flats.append(grads[bucket[0]])
        else:
            flats.append(jnp.concatenate(
                [grads[i].reshape(-1) for i in bucket]))
    # the extras (metric deltas, BN aux moments, the health bit — all
    # small) CONCAT into the first f32 bucket's payload rather than
    # riding as extra psum operands: XLA-CPU rendezvouses multi-operand
    # all-reduces per operand, so one fused operand is one barrier
    ex_flat = [jnp.asarray(e, jnp.float32).reshape(-1) for e in extras]
    ex_sizes = [int(e.shape[0]) for e in ex_flat]
    ex_host = next((k for k, f in enumerate(flats)
                    if f.dtype == jnp.float32), None)
    if ex_flat and ex_host is not None:
        host_shape = flats[ex_host].shape
        flats[ex_host] = jnp.concatenate(
            [flats[ex_host].reshape(-1)] + ex_flat)
    sflats = [jax.lax.psum(f, axis) for f in flats]
    if ex_flat and ex_host is not None:
        host = sflats[ex_host]
        n_own = int(host.shape[0]) - sum(ex_sizes)
        sextras, off = [], n_own
        for n in ex_sizes:
            sextras.append(jax.lax.dynamic_slice_in_dim(host, off, n))
            off += n
        sflats[ex_host] = jax.lax.dynamic_slice_in_dim(
            host, 0, n_own).reshape(host_shape)
        sextras = [s.reshape(e.shape).astype(e.dtype)
                   for s, e in zip(sextras, extras)]
    else:
        sextras = jax.lax.psum(tuple(extras), axis) if extras else ()
    out = list(grads)
    for flat, bucket in zip(sflats, plan):
        if len(bucket) == 1:
            out[bucket[0]] = flat
            continue
        off = 0
        for i in bucket:
            n = int(_np.prod(grads[i].shape)) if grads[i].shape else 1
            out[i] = jax.lax.dynamic_slice_in_dim(flat, off, n).reshape(
                grads[i].shape)
            off += n
    n_psums = len(plan) + (1 if (ex_flat and ex_host is None) else 0)
    return out, plan, sextras, n_psums


def predict_pod_plan(shapes, dtypes=None, cap_bytes=None, extras=True,
                     dp=1):
    """Static mirror of the pod fast path's in-graph bucket plan — the
    plan-introspection hook mxcost uses: given the parameter shapes (and
    dtypes) a fused step would exchange, derive the same plan
    `_pod_bucket_psum` cuts (the shared `kvstore.plan_buckets` rule in
    reversed parameter order) and the resulting collective economy.
    ``extras=True`` models the bundled metric/aux/health payload, which
    folds into the first f32 bucket when one exists and otherwise costs
    one extra psum — exactly the trace-time behavior, so the returned
    ``collectives_per_step``/``bytes_per_step`` match what
    `FusedTrainStep.pod_stats` reports after tracing a step that
    carries extras (metrics/aux/health — the normal fit path; pass
    ``extras=False`` for a bare step)."""
    from .analysis import cost as _cost
    # cap_bytes=None resolves MXNET_KVSTORE_BUCKET_MB inside the
    # enumerator — ONE cap-resolution rule, shared with the kvstore
    return _cost.enumerate_collectives(
        shapes, dtypes=dtypes, dp=dp, cap_bytes=cap_bytes, extras=extras,
        name="pod-plan")


def _one_step_jit(traced, label="", donate_inputs=False):
    """1-step program over a traced core; the inner carry is donated.
    Compiled through the unified program cache (compile/): a process
    that traced an identical core loads the executable from the disk
    tier instead of paying the XLA compile.

    `donate_inputs=True` builds the auto-donation variant: the batch
    inputs ride as their OWN argument (donated) while the hyper rows
    (lr/wd[/gmul]) stay in the non-donated remainder — the caller
    proved via jaxpr liveness (analysis.cost.jaxpr_dying_inputs) that
    every input buffer dies inside the step, and re-owns the staged
    inputs first (reown_for_donation discipline), so XLA reuses the
    batch's HBM for activations instead of holding it live."""
    from .compile import cached_jit

    if donate_inputs:
        def step1d(inner, inputs, xrest, *extras):
            return traced(inner, (inputs,) + tuple(xrest), *extras)

        return cached_jit(step1d, donate_argnums=(0, 1),
                          graph_key=("step1d", traced.graph_hash),
                          label=label or "fused/step1")

    def step1(inner, x, *extras):
        return traced(inner, x, *extras)

    return cached_jit(step1, donate_argnums=(0,),
                      graph_key=("step1", traced.graph_hash),
                      label=label or "fused/step1")


def _scan_block_jit(traced, mcarry_index=None, label="",
                    donate_inputs=False):
    """K-step program: `lax.scan` of the traced core over K stacked
    per-step inputs.  Returns (new_inner, ys, mys, last): `ys` stacks
    every step's outputs (so callers can expose batch j's outputs to a
    batch-j callback), `mys` stacks the metric carry BEFORE each step
    when `mcarry_index` names its slot in the inner carry (entries
    C_{-1}..C_{K-2}; together with the final carry that is every
    per-step metric state — stacked as scan OUTPUTS, i.e. fresh
    buffers, because the inner carry itself is donated and its entry
    tuples are dead after the dispatch), and `last` is step K-1's
    outputs sliced IN-PROGRAM (no extra host dispatch for the common
    "latest outputs" read)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from .compile import cached_jit

    def _run(inner, xs_list, extras):
        xs = jax.tree_util.tree_map(lambda *vs: jnp.stack(vs), *xs_list)

        def body(inn, x):
            new_inn, out = traced(inn, x, *extras)
            y = (out, inn[mcarry_index]) if mcarry_index is not None \
                else (out, None)
            return new_inn, y

        new_inner, (ys, mys) = lax.scan(body, inner, xs)
        last = jax.tree_util.tree_map(lambda y: y[-1], ys)
        return new_inner, ys, mys, last

    if donate_inputs:
        # auto-donation variant: per-step batch inputs as their own
        # donated argument; hyper rows stay non-donated (see
        # _one_step_jit).  xs_inputs[j] pairs back with xs_rest[j].
        def stepkd(inner, xs_inputs, xs_rest, *extras):
            xs_list = tuple((inp,) + tuple(rest)
                            for inp, rest in zip(xs_inputs, xs_rest))
            return _run(inner, xs_list, extras)

        return cached_jit(stepkd, donate_argnums=(0, 1),
                          graph_key=("scan2d", mcarry_index,
                                     traced.graph_hash),
                          label=label or "fused/scan")

    def stepk(inner, xs_list, *extras):
        return _run(inner, xs_list, extras)

    return cached_jit(stepk, donate_argnums=(0,),
                      graph_key=("scan2", mcarry_index,
                                 traced.graph_hash),
                      label=label or "fused/scan")


class _BlockMetricView:
    """Per-logical-step metric exposure for a K-step fused block.

    A K-step scan applies the whole block before any callback fires, so
    a batch-j callback would otherwise observe block-FINAL metric totals
    — and a callback that resets the metric mid-burst (Speedometer
    auto_reset) would silently lose the rest of the block from its next
    window.  The scan stacks the metric carry BEFORE every step (`mys`
    from `_scan_block_jit`: C_{-1}..C_{K-2}, fresh scan outputs — the
    inner carry's own tuples are donated and dead); with the final
    carry C_{K-1} that is every per-step state.  `expose(j)` installs
    batch-j totals before the batch-j callback, reset-aware:

    the visible total must always equal host-materialized state plus the
    installed device tuple.  `A` tracks the cumulative carry already
    absorbed into host state (by a `get()` materialize) or discarded (by
    a `reset()`): an untouched metric gets the cumulative carry C_j - A;
    a touched one re-bases at the previous step (A = C_{j-1}) so only
    step j's delta lands on whatever the callback left behind.  All
    arithmetic is lazy device scalars — no host sync."""

    def __init__(self, metric_objs, prestep_carries, finals):
        self._metrics = list(metric_objs)
        self._pre = prestep_carries       # per metric (sum_K, num_K)
        self._finals = list(finals)       # per metric tuple: C_{K-1}
        self._k = None if prestep_carries is None else \
            len(finals) and int(prestep_carries[0][1].shape[0])
        self._installed = {}              # id(m) -> tuple we set
        self._absorbed = {}               # id(m) -> A (None = zero)

    def arm(self):
        """Record the dispatch-time install (block-final totals) so the
        first `expose` can tell 'untouched' from 'callback consumed'."""
        for m, f in zip(self._metrics, self._finals):
            self._installed[id(m)] = f

    def _after(self, mi, j):
        """Cumulative carry AFTER step j (C_j)."""
        if j + 1 >= self._k:
            return self._finals[mi]
        s_stack, n_stack = self._pre[mi]
        return (s_stack[j + 1], n_stack[j + 1])

    def _before(self, mi, j):
        """Cumulative carry BEFORE step j (C_{j-1}; j=0 -> block entry)."""
        s_stack, n_stack = self._pre[mi]
        return (s_stack[j], n_stack[j])

    def expose(self, j):
        if self._pre is None:
            return
        for mi, m in enumerate(self._metrics):
            if m._device_totals is not self._installed.get(id(m)):
                # a callback materialized (get) or reset the metric —
                # everything it consumed is accounted for in its host
                # state; only deltas past that point may land on device.
                # Mid-burst the consumed value was step j-1's install, so
                # re-base at C_{j-1}.  BEFORE the first expose the armed
                # value was the block-FINAL totals: a materialize
                # absorbed C_{K-1} (host totals nonzero -> re-base
                # there); a reset discarded everything (host zeroed ->
                # re-base at block entry)
                if j > 0:
                    self._absorbed[id(m)] = self._before(mi, j)
                elif getattr(m, "num_inst", 0) or \
                        getattr(m, "sum_metric", 0.0):
                    self._absorbed[id(m)] = self._finals[mi]
                else:
                    self._absorbed[id(m)] = self._before(mi, 0)
            a = self._absorbed.get(id(m))
            cur = self._after(mi, j)
            if a is not None:
                cur = (cur[0] - a[0], cur[1] - a[1])
            m._device_totals = cur
            self._installed[id(m)] = cur


# ---------------------------------------------------------------------------
# FusedOptimizer: all parameter updates in one donated program
# ---------------------------------------------------------------------------

class FusedOptimizer:
    """One-dispatch optimizer application for a fixed parameter set.

    Replaces N per-parameter update dispatches (reference
    `model.py _update_params` / `gluon/trainer.py _update`) with a single
    donated XLA program.  Weight and state buffers are donated — the
    caller's NDArrays are repointed to the new buffers in place.
    """

    def __init__(self, optimizer):
        self._opt = optimizer
        self._jit = None
        self._broken = False

    def _build(self):
        import jax
        opt = self._opt

        def step(ws, gs, ss, lr_vec, wd_vec, t_vec, rescale):
            new_ws, new_ss = _apply_traced(opt, self._call_indices, ws, gs,
                                           ss, self._call_ctx, lr_vec,
                                           wd_vec, t_vec, rescale)
            new_ws = [_constrain_like(w, s)
                      for w, s in zip(new_ws, self._call_w_shardings)]
            new_ss = tuple(_constrain_like(s, sh)
                           for s, sh in zip(new_ss, self._call_s_shardings))
            return new_ws, new_ss

        self._jit = jax.jit(step, donate_argnums=(0, 2))

    def _hyper(self, indices):
        """Advance host-side update counts and collect per-parameter
        hyperparameters for injection (exact scheduler semantics: the real
        `_update_count`/`_get_lr`/`_get_wd` run on the host every step)."""
        opt = self._opt
        for i in indices:
            opt._update_count(i)
        lrs = _np.asarray([opt._get_lr(i) for i in indices], _np.float32)
        wds = _np.asarray([opt._get_wd(i) for i in indices], _np.float32)
        ts = _np.asarray([opt._index_update_count[i] for i in indices],
                         _np.float32)
        rescale = _np.float32(opt.rescale_grad)
        return lrs, wds, ts, rescale

    def __call__(self, indices, weights, grads, states):
        """Apply updates for all (index, weight, grad, state) in one
        program; falls back to the eager per-parameter path if the
        optimizer cannot trace."""
        opt = self._opt
        if self._broken:
            for i, w, g, s in zip(indices, weights, grads, states):
                opt.update_multi_precision(i, w, g, s)
            return
        lrs, wds, ts, rescale = self._hyper(indices)
        if self._jit is None:
            self._build()
        ws = [w._data for w in weights]
        gs = [g._data for g in grads]
        ss = tuple(_state_data(s) for s in states)
        self._call_indices = list(indices)
        self._call_ctx = weights[0].context
        self._call_w_shardings = [getattr(w, "sharding", None) for w in ws]
        self._call_s_shardings = tuple(_sharding_tree(s) for s in states)
        from . import analysis as _analysis
        if _analysis.enabled():
            self._step_no = getattr(self, "_step_no", 0) + 1
            names = _opt_param_names(opt, self._call_indices)
            _analysis.donation.record(
                f"FusedOptimizer step {self._step_no}",
                list(zip(names, ws)) +
                [(n + ".state", s) for n, s in zip(names, ss)])
        # counts were already advanced; replay through the raw update on
        # fallback (not update_multi_precision, which would double-count)
        try:
            with _no_rng():
                new_ws, new_ss = self._jit(ws, gs, ss, lrs, wds, ts, rescale)
        except _untraceable() as e:
            # selection: raised while tracing, before anything was
            # donated (a compile or device error propagates)
            self._broken = True
            _log.warning(
                "%s.update does not trace (%s); using the per-parameter "
                "path", type(opt).__name__, str(e)[:200])
            saved = dict(vars(opt))
            try:
                opt._update_count = lambda i: None  # already counted above
                for i, w, g, s in zip(indices, weights, grads, states):
                    opt.update_multi_precision(i, w, g, s)
            finally:
                for k in list(vars(opt)):
                    if k not in saved:
                        delattr(opt, k)
                opt.__dict__.update(saved)
            return
        except Exception as e:
            # the selected program failed to compile or run: name the
            # donated buffers it consumed, if any, and raise
            names = _opt_param_names(opt, self._call_indices)
            _raise_if_unrecoverable(
                "fused optimizer apply", e,
                list(zip(names, ws)) +
                [(n + ".state", s) for n, s in zip(names, ss)])
            raise
        for w, nw in zip(weights, new_ws):
            w._set_data(nw)
        for s, ns in zip(states, new_ss):
            _state_write_back(s, ns)


# ---------------------------------------------------------------------------
# FusedTrainStep: Module's forward+backward+update(+metric) in one program
# ---------------------------------------------------------------------------

class FusedTrainStep:
    """The `Module.fit` hot loop as one donated XLA program — or, in block
    mode, K train steps as one `lax.scan` program per dispatch.

    Built by `Module.init_optimizer` when eligible (single-process kvstore,
    plain ``write`` grads, no module states).  Each call:

      host:   advance optimizer counts, gather lr/wd/t scalars
      device: ONE program = forward + vjp + optimizer (traced public
              object) + BN-aux update + metric accumulation + key split
              — times K when the fit loop hands over a block of batches

    Parameters, optimizer state, aux state, the metric accumulator and the
    RNG key are donated carries — steady-state training allocates nothing
    and dispatches once per batch (once per K batches in block mode).

    Why blocks: where the host's dispatches serialize with the device
    (the single-process case the reference attacks with bulk-exec
    segments, `src/executor/graph_executor.cc:1194-1316`), the per-step
    host Python adds 1:1 to wall time.  `lax.scan` over K stacked batches amortizes the
    dispatch plus all host-side bookkeeping across K steps, which is what
    lets the public `fit` loop match a hand-pipelined raw-JAX loop.

    The expensive part of building these programs is tracing the framework
    graph (Python op dispatch over the whole Symbol).  That trace runs ONCE
    into a closed jaxpr; the 1-step jit and every K-step scan body replay
    the jaxpr (cheap) instead of re-running framework Python, so adding
    block mode does not multiply trace time.
    """

    def __init__(self, module, updater):
        import jax
        self._mod = module
        self._updater = updater
        self._symbol = module._symbol
        self._opt = updater.optimizer
        self._contexts = module._context
        exec0 = module._exec_group.execs[0]
        self._exec0 = exec0

        self._arg_names = self._symbol.list_arguments()
        self._aux_names = self._symbol.list_auxiliary_states()
        self._param_names = [n for n in module._exec_group.param_names
                             if module._exec_group.grad_req.get(n) == "write"]
        input_names = (module._exec_group.data_names +
                       module._exec_group.label_names)
        self._input_names = input_names
        # "fixed" args: bound but not updated (grad_req null non-inputs)
        self._fixed_names = [n for n in self._arg_names
                             if n not in self._param_names and
                             n not in input_names]
        ndev = len(self._contexts)
        update_on_kv = bool(module._update_on_kvstore)
        self._indices = [i if (update_on_kv or ndev == 1) else i * ndev
                         for i in range(len(module._exec_group.param_names))]
        self._indices = [self._indices[module._exec_group.param_names.index(n)]
                         for n in self._param_names]

        # device mesh for multi-device data parallelism — composed
        # dp×tp×pp meshes accepted from Module (`mesh=` / MXNET_MESH
        # spec through parallel/mesh.py); default: every context on one
        # 'dp' axis.  The batch shards over the dp axis only; params/
        # state replicate over it, and tensors the user sharded over the
        # OTHER axes (TP/PP) keep their layout (`_collect_misplaced`
        # respects same-mesh NamedShardings, `_constrain_like` pins the
        # step outputs to the input layouts).
        devices = [c.jax_device for c in self._contexts]
        mesh = getattr(module, "_mesh", None)
        if mesh is None and len(devices) > 1:
            from .parallel.mesh import mesh_from_spec
            mesh = mesh_from_spec(devices=devices)
        if len(devices) > 1 or mesh is not None:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from .parallel.mesh import dp_axis_of
            if mesh is None:
                mesh = Mesh(_np.array(devices), ("dp",))
            self._mesh = mesh
            self._dp_axis = dp_axis_of(mesh)
            self._dp_size = int(mesh.shape[self._dp_axis])
            self._data_sharding = NamedSharding(mesh, P(self._dp_axis))
            self._rep_sharding = NamedSharding(mesh, P())
        else:
            from jax.sharding import SingleDeviceSharding
            self._mesh = None
            self._dp_axis = None
            self._dp_size = 1
            self._data_sharding = SingleDeviceSharding(devices[0])
            self._rep_sharding = SingleDeviceSharding(devices[0])
        # ZeRO-style weight-update sharding (MXNET_ZERO): optimizer-state
        # tensors lay out sharded over dp, so GSPMD lowers the gradient
        # exchange feeding the update to reduce-scatter, runs the
        # optimizer on the local 1/N shard only, and all-gathers the new
        # weights — the MLPerf-pods paper's weight-update sharding, via
        # sharding annotations instead of hand-written collectives
        # (parallel/zero.py holds the explicit shard_map machinery).
        from . import config as _config
        self._zero = bool(_config.get("MXNET_ZERO")) and \
            self._mesh is not None and self._dp_size > 1

        from .symbol.symbol import graph_eval_fn
        # scan-over-layers (MXNET_FUSED_SCAN): runs of structurally
        # identical blocks lower to ONE lax.scan body over stacked
        # per-layer params instead of N inlined copies — the jaxpr (and
        # so the unified program cache key, via graph_hash_of_jaxpr)
        # shrinks to one layer body; XLA compiles the layer once
        self._scan_plan = _maybe_scan_plan(self._symbol)
        self.scan_runs = [] if self._scan_plan is None else \
            [(r["name"], r["length"]) for r in self._scan_plan["runs"]]
        self._gfn, _, _, self._n_rng = graph_eval_fn(
            self._symbol, True, scan=self._scan_plan)
        # pod SPMD fast path (MXNET_POD_SPMD): run the WHOLE step core
        # inside shard_map over the dp axis with a bucketed single-psum
        # gradient exchange.  The GSPMD global-view lowering inserts one
        # all-reduce per gradient tensor at its producing dot; on a wide
        # mesh every collective is a cross-device barrier, so O(params)
        # barriers per step amplify per-partition skew.  The pod path
        # exchanges ALL gradients in O(buckets) collectives
        # (MXNET_KVSTORE_BUCKET_MB caps a bucket — the same knob and
        # planning rule as the kvstore scheduler; its speed on a real
        # mesh is not measured).  Semantics: the psum of
        # per-shard gradients is exactly the reference kvstore's
        # cross-device SUM (comm.h Reduce), so sum-normalized graphs
        # (normalization='null') match the global-view program bit-for-
        # bit in structure; batch-normalized losses keep their classic
        # per-device normalization, as on the reference engine.
        self._pod_axis = None
        self.pod_stats = None
        if self._dp_size > 1 and not self._zero and \
                bool(_config.get("MXNET_POD_SPMD")) and \
                self._mesh is not None and \
                all(int(self._mesh.shape[a]) == 1
                    for a in self._mesh.axis_names
                    if a != self._dp_axis) and \
                self._pod_graph_ok():
            self._pod_axis = self._dp_axis
        self._key = None
        self._jit = None          # 1-step program
        self._jit_block = {}      # K -> K-step scan program
        self._core_closed = None  # the once-traced step jaxpr
        self._core_sig = None     # input signature the core was traced for
        self._core_cache = {}     # in_sig -> traced program set (retrace
                                  # survival for alternating signatures)
        self._autodonate_on = False  # per-core liveness decision (see
                                     # _decide_autodonate)
        self._derive_fn = None    # masters -> low-precision weights (flush)
        self.last_outputs = None
        self._block_outs = None   # scan ys: per-batch outputs of a block
        self.broken = False
        self._carry = None  # steady-state fast-path cache (see _dispatch)
        self._block_view = None  # per-step metric exposure for bursts
        self._derive_ws = False  # set by _build_core (see _master_positions)
        self._guardian = None    # resilience.guardian.TrainingGuardian
        self._guard = False      # in-graph health word armed (see below)
        FusedTrainStep._seq = getattr(FusedTrainStep, "_seq", 0) + 1
        self._audit_key = f"FusedTrainStep#{FusedTrainStep._seq}"
        self._step_no = 0   # donation-tracker step counter

    def attach_guardian(self, guardian):
        """Arm (or disarm, with None) the training guardian's in-graph
        health word: the step core gains an all-finite + gradient-norm
        reduction and a conditional update (a non-finite step's weight/
        state/aux/metric updates are `where`-selected away while RNG key
        and update counts advance — the deterministic skip-batch path).
        Flipping the armed state drops the traced cores so the next
        dispatch rebuilds with (or without) the health machinery."""
        armed = guardian is not None and getattr(guardian, "in_graph",
                                                 True)
        self._guardian = guardian
        if armed != self._guard:
            self._guard = armed
            self._core_closed = None
            self._core_cache = {}
            self._carry = None
            self._t_vec = None

    # -- placement of persistent buffers -------------------------------------
    # Every call normalizes buffer shardings (a no-op once placed): other
    # code paths — set_params at epoch boundaries, checkpoint loads — may
    # legally repoint these NDArrays at single-device arrays between steps.
    def _collect_misplaced(self, a, out, target=None):
        from jax.sharding import NamedSharding
        target = target if target is not None else self._rep_sharding
        cur = getattr(a._data, "sharding", None)
        if cur == target:
            return
        if target is self._rep_sharding and self._mesh is not None and \
                self._pod_axis is None and \
                isinstance(cur, NamedSharding) and cur.mesh == self._mesh:
            # user-sharded on the fused mesh (TP/PP axes): keep the layout
            # (the pod fast path instead REQUIRES replicated carries — its
            # shard_map in_specs claim P() — so it never takes this branch)
            return
        out.append((a, target))

    def _pod_graph_ok(self):
        """Graph eligibility for the pod shard_map fast path.  Fall back
        to the GSPMD lowering when the program samples RNG (per-shard
        streams would diverge from the global-view program), when a
        SoftmaxOutput normalizes by batch/valid (its scale would bake the
        SHARD batch size into the traced graph), when a train-mode
        BatchNorm is NOT sync=True (the fused global-view program
        computes GLOBAL-batch moments — that is this framework's
        documented BatchNorm semantics — but inside shard_map a plain
        mean reduces over the SHARD batch; sync BN psums the moments so
        it keeps the global statistics on either lowering), or when an
        aux state is non-floating (aux updates are pmean-averaged across
        shards — the reference executor group's cross-device aux
        averaging)."""
        if self._n_rng:
            return False
        try:
            import json as _json
            g = _json.loads(self._symbol.tojson())
            for node in g.get("nodes", []):
                attrs = node.get("attrs") or {}
                if node.get("op") in ("SoftmaxOutput", "Softmax") and \
                        attrs.get("normalization", "null") != "null":
                    return False
                if node.get("op") in ("BatchNorm", "BatchNorm_v1") and \
                        str(attrs.get("use_global_stats", "False")
                            ).lower() not in ("true", "1"):
                    if str(attrs.get("sync", "False")).lower() not in \
                            ("true", "1"):
                        return False
                    if str(attrs.get("sync_axis", "dp")) != self._dp_axis:
                        # sync BN psums over its `sync_axis` NAME; on a
                        # mesh whose dp axis is named differently the
                        # in-op axis probe would silently fail and the
                        # moments would go shard-local — fall back to
                        # the global-view lowering, which computes
                        # global-batch moments regardless of axis names
                        return False
        except Exception:
            return False
        try:
            import jax.numpy as jnp
            for n in self._aux_names:
                if not jnp.issubdtype(
                        self._exec0.aux_dict[n].dtype, jnp.floating):
                    return False
        except Exception:
            return False
        return True

    def _zero_sharding(self, a):
        """Dim-0-over-dp NamedSharding for a ZeRO-eligible optimizer
        state tensor (dim0 divides the dp axis), else replicated.
        Scalars and ragged tensors stay replicated — the big tensors
        carry virtually all the optimizer-state bytes."""
        if not self._zero:
            return self._rep_sharding
        shape = tuple(a.shape)
        if not shape or shape[0] % self._dp_size:
            return self._rep_sharding
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(
            self._mesh,
            P(*((self._dp_axis,) + (None,) * (len(shape) - 1))))

    def _place_state(self, s, out):
        if isinstance(s, NDArray):
            self._collect_misplaced(s, out, self._zero_sharding(s))
        elif isinstance(s, (tuple, list)):
            for x in s:
                self._place_state(x, out)

    def _place_all(self):
        import jax
        exec0 = self._exec0
        upd = self._updater
        need = [(i, n) for i, n in zip(self._indices, self._param_names)
                if i not in upd.states]
        if need:
            self._create_states(need)
        todo = []
        for n in self._param_names + self._fixed_names:
            self._collect_misplaced(exec0.arg_dict[n], todo)
        for n in self._aux_names:
            self._collect_misplaced(exec0.aux_dict[n], todo)
        for i in self._indices:
            self._place_state(upd.states[i], todo)
        if todo:
            # ONE batched transfer instead of a round trip per array
            # (per-leaf target shardings: replicated, or dp-sharded for
            # ZeRO-eligible optimizer state)
            moved = jax.device_put([a._data for a, _ in todo],
                                   [t for _, t in todo])
            for (a, _), v in zip(todo, moved):
                a._set_data(v)

    def _create_states(self, need):
        """All missing optimizer states in ONE compiled program from the
        device-resident weights (masters are casts, the rest zeros): no
        per-parameter dispatches, no weight download, no state upload —
        on a remote device the old fetch-create-upload path cost seconds
        of round trips.  Falls back to the host-staged path when the
        optimizer's create_state cannot trace."""
        exec0 = self._exec0
        upd = self._updater
        ctx = self._contexts[0]
        indices = [i for i, _ in need]
        ws = [exec0.arg_dict[n]._data for _, n in need]
        states = create_states_on_device(self._opt, indices, ws, ctx)
        if states is None:
            self._create_states_host(need)
            return
        for (i, _), s in zip(need, states):
            upd.states[i] = s
            upd.states_synced[i] = True

    def _create_states_host(self, need):
        """Host-staged fallback: ONE batched weight read, create_state on
        staged shells under a bulk scope, one batched upload (done by the
        placement pass that follows)."""
        import jax
        from . import engine as _engine
        exec0 = self._exec0
        upd = self._updater
        host_ws = jax.device_get(
            [exec0.arg_dict[n]._data for _, n in need])
        with _engine.bulk(1 << 16):
            for (i, n), hw in zip(need, host_ws):
                tgt = exec0.arg_dict[n]
                shell = NDArray(_np.asarray(hw), ctx=tgt.context)
                _engine.stage(shell)
                upd.states[i] = self._opt.create_state_multi_precision(
                    i, shell)
                upd.states_synced[i] = True
                _engine.unstage(shell)  # scratch; never uploaded

    # -- derived low-precision weights ---------------------------------------
    def _master_positions(self):
        """For every trainable param, the leaf index of its fp32 master in
        the optimizer-state pytree — or None when any param lacks one.

        When every weight has a master (bf16/fp16 multi-precision
        training), the low-precision weights need not be dispatch
        arguments at all: the program derives them from the masters at
        entry (one cast XLA fuses into the first consumer), dropping
        n_params input leaves + donation aliases from every step."""
        import jax
        exec0 = self._exec0
        upd = self._updater
        pos = []
        probed = {}   # state-structure key -> master leaf index (or None)
        for i, n in zip(self._indices, self._param_names):
            w = exec0.arg_dict[n]
            if _np.dtype(w.dtype) == _np.float32:
                return None
            leaves = jax.tree_util.tree_leaves(
                _state_data(upd.states.get(i)))
            cands = [j for j, lf in enumerate(leaves)
                     if str(getattr(lf, "dtype", "")) == "float32"
                     and tuple(getattr(lf, "shape", ())) == tuple(w.shape)]
            if len(cands) == 1:
                pos.append(cands[0])
                continue
            if not cands:
                return None
            # ambiguous (e.g. adam/sgd-momentum: momentum and master are
            # both fp32 of the weight's shape): probe the optimizer's
            # state STRUCTURE with a tiny nonzero weight and find the leaf
            # equal to its fp32 copy.  The structure is a property of the
            # optimizer, not of the individual parameter, so one probe per
            # distinct (dtype, leaf-structure) serves all 100+ params —
            # and it runs on the HOST backend (no device dispatch per
            # probe).
            key = (str(_np.dtype(w.dtype)), tuple(cands),
                   tuple(str(getattr(lf, "dtype", "")) for lf in leaves))
            if key not in probed:
                from .ndarray.ndarray import array as _arr
                from .context import cpu as _cpu
                tw = _arr(_np.linspace(0.1, 0.9, 4, dtype=_np.float32),
                          ctx=_cpu(), dtype=w.dtype)
                ps = self._opt.create_state_multi_precision(i, tw)
                pl = jax.tree_util.tree_leaves(_state_data(ps))
                host = jax.device_get([tw._data] + [
                    pl[j] for j in cands if j < len(pl)])
                target = _np.asarray(host[0], _np.float32)
                hit = [j for j, hv in zip(
                    [c for c in cands if c < len(pl)], host[1:])
                    if _np.array_equal(_np.asarray(hv, _np.float32), target)]
                probed[key] = hit[0] if len(hit) == 1 else None
            if probed[key] is None:
                return None
            pos.append(probed[key])
        return pos

    # -- the traced step core ------------------------------------------------
    def _build_core(self, metric_fns):
        """The one-step train function over raw arrays.  Returned as plain
        Python; `_trace_core` runs it exactly once under `make_jaxpr`.

        Its phases run under `jax.named_scope`s — ``fwd``, ``bwd``,
        ``exchange``, ``optimizer``, ``guardian``, ``metric`` (the names
        `compile.op_scopes` classifies by) — which reach the compiled
        program as `op_name` metadata and change nothing else in it."""
        import jax
        import jax.numpy as jnp

        gfn = self._gfn
        arg_names = self._arg_names
        param_pos = {n: k for k, n in enumerate(self._param_names)}
        input_pos = {n: k for k, n in enumerate(self._input_names)}
        fixed_pos = {n: k for k, n in enumerate(self._fixed_names)}
        n_label = len(self._mod._exec_group.label_names)
        opt = self._opt
        indices = self._indices
        ctx = self._contexts[0]
        n_rng = self._n_rng
        mp_pos = self._master_positions()
        self._derive_ws = mp_pos is not None and len(mp_pos) > 0
        self._mp_pos = mp_pos
        self._w_dtypes = [self._exec0.arg_dict[n].dtype
                          for n in self._param_names]
        derive = self._derive_ws
        w_dtypes = self._w_dtypes
        guard = self._guard
        pod_axis = self._pod_axis
        pod_dp = self._dp_size
        if pod_axis is not None:
            from . import config as _config
            pod_cap = max(1, int(float(_config.get(
                "MXNET_KVSTORE_BUCKET_MB")) * (1 << 20)))
        else:
            pod_cap = None

        def core(inner, x, fixed, rescale):
            ws, ss, auxs, mcarry, key, t_vec = inner
            if guard:
                # gmul: the guardian's per-step gradient multiplier (1.0
                # in production; NaN / spike-scale under fault injection)
                inputs, lr_vec, wd_vec, gmul = x
            else:
                inputs, lr_vec, wd_vec = x
            if derive:
                with jax.named_scope("fwd"):
                    ws = [jax.tree_util.tree_leaves(s)[p].astype(dt)
                          for s, p, dt in zip(ss, mp_pos, w_dtypes)]
            # t advances IN-GRAPH (donated carry): the host passes the
            # update counts once when (re)arming and never re-uploads the
            # vector — keeping every steady-state dispatch argument a
            # device array so the C++ fast dispatch path engages
            t_vec = t_vec + jnp.float32(1.0)
            if n_rng:
                key, sub = jax.random.split(key)
            else:
                sub = key

            def forward(pws):
                args = []
                for n in arg_names:
                    if n in param_pos:
                        args.append(pws[param_pos[n]])
                    elif n in input_pos:
                        args.append(inputs[input_pos[n]])
                    else:
                        args.append(fixed[fixed_pos[n]])
                outs, new_aux = gfn(tuple(args), tuple(auxs), sub)
                return tuple(outs), tuple(new_aux)

            with jax.named_scope("fwd"):
                outs, vjp, new_aux = jax.vjp(forward, list(ws),
                                             has_aux=True)
                # scan carries must keep invariant dtypes (see gluon
                # core): pin aux updates to the stored aux dtype
                new_aux = tuple(
                    na.astype(a.dtype) if na.dtype != a.dtype else na
                    for na, a in zip(new_aux, auxs))
            with jax.named_scope("bwd"):
                cts = tuple(
                    jnp.ones(o.shape, o.dtype)
                    if jnp.issubdtype(o.dtype, jnp.floating)
                    else jnp.zeros(o.shape, o.dtype) for o in outs)
                (grads,) = vjp(cts)
                if guard:
                    grads = [g * jnp.asarray(gmul, g.dtype) for g in grads]
            pod_deltas = pod_outs_bad = None
            if pod_axis is not None:
                # the pod fast path's gradient exchange: every gradient
                # bucket, every metric delta, the BN aux moments and the
                # guardian's local-health bit ride ONE psum bind — a
                # single cross-device barrier per step.  Downstream
                # (update, guardian, optimizer state) runs on globally
                # identical values, replicated across the shards.
                labels_p = inputs[len(inputs) - n_label:] if n_label \
                    else ()
                extras = []
                with jax.named_scope("metric"):
                    for fn, _m in metric_fns:
                        dsum, dnum = fn(list(labels_p), list(outs))
                        # dnum rides the float bundle; counts are exact
                        # in f32 well past any step's sample count
                        extras.append(jnp.asarray(dsum, jnp.float32))
                        extras.append(jnp.asarray(dnum, jnp.float32))
                n_metric = len(metric_fns)
                extras.extend(list(new_aux))
                if guard:
                    with jax.named_scope("guardian"):
                        oks = [jnp.isfinite(o).all() for o in outs
                               if jnp.issubdtype(o.dtype, jnp.floating)]
                        bad = jnp.float32(len(oks)) - sum(
                            (o.astype(jnp.float32) for o in oks),
                            jnp.float32(0.0))
                    extras.append(bad)
                with jax.named_scope("exchange"):
                    grads, plan, sext, n_psums = _pod_bucket_psum(
                        grads, pod_axis, pod_cap, extras)
                    self._pod_plan = plan
                    self._pod_psums = n_psums
                    pod_deltas = [(sext[2 * j], sext[2 * j + 1])
                                  for j in range(n_metric)]
                    # aux updates (BN moments) are averaged across shards
                    # — the reference executor group's cross-device aux
                    # merge
                    a0 = 2 * n_metric
                    new_aux = tuple(
                        (sext[a0 + j] / jnp.asarray(pod_dp, na.dtype))
                        .astype(na.dtype)
                        for j, na in enumerate(new_aux))
                if guard:
                    pod_outs_bad = sext[-1]
            with jax.named_scope("optimizer"):
                new_ws, new_ss = _apply_traced(opt, indices, ws, grads, ss,
                                               ctx, lr_vec, wd_vec, t_vec,
                                               rescale)
            if guard:
                with jax.named_scope("guardian"):
                    # the health word, computed where the data lives: one
                    # all-finite reduction over grads + floating outputs +
                    # the applied update, and the spike detector's signal —
                    # the parameter-DISPLACEMENT ratio ||new_w - w|| / ||w||.
                    # (A gradient norm is a poor damage proxy: a wrecked
                    # model can saturate into normal-looking gradients, and
                    # a converged model's gradient noise spans decades.  The
                    # displacement ratio measures the damage itself.)
                    parts = [jnp.isfinite(g).all() for g in grads]
                    if pod_axis is not None:
                        # the shard-local output check already crossed the
                        # wire inside the bundled exchange: a shard whose
                        # LOCAL outputs went non-finite refuses the step on
                        # every shard (grads/new_ws are globally identical
                        # post-exchange, so those checks need no wire)
                        parts.append(pod_outs_bad <= jnp.float32(0.5))
                    else:
                        parts += [jnp.isfinite(o).all() for o in outs
                                  if jnp.issubdtype(o.dtype, jnp.floating)]
                    parts += [jnp.isfinite(nw).all() for nw in new_ws]
                    finite = parts[0]
                    for p in parts[1:]:
                        finite = jnp.logical_and(finite, p)
                    unorm2 = sum(
                        jnp.sum(jnp.square(nw.astype(jnp.float32)
                                           - w.astype(jnp.float32)))
                        for nw, w in zip(new_ws, ws))
                    wnorm2 = sum(
                        jnp.sum(jnp.square(w.astype(jnp.float32)))
                        for w in ws)
                    signal = jnp.sqrt(unorm2) / (jnp.sqrt(wnorm2)
                                                 + jnp.float32(1e-12))
                    # skip-batch: a non-finite step's updates are refused IN
                    # THE PROGRAM — weights/optimizer state/aux keep their
                    # input values; RNG key and update counts still advance,
                    # so a skipped step is deterministic and reproducible
                    def keep(new, old):
                        return jax.tree_util.tree_map(
                            lambda n, o: jnp.where(finite, n,
                                                   o.astype(n.dtype)),
                            new, old)

                    new_ws = [jnp.where(finite, nw, w.astype(nw.dtype))
                              for nw, w in zip(new_ws, ws)]
                    new_ss = tuple(keep(ns, s) for ns, s in zip(new_ss, ss))
                    # BN aux updated by a non-finite forward is refused too
                    new_aux = tuple(
                        jnp.where(finite, na, a.astype(na.dtype))
                        for na, a in zip(new_aux, auxs))
            # keep the persistent carries in their input layout (replicated
            # for DP; whatever the user sharded for TP/ZeRO).  Inside the
            # pod shard_map the layout is enforced by the out_specs
            # instead — sharding constraints are global-view constructs.
            if pod_axis is None:
                new_ss = tuple(
                    _constrain_like(s, sh)
                    for s, sh in zip(new_ss, self._call_s_shardings))
                new_aux = tuple(
                    _constrain_like(a, s)
                    for a, s in zip(new_aux, self._call_a_shardings))
            if derive:
                new_ws = ()   # flush re-derives from the masters on demand
            elif pod_axis is None:
                new_ws = tuple(
                    _constrain_like(w, s)
                    for w, s in zip(new_ws, self._call_w_shardings))
            else:
                new_ws = tuple(new_ws)
            with jax.named_scope("metric"):
                labels = inputs[len(inputs) - n_label:] if n_label else ()
                new_mcarry = []
                for j, ((fn, _), (msum, mnum)) in enumerate(
                        zip(metric_fns, mcarry)):
                    if pod_deltas is not None:
                        # global deltas arrived inside the bundled exchange
                        dsum, dnum = pod_deltas[j]
                        dnum = dnum.astype(jnp.int32)
                    else:
                        dsum, dnum = fn(list(labels), list(outs))
                        dsum = jnp.asarray(dsum, jnp.float32)
                        dnum = jnp.asarray(dnum, jnp.int32)
                    if guard:
                        # a skipped batch must not poison the metric totals
                        dsum = jnp.where(finite, dsum, jnp.zeros_like(dsum))
                        dnum = jnp.where(finite, dnum, jnp.zeros_like(dnum))
                    # counts carry as int32: float32 would silently stop
                    # incrementing past 2^24 samples
                    new_mcarry.append((msum + dsum, mnum + dnum))
            new_inner = (new_ws, new_ss, tuple(new_aux), tuple(new_mcarry),
                         key, t_vec)
            if guard:
                # per-step health word: fetched asynchronously by the
                # guardian (device scalars; no host sync on this path)
                with jax.named_scope("guardian"):
                    ok = finite.astype(jnp.float32)
                return new_inner, (tuple(outs), (ok, signal))
            return new_inner, tuple(outs)

        return core

    def _trace_core(self, core, example):
        """Run the framework trace ONCE; every program replays the jaxpr.
        In pod mode the traced unit is the shard_map-wrapped core: batch
        inputs and graph outputs shard over the dp axis, every carry is
        replicated, and the graph's Python runs on SHARD-local shapes
        inside the manual mesh — so the replayed jaxpr is one shard_map
        equation whose body was closed under the mesh it runs in."""
        if self._pod_axis is not None:
            import jax
            from jax.sharding import PartitionSpec as P
            shd, rep = P(self._pod_axis), P()
            x = example[1]   # (inputs, lr_vec, wd_vec[, gmul])
            core = jax.shard_map(
                core, mesh=self._mesh,
                in_specs=(rep, (shd,) + (rep,) * (len(x) - 1), rep, rep),
                out_specs=(rep, (shd, rep) if self._guard else shd),
                check_vma=False)
        self._core_closed = _TracedCore(core, example)

    def _trace_step(self, metric_fns, inner, x0, fixed, rescale_dev, ws):
        """Build and trace the step core for this signature, decide its
        lowering (pod shard_map vs global view) and donation, and drop
        the programs built over the previous core.  Returns `inner`
        (rebuilt without the weights when they derive from masters)."""
        if self._pod_axis is not None and not self._pod_outs_ok(x0[0]):
            # a reduced (non-batch-led) graph output cannot ride the pod
            # fast path; this step lowers global-view
            _log.info("pod fast path disabled: graph outputs are not "
                      "batch-led")
            self._pod_axis = None
            self.pod_stats = None
        core = self._build_core(metric_fns)
        # derive mode decided inside _build_core: rebuild inner
        if self._derive_ws:
            inner = ((),) + inner[1:]
        self._trace_core(core, (inner, x0, fixed, rescale_dev))
        if self._pod_axis is not None:
            plan = getattr(self, "_pod_plan", [])
            nbytes = sum(int(_np.prod(w.shape)) * w.dtype.itemsize
                         for w in ws) if ws else 0
            self.pod_stats = {
                "axis": self._pod_axis, "dp": self._dp_size,
                "params": len(self._param_names),
                "buckets": len(plan),
                # binds actually dispatched: the extras psum costs one
                # extra when no f32 bucket existed to fold it into
                "collectives_per_step": getattr(
                    self, "_pod_psums", len(plan)),
                "bytes_per_step": nbytes,
            }
            from . import profiler as _profiler
            _profiler.record_kvstore("pod_exchange", **self.pod_stats)
        self._autodonate_on = self._decide_autodonate(inner, x0)
        self._jit = None
        self._jit_block = {}
        self._scan_jit = None
        return inner

    def _pod_outs_ok(self, inputs):
        """Every graph output must be batch-led (the shard_map out_spec
        stitches the per-shard rows back into the global batch); a
        scalar/reduced output has no general reconstitution rule."""
        batch = inputs[0].shape[0]
        _, out_shapes, _ = self._symbol.infer_shape(
            **{n: tuple(v.shape)
               for n, v in zip(self._input_names, inputs)})
        return all(s and s[0] == batch for s in out_shapes)

    def _build1(self):
        self._jit = _one_step_jit(self._core_closed, label=self._audit_key,
                                  donate_inputs=self._autodonate_on)

    def _buildk(self, k):
        # one scan-jit serves every K (xs arity keys the jit's own cache);
        # the per-K dict entry is the "this block size has run" record.
        # mcarry_index=3: the metric accumulator's slot in the inner
        # carry — the scan stacks it per step for the callback burst
        jitk = self._scan_jit if getattr(self, "_scan_jit", None) is not None \
            else _scan_block_jit(self._core_closed, mcarry_index=3,
                                 label=self._audit_key,
                                 donate_inputs=self._autodonate_on)
        self._scan_jit = jitk
        self._jit_block[k] = jitk
        return jitk

    def _decide_autodonate(self, inner, x0):
        """Trace-time auto-donation decision (MXNET_FUSED_AUTODONATE):
        donate the staged batch inputs iff EVERY input leaf provably
        dies inside the traced step — its invar never reaches the core
        jaxpr's outvars (analysis.cost.jaxpr_dying_inputs).  A graph
        that echoes an input into its heads keeps the buffer live in
        `last_outputs`, so donation stays off for the whole input set.
        The dispatch re-owns staged inputs before a donating call
        (reown_for_donation): staged arrays can be device_put of HOST
        memory or adopted caller-owned arrays (prestage/io ring), both
        unsafe to donate raw."""
        from . import config as _config
        if not bool(_config.get("MXNET_FUSED_AUTODONATE")):
            return False
        try:
            import jax
            from .analysis import cost as _cost
            n_inner = len(jax.tree_util.tree_leaves(inner))
            n_inputs = len(jax.tree_util.tree_leaves(tuple(x0[0])))
            if not n_inputs:
                return False
            idx = list(range(n_inner, n_inner + n_inputs))
            dying = _cost.jaxpr_dying_inputs(self._core_closed._closed,
                                             idx)
            return len(dying) == n_inputs
        except Exception as e:
            _log.debug("auto-donation liveness analysis failed (%s); "
                       "keeping inputs undonated", str(e)[:200])
            return False

    # -- per-call ------------------------------------------------------------
    def _metric_leaves(self, eval_metric):
        """Leaf metrics with device-side update fns, or None when any leaf
        cannot run in-graph (caller then uses the host update path)."""
        from . import metric as _metric
        if eval_metric is None:
            return []
        if isinstance(eval_metric, _metric.CompositeEvalMetric):
            leaves = eval_metric.metrics
        else:
            leaves = [eval_metric]
        out = []
        for m in leaves:
            fn = getattr(m, "device_update", None)
            if fn is None:
                return None
            out.append((fn, m))
        return out

    def __call__(self, data_batch, eval_metric=None):
        """Run one fused train step.  Returns True when handled (metric
        included); False -> caller must use the unfused path."""
        return self._dispatch([data_batch], eval_metric)

    def call_block(self, batches, eval_metric=None):
        """Run len(batches) train steps as ONE `lax.scan` dispatch.
        Returns True when handled; False -> caller runs them one by one."""
        return self._dispatch(list(batches), eval_metric)

    def _batch_sig(self, batches):
        sig = None
        for b in batches:
            s = tuple((getattr(v, "shape", None), getattr(v, "dtype", None))
                      for v in list(b.data) + list(b.label or []))
            if sig is None:
                sig = s
            elif s != sig:
                return None   # mixed shapes cannot share one program
        return sig

    def _dispatch(self, batches, eval_metric):
        if self.broken:
            return False
        import jax
        mod = self._mod
        k = len(batches)

        metric_fns = self._metric_leaves(eval_metric)
        if metric_fns is None:
            self.flush()
            return False
        in_sig = self._batch_sig(batches)
        if in_sig is None:
            self.flush()
            return False
        from . import analysis as _analysis
        # steady-state fast path: when every persistent buffer is still the
        # array WE wrote back last step (verified by identity), placement,
        # sharding collection and signature validation are all known-good
        # and skipped — per-step host work drops to the hyper scalars and
        # the dispatch itself
        carry = self._carry if getattr(self, "_carry", None) else None
        exec0 = self._exec0
        if carry is not None:
            # load_optimizer_states swaps the whole states dict — identity
            # of the dict covers external state replacement; the input
            # signature must also match (a new batch shape needs the full
            # validation path before the donating dispatch).  The exec
            # buffers are compared against what WE last physically wrote
            # (`_seen_*`): in steady state write-backs are deferred (see
            # flush()), so the dicts still hold the last-flushed arrays.
            ok = getattr(self, "_carry_sdict", None) is \
                self._updater.states and \
                in_sig == getattr(self, "_carry_in_sig", None) and \
                self._owns_exec_buffers() and \
                all(exec0.aux_dict[n]._data is a
                    for n, a in zip(self._aux_names, self._seen_aux))
            if not ok:
                carry = None
        # a metric change forces the cold path too — decide BEFORE the
        # flush block, which must run whenever the cold path will read the
        # exec-dict arrays (in steady state they were donated last step);
        # the build itself runs AFTER placement (it probes the optimizer
        # states _place_all creates)
        need_build = self._core_closed is None or \
            metric_fns_changed(self._metric_sig(), metric_fns)
        if need_build:
            self._metric_ids = [id(m) for _, m in metric_fns]
            self._core_closed = None   # metric set is baked into the core
            self._core_cache = {}      # shapes AND metrics key the cores
            carry = None
        if carry is None:
            if self._owns_exec_buffers():
                self.flush()
            else:
                # an external writer repointed the exec buffers (its values
                # win — Module's hooks flush beforehand on every public
                # path); stale pending results must not clobber them.
                # Pending optimizer/aux write-backs are dropped WITH the
                # externally-set weights' blessing — warn so bypassing the
                # public API is diagnosable (Module always flushes first).
                if not getattr(self, "_flushed", True):
                    _log.warning(
                        "fused step: exec buffers were repointed externally "
                        "with results pending; dropping the pending "
                        "optimizer-state/aux write-backs (use the public "
                        "Module APIs, which flush first)")
                self._flushed = True
            # the holders have what the old carry had (or newer values
            # from outside): let go of it, so that the cold dispatch does
            # not hold the last block's masters and momentum beside the
            # copies it is about to make
            self._carry = None
            self._place_all()

        exec0 = self._exec0
        n_inputs_ok = all(
            len(list(b.data) + list(b.label or [])) == len(self._input_names)
            for b in batches)
        if not n_inputs_ok:
            self.flush()   # caller runs unfused on the public buffers
            return False
        if self._dp_size > 1 and any(
                (shape[0] if shape else 0) % self._dp_size
                for shape, _dt in in_sig):
            # e.g. a partial tail batch: not shardable over the dp axis —
            # this batch takes the unfused path, the step stays usable
            self.flush()
            return False
        xs_inputs = []
        for b in batches:
            data = list(b.data) + list(b.label or [])
            pre = getattr(self, "_prestaged", None)
            if pre is not None and pre[0] is b:
                xs_inputs.append(pre[1])  # transfer already in flight
                self._prestaged = None
            else:
                xs_inputs.append(self._stage_inputs(data))
        fixed = [exec0.arg_dict[n]._data for n in self._fixed_names]
        if carry is not None:
            ws, ss, auxs = carry  # shardings unchanged (constrained)
        else:
            states = [self._updater.states[i] for i in self._indices]
            ws = [exec0.arg_dict[n]._data for n in self._param_names]
            ss = tuple(_state_data(s) for s in states)
            auxs = [exec0.aux_dict[n]._data for n in self._aux_names]
            self._call_w_shardings = [getattr(w, "sharding", None)
                                      for w in ws]
            self._call_s_shardings = tuple(_sharding_tree(s)
                                           for s in states)
            self._call_a_shardings = [getattr(a, "sharding", None)
                                      for a in auxs]
            # cold dispatch: these arrays may be externally staged
            # (checkpoint restore, set_params at epoch boundaries) —
            # donating host-staged buffers into an AOT executable
            # corrupts them; re-own through one XLA copy first
            nbytes = _tree_nbytes((ws, ss, auxs))
            whole = nbytes <= REOWN_IN_PLACE_BYTES
            with _obs_trace.phase(
                    "fused.reown", cat="compile", bytes=nbytes,
                    leaves=len(jax.tree_util.tree_leaves((ws, ss, auxs))),
                    mode="whole" if whole else "in_place"):
                if whole:
                    ws, ss, auxs = reown_for_donation((ws, ss, auxs))
                else:
                    del ws, ss, auxs
                    ws, ss, auxs = self._reown_in_place(states)

        mcarry = []
        for fn, m in metric_fns:
            pend = getattr(m, "_device_totals", None)
            if pend is None:
                import jax.numpy as jnp
                pend = (jax.device_put(jnp.zeros((), jnp.float32),
                                       self._rep_sharding),
                        jax.device_put(jnp.zeros((), jnp.int32),
                                       self._rep_sharding))
            mcarry.append(tuple(pend))

        if self._key is None:
            from . import random as _random
            self._key = jax.device_put(_random.next_key(),
                                       self._rep_sharding)

        # recompilation audit: past every unfused-bail check, a changed
        # signature now really does force a fresh XLA compile — record it
        # with the exact arg that moved (noting any earlier would claim
        # compiles for batches the eligibility checks sent unfused, and
        # poison the history for the eventual real compile)
        _analysis.recompile.note(self._audit_key, self._input_names, in_sig)
        if self._core_closed is not None and \
                in_sig != getattr(self, "_core_sig", None):
            # the input signature changed (the recompile auditor recorded
            # the churn above): the once-traced core jaxpr is
            # shape-specialized, so swap in this signature's cached
            # program set — or drop the core and re-trace.  A ragged tail
            # batch costs a recompile, not a permanently broken fast path.
            cached = getattr(self, "_core_cache", {}).get(in_sig)
            if cached is not None:
                (self._core_closed, self._jit, self._scan_jit,
                 self._jit_block, self._derive_ws, self._mp_pos,
                 self._w_dtypes, self._pod_axis, self._pod_plan,
                 self.pod_stats, self._autodonate_on) = cached
            else:
                self._core_closed = None

        opt = self._opt
        # snapshot counts so a failed attempt doesn't double-count the step
        # when the caller re-runs it through the unfused path
        counts_before = dict(opt._index_update_count)
        num_update_before = opt.num_update
        rows, rescale_dev = advance_hyper_rows(opt, self._indices, k, self,
                                               self._rep_sharding)
        t_vec = getattr(self, "_t_vec", None) if carry is not None else None
        if t_vec is None:
            # seed the in-graph counter with counts BEFORE this block (the
            # program itself adds +1 per step); re-owned — it is donated,
            # and device_put of host memory must not be (see
            # reown_for_donation)
            t_vec = reown_for_donation(jax.device_put(_np.asarray(
                [opt._index_update_count[i] - k for i in self._indices],
                _np.float32), self._rep_sharding))

        inner = (() if self._derive_ws and self._core_closed is not None
                 else tuple(ws), ss, tuple(auxs), tuple(mcarry),
                 self._key, t_vec)
        if self._guard:
            # the guardian's per-step gradient multipliers (1.0 outside
            # fault injection) ride the per-step inputs, and the site
            # hooks grad.nonfinite / loss.spike fire here — once per step
            gmuls = self._guardian.step_multipliers(k)
            xs = [(tuple(inp), lr_j, wd_j, gm)
                  for inp, (lr_j, wd_j), gm
                  in zip(xs_inputs, rows, gmuls)]
        else:
            xs = [(tuple(inp), lr_j, wd_j)
                  for inp, (lr_j, wd_j) in zip(xs_inputs, rows)]

        if _analysis.enabled():
            # name every donated carry leaf BEFORE the consuming dispatch:
            # a later read of a stale buffer then names its parameter and
            # the step that ate it (analysis.donation)
            self._step_no += k
            _analysis.donation.record(
                f"{self._audit_key} step {self._step_no}",
                self._donation_groups(ws, ss, auxs) +
                [("<metric accumulator>", mcarry),
                 ("<rng key>", self._key), ("<update counts>", t_vec)])

        def rewind(flush):
            # the block never ran: neither the optimizer's nor the
            # guardian's step counters may count it
            opt._index_update_count = counts_before
            opt.num_update = num_update_before
            if self._guard:
                self._guardian._gstep -= k
            if flush:
                self.flush()   # pending results of prior steps are intact
            self._carry = None
            self._t_vec = None
            self._block_view = None
            self.broken = True

        if self._core_closed is None:
            # SELECTION: the framework trace runs the graph's and the
            # public optimizer's Python once.  A step whose Python cannot
            # run under a trace (`_untraceable`: an optimizer drawing host
            # RNG or reading a value back) was never eligible — Module.fit
            # keeps the reference forward_backward+update path for it.
            # Every other failure of the trace — the pod shard_map
            # refusing its mesh or specs above all — is the selected step
            # failing, and is raised like a compile or dispatch error.
            try:
                with _no_rng():
                    inner = self._trace_step(metric_fns, inner, xs[0],
                                             fixed, rescale_dev, ws)
            except _untraceable() as e:
                rewind(flush=True)
                self._core_closed = None
                _log.warning("fused train step not traceable (%s); "
                             "Module.fit uses forward_backward+update",
                             str(e)[:300])
                return False
            except Exception as e:
                rewind(flush=True)
                self._core_closed = None
                raise MXNetError(
                    f"fused train step failed to trace "
                    f"({type(e).__name__}: {str(e)[:300]})") from e
        # a lower/compile/dispatch failure (XLA, Mosaic, a sharding
        # mismatch, RESOURCE_EXHAUSTED) is the selected step failing ON
        # ITS DEVICE: raised with its cause, never replaced by another
        # path
        try:
            if k == 1:
                if self._jit is None:
                    self._build1()
                if self._autodonate_on:
                    with _quiet_donation():
                        new_inner, outs = self._jit(
                            inner,
                            reown_for_donation(tuple(xs[0][0])),
                            tuple(xs[0][1:]), fixed, rescale_dev)
                else:
                    new_inner, outs = self._jit(inner, xs[0], fixed,
                                                rescale_dev)
                ys = mys = None
            else:
                jitk = self._jit_block.get(k)
                if jitk is None:
                    jitk = self._buildk(k)
                if self._autodonate_on:
                    with _quiet_donation():
                        new_inner, ys, mys, outs = jitk(
                            inner,
                            reown_for_donation(
                                tuple(tuple(x[0]) for x in xs)),
                            tuple(tuple(x[1:]) for x in xs),
                            fixed, rescale_dev)
                else:
                    new_inner, ys, mys, outs = jitk(
                        inner, tuple(xs), fixed, rescale_dev)
        except Exception as e:
            try:
                _raise_if_unrecoverable("fused train step", e,
                                        self._donation_groups(ws, ss, auxs))
            except MXNetError:
                rewind(flush=False)   # the carry was consumed
                raise
            rewind(flush=True)
            raise MXNetError(
                f"fused train step failed to lower, compile or run "
                f"({type(e).__name__}: {str(e)[:300]})") from e

        health = None
        if self._guard:
            # step_out is (outputs, (ok, signal)): split the health word
            # off the output views (device arrays — the guardian gathers
            # them asynchronously, never on this path)
            if ys is not None:
                ys, health = ys
                outs = outs[0]
            else:
                outs, health = outs
        new_ws, new_ss, new_aux, new_mcarry, new_key, new_t = new_inner
        finals = []
        for (fn, m), pend in zip(metric_fns, new_mcarry):
            t = tuple(pend)
            m._device_totals = t
            finals.append(t)
        # per-step metric exposure for the callback burst: batch-j
        # callbacks must see batch-j metric state, not block-final state
        if mys is not None:
            self._block_view = _BlockMetricView(
                [m for _, m in metric_fns], mys, finals)
            self._block_view.arm()
        else:
            self._block_view = None
        self._key = new_key
        self._t_vec = new_t
        ctx0 = self._contexts[0]
        self.last_outputs = [NDArray(o, ctx=ctx0) for o in outs]
        # per-batch outputs of the block (stacked scan ys): a batch-j
        # callback reading get_outputs() must see batch j's outputs, not
        # the block-final ones — the fit loop moves `block_cursor` as it
        # fires the callback burst and `current_outputs` slices lazily
        self._block_outs = ys
        self._block_len = k
        self.block_cursor = k - 1
        self._block_cache = {}
        mod._params_dirty = True
        # arm the steady-state fast path; the ~600 NDArray write-backs are
        # DEFERRED (donation invalidated the old buffers, but nothing reads
        # them until an external consumer calls flush() via Module) — on a
        # one-core host the per-step Python was serializing with the device
        was_cold = carry is None
        self._carry = (list(new_ws), tuple(new_ss), list(new_aux))
        self._carry_sdict = self._updater.states
        self._carry_in_sig = in_sig
        self._flushed = False
        self._core_sig = in_sig
        if len(self._core_cache) < 8 or in_sig in self._core_cache:
            # keep the freshest program set per signature so an
            # alternating shape (epoch tail) swaps instead of re-tracing
            self._core_cache[in_sig] = (
                self._core_closed, self._jit, self._scan_jit,
                self._jit_block, self._derive_ws,
                getattr(self, "_mp_pos", None),
                getattr(self, "_w_dtypes", None),
                self._pod_axis,
                getattr(self, "_pod_plan", None), self.pod_stats,
                self._autodonate_on)
        if was_cold:
            # first step of a signature: write through immediately so the
            # `_seen_*` identity snapshots exist for the fast-path check
            self.flush()
        if health is not None:
            self._guardian.record_health(k, health[0], health[1])
        return True

    def _donation_groups(self, ws, ss, auxs):
        """(owner_name, pytree) pairs for every donated persistent buffer
        — the donation tracker's and the unrecoverable-failure error's
        naming source."""
        groups = list(zip(self._param_names, ws))
        groups += [(n + ".state", s) for n, s in zip(self._param_names, ss)]
        groups += list(zip(self._aux_names, auxs))
        return groups

    def _stage_inputs(self, data):
        """Place a batch's arrays onto the data sharding (dtype-cast
        host-side first — e.g. fp32 pipeline output to a bf16 model —
        which also halves the host->device bytes)."""
        import jax
        exec0 = self._exec0
        inputs = []
        for v, name in zip(data, self._input_names):
            raw = v._data if isinstance(v, NDArray) else _np.asarray(v)
            tgt = exec0.arg_dict[name]
            if hasattr(raw, "astype") and raw.dtype != tgt.dtype and \
                    name not in self._mod._exec_group.label_names:
                raw = raw.astype(tgt.dtype)
            if getattr(raw, "sharding", None) == self._data_sharding:
                inputs.append(raw)  # already placed; skip the dispatch
            else:
                inputs.append(jax.device_put(raw, self._data_sharding))
        return inputs

    def prestage(self, data_batch):
        """Start the (async) device placement of a FUTURE batch while the
        current step's program is still executing — the reference
        PrefetcherIter's H2D pipelining role (`src/io/iter_prefetcher.h`),
        driven from `Module.prepare` in the fit loop.  `_dispatch` adopts
        the in-flight transfer by batch identity."""
        if self.broken:
            return
        try:
            data = list(data_batch.data) + list(data_batch.label or [])
            if len(data) != len(self._input_names):
                return
            self._prestaged = (data_batch, self._stage_inputs(data))
        except Exception:
            self._prestaged = None

    def ring_placement(self):
        """This step's staging target for the h2d ring
        (`io_plane.RingPlacement`): the data sharding plus per-input
        target dtypes, exactly what `_stage_inputs` produces — so ring
        batches are adopted by sharding identity with no second
        transfer and no signature churn (zero steady-state
        recompiles)."""
        from .io_plane import RingPlacement
        return RingPlacement.for_fused_step(self)

    def set_block_cursor(self, j):
        """Point `get_outputs()` AND the in-graph metrics at logical
        step j of the last block — the fit loop calls this as it fires
        the batch-j callback burst, so each batch-end callback observes
        per-step state (outputs + metric totals), not block-final
        state."""
        self.block_cursor = j
        if self._block_view is not None:
            self._block_view.expose(j)

    def cached_programs(self):
        """The live CachedPrograms this step compiled (current signature
        plus every cached alternate) — the checkpoint ``programs/``
        payload's source."""
        progs = {}
        for p in (self._jit, getattr(self, "_scan_jit", None)):
            if p is not None and hasattr(p, "export_to"):
                progs[id(p)] = p
        for entry in getattr(self, "_core_cache", {}).values():
            for p in entry[1:3]:
                if p is not None and hasattr(p, "export_to"):
                    progs[id(p)] = p
        return list(progs.values())

    def export_programs(self, directory):
        """Serialize this step's compiled executables into `directory`
        as program-cache entries (checkpoint payload); returns count."""
        return sum(p.export_to(directory) for p in self.cached_programs())

    def compile_phase_stats(self):
        """Cold-start phase breakdown for the traced step: framework
        trace seconds, the traced jaxpr's (recursive) equation count —
        the graph-size number the XLA compile scales with, ONE layer
        body per scan-deduped run — and per-program lower/compile
        seconds from the unified cache (tools/warmup.py
        --measure-budgets reads this)."""
        core = getattr(self, "_core_closed", None)
        out = {
            "trace_s": getattr(core, "trace_s", None)
            if core is not None else None,
            "jaxpr_eqns": core.num_eqns() if core is not None else None,
            "scan_runs": list(getattr(self, "scan_runs", []) or []),
            "autodonate": bool(getattr(self, "_autodonate_on", False)),
            "programs": [],
        }
        for p in self.cached_programs():
            out["programs"].append({
                "label": getattr(p, "label", ""),
                "compiles": int(getattr(p, "compile_count", 0)),
                "disk_hits": int(getattr(p, "disk_hits", 0)),
                "lower_s": float(getattr(p, "lower_s_total", 0.0)),
                "compile_s": float(getattr(p, "compile_s_total", 0.0)),
            })
        return out

    def current_outputs(self):
        """Outputs of the batch `block_cursor` points at (per-batch view
        into the scan ys), or the plain last outputs, or None when the
        last step did not run fused."""
        ys = getattr(self, "_block_outs", None)
        if ys is not None:
            j = min(getattr(self, "block_cursor", self._block_len - 1),
                    self._block_len - 1)
            if j == self._block_len - 1:
                return self.last_outputs
            got = self._block_cache.get(j)
            if got is None:
                ctx0 = self._contexts[0]
                got = [NDArray(y[j], ctx=ctx0) for y in ys]
                self._block_cache[j] = got
            return got
        return self.last_outputs

    def clear_outputs(self):
        """Invalidate output views (an unfused forward/step supersedes)."""
        self.last_outputs = None
        self._block_outs = None

    def _reown_in_place(self, states):
        """The cold dispatch's (ws, ss, auxs), re-owned one leaf at a time,
        each copy put in its holder's place (the executors' arrays, the
        optimizer's states) so that the original is released before the
        next leaf is copied.  `reown_for_donation` of the whole carry holds
        two copies of every parameter, master and momentum at once; past
        REOWN_IN_PLACE_BYTES that second copy is what does not fit beside
        the program.  The holders end up on buffers of equal value that this
        process's XLA computations own, which is what they hold after any
        flush."""
        execs = self._mod._exec_group.execs

        def swap(dicts, name):
            old = dicts[0][name]._data
            new = reown_for_donation(old)
            for d in dicts:
                if d[name]._data is old:
                    d[name]._set_data(new)
            return new

        def swap_state(s):
            if isinstance(s, NDArray):
                s._set_data(reown_for_donation(s._data))
                return s._data
            if isinstance(s, (tuple, list)):
                return tuple(swap_state(x) for x in s)
            return s

        ws = [swap([e.arg_dict for e in execs], n)
              for n in self._param_names]
        ss = tuple(swap_state(s) for s in states)
        auxs = [swap([e.aux_dict for e in execs], n)
                for n in self._aux_names]
        if getattr(self, "_seen_ws", None) is not None:
            self._seen_ws, self._seen_aux = list(ws), list(auxs)
        return ws, ss, auxs

    def _owns_exec_buffers(self):
        """True while the exec dicts still hold the arrays WE last wrote
        (nobody repointed them externally since the last flush)."""
        seen = getattr(self, "_seen_ws", None)
        if seen is None:
            return True
        exec0 = self._exec0
        return all(exec0.arg_dict[n]._data is w
                   for n, w in zip(self._param_names, seen))

    def _derived_weights(self, new_ss):
        """Low-precision weights re-derived from the fp32 masters — only
        flush pays this (a tiny cast program), never the hot loop."""
        import jax
        if self._derive_fn is None:
            mp_pos, dts = self._mp_pos, self._w_dtypes

            def derive(ss):
                return tuple(
                    jax.tree_util.tree_leaves(s)[p].astype(dt)
                    for s, p, dt in zip(ss, mp_pos, dts))

            self._derive_fn = jax.jit(derive)
        return list(self._derive_fn(tuple(new_ss)))

    def flush(self):
        """Write the pending step results (deferred donated-carry arrays)
        into the public NDArrays: parameters, optimizer state, aux states.
        Steady-state training never needs this; any external reader —
        get_params, checkpointing, the unfused fallback, a forward() —
        must see current values, so Module routes through here first."""
        if getattr(self, "_flushed", True) or self._carry is None:
            return
        self._flushed = True
        new_ws, new_ss, new_aux = self._carry
        if self._derive_ws and not new_ws:
            new_ws = self._derived_weights(new_ss)
        groups = self._mod._exec_group
        for n, nw in zip(self._param_names, new_ws):
            for e in groups.execs:
                e.arg_dict[n]._set_data(nw)
        states = [self._updater.states[i] for i in self._indices]
        for s, ns in zip(states, new_ss):
            _state_write_back(s, ns)
        for n, na in zip(self._aux_names, new_aux):
            for e in groups.execs:
                e.aux_dict[n]._set_data(na)
        self._seen_ws = list(new_ws)
        self._seen_aux = list(new_aux)

    def _metric_sig(self):
        return getattr(self, "_metric_ids", None)


def metric_fns_changed(prev_ids, metric_fns):
    return prev_ids != [id(m) for _, m in metric_fns]


# ---------------------------------------------------------------------------
# FusedInference: the request path's per-signature program cache
# ---------------------------------------------------------------------------

class FusedInference:
    """Inference over a pinned parameter set as one XLA program per input
    signature — the request-path face of the per-signature caches the
    fused train steps keep.

    The whole Symbol compiles to ONE program (graph_eval_fn); parameters
    and aux states are device-resident constants of the call, so every
    dispatch ships only the request tensors.  `jax.jit`'s own cache keys
    on the input signature: a fixed set of shape buckets therefore costs
    exactly one compile each (paid at warmup), and every dispatch is
    noted with the recompile auditor under `audit_key` so
    ``MXNET_ANALYSIS=1`` can certify zero post-warmup compiles.

    Thread-safe for concurrent callers: dispatch state is per-call; the
    only mutation, `set_params`, swaps the whole param list atomically
    (in-flight calls finish against the snapshot they captured).
    """

    def __init__(self, symbol, ctx, data_names, audit_key=None):
        import jax
        from .symbol.symbol import graph_eval_fn
        self._symbol = symbol
        self._ctx = ctx
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        unknown = [n for n in data_names if n not in self._arg_names]
        if unknown:
            # silently filtering would misalign every later input list
            raise MXNetError(
                f"FusedInference: data names {unknown} are not arguments "
                f"of the symbol (has {self._arg_names})")
        self._data_names = list(data_names)
        # every non-data argument is a candidate parameter slot; slots the
        # param dict never fills (e.g. a loss head's label input, whose
        # shape follows the batch) become per-call inputs instead —
        # `extra_names` after set_params — fed zeros by the serving layer
        self._slot_names = [n for n in self._arg_names
                            if n not in self._data_names]
        self._input_names = list(self._data_names)
        self._scan_plan = _maybe_scan_plan(symbol)
        self.scan_runs = [] if self._scan_plan is None else \
            [(r["name"], r["length"]) for r in self._scan_plan["runs"]]
        self._gfn, _, _, self._n_rng = graph_eval_fn(
            symbol, False, scan=self._scan_plan)
        # (jit, extra_names, params, aux): ONE reference, swapped whole,
        # so a concurrent dispatch never pairs a rebuilt program with the
        # previous partition's param list (or new params with old aux)
        self._state = None
        self._graph_hash = None   # lazy symbol-JSON hash (disk-tier key)
        self._key = jax.random.PRNGKey(0)   # inference path draws nothing
        FusedInference._seq = getattr(FusedInference, "_seq", 0) + 1
        self.audit_key = audit_key or f"FusedInference#{FusedInference._seq}"

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    def set_params(self, arg_params, aux_params=None, aux_shapes=None):
        """Pin the parameter set: every argument `arg_params` covers and
        every aux state becomes a device-resident array, moved in ONE
        batched transfer.  Uncovered argument slots become per-call
        inputs (`extra_names` — their shapes may follow the batch); aux
        states absent from `aux_params` are zeros of ``aux_shapes[name]``
        (the `Executor._simple_bind` convention).  Atomic with respect to
        concurrent dispatches: in-flight calls finish against the
        (params, aux) snapshot they captured."""
        import jax
        aux_params = aux_params or {}
        aux_shapes = aux_shapes or {}
        param_names = [n for n in self._slot_names if n in arg_params]
        extra_names = [n for n in self._slot_names if n not in arg_params]

        def value(v):
            return v._data if isinstance(v, NDArray) else _np.asarray(v)

        plan = [value(arg_params[n]) for n in param_names]
        for n in self._aux_names:
            if n in aux_params:
                plan.append(value(aux_params[n]))
            elif n in aux_shapes:
                plan.append(_np.zeros(aux_shapes[n], _np.float32))
            else:
                raise MXNetError(
                    f"FusedInference: no value or shape for aux '{n}'")
        moved = jax.device_put(plan, self._ctx.jax_device)
        state = self._state
        if state is not None and state[1] == extra_names:
            jit = state[0]   # same partition: keep every compiled program
        else:
            jit = self._build(param_names, extra_names)
        self._state = (jit, extra_names,
                       moved[:len(param_names)], moved[len(param_names):])

    @property
    def extra_names(self):
        """Argument slots fed per-call (shapes may follow the batch)."""
        return self._state[1] if self._state is not None else []

    def _build(self, param_names, extra_names):
        from .compile import cached_jit, graph_hash_of_text
        gfn = self._gfn
        param_pos = {n: k for k, n in enumerate(param_names)}
        input_pos = {n: k for k, n in enumerate(self._input_names)}
        extra_pos = {n: k for k, n in enumerate(extra_names)}
        arg_names = self._arg_names

        def run(params, inputs, extras, aux, key):
            args = []
            for n in arg_names:
                if n in param_pos:
                    args.append(params[param_pos[n]])
                elif n in input_pos:
                    args.append(inputs[input_pos[n]])
                else:
                    args.append(extras[extra_pos[n]])
            outs, _ = gfn(tuple(args), tuple(aux), key)
            return outs

        # symbol JSON (not object identity) keys the disk tier: a fresh
        # process loading the same graph hits the serialized executables
        if self._graph_hash is None:
            self._graph_hash = graph_hash_of_text(self._symbol.tojson())
        return cached_jit(
            run,
            graph_key=("infer", self._graph_hash, tuple(param_names),
                       tuple(extra_names), tuple(self._input_names)),
            label=self.audit_key)

    def signature(self, inputs):
        """(shape, dtype) per data input — the recompile auditor's
        currency for this program."""
        return tuple((tuple(v.shape), str(v.dtype)) for v in inputs)

    def program_count(self):
        """Compiled programs so far (one per signature)."""
        return self._state[0]._cache_size() if self._state is not None \
            else 0

    def cached_programs(self):
        """The live CachedProgram behind the current partition."""
        state = self._state
        if state is not None and hasattr(state[0], "export_to"):
            return [state[0]]
        return []

    def export_programs(self, directory):
        """Serialize the compiled bucket programs into `directory` as
        program-cache entries (warmed-image / payload export)."""
        return sum(p.export_to(directory) for p in self.cached_programs())

    def register_warm(self, inputs):
        """Declare `inputs`' signature as an expected bucket BEFORE
        compiling it, so warmup compiles never read as shape churn."""
        from .analysis import recompile as _recompile
        _recompile.register(self.audit_key, self._input_names,
                            self.signature(inputs))

    def __call__(self, inputs, extras=()):
        """Run the program for `inputs` (raw arrays ordered like
        `data_names`; `extras` ordered like `extra_names`); returns the
        raw output arrays."""
        state = self._state
        if state is None:
            raise MXNetError("FusedInference: set_params before calling")
        jit, extra_names, params, aux = state
        if len(extras) != len(extra_names):
            # caller built extras against a partition a concurrent
            # set_params just replaced: fail clean (retryable), never
            # bind the wrong arrays
            raise MXNetError(
                "FusedInference: extras changed under a concurrent "
                "set_params; retry the request")
        from .analysis import recompile as _recompile
        _recompile.note(self.audit_key, self._input_names,
                        self.signature(inputs))
        return jit(params, list(inputs), list(extras), aux, self._key)
