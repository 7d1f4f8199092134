"""Control-flow operators: `_foreach`, `_while_loop`, `_cond`.

Reference: `src/operator/control_flow.cc:1255-1423` (+ subgraph plumbing in
`subgraph_op_common.cc`), where each op carries CachedOp subgraphs executed
by an interpreter loop on the engine.  Here the lowering is direct and
TPU-native: the subgraph (stored as symbol JSON in the op attrs, so graphs
save/load like any other) is evaluated through `graph_eval_fn` inside

* `_foreach`     -> `jax.lax.scan`   (slices scan on axis 0, states carry)
* `_while_loop`  -> a masked `lax.scan` over max_iterations (static shapes
                    are what the XLA compilation model wants; entries past
                    termination are zeros, the reference leaves them
                    undefined — `docs` of nd.contrib.while_loop).  With NO
                    per-step outputs (num_out_data == 0) and outside
                    training, a TRUE `lax.while_loop` runs instead: early
                    termination, cost scales with actual iterations
* `_cond`        -> `jax.lax.cond`

so a hybridized RNN becomes ONE scan in the compiled program instead of T
unrolled cell bodies, and gradients come from jax's scan/cond vjp instead
of the reference's per-op backward interpreter.

Input layout (built by `symbol/contrib.py`): tensor inputs are
[data..., states..., closure...] for `_foreach`, [vars..., closure...] for
`_while_loop`, [pred, closure...] for `_cond`; `arg_map` in the attrs maps
each subgraph argument NAME to its slot ("d0"/"s1"/"v0"/"c2"), so the
rebuilt-from-JSON subgraph binds by name, not by object identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register, REQUIRED
from ..base import MXNetError


def _json_str(v):
    """Keep subgraph attrs as canonical JSON strings: `py_literal` may have
    parsed a pure-literal JSON document into a dict on symbol reload."""
    if isinstance(v, str):
        return v
    import json
    return json.dumps(_delist(v))


def _delist(v):
    if isinstance(v, tuple):
        return [_delist(x) for x in v]
    if isinstance(v, dict):
        return {k: _delist(x) for k, x in v.items()}
    return v


@functools.lru_cache(maxsize=256)
def _subgraph(json_str):
    from ..symbol.symbol import load_json
    sym = load_json(json_str)
    if sym.list_auxiliary_states():
        raise MXNetError(
            "control-flow subgraphs with auxiliary states (BatchNorm "
            "running stats) are not supported; move the stateful layer "
            "outside the loop body")
    return sym


def _sub_eval(json_str, train):
    """(eval_fn, arg_names) for a stored subgraph."""
    from ..symbol.symbol import graph_eval_fn
    sym = _subgraph(json_str)
    gfn, _, _, _ = graph_eval_fn(sym, train)
    return gfn, sym.list_arguments()


def _binder(arg_names, arg_map):
    """Positions of each subgraph argument: (kind, index) per name.

    `arg_map` entries are emitted in the subgraph's topo order over
    variable nodes (`symbol/contrib.py _classify_args`) — the SAME order
    `list_arguments()` yields after the JSON round trip — so binding is
    POSITIONAL.  Binding through a name->tag dict would collapse two
    distinct outer Variables that share a name (legal in the symbol API,
    and common in nested foreach/while_loop bodies reusing inner names)
    onto one slot, silently computing with the wrong input."""
    entries = [(n, t) for n, t in arg_map]
    if len(entries) == len(arg_names) and \
            all(n == en for n, (en, _t) in zip(arg_names, entries)):
        return [(t[0], int(t[1:])) for _n, t in entries]
    # name order disagrees (a hand-edited graph JSON): fall back to
    # name-keyed binding, refusing ambiguity instead of mis-binding
    amap = {}
    for n, t in entries:
        if n in amap and amap[n] != t:
            raise MXNetError(
                f"control-flow subgraph has two inputs named {n!r} with "
                "different slots and a reordered arg_map; cannot bind "
                "unambiguously — give loop-body inputs unique names")
        amap[n] = t
    slots = []
    for n in arg_names:
        tag = amap.get(n)
        if tag is None:
            raise MXNetError(f"control-flow subgraph argument {n!r} has no "
                             "slot mapping (corrupt arg_map)")
        slots.append((tag[0], int(tag[1:])))
    return slots


_FOREACH_PARAMS = {
    "num_args": REQUIRED, "subgraph": REQUIRED, "arg_map": REQUIRED,
    "num_data": REQUIRED, "num_states": REQUIRED, "num_out_data": REQUIRED,
}


@register("_foreach", nin=-1, variadic_param="num_args",
          params=_FOREACH_PARAMS,
          param_types={"subgraph": _json_str},
          nout=lambda p: int(p["num_out_data"]) + int(p["num_states"]),
          needs_rng=True, mode_dependent=True)
def _foreach(params, *arrays):
    """reference control_flow.cc:1255 (ForeachState + ForeachComputeExCPU)
    lowered to one `lax.scan`.

    In training the body is re-materialised under the scanned layers' rule
    (`symbol._rematerialised`) where it holds an operator registered with
    `scan_remat` or names values `ops.registry.scan_kept` (a flash kernel's
    outputs): a step then keeps its inputs and those values, and its
    backward pass computes the rest again, so the stacked activations do
    not grow with the steps times the body's layers.  Any other body lowers
    as it always has.  A traced training program counts `scan.remat.kept` /
    `.kept_bytes` as a scanned run does, and `loop.steps`."""
    from .. import obs
    from ..symbol.symbol import _rematerialised, _count_kept
    train = bool(params.get("_train", False))
    gfn, arg_names = _sub_eval(params["subgraph"], train)
    slots = _binder(arg_names, params["arg_map"])
    nd_ = int(params["num_data"])
    ns = int(params["num_states"])
    n_out = int(params["num_out_data"])
    key = arrays[-1]
    arrays = arrays[:-1]
    data = tuple(arrays[:nd_])
    states = tuple(arrays[nd_:nd_ + ns])
    closure = tuple(arrays[nd_ + ns:])

    def step(cl, st, xs, sk):
        ins = tuple(xs[i] if k == "d" else st[i] if k == "s" else cl[i]
                    for k, i in slots)
        outs, _ = gfn(ins, (), sk)
        return tuple(outs[n_out:]), tuple(outs[:n_out])

    if train:
        remat = _rematerialised(step)
        length = int(data[0].shape[0]) if data else 0
        avals = [tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in group)
                 for group in (closure, states,
                               tuple(d[0] for d in data))]
        # the kept set follows the shapes alone, not the key's value
        fixed = jax.random.PRNGKey(0)
        kept = _count_kept(lambda cl, st, xs: remat(cl, st, xs, fixed),
                           length, *avals)
        if kept or any(n.op is not None and n.op.scan_remat
                       for n in _subgraph(params["subgraph"])._topo()):
            step = remat
        obs.counter("loop.steps").inc(length)

    def body(carry, xs):
        st, k = carry
        k, sk = jax.random.split(k)
        new_states, ys = step(closure, st, xs, sk)
        return (new_states, k), ys

    (fin_states, _), ys = jax.lax.scan(body, (states, key), data)
    return tuple(ys) + tuple(fin_states)


_WHILE_PARAMS = {
    "num_args": REQUIRED, "cond_subgraph": REQUIRED, "func_subgraph": REQUIRED,
    "cond_arg_map": REQUIRED, "func_arg_map": REQUIRED,
    "num_vars": REQUIRED, "num_out_data": REQUIRED,
    "max_iterations": REQUIRED,
}


@register("_while_loop", nin=-1, variadic_param="num_args",
          params=_WHILE_PARAMS,
          param_types={"cond_subgraph": _json_str,
                       "func_subgraph": _json_str},
          nout=lambda p: int(p["num_out_data"]) + int(p["num_vars"]),
          needs_rng=True, mode_dependent=True)
def _while_loop(params, *arrays):
    """reference control_flow.cc `_while_loop` as a masked scan: static
    max_iterations trip count (what the symbolic reference op also
    requires), with an `active` predicate freezing vars once the condition
    fails.  Outputs are padded to max_iterations; padding rows are zeros
    (reference: undefined)."""
    train = bool(params.get("_train", False))
    cfn, c_names = _sub_eval(params["cond_subgraph"], train)
    ffn, f_names = _sub_eval(params["func_subgraph"], train)
    c_slots = _binder(c_names, params["cond_arg_map"])
    f_slots = _binder(f_names, params["func_arg_map"])
    nv = int(params["num_vars"])
    n_out = int(params["num_out_data"])
    max_iter = int(params["max_iterations"])
    key = arrays[-1]
    arrays = arrays[:-1]
    vs = tuple(arrays[:nv])
    closure = tuple(arrays[nv:])

    def pick(slots, vals):
        return tuple(vals[i] if k == "v" else closure[i]
                     for k, i in slots)

    if n_out == 0 and not train:
        # fast path: no per-step outputs to pad means the result shape is
        # iteration-count independent, so a TRUE `lax.while_loop` applies —
        # cost scales with ACTUAL iterations, not max_iterations (the
        # masked scan below runs the full static trip count even when the
        # condition fails on step 1).  Inference only: while_loop has no
        # reverse-mode derivative, training keeps the differentiable scan.
        def w_cond(carry):
            vals, i, k = carry
            (c,), _ = cfn(pick(c_slots, vals), (),
                          jax.random.fold_in(k, 0))
            return jnp.logical_and(i < max_iter, jnp.squeeze(c) != 0)

        def w_body(carry):
            vals, i, k = carry
            k, fk = jax.random.split(k)
            outs, _ = ffn(pick(f_slots, vals), (), fk)
            return (tuple(outs), i + 1, k)

        fin_vals, _, _ = jax.lax.while_loop(
            w_cond, w_body, (vs, jnp.int32(0), key))
        return tuple(fin_vals)

    def body(carry, _):
        vals, active, k = carry
        k, ck, fk = jax.random.split(k, 3)
        (c,), _ = cfn(pick(c_slots, vals), (), ck)
        active = jnp.logical_and(active, jnp.squeeze(c) != 0)

        # func runs UNDER lax.cond, exactly like the reference stops
        # executing when cond fails — masking its outputs with where()
        # instead would both waste the iterations and poison gradients
        # when a terminated-range step computes inf/NaN (where's vjp
        # multiplies the NaN cotangent by zero -> NaN)
        def run(vs):
            outs, _ = ffn(pick(f_slots, vs), (), fk)
            return tuple(outs[:n_out]), tuple(outs[n_out:])

        out_shapes = jax.eval_shape(lambda vs: run(vs)[0], vals)

        def skip(vs):
            return tuple(jnp.zeros(s.shape, s.dtype)
                         for s in out_shapes), vs

        step_out, new_vals = jax.lax.cond(active, run, skip, vals)
        return (new_vals, active, k), step_out

    (fin_vals, _, _), ys = jax.lax.scan(
        body, (vs, jnp.bool_(True), key), None, length=max_iter)
    return tuple(ys) + tuple(fin_vals)


_COND_PARAMS = {
    "num_args": REQUIRED, "then_subgraph": REQUIRED, "else_subgraph": REQUIRED,
    "then_arg_map": REQUIRED, "else_arg_map": REQUIRED,
    "num_outputs": REQUIRED,
}


@register("_cond", nin=-1, variadic_param="num_args",
          params=_COND_PARAMS,
          param_types={"then_subgraph": _json_str,
                       "else_subgraph": _json_str},
          nout=lambda p: int(p["num_outputs"]),
          needs_rng=True, mode_dependent=True)
def _cond(params, *arrays):
    """reference control_flow.cc `_cond` lowered to `lax.cond`: one branch
    executes on device (the reference fetches pred to the host and runs a
    CachedOp; here the branch select stays in-program — no host sync)."""
    train = bool(params.get("_train", False))
    tfn, t_names = _sub_eval(params["then_subgraph"], train)
    efn, e_names = _sub_eval(params["else_subgraph"], train)
    t_slots = _binder(t_names, params["then_arg_map"])
    e_slots = _binder(e_names, params["else_arg_map"])
    key = arrays[-1]
    pred = arrays[0]
    closure = tuple(arrays[1:-1])

    def pick(slots):
        return tuple(closure[i] for _k, i in slots)

    def then_b(k):
        outs, _ = tfn(pick(t_slots), (), k)
        return tuple(outs)

    def else_b(k):
        outs, _ = efn(pick(e_slots), (), k)
        return tuple(outs)

    return jax.lax.cond(jnp.squeeze(pred) != 0, then_b, else_b, key)
