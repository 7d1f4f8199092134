"""Symbol: the symbolic graph API.

Re-expression of the reference's `nnvm::Symbol`/`Graph` + python surface
(`python/mxnet/symbol/symbol.py`).  A Symbol is a DAG of op nodes over
variable leaves; composition is pure bookkeeping (no compute).  Binding a
Symbol produces an `Executor` (`executor.py`) that compiles the whole graph
into ONE XLA computation — the TPU-native generalization of the reference's
GraphExecutor + bulk-exec segments (`src/executor/graph_executor.cc:1194-1316`:
where the reference fuses consecutive engine ops into segments, XLA compiles
the entire forward/backward as a single fused program).

Graph JSON (`tojson`/`load`) keeps the reference's schema — nodes with
{op, name, attrs, inputs}, arg_nodes, heads — so saved model structure is
interchangeable (`symbol.py:1192 save`, `src/nnvm/legacy_json_util.cc`).
"""
from __future__ import annotations

import contextlib
import json
import re
import threading

import numpy as _np

from ..base import MXNetError, py_literal
from ..ops import registry as _reg

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "check_unique_names"]


def check_unique_names(symbol):
    """Reject graphs whose VARIABLE names shadow each other (bind-time
    gate, called by the Executor).

    Two distinct nodes sharing a name where at least one is a variable
    break `arg_dict`: the dict collapses the duplicates and binding
    silently trains/feeds the wrong arrays.  Same-name OP pairs are
    tolerated — gluon's hybridize traces name every layer's op ``fwd``
    by design, and op identity is positional — the `mxlint`
    duplicate-name warning covers them.  Empty names always raise."""
    seen = {}
    for node in symbol._topo():
        if not str(node.name).strip():
            kind = "variable" if node.is_variable else f"op {node.op.name}"
            raise MXNetError(f"invalid graph: {kind} node has an empty "
                             "name")
        first = seen.get(node.name)
        if first is None:
            seen[node.name] = node
        elif node.is_variable or first.is_variable:
            raise MXNetError(
                f"invalid graph: two distinct nodes share the name "
                f"'{node.name}' "
                f"({'variable' if first.is_variable else first.op.name} vs "
                f"{'variable' if node.is_variable else node.op.name}); "
                "duplicate names silently shadow each other in "
                "arg_dict/tojson — rename one (mxlint: duplicate-name)")


class _NameManager:
    _tls = threading.local()

    @classmethod
    def next_name(cls, hint):
        if not hasattr(cls._tls, "counts"):
            cls._tls.counts = {}
        c = cls._tls.counts.get(hint, 0)
        cls._tls.counts[hint] = c + 1
        return f"{hint}{c}"

    @classmethod
    @contextlib.contextmanager
    def fresh(cls):
        """Compose under counts that start at zero and put the thread's
        own back on exit: a graph built inside has the same auto names
        whatever the process composed before."""
        saved = getattr(cls._tls, "counts", {})
        cls._tls.counts = {}
        try:
            yield
        finally:
            cls._tls.counts = saved


class _Node:
    """One graph node: an op application or a variable leaf."""

    __slots__ = ("op", "name", "attrs", "inputs", "_extra_attrs")

    def __init__(self, op, name, attrs, inputs):
        self.op = op              # OpDef or None for variables
        self.name = name
        self.attrs = attrs        # canonicalized op params
        self.inputs = inputs      # list[(Node, int out_index)]
        self._extra_attrs = {}    # user attrs (__shape__, lr_mult, ctx_group...)

    @property
    def is_variable(self):
        return self.op is None

    def num_outputs(self):
        if self.op is None:
            return 1
        return self.op.num_outputs(self.attrs)


class Symbol:
    """An output list over a graph (reference `Symbol`)."""

    __slots__ = ("_entries",)

    def __init__(self, entries):
        self._entries = list(entries)  # list[(Node, out_index)]

    # -- basic info ----------------------------------------------------------
    @property
    def name(self):
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    def __repr__(self):
        names = [n.name for n, _ in self._entries]
        return f"<Symbol {' '.join(names)}>"

    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        for i in range(len(self._entries)):
            yield self[i]

    def __getitem__(self, index):
        if isinstance(index, str):
            outs = self.list_outputs()
            if index in outs:
                return Symbol([self._entries[outs.index(index)]])
            raise MXNetError(f"Cannot find output {index}")
        if isinstance(index, slice):
            return Symbol(self._entries[index])
        return Symbol([self._entries[index]])

    def __copy__(self):
        return Symbol(self._entries)

    def __deepcopy__(self, memo):
        # graph nodes are immutable-by-convention; sharing is safe
        return Symbol(self._entries)

    # -- graph walks ---------------------------------------------------------
    def _topo(self):
        """Post-order topological node list (deterministic, DFS input order —
        matches the reference's DFSVisit ordering used for argument lists)."""
        seen = {}
        order = []

        def visit(node):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for src, _ in node.inputs:
                visit(src)
            order.append(node)

        for node, _ in self._entries:
            visit(node)
        return order

    def _aux_node_ids(self):
        """Variable nodes feeding aux-state slots (BatchNorm running stats...)."""
        aux = set()
        for node in self._topo():
            if node.is_variable or not node.op:
                continue
            naux = node.op.num_aux(node.attrs)
            if naux:
                for src, _ in node.inputs[-naux:]:
                    if src.is_variable:
                        aux.add(id(src))
        return aux

    def list_arguments(self):
        """Reference `symbol.py list_arguments` (excludes aux states)."""
        aux = self._aux_node_ids()
        return [n.name for n in self._topo()
                if n.is_variable and id(n) not in aux]

    def list_auxiliary_states(self):
        aux = self._aux_node_ids()
        return [n.name for n in self._topo()
                if n.is_variable and id(n) in aux]

    def list_inputs(self):
        return [n.name for n in self._topo() if n.is_variable]

    def list_outputs(self):
        out = []
        for node, idx in self._entries:
            if node.num_outputs() > 1:
                out.append(f"{node.name}_output{idx}")
            else:
                out.append(f"{node.name}_output")
        return out

    def get_internals(self):
        """All intermediate outputs as a grouped Symbol (reference
        `symbol.py get_internals`)."""
        entries = []
        for node in self._topo():
            for i in range(node.num_outputs()):
                entries.append((node, i))
        return Symbol(entries)

    def get_children(self):
        kids = []
        for node, _ in self._entries:
            kids.extend(node.inputs)
        return Symbol(kids) if kids else None

    # -- attributes ----------------------------------------------------------
    def attr(self, key):
        if len(self._entries) == 1:
            return self._entries[0][0]._extra_attrs.get(key)
        return None

    def _set_attr(self, **kwargs):
        for node, _ in self._entries:
            node._extra_attrs.update(kwargs)

    def attr_dict(self):
        out = {}
        for node in self._topo():
            d = {}
            d.update({k: str(v) for k, v in node._extra_attrs.items()})
            if node.op is not None:
                d.update({k: str(v) for k, v in node.attrs.items()})
            if d:
                out[node.name] = d
        return out

    # -- shape/type inference -------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        import jax
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        shapes = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    shapes[n] = s
        shapes.update({k: v for k, v in kwargs.items() if v is not None})
        avals, out_avals, aux_avals = _infer_graph(self, shapes, partial)
        if avals is None:
            return None, None, None
        arg_shapes = [avals.get(n) for n in arg_names]
        aux_shapes = [avals.get(n) for n in aux_names]
        return (arg_shapes, out_avals, aux_shapes)

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        dtypes = {}
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    dtypes[n] = t
        dtypes.update(kwargs)
        # types ride the same aval inference as shapes
        shapes_known = {}
        try:
            inferred = _infer_graph_types(self, dtypes)
        except Exception:
            return None, None, None
        arg_types = [inferred.get(n, _np.float32) for n in arg_names]
        aux_types = [inferred.get(n, _np.float32)
                     for n in self.list_auxiliary_states()]
        out_types = [_np.float32] * len(self._entries)
        return arg_types, out_types, aux_types

    # -- binding / eval -------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        """Allocate argument/grad/aux arrays from inferred shapes and return an
        Executor (reference `symbol.py:1290 simple_bind` →
        `graph_executor.cc:1575`)."""
        from ..executor import Executor
        from ..context import current_context
        import os
        ctx = ctx or current_context()
        sym = self
        backend = os.environ.get("MXNET_SUBGRAPH_BACKEND")
        if backend:
            # reference build_subgraph.cc: env-selected backend partitions
            # the graph at bind time
            from ..subgraph import partition_graph
            sym = partition_graph(self, backend)
        return Executor._simple_bind(sym, ctx, grad_req, type_dict, kwargs,
                                     group2ctx=group2ctx)

    def bind(self, ctx, args, args_grad=None, grad_req="write", aux_states=None,
             group2ctx=None, shared_exec=None):
        """Bind with caller-provided buffers (reference `symbol.py:1554 bind`)."""
        from ..executor import Executor
        return Executor._bind(self, ctx, args, args_grad, grad_req, aux_states)

    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx, kwargs)
        return ex.forward()

    def __call__(self, *args, **kwargs):
        """Composition: replace variable leaves with other symbols
        (reference Symbol.__call__/_compose)."""
        s = self.__copy__()
        s._compose(*args, **kwargs)
        return s

    def _compose(self, *args, **kwargs):
        name = kwargs.pop("name", None)
        if args and kwargs:
            raise MXNetError("compose only accepts input Symbols "
                             "either as positional or keyword arguments, not both")
        mapping = {}
        if args:
            free_vars = [n for n in self._topo() if n.is_variable]
            if len(args) > len(free_vars):
                raise MXNetError("too many positional inputs to compose")
            for node, sym in zip(free_vars, args):
                mapping[id(node)] = sym._entries[0]
        for k, v in kwargs.items():
            for node in self._topo():
                if node.is_variable and node.name == k:
                    mapping[id(node)] = v._entries[0]
        if not mapping:
            return
        remap = {}

        def rebuild(node):
            if id(node) in remap:
                return remap[id(node)]
            if id(node) in mapping:
                src, idx = mapping[id(node)]
                remap[id(node)] = src
                return src
            if node.is_variable:
                remap[id(node)] = node
                return node
            new_inputs = []
            for src, idx in node.inputs:
                ns = rebuild(src)
                new_inputs.append((ns, idx))
            nn = _Node(node.op, node.name, node.attrs, new_inputs)
            nn._extra_attrs = dict(node._extra_attrs)
            remap[id(node)] = nn
            return nn

        self._entries = [(rebuild(n), i) for n, i in self._entries]

    # -- gradient ------------------------------------------------------------
    def grad(self, wrt):
        raise MXNetError("Symbol.grad: bind with grad_req and use "
                         "Executor.backward (as the reference recommends)")

    # -- serialization ---------------------------------------------------------
    def tojson(self):
        nodes = self._topo()
        nid = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append({
                "op": "null" if n.is_variable else n.op.name,
                "name": n.name,
                "attrs": {k: str(v) for k, v in
                          (n.attrs.items() if n.op else
                           n._extra_attrs.items())},
                "inputs": [[nid[id(src)], idx, 0] for src, idx in n.inputs],
            })
        arg_nodes = [i for i, n in enumerate(nodes) if n.is_variable]
        heads = [[nid[id(n)], i, 0] for n, i in self._entries]
        return json.dumps({"nodes": jnodes, "arg_nodes": arg_nodes,
                           "node_row_ptr": list(range(len(nodes) + 1)),
                           "heads": heads,
                           "attrs": {"mxnet_version": ["int", 10200],
                                     "framework": ["str", "incubator_mxnet_tpu"]}},
                          indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # -- operator overloads ----------------------------------------------------
    def __add__(self, other):
        return _sym_binary(self, other, "broadcast_add", "_plus_scalar")

    def __radd__(self, other):
        return self.__add__(other)

    def __sub__(self, other):
        return _sym_binary(self, other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return _sym_binary(self, other, None, "_rminus_scalar")

    def __mul__(self, other):
        return _sym_binary(self, other, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self.__mul__(other)

    def __truediv__(self, other):
        return _sym_binary(self, other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return _sym_binary(self, other, None, "_rdiv_scalar")

    def __pow__(self, other):
        return _sym_binary(self, other, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return _sym_apply("negative", [self], {})

    def __eq__(self, other):
        return _sym_binary(self, other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        return _sym_binary(self, other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return _sym_binary(self, other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return _sym_binary(self, other, "broadcast_greater_equal",
                           "_greater_equal_scalar")

    def __lt__(self, other):
        return _sym_binary(self, other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _sym_binary(self, other, "broadcast_lesser_equal",
                           "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)


# ---------------------------------------------------------------------------

_WALK_CAP = 2000  # composition-time name-check budget (see below)


def _reject_name_collision(names, entries, op_name):
    """Composition-time duplicate rejection for EXPLICITLY named ops: the
    new node's name and its to-be-auto-created parameter variable names
    must not collide with a VARIABLE already in the input graphs —
    `arg_dict` would collapse the duplicates and bind would train/feed
    the wrong arrays.  Same-name OP pairs stay legal (gluon names every
    layer's traced op ``fwd``; op identity is positional) and are left
    to the mxlint duplicate-name warning.  Auto-generated names are
    collision-free per thread (_NameManager counters), so only explicit
    names pay this walk — and the walk is CAPPED: past _WALK_CAP visited
    nodes (big unrolled graphs, where per-op walks go quadratic) the
    early build-time error is ceded to the O(n) bind-time gate
    `check_unique_names`, which enforces the same invariant."""
    seen = set()
    stack = [n for n, _ in entries]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        if len(seen) >= _WALK_CAP:
            return
        seen.add(id(node))
        if node.is_variable and node.name in names:
            raise MXNetError(
                f"cannot create op ({op_name}) named "
                f"'{sorted(names, key=len)[0]}': it would carry the name "
                f"'{node.name}', which already names a variable in the "
                "input graph; duplicate node names silently shadow each "
                "other in arg_dict/tojson — pick a unique name")
        stack.extend(src for src, _ in node.inputs)


def _sym_apply(op_name, inputs, kwargs):
    op = _reg.get(op_name)
    name = kwargs.pop("name", None)
    attr = kwargs.pop("attr", None)
    if name is not None and not str(name).strip():
        raise MXNetError(f"Operator {op_name}: node name must be a "
                         "non-empty string")
    explicit_name = name is not None
    if op.variadic_param and op.variadic_param not in kwargs:
        kwargs[op.variadic_param] = len(inputs)
    params = op.canonicalize_params(kwargs)
    params.pop("ctx", None)
    if name is None:
        hint = re.sub("^_", "", op.name.lower())
        name = _NameManager.next_name(hint + "_" if not hint.endswith("_") else hint)
    entries = []
    for s in inputs:
        if not isinstance(s, Symbol):
            raise TypeError(f"Operator {op_name}: inputs must be Symbol, got "
                            f"{type(s).__name__}")
        if len(s._entries) != 1:
            raise MXNetError("cannot use a multi-output Symbol as an op input; "
                             "select one output first")
        entries.append(s._entries[0])
    # auto-create variables for missing trailing inputs (weights, biases, aux
    # states) — the reference does this in Symbol composition, producing the
    # canonical `{name}_weight` / `{name}_moving_mean` argument names
    from ..attribute import current_attrs
    scope_attrs = current_attrs()
    slot_names = op.list_input_names(params)
    if explicit_name:
        missing = slot_names[len(entries):] if slot_names is not None else []
        _reject_name_collision(
            {name} | {f"{name}_{slot}" for slot in missing}, entries,
            op.name)
    if slot_names is not None and len(entries) < len(slot_names):
        for slot in slot_names[len(entries):]:
            vnode = _Node(None, f"{name}_{slot}", {}, [])
            # auto-created parameters inherit the scope (ctx_group,
            # lr_mult, ...) like explicitly declared Variables do
            vnode._extra_attrs.update(scope_attrs)
            entries.append((vnode, 0))
    node = _Node(op, name, params, entries)
    if scope_attrs:
        node._extra_attrs.update(scope_attrs)
    if attr:
        node._extra_attrs.update(attr)
    nout = node.num_outputs()
    return Symbol([(node, i) for i in range(nout)]) if nout > 1 \
        else Symbol([(node, 0)])


def _sym_binary(lhs, rhs, tensor_op, scalar_op):
    if isinstance(rhs, Symbol):
        if tensor_op is None:
            raise TypeError("unsupported operand")
        return _sym_apply(tensor_op, [lhs, rhs], {})
    if isinstance(rhs, (int, float, bool)):
        return _sym_apply(scalar_op, [lhs], {"scalar": float(rhs)})
    return NotImplemented


def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs):
    """Create a symbolic variable (reference `symbol.py Variable`)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    if not name.strip():
        raise MXNetError("variable name must be a non-empty string "
                         "(empty names cannot be addressed in arg_dict "
                         "or saved JSON)")
    node = _Node(None, name, {}, [])
    from ..attribute import current_attrs
    node._extra_attrs.update(current_attrs())
    if shape is not None:
        node._extra_attrs["__shape__"] = tuple(shape)
    if dtype is not None:
        node._extra_attrs["__dtype__"] = dtype
    if lr_mult is not None:
        node._extra_attrs["__lr_mult__"] = lr_mult
    if wd_mult is not None:
        node._extra_attrs["__wd_mult__"] = wd_mult
    if init is not None:
        node._extra_attrs["__init__"] = init
    if attr:
        node._extra_attrs.update(attr)
    node._extra_attrs.update(kwargs)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols):
    """Group symbols into one multi-output Symbol (reference `symbol.py Group`)."""
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


def load_json(json_str):
    """Rebuild a Symbol from graph JSON (reference `symbol.py:2566 load`,
    versioned loader `src/nnvm/legacy_json_util.cc:197-222`)."""
    from ..compat.legacy_json import upgrade_json
    g = upgrade_json(json_str)
    nodes = []
    for jn in g["nodes"]:
        attrs = {k: v for k, v in jn.get("attrs", jn.get("param", {})).items()}
        if jn["op"] == "null":
            node = _Node(None, jn["name"], {}, [])
            if "__shape__" in attrs:
                # JSON carries attrs as strings; every reader of a
                # variable's shape (inference, the graph passes) takes a
                # tuple, as `Variable(shape=...)` stores it
                attrs["__shape__"] = tuple(py_literal(attrs["__shape__"]))
            node._extra_attrs.update(attrs)
        else:
            op = _reg.get(jn["op"])
            params = op.canonicalize_params(attrs)
            params.pop("ctx", None)
            node = _Node(op, jn["name"],
                         params,
                         [(nodes[i], oi) for i, oi, *_ in jn["inputs"]])
        nodes.append(node)
    heads = g.get("heads")
    if heads:
        entries = [(nodes[i], oi) for i, oi, *_ in heads]
    else:
        entries = [(nodes[-1], 0)]
    return Symbol(entries)


# ---------------------------------------------------------------------------
# Graph-level inference helpers shared with the executor
# ---------------------------------------------------------------------------

def _rematerialised(body):
    """A scanned layer `body` whose backward pass computes its activations
    again, all but the values an operator's forward rule has named
    `ops.registry.scan_kept`.  A body that names nothing is under a bare
    `jax.checkpoint`."""
    import jax
    from ..ops.registry import SCAN_KEPT
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(SCAN_KEPT))


def _count_kept(body, length, *avals):
    """Counters of what `_rematerialised(body)` keeps over a run of `length`
    layers, from the avals, a traced run: `scan.remat.kept` named values a
    layer and `scan.remat.kept_bytes`, their bytes times the layers.  Read
    as `jax.ad_checkpoint.print_saved_residuals` reads them: the residuals
    of the linearized body that a `name` equation of our name made."""
    import jax
    from .. import obs
    from ..ops.registry import SCAN_KEPT
    closed, (_, pullback) = jax.make_jaxpr(
        lambda *xs: jax.linearize(body, *xs), return_shape=True)(*avals)
    eqns = closed.jaxpr.eqns
    # a residual that the known part reads too comes out of a
    # `reduce_precision` to its own precision, which `jax.checkpoint` puts
    # behind whatever made it
    made = {id(eqn.outvars[0]): eqn.invars[0] for eqn in eqns
            if eqn.primitive.name == "reduce_precision"}
    residuals = {id(made.get(id(v), v)) for v in closed.jaxpr.outvars[
        -len(jax.tree_util.tree_leaves(pullback)):]}
    kept = [eqn.outvars[0].aval for eqn in eqns
            if eqn.primitive.name == "name"
            and eqn.params["name"] == SCAN_KEPT
            and residuals & {id(v) for v in eqn.invars + eqn.outvars}]
    obs.counter("scan.remat.kept").inc(len(kept))
    obs.counter("scan.remat.kept_bytes").inc(
        length * sum(a.size * a.dtype.itemsize for a in kept))


def graph_eval_fn(symbol, is_train, n_rng_hint=None, scan=None):
    """Build a pure function (args_dict_values, aux_values, key) -> (outputs,
    new_aux) executing the graph.  This function is what the executor jits:
    the entire Symbol becomes ONE XLA computation.

    `scan` is an optional scan-over-layers plan
    (`analysis.graph_passes.scan_plan(symbol)`): each planned run of
    structurally identical layer blocks is emitted as ONE `lax.scan`
    body over per-layer parameters stacked INSIDE the traced program, so
    XLA compiles the layer body once instead of N inlined copies while
    arguments, aux states and checkpoints keep their per-layer layout.
    A run whose per-layer shapes turn out unequal at trace time (or
    whose carry changes shape) silently falls back to the inlined path —
    the plan is structural, shapes are only known here.  A scanned body
    that holds an operator registered with `scan_remat` recomputes its
    activations in the backward pass instead of stacking them, but for
    the values its operators name `ops.registry.scan_kept` (a kernel's
    outputs that a written backward pass reads): those are stacked, and
    the kernel runs once a layer.  `mx.obs` counters `scan.remat.kept`
    and `scan.remat.kept_bytes` say, a traced training run, how many
    values a layer keeps and their bytes over the run's layers."""
    import jax
    import jax.numpy as jnp

    from ..ops import layout as _layout

    topo = symbol._topo()
    aux_ids = symbol._aux_node_ids()
    arg_nodes = [n for n in topo if n.is_variable and id(n) not in aux_ids]
    aux_nodes = [n for n in topo if n.is_variable and id(n) in aux_ids]
    rng_nodes = [n for n in topo if (not n.is_variable) and n.op.needs_rng]
    rng_index = {id(n): i for i, n in enumerate(rng_nodes)}
    use_nhwc = _layout.enabled()
    scan_first = {}
    if scan:
        for run in scan.get("runs", ()):
            scan_first[id(run["segments"][0][0])] = run

    def fn(arg_values, aux_values, key):
        env = {}
        for node, v in zip(arg_nodes, arg_values):
            env[id(node)] = (v,)
        aux_env = {}
        for node, v in zip(aux_nodes, aux_values):
            env[id(node)] = (v,)
            aux_env[id(node)] = v
        keys = jax.random.split(key, max(len(rng_nodes), 1))
        new_aux = dict(aux_env)
        # internal execution-layout pass (ops/layout.py): spatial ops run
        # NHWC (MXU-friendly), elementwise ops flow the tag through, every
        # other consumer and the graph heads see the API's NCHW — the
        # reference's cuDNN/MKLDNN layout selection done at graph level
        tags = {}

        def eval_node(node, e_env, e_tags, e_aux, key_for):
            params = dict(node.attrs)
            if node.op.mode_dependent:
                params["_train"] = bool(is_train)
            ins = [e_env[id(src)][idx] for src, idx in node.inputs]
            op_fn = node.op.fn
            out_tag = None
            if use_nhwc:
                in_tags = [e_tags.get((id(src), idx))
                           for src, idx in node.inputs]
                nat = _layout.NATIVE.get(node.op.name)
                if nat is not None and nat[1](node.op.name, params, ins[0]):
                    if in_tags[0] != "NHWC":
                        ins[0] = _layout.to_nhwc(ins[0])
                    # non-spatial slots (weights, vectors) must arrive in
                    # their API layout — untag any computed NHWC feed
                    ins[1:] = [_layout.to_nchw(v) if t == "NHWC" else v
                               for v, t in zip(ins[1:], in_tags[1:])]
                    op_fn = nat[0]
                    out_tag = "native"   # spatial output 0 only
                elif node.op.name in _layout.AGNOSTIC and \
                        any(t == "NHWC" for t in in_tags) and \
                        all(_layout.layout_safe_input(v, t)
                            for v, t in zip(ins, in_tags)):
                    out_tag = "all"
                else:
                    ins = [_layout.to_nchw(v) if t == "NHWC" else v
                           for v, t in zip(ins, in_tags)]
            if node.op.dynamic_params:
                for pname in node.op.dynamic_params:
                    ins.append(jnp.asarray(params.pop(pname), dtype="float32"))
            if node.op.needs_rng:
                ins.append(key_for(node))
            # the MXNet operator kind and node name reach the compiled
            # program as `op_name` metadata, on this op's forward ops and
            # on their transposes in a backward pass (compile.op_scopes)
            with jax.named_scope(f"{node.op.name}:{node.name}"):
                out = op_fn(params, *ins)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            nout = node.op.num_outputs(params)
            naux = node.op.num_aux(params)
            if naux and len(out) > nout:
                # write back aux updates
                for (src, _), upd in zip(node.inputs[-naux:], out[nout:]):
                    if id(src) in e_aux:
                        e_aux[id(src)] = upd
            e_env[id(node)] = tuple(out[:nout])
            if out_tag == "native":
                e_tags[(id(node), 0)] = "NHWC"
            elif out_tag == "all":
                for oi in range(nout):
                    e_tags[(id(node), oi)] = "NHWC"

        def main_key(node):
            return keys[rng_index[id(node)]]

        def try_scan_run(run):
            """Emit one planned run as lax.scan; False -> inline it."""
            length = run["length"]
            carry_src, carry_idx = run["carry"]
            c0 = env[id(carry_src)][carry_idx]
            if tags.get((id(carry_src), carry_idx)) == "NHWC":
                # scan carries cross in API layout (a lossless transpose
                # pair against the inlined path's flowing tag)
                c0 = _layout.to_nchw(c0)
            stacks, aux_stacks, key_stacks = [], [], []
            for slot_nodes in run["params"]:
                vals = [env[id(v)][0] for v in slot_nodes]
                s0 = (vals[0].shape, vals[0].dtype)
                if any((v.shape, v.dtype) != s0 for v in vals[1:]):
                    return False
                stacks.append(jnp.stack(vals))
            for slot_nodes in run["aux"]:
                vals = [new_aux[id(v)] for v in slot_nodes]
                s0 = (vals[0].shape, vals[0].dtype)
                if any((v.shape, v.dtype) != s0 for v in vals[1:]):
                    return False
                aux_stacks.append(jnp.stack(vals))
            for slot_nodes in run["rng"]:
                key_stacks.append(jnp.stack(
                    [keys[rng_index[id(n)]] for n in slot_nodes]))
            template = run["segments"][0]
            t_param = {id(v): s
                       for s, slots in enumerate(run["params"])
                       for v in (slots[0],)}
            t_aux_vars = [slots[0] for slots in run["aux"]]
            t_rng = {id(n): s for s, slots in enumerate(run["rng"])
                     for n in (slots[0],)}
            boundary0 = template[-1]

            def body(c, xs):
                pvals, avals, kvals = xs
                benv = {id(carry_src):
                        tuple(c if i == carry_idx else None
                              for i in range(carry_idx + 1))}
                for v, s in t_param.items():
                    benv[v] = (pvals[s],)
                baux = {}
                for v, a in zip(t_aux_vars, avals):
                    benv[id(v)] = (a,)
                    baux[id(v)] = a
                btags = {}
                for n in template:
                    eval_node(n, benv, btags, baux,
                              lambda m: kvals[t_rng[id(m)]])
                c_out = benv[id(boundary0)][0]
                if btags.get((id(boundary0), 0)) == "NHWC":
                    c_out = _layout.to_nchw(c_out)
                return c_out, tuple(baux[id(v)] for v in t_aux_vars)

            xs = (tuple(stacks), tuple(aux_stacks), tuple(key_stacks))
            xs0 = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), xs)
            try:
                c_aval = jax.eval_shape(lambda c, x: body(c, x)[0], c0, xs0)
            except Exception:
                return False
            if tuple(c_aval.shape) != tuple(c0.shape) or \
                    c_aval.dtype != c0.dtype:
                return False   # shape-changing block: scan carry invalid
            if any(n.op.scan_remat for n in template):
                # an operator of this layer keeps far more for its backward
                # pass than it takes in (`OpDef.scan_remat`): stacked over
                # the layers that would not fit, so the backward pass
                # computes the layer's activations again from its carry,
                # but for what the layer's operators name as kept
                body = _rematerialised(body)
                if is_train:
                    _count_kept(body, length, c_aval, xs0)
            carry_out, ys = jax.lax.scan(body, c0, xs)
            env[id(run["boundary"])] = (carry_out,)
            for slot, layer_nodes in enumerate(run["aux"]):
                for li, v in enumerate(layer_nodes):
                    if id(v) in new_aux:
                        new_aux[id(v)] = ys[slot][li]
            skip.update(run["covered"])
            return True

        skip = set()
        for node in topo:
            if node.is_variable or id(node) in skip:
                continue
            run = scan_first.get(id(node))
            if run is not None and try_scan_run(run):
                continue
            eval_node(node, env, tags, new_aux, main_key)
        outputs = tuple(
            _layout.to_nchw(env[id(node)][idx])
            if tags.get((id(node), idx)) == "NHWC" else env[id(node)][idx]
            for node, idx in symbol._entries)
        aux_out = tuple(new_aux[id(n)] for n in aux_nodes)
        return outputs, aux_out

    return fn, arg_nodes, aux_nodes, len(rng_nodes)


def _infer_graph(symbol, shapes, partial):
    """Shape inference by abstract evaluation (replaces the InferShape
    fixpoint, `src/executor/infer_graph_attr_pass.cc:73`).

    Layout-marked variables with a 0 batch dim (RNN begin states) need the
    data batch size.  When a *bound input variable* carries an explicit
    ``__layout__`` attr ('NT'/'TN'/'NTC'/'TNC'), its N position identifies
    the batch dim authoritatively — that hint is tried first.  Only
    layout-less graphs fall back to probing each leading dim of the first
    bound shape and keeping the first that infers cleanly (which can guess
    wrong when batch == time; hence the layout preference).
    """
    hints = []
    for n in symbol._topo():
        if n.is_variable and n.name in shapes:
            layout = n._extra_attrs.get("__layout__")
            bound = tuple(shapes[n.name] or ())
            if layout:
                bpos = str(layout).find("N")
                if 0 <= bpos < len(bound) and bound[bpos] > 0:
                    hints.append(bound[bpos])
    first = next((tuple(v) for v in shapes.values()
                  if v and tuple(v) and tuple(v)[0] > 0), None)
    if first:
        hints += [d for d in first[:2] if d > 0]
    hints = list(dict.fromkeys(hints)) or [None]
    last_err = None
    for hint in hints:
        try:
            return _infer_graph_with_hint(symbol, shapes, partial, hint)
        except MXNetError as e:
            last_err = e
    raise last_err


def _infer_graph_with_hint(symbol, shapes, partial, batch_hint):
    import jax

    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    topo = symbol._topo()

    # seed known shapes: explicit kwargs beat __shape__ attrs
    known = {}
    for n in topo:
        if n.is_variable:
            cand = None
            if n.name in shapes:
                cand = tuple(shapes[n.name])
            elif "__shape__" in n._extra_attrs:
                cand = tuple(n._extra_attrs["__shape__"])
                layout = n._extra_attrs.get("__layout__")
                if cand and batch_hint is not None and layout:
                    bpos = str(layout).find("N")
                    if 0 <= bpos < len(cand) and cand[bpos] == 0:
                        cand = tuple(batch_hint if i == bpos else d
                                     for i, d in enumerate(cand))
            # shapes containing 0 are "unknown dims" (deferred init) — solve
            if cand is not None and all(d > 0 for d in cand):
                known[n.name] = cand

    # forward abstract interpretation with on-demand variable shape solving:
    # variables without shapes get inferred where unambiguous (weight shapes
    # from FullyConnected/Convolution attrs, like the reference's backward
    # shape inference); otherwise inference fails unless partial.
    env = {}
    missing = []

    def aval(shape, dtype=_np.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    for node in topo:
        if node.is_variable:
            if node.name in known:
                env[id(node)] = (aval(known[node.name]),)
            else:
                env[id(node)] = None
                missing.append(node)
            continue
        ins = []
        unknown = False
        for src, idx in node.inputs:
            e = env[id(src)]
            if e is None:
                unknown = True
                break
            ins.append(e[idx])
        if unknown:
            solved = _solve_param_shapes(node, env)
            if solved:
                ins = []
                for src, idx in node.inputs:
                    e = env[id(src)]
                    ins.append(e[idx])
                unknown = False
            elif partial:
                env[id(node)] = None
                continue
            else:
                bad = [src.name for src, _ in node.inputs if env[id(src)] is None]
                raise MXNetError(
                    f"infer_shape: cannot determine shape of {bad} for op "
                    f"{node.name}; provide them (reference InferShape errors "
                    f"the same way)")
        params = dict(node.attrs)
        if node.op.mode_dependent:
            params["_train"] = False
        if node.op.dynamic_params:
            for pname in node.op.dynamic_params:
                ins.append(aval((), _np.float32))
                params.pop(pname)
        if node.op.needs_rng:
            ins.append(jax.ShapeDtypeStruct((2,), _np.uint32))
        try:
            out = jax.eval_shape(lambda *xs: node.op.fn(params, *xs), *ins)
        except Exception as e:
            raise MXNetError(f"infer_shape failed at {node.op.name} "
                             f"'{node.name}': {e}") from e
        if not isinstance(out, (tuple, list)):
            out = (out,)
        env[id(node)] = tuple(out[:node.op.num_outputs(params)])

    result = {}
    for n in topo:
        if n.is_variable and env.get(id(n)) is not None:
            result[n.name] = tuple(env[id(n)][0].shape)
    out_shapes = []
    for node, idx in symbol._entries:
        e = env.get(id(node))
        out_shapes.append(tuple(e[idx].shape) if e else None)
    return result, out_shapes, None


def _solve_subgraph_shapes(node, env):
    """Shape inference THROUGH control-flow subgraphs: run the subgraph's
    own inference with the shapes known at the node's inputs (data slices
    lose their scan axis), then write solved closure/state variable shapes
    back to the outer graph — the reference does the equivalent via each
    control-flow op's InferShape recursing into its CachedOp subgraph
    (`src/operator/control_flow.cc` ForeachShape/WhileLoopShape)."""
    import jax
    from ..ops import control_flow as _cf
    p = node.attrs
    op_name = node.op.name
    ins = node.inputs

    def in_shape(idx):
        src, oi = ins[idx]
        e = env[id(src)]
        return None if e is None else tuple(e[oi].shape)

    if op_name == "_foreach":
        nd_, ns = int(p["num_data"]), int(p["num_states"])

        def slot_index(tag):
            k, i = tag[0], int(tag[1:])
            return i if k == "d" else nd_ + i if k == "s" else nd_ + ns + i
        graphs = [(p["subgraph"], p["arg_map"])]
    elif op_name == "_while_loop":
        nv = int(p["num_vars"])

        def slot_index(tag):
            k, i = tag[0], int(tag[1:])
            return i if k == "v" else nv + i
        graphs = [(p["cond_subgraph"], p["cond_arg_map"]),
                  (p["func_subgraph"], p["func_arg_map"])]
    else:  # _cond

        def slot_index(tag):
            return 1 + int(tag[1:])
        graphs = [(p["then_subgraph"], p["then_arg_map"]),
                  (p["else_subgraph"], p["else_arg_map"])]

    for gjson, amap in graphs:
        sub = _cf._subgraph(_cf._json_str(gjson))
        known = {}
        for name, tag in amap:
            shp = in_shape(slot_index(tag))
            if shp is not None:
                known[name] = shp[1:] if (op_name == "_foreach" and
                                          tag[0] == "d") else shp
        try:
            solved, _, _ = _infer_graph(sub, known, True)
        except MXNetError:
            continue
        for name, tag in amap:
            if name in solved and solved[name] and \
                    all(dim > 0 for dim in solved[name]):
                src, _ = ins[slot_index(tag)]
                if src.is_variable and env[id(src)] is None:
                    env[id(src)] = (jax.ShapeDtypeStruct(
                        tuple(solved[name]), _np.float32),)
    return all(env[id(src)] is not None for src, _ in ins)


def _solve_param_shapes(node, env):
    """Infer unbound parameter-variable shapes from op attrs + known data shape
    (the reference does this through each op's InferShape; we encode the rules
    for the parameterized layers)."""
    import jax
    op_name = node.op.name
    ins = node.inputs

    if op_name in ("_foreach", "_while_loop", "_cond"):
        return _solve_subgraph_shapes(node, env)

    def dshape():
        e = env[id(ins[0][0])]
        return None if e is None else tuple(e[ins[0][1]].shape)

    def setvar(i, shape, dtype=_np.float32):
        src, _ = ins[i]
        if src.is_variable and env[id(src)] is None:
            env[id(src)] = (jax.ShapeDtypeStruct(tuple(shape), dtype),)

    d = dshape()
    if d is None:
        return False
    p = node.attrs
    if op_name in ("FullyConnected", "_sg_pallas_fc_relu"):
        num_hidden = int(p["num_hidden"])
        in_units = 1
        if p.get("flatten", True):
            for s in d[1:]:
                in_units *= s
        else:
            in_units = d[-1]
        setvar(1, (num_hidden, in_units))
        if not p.get("no_bias"):
            setvar(2, (num_hidden,))
    elif op_name == "Convolution":
        nf = int(p["num_filter"])
        g = int(p.get("num_group", 1))
        kernel = tuple(p["kernel"])
        setvar(1, (nf, d[1] // g) + kernel)
        if not p.get("no_bias"):
            setvar(2, (nf,))
    elif op_name == "_contrib_quantized_conv":
        nf = int(p["num_filter"])
        g = int(p.get("num_group", 1))
        kernel = tuple(p["kernel"])
        setvar(1, (nf, d[1] // g) + kernel, _np.int8)
        first_minmax = 2
        if not p.get("no_bias"):
            setvar(2, (nf,), _np.int8)
            first_minmax = 3
        for i in range(first_minmax, len(ins)):
            setvar(i, (1,))
    elif op_name == "_contrib_quantized_fully_connected":
        num_hidden = int(p["num_hidden"])
        in_units = 1
        if p.get("flatten", True):
            for s in d[1:]:
                in_units *= s
        else:
            in_units = d[-1]
        setvar(1, (num_hidden, in_units), _np.int8)
        first_minmax = 2
        if not p.get("no_bias"):
            setvar(2, (num_hidden,), _np.int8)
            first_minmax = 3
        for i in range(first_minmax, len(ins)):
            setvar(i, (1,))
    elif op_name == "Deconvolution":
        nf = int(p["num_filter"])
        g = int(p.get("num_group", 1))
        kernel = tuple(p["kernel"])
        setvar(1, (d[1], nf // g) + kernel)
        if not p.get("no_bias"):
            setvar(2, (nf,))
    elif op_name in ("BatchNorm", "BatchNorm_v1", "_contrib_SyncBatchNorm"):
        c = d[int(p.get("axis", 1)) % len(d)]
        for i in range(1, 5):
            setvar(i, (c,))
    elif op_name == "_contrib_DeformableConvolution":
        # inputs: data, offset, weight[, bias]
        nf = int(p["num_filter"])
        g = int(p.get("num_group", 1))
        kernel = tuple(p["kernel"])
        setvar(2, (nf, d[1] // g) + kernel)
        if not p.get("no_bias"):
            setvar(3, (nf,))
    elif op_name == "LayerNorm":
        c = d[int(p.get("axis", -1)) % len(d)]
        setvar(1, (c,))
        setvar(2, (c,))
    elif op_name == "InstanceNorm":
        setvar(1, (d[1],))
        setvar(2, (d[1],))
    elif op_name == "Embedding":
        setvar(1, (int(p["input_dim"]), int(p["output_dim"])))
    elif op_name == "LeakyReLU" and p.get("act_type") == "prelu" and len(ins) > 1:
        setvar(1, (d[1],))
    elif op_name == "RNN":
        from ..ops.nn import rnn_param_size
        H = int(p["state_size"])
        L = int(p["num_layers"])
        bi = bool(p.get("bidirectional"))
        dcount = 2 if bi else 1
        setvar(1, (rnn_param_size(p["mode"], d[2], H, L, bi),))
        setvar(2, (L * dcount, d[1], H))
        if p["mode"] == "lstm" and len(ins) > 3:
            setvar(3, (L * dcount, d[1], H))
    elif op_name in ("SoftmaxOutput", "Softmax", "LinearRegressionOutput",
                     "LogisticRegressionOutput", "MAERegressionOutput",
                     "SVMOutput"):
        if op_name in ("SoftmaxOutput", "Softmax"):
            if p.get("multi_output"):
                setvar(1, (d[0],) + tuple(d[2:]))
            else:
                setvar(1, tuple(d[:-1]))
        elif op_name == "SVMOutput":
            setvar(1, (d[0],))
        else:
            setvar(1, d)
    else:
        return False
    return all(env[id(src)] is not None for src, _ in ins)


def _infer_graph_types(symbol, dtypes):
    known = dict(dtypes)
    out = {}
    for n in symbol._topo():
        if n.is_variable:
            out[n.name] = _np.dtype(known.get(n.name, _np.float32))
    return out
