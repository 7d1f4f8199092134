"""Static graph passes over `Symbol` (and saved symbol JSON).

Topo-ordered analyses in the TVM/grappler pass mold: each pass walks the
graph once and returns findings, no mutation.  The catalog:

* ``graph.names``  — duplicate node names (distinct nodes sharing a name
  silently shadow each other in `tojson` / `arg_dict`), empty names.
* ``graph.dead``   — outputs of multi-output ops that no node consumes
  and no head exposes: computed, shipped through XLA, thrown away.
* ``graph.aux``    — aux-state hazards: one running-stat variable feeding
  the aux slots of several ops (racing writers), or an aux variable also
  consumed as a regular input.
* ``graph.dtype``  — float64 introduction: explicit f64 variables/casts
  (TPUs have no f64 ALU; XLA emulates slowly or demotes), plus which
  graph outputs the promotion reaches when shapes allow inference.
* ``graph.unbound``— variables whose shape can be inferred neither from
  the provided input shapes nor from op attrs (bind will fail there).
* ``graph.layout`` — TPU tiling hints: channel/feature dims that are not
  multiples of 8 (sublane) / 128 (lane) pad to the next tile and waste
  MXU throughput.  Hint severity: advisory, not a defect.

Per-node suppression: set the ``__lint__`` attr on a Variable/op to
``"off"`` (suppress everything on that node) or a comma list of codes,
e.g. ``attr={"__lint__": "tpu-layout,dead-output"}``.
"""
from __future__ import annotations

import json as _json

import numpy as _np

from ..base import np_dtype
from .findings import Finding, Report, ERROR, WARN, HINT

__all__ = ["check", "check_json", "scan_plan", "PASS_CATALOG",
           "SCAN_MIN_RUN", "SCAN_HINT_RUN"]

PASS_CATALOG = {
    "graph.names": ("duplicate-name", "empty-name", "bad-json",
                    "unloadable"),
    "graph.dead": ("dead-output", "unreachable-node"),
    "graph.aux": ("shared-aux", "aux-as-input", "unreachable-node"),
    "graph.dtype": ("f64-promotion", "f64-output"),
    "graph.unbound": ("unbound-input",),
    "graph.layout": ("tpu-layout",),
    "graph.scan": ("scan-opportunity",),
}

# feature/channel attrs per op for the layout pass
_FEATURE_ATTRS = {
    "FullyConnected": ("num_hidden", "num_hidden"),
    "Convolution": ("num_filter", "num_filter"),
    "Deconvolution": ("num_filter", "num_filter"),
    "Embedding": ("output_dim", "output_dim"),
    "RNN": ("state_size", "state_size"),
}

# multi-output ops whose trailing outputs are optional state taps the
# caller may legitimately ignore: op name -> index of the first optional
# output (int, or a callable over the node attrs)
_OPTIONAL_TAIL_OUTPUTS = {
    "RNN": 1,
    # control-flow ops: outputs past num_out_data are the final loop
    # states (an unrolled LSTM discards them by design)
    "_foreach": lambda attrs: int(attrs.get("num_out_data", 0)),
    "_while_loop": lambda attrs: int(attrs.get("num_out_data", 0)),
}


def _suppressed(node, code):
    tag = node._extra_attrs.get("__lint__")
    if not tag:
        return False
    tag = str(tag)
    return tag == "off" or code in {t.strip() for t in tag.split(",")}


def _finding(out, node, pass_name, code, severity, message):
    if not _suppressed(node, code):
        out.append(Finding(pass_name, code, severity, message,
                           node=node.name))


# ---------------------------------------------------------------------------
# individual passes
# ---------------------------------------------------------------------------

def _pass_names(symbol, topo):
    out = []
    seen = {}
    for node in topo:
        if not str(node.name).strip():
            _finding(out, node, "graph.names", "empty-name", ERROR,
                     "node has an empty name; it cannot be addressed in "
                     "arg_dict / saved JSON")
            continue
        first = seen.get(node.name)
        if first is None:
            seen[node.name] = node
            continue
        involves_var = node.is_variable or first.is_variable
        _finding(out, node, "graph.names", "duplicate-name",
                 ERROR if involves_var else WARN,
                 f"two distinct nodes share the name '{node.name}'; "
                 + ("arg_dict collapses the duplicates and bind "
                    "trains/feeds the wrong arrays (bind rejects this)"
                    if involves_var else
                    "by-name output lookup and tojson round-trips "
                    "silently shadow one of them"))
    return out


def _pass_dead_outputs(symbol, topo):
    consumed = set()
    for node in topo:
        for src, idx in node.inputs:
            consumed.add((id(src), idx))
    heads = {(id(n), i) for n, i in symbol._entries}
    out = []
    for node in topo:
        if node.is_variable:
            continue
        nout = node.num_outputs()
        if nout <= 1:
            continue  # single-output non-heads cannot appear in topo
        optional_from = _OPTIONAL_TAIL_OUTPUTS.get(node.op.name, nout)
        if callable(optional_from):
            optional_from = optional_from(node.attrs)
        for i in range(nout):
            if i >= optional_from:
                continue
            if (id(node), i) not in consumed and (id(node), i) not in heads:
                _finding(out, node, "graph.dead", "dead-output", WARN,
                         f"output {i} of '{node.name}' "
                         f"('{node.name}_output{i}') is computed but never "
                         "consumed and is not a graph head — dead compute "
                         "shipped through XLA")
    return out


def _pass_aux(symbol, topo):
    out = []
    aux_writers = {}   # id(var) -> (var, [op names])
    aux_readers = {}   # id(var) -> [op names] via NON-aux slots
    for node in topo:
        if node.is_variable:
            continue
        naux = node.op.num_aux(node.attrs)
        n_in = len(node.inputs)
        for k, (src, _idx) in enumerate(node.inputs):
            if not src.is_variable:
                continue
            if naux and k >= n_in - naux:
                aux_writers.setdefault(id(src), (src, []))[1].append(
                    node.name)
            else:
                aux_readers.setdefault(id(src), []).append(node.name)
    for vid, (var, writers) in aux_writers.items():
        if len(writers) > 1:
            _finding(out, var, "graph.aux", "shared-aux", WARN,
                     f"aux state '{var.name}' feeds the running-state "
                     f"slots of {len(writers)} ops ({', '.join(writers[:4])}"
                     f"{', ...' if len(writers) > 4 else ''}); every train "
                     "step races their writes — last writer wins")
        readers = aux_readers.get(vid)
        if readers:
            _finding(out, var, "graph.aux", "aux-as-input", WARN,
                     f"aux state '{var.name}' is also consumed as a "
                     f"regular input by {readers[0]}; it will be updated "
                     "in place under that reader")
    return out


def _is_f64(value):
    try:
        return np_dtype(value) == _np.float64
    except Exception:
        return False


def _pass_dtype(symbol, topo, env):
    out = []
    origins = []
    for node in topo:
        if node.is_variable:
            if _is_f64(node._extra_attrs.get("__dtype__")):
                origins.append(node)
                _finding(out, node, "graph.dtype", "f64-promotion", WARN,
                         f"variable '{node.name}' is declared float64; "
                         "TPUs have no f64 ALU — XLA emulates it slowly "
                         "or demotes with precision surprises")
            continue
        for key, val in node.attrs.items():
            if key in ("dtype", "out_type") and _is_f64(val):
                origins.append(node)
                _finding(out, node, "graph.dtype", "f64-promotion", WARN,
                         f"op '{node.name}' ({node.op.name}) produces "
                         f"float64 ({key}={val!r}); TPUs have no f64 ALU "
                         "— the whole downstream graph pays for emulation")
    if origins and env:
        f64_heads = []
        outs = symbol.list_outputs()
        for oname, (node, idx) in zip(outs, symbol._entries):
            avals = env.get(id(node))
            if avals and idx < len(avals) and avals[idx] is not None and \
                    _np.dtype(avals[idx].dtype) == _np.float64:
                f64_heads.append(oname)
        if f64_heads:
            n, _i = symbol._entries[0]
            out.append(Finding(
                "graph.dtype", "f64-output", WARN,
                "the f64 promotion reaches graph output(s) "
                f"{', '.join(f64_heads[:4])}"
                f"{', ...' if len(f64_heads) > 4 else ''}; every consumer "
                "inherits the emulation cost", node=n.name))
    return out


def _pass_unbound(symbol, topo, shapes):
    """Variables the framework's own partial shape inference cannot solve
    from the provided inputs — `simple_bind` will fail exactly there."""
    try:
        kw = {k: tuple(v) for k, v in shapes.items() if v}
        arg_shapes, _, aux_shapes = symbol.infer_shape_partial(**kw)
    except Exception:
        return []   # inference itself broke; other passes still apply
    out = []
    names = symbol.list_arguments() + symbol.list_auxiliary_states()
    solved = list(arg_shapes or []) + list(aux_shapes or [])
    var_nodes = {n.name: n for n in topo if n.is_variable}
    for name, shp in zip(names, solved):
        if shp is not None and all(shp):
            continue
        node = var_nodes.get(name)
        if node is not None:
            _finding(out, node, "graph.unbound", "unbound-input", WARN,
                     f"shape of variable '{name}' cannot be inferred "
                     "from the provided input shapes or op attrs; "
                     "simple_bind will fail here — provide its shape")
    return out


def _pass_layout(symbol, topo):
    out = []
    for node in topo:
        if node.is_variable or node.op.name not in _FEATURE_ATTRS:
            continue
        attr, label = _FEATURE_ATTRS[node.op.name]
        try:
            d = int(node.attrs.get(attr))
        except (TypeError, ValueError):
            continue
        if d <= 0 or (d % 8 == 0 and d % 128 == 0):
            continue
        lane_pad = -d % 128
        sub_pad = -d % 8
        waste = 100.0 * lane_pad / (d + lane_pad)
        parts = []
        if sub_pad:
            parts.append(f"pads {sub_pad} sublanes to the next multiple "
                         "of 8")
        if lane_pad:
            parts.append(f"pads {lane_pad} lanes to the next multiple of "
                         f"128 ({waste:.0f}% of the padded tile wasted)")
        _finding(out, node, "graph.layout", "tpu-layout", HINT,
                 f"'{node.name}' {label}={d} is not TPU-tile aligned: "
                 + "; ".join(parts))
    return out


# ---------------------------------------------------------------------------
# best-effort abstract evaluation (shape+dtype), partial-tolerant
# ---------------------------------------------------------------------------

def _abstract_env(symbol, shapes, dtypes=None):
    """{id(node): tuple(ShapeDtypeStruct|None)} walking topo order; a node
    whose inputs cannot be resolved gets None (partial inference — the
    passes that consume the env skip unknowns).  Variables seed from the
    provided `shapes`, then ``__shape__`` attrs; declared ``__dtype__``
    attrs carry real dtypes so f64 propagation is visible, and the
    optional `dtypes` map ({var_name: dtype}) overrides both — a
    quantized model's int8 weights live in its params dict, not its
    variable attrs, and the cost analyzer feeds them through here."""
    import jax
    from ..symbol.symbol import _solve_param_shapes

    shapes = dict(shapes or {})
    dtypes = dict(dtypes or {})
    topo = symbol._topo()
    env = {}

    def var_aval(node):
        cand = None
        if node.name in shapes and shapes[node.name]:
            cand = shapes[node.name]
        elif "__shape__" in node._extra_attrs:
            cand = node._extra_attrs["__shape__"]
        if isinstance(cand, str):
            # saved JSON stringifies attrs: "(4, 8)" -> (4, 8)
            import ast as _ast
            try:
                cand = _ast.literal_eval(cand)
            except (ValueError, SyntaxError):
                cand = None
        cand = tuple(cand) if cand is not None else None
        if cand is None or not all(isinstance(d, int) and d > 0
                                   for d in cand):
            return None
        dt = _np.float32
        declared = dtypes.get(node.name,
                              node._extra_attrs.get("__dtype__"))
        if declared is not None:
            try:
                dt = np_dtype(declared)
            except Exception:
                pass
        return jax.ShapeDtypeStruct(cand, dt)

    for node in topo:
        if node.is_variable:
            aval = var_aval(node)
            env[id(node)] = (aval,) if aval is not None else None
            continue
        ins = []
        unknown = False
        for src, idx in node.inputs:
            e = env.get(id(src))
            if e is None or idx >= len(e) or e[idx] is None:
                unknown = True
                break
            ins.append(e[idx])
        if unknown:
            try:
                solved = _solve_param_shapes(node, env)
            except Exception:
                solved = False
            if solved:
                ins = [env[id(src)][idx] for src, idx in node.inputs]
            else:
                env[id(node)] = None
                continue
        params = dict(node.attrs)
        if node.op.mode_dependent:
            params["_train"] = False
        if node.op.dynamic_params:
            for pname in node.op.dynamic_params:
                ins.append(jax.ShapeDtypeStruct((), _np.float32))
                params.pop(pname, None)
        if node.op.needs_rng:
            ins.append(jax.ShapeDtypeStruct((2,), _np.uint32))
        try:
            outv = jax.eval_shape(lambda *xs: node.op.fn(params, *xs), *ins)
        except Exception:
            env[id(node)] = None
            continue
        if not isinstance(outv, (tuple, list)):
            outv = (outv,)
        env[id(node)] = tuple(outv[:node.num_outputs()])
    return env


# ---------------------------------------------------------------------------
# scan-over-layers: repeated-subgraph isomorphism over the linear spine
# ---------------------------------------------------------------------------

# lower runs of >= SCAN_MIN_RUN identical segments; lint only complains
# about runs >= SCAN_HINT_RUN that could NOT lower (the compile-time win
# below 4 repeats rarely justifies a graph rewrite worth shouting about)
SCAN_MIN_RUN = 2
SCAN_HINT_RUN = 4


def _clean_cuts(ops, pos, heads, min_run):
    """(Positions p where every op->op edge crossing the cut after ops[p]
    originates AT ops[p] — i.e. the graph's linear spine points —, the
    edges left out of that count).  `_topo` guarantees edges go
    earlier->later, so a clean cut means everything after it sees only
    ops[p]'s outputs (plus variables).  Graph heads act as virtual
    consumers past the end: a head produced mid-graph dirties every later
    cut, so a scanned run can never hide a value a caller reads.

    Left out: an edge that CANNOT lie inside a layer of any run.  A run is
    at least `min_run` layers of equal length among the n ops, so a layer
    is n // min_run ops at most, and an edge longer than that has an end
    outside every layer it touches: it is a value made in front of a stack
    and read behind it (the weights and targets of a loss that the graph
    derives from its input), not a layer's residual.  `scan_plan` then
    holds each run to what made leaving it out sound: the edge starts in
    front of the run's first layer and ends behind its last
    (`_bypassed`).  A shorter edge could be either and counts as a
    layer's own, as every edge did before: a stack under such an edge
    stays unfolded (tests/test_scan_layers.py states both outcomes)."""
    n = len(ops)
    dirty = [False] * n
    spans = []
    for node in ops:
        j = pos[id(node)]
        for src, _ in node.inputs:
            if not src.is_variable:
                spans.append((pos[id(src)], j))
    for hnode, _ in heads:
        if not hnode.is_variable:
            spans.append((pos[id(hnode)], n))
    long = [(i, j) for i, j in spans if j - i > n // min_run and j < n]
    for i, j in set(spans) - set(long):
        for p in range(i + 1, j):
            dirty[p] = True
    return [p for p in range(n) if not dirty[p]], long


def _bypassed(segments, pos, long):
    """Whether every long edge left out of the cuts passes over the run
    whole (or misses it): from in front of its first op to behind its
    last."""
    first, last = pos[id(segments[0][0])], pos[id(segments[-1][-1])]
    return all((i < first and j > last) or j <= first or i >= last
               for i, j in long)


def _seg_signature(seg, seg_ids, prev_boundary, aux_ids):
    """Structural signature of one spine segment: op names + attrs +
    input wiring with node identities erased (local position / carry /
    param slot / aux slot).  Two segments with equal signatures are
    isomorphic layer bodies differing only in which parameters feed
    them."""
    seg_pos = {id(node): i for i, node in enumerate(seg)}
    params_order, aux_order = [], []
    param_slot, aux_slot = {}, {}
    rng_order = []
    sig = []
    for node in seg:
        naux = node.op.num_aux(node.attrs)
        n_in = len(node.inputs)
        enc = []
        for k, (src, idx) in enumerate(node.inputs):
            if src.is_variable:
                if naux and k >= n_in - naux and id(src) in aux_ids:
                    if id(src) not in aux_slot:
                        aux_slot[id(src)] = len(aux_order)
                        aux_order.append(src)
                    enc.append(("aux", aux_slot[id(src)]))
                else:
                    if id(src) not in param_slot:
                        param_slot[id(src)] = len(params_order)
                        params_order.append(src)
                    enc.append(("param", param_slot[id(src)]))
            elif id(src) in seg_ids:
                enc.append(("local", seg_pos[id(src)], idx))
            elif prev_boundary is not None and src is prev_boundary \
                    and idx == 0:
                enc.append(("carry",))
            else:
                # not the immediately-preceding boundary's output 0:
                # structurally unique, never joins a run
                enc.append(("extern", id(src), idx))
        if node.op.needs_rng:
            rng_order.append(node)
        sig.append((node.op.name,
                    tuple(sorted((str(k), str(v))
                                 for k, v in node.attrs.items())),
                    tuple(enc)))
    return tuple(sig), params_order, aux_order, rng_order


def _run_eligible(segments, params, auxs, head_nodes, var_consumers,
                  heads):
    """Why a run of equal-signature segments cannot lower, or None."""
    covered = {id(n) for seg in segments for n in seg}
    final_boundary = segments[-1][-1]
    for seg in segments:
        if seg[-1].num_outputs() != 1:
            return "multi-output block boundary"
    for seg in segments[:-1]:
        if id(seg[-1]) in head_nodes:
            return "intermediate block output is a graph head"
    for n_id in covered:
        if n_id in head_nodes and n_id != id(final_boundary):
            return "internal node is a graph head"
    for layer_vars in list(params) + list(auxs):
        for seg, v in zip(segments, layer_vars):
            seg_ids = {id(n) for n in seg}
            consumers = var_consumers.get(id(v), ())
            if any(id(c) not in seg_ids for c in consumers):
                return "parameter '%s' shared outside its layer" % v.name
            if any(h is v for h, _ in heads):
                return "parameter '%s' is a graph head" % v.name
    return None


def scan_plan(symbol, min_run=SCAN_MIN_RUN):
    """Detect runs of structurally identical layer blocks on the graph's
    linear spine — the repeated-subgraph isomorphism pass behind
    scan-over-layers lowering (`symbol.graph_eval_fn`) and the
    ``scan-opportunity`` lint.

    Returns ``{"runs": [...], "rejected": [...]}``.  Each run dict
    carries everything the evaluator needs to emit ONE `lax.scan` body
    over stacked per-layer parameters instead of N inlined copies:

    * ``length``    — layer count N
    * ``carry``     — (node, out_idx) feeding the first layer
    * ``boundary``  — final layer's output node (single-output)
    * ``segments``  — per-layer op node lists (topo order)
    * ``params``    — [slot][layer] parameter variable nodes
    * ``aux``       — [slot][layer] aux-state variable nodes
    * ``rng``       — [slot][layer] rng-consuming op nodes
    * ``covered``   — ids of every op the scan replaces

    Rejected entries ({"node", "length", "reason"}) are equal-signature
    runs that cannot lower (shared weights, exposed internals, ...) —
    the lint surfaces the ones >= SCAN_HINT_RUN."""
    topo = symbol._topo()
    ops = [n for n in topo if not n.is_variable]
    out = {"runs": [], "rejected": []}
    if len(ops) < 2 * max(min_run, 2):
        return out
    pos = {id(n): i for i, n in enumerate(ops)}
    aux_ids = symbol._aux_node_ids()
    heads = list(symbol._entries)
    head_nodes = {id(n) for n, _ in heads}
    var_consumers = {}
    for n in ops:
        for src, _ in n.inputs:
            if src.is_variable:
                var_consumers.setdefault(id(src), []).append(n)

    cuts, long = _clean_cuts(ops, pos, heads, max(min_run, 2))
    if len(cuts) < 2:
        return out
    # segments between consecutive clean cuts (first segment starts at 0)
    segs, seg_meta = [], []
    start = 0
    for p in cuts:
        seg = ops[start:p + 1]
        prev_boundary = ops[start - 1] if start else None
        seg_ids = {id(n) for n in seg}
        sig, params_order, aux_order, rng_order = _seg_signature(
            seg, seg_ids, prev_boundary, aux_ids)
        segs.append(seg)
        seg_meta.append((sig, params_order, aux_order, rng_order))
        start = p + 1

    # A "layer" can span several unit segments (e.g. Conv+BN+Act between
    # three consecutive clean cuts): look for period-p repetition in the
    # unit-signature sequence, then re-derive the signature of each
    # MERGED layer segment exactly.  Unit-level equality is the cheap
    # filter; merged-level equality is the proof.
    m = len(segs)
    unit = [meta[0] for meta in seg_meta]
    max_p = max(1, min(8, m // max(min_run, 2)))
    candidates = []
    for p in range(1, max_p + 1):
        i = 0
        while i + 2 * p <= m:
            length = 1
            while i + (length + 1) * p <= m and \
                    unit[i + length * p:i + (length + 1) * p] == \
                    unit[i:i + p]:
                length += 1
            if length >= min_run:
                # coverage first, then the smaller period (one layer per
                # repetition, not two)
                candidates.append((length * p, -p, i, p, length))
                i += length * p
            else:
                i += 1
    taken = [False] * m
    runs_spec = []
    for _cov, _negp, i, p, length in sorted(candidates, reverse=True):
        if any(taken[i:i + length * p]):
            continue
        for q in range(i, i + length * p):
            taken[q] = True
        runs_spec.append((i, p, length))
    runs_spec.sort()

    for i, p, length in runs_spec:
        segments = [sum((segs[i + l * p + q] for q in range(p)), [])
                    for l in range(length)]
        metas = []
        ok = True
        for l in range(length):
            u0 = i + l * p
            prev_boundary = segs[u0 - 1][-1] if u0 else None
            seg = segments[l]
            metas.append(_seg_signature(seg, {id(n) for n in seg},
                                        prev_boundary, aux_ids))
            if metas[l][0] != metas[0][0]:
                ok = False
                break
        first = segments[0][0]
        if not ok or not any(e == ("carry",) for _, _, enc in metas[0][0]
                             for e in enc):
            out["rejected"].append({
                "node": first.name, "length": length,
                "reason": "layer bodies are not structurally identical "
                          "under the carry chain"})
            continue
        # [slot][layer] variable/rng nodes
        params = [[metas[l][1][s] for l in range(length)]
                  for s in range(len(metas[0][1]))]
        auxs = [[metas[l][2][s] for l in range(length)]
                for s in range(len(metas[0][2]))]
        rngs = [[metas[l][3][s] for l in range(length)]
                for s in range(len(metas[0][3]))]
        reason = _run_eligible(segments, params, auxs, head_nodes,
                               var_consumers, heads)
        if reason is None and not _bypassed(segments, pos, long):
            reason = "a value made in front of the run is read inside it"
        if reason is None:
            carry_src = None
            seg0_ids = {id(n) for n in segments[0]}
            for src, idx in (inp for n in segments[0]
                             for inp in n.inputs):
                if not src.is_variable and id(src) not in seg0_ids:
                    carry_src = (src, idx)
                    break
            if carry_src is not None:
                out["runs"].append({
                    "length": length,
                    "carry": carry_src,
                    "boundary": segments[-1][-1],
                    "segments": segments,
                    "params": params,
                    "aux": auxs,
                    "rng": rngs,
                    "covered": {id(n) for seg in segments for n in seg},
                    "first": first,
                    "name": first.name,
                })
            else:
                out["rejected"].append({
                    "node": first.name, "length": length,
                    "reason": "no op-produced carry feeds the first "
                              "layer"})
        else:
            out["rejected"].append({"node": first.name,
                                    "length": length,
                                    "reason": reason})
    return out


def _pass_scan(symbol, topo):
    """scan-opportunity: a run of >= SCAN_HINT_RUN structurally identical
    blocks that the scan-over-layers lowering will NOT collapse — XLA
    still receives N inlined copies of the layer body."""
    out = []
    try:
        plan = scan_plan(symbol)
    except Exception:
        return out
    from .. import config as _config
    lowering_on = bool(_config.get("MXNET_FUSED_SCAN"))
    candidates = list(plan["rejected"])
    if not lowering_on:
        candidates += [{"node": r["name"], "length": r["length"],
                        "reason": "lowering disabled (MXNET_FUSED_SCAN=0)"}
                       for r in plan["runs"]]
    for rej in candidates:
        if rej["length"] < SCAN_HINT_RUN:
            continue
        node = next((n for n in topo if n.name == rej["node"]), None)
        f = Finding(
            "graph.scan", "scan-opportunity", HINT,
            "run of %d structurally identical blocks starting at '%s' "
            "did not lower to lax.scan (%s) — XLA compiles %d inlined "
            "copies of the layer body" % (rej["length"], rej["node"],
                                          rej["reason"], rej["length"]),
            node=rej["node"])
        if node is None or not _suppressed(node, "scan-opportunity"):
            out.append(f)
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def check(symbol, shapes=None, hints=True, target=None):
    """Run the graph-pass catalog over a Symbol.

    Parameters
    ----------
    symbol : Symbol
    shapes : optional {var_name: shape} — enables the unbound-input pass
        and dtype propagation (same convention as `infer_shape` kwargs).
    hints : include perf hints (tpu-layout) alongside errors/warnings.
    """
    topo = symbol._topo()
    report = Report(target=target)
    report.extend(_pass_names(symbol, topo))
    report.extend(_pass_dead_outputs(symbol, topo))
    report.extend(_pass_aux(symbol, topo))
    env = {}
    try:
        env = _abstract_env(symbol, shapes)
    except Exception:
        env = {}
    report.extend(_pass_dtype(symbol, topo, env))
    if shapes:
        report.extend(_pass_unbound(symbol, topo, shapes))
    if hints:
        report.extend(_pass_layout(symbol, topo))
        report.extend(_pass_scan(symbol, topo))
    return report


def _json_structural(graph, target):
    """Passes that need the raw node table: duplicate names across the
    WHOLE file and nodes unreachable from any head (a Symbol object only
    ever holds reachable nodes, so these exist only for saved JSON)."""
    out = []
    nodes = graph.get("nodes", [])
    seen = {}
    for i, jn in enumerate(nodes):
        name = jn.get("name", "")
        if not str(name).strip():
            out.append(Finding("graph.names", "empty-name", ERROR,
                               f"node #{i} has an empty name", node=str(i),
                               location=target))
            continue
        if name in seen:
            out.append(Finding(
                "graph.names", "duplicate-name", ERROR,
                f"nodes #{seen[name]} and #{i} share the name '{name}'; "
                "loading this graph silently shadows one of them",
                node=name, location=target))
        else:
            seen[name] = i
    heads = [h[0] for h in graph.get("heads", [])]
    reachable = set()
    stack = list(heads)
    while stack:
        nid = stack.pop()
        if nid in reachable or nid >= len(nodes):
            continue
        reachable.add(nid)
        for inp in nodes[nid].get("inputs", []):
            stack.append(inp[0])
    for i, jn in enumerate(nodes):
        if i in reachable:
            continue
        is_var = jn.get("op") == "null"
        kind = "aux/argument state" if is_var else "op"
        out.append(Finding(
            "graph.aux" if is_var else "graph.dead",
            "unreachable-node", WARN,
            f"{kind} '{jn.get('name')}' (node #{i}) is not reachable from "
            "any graph head — dead " +
            ("state the loader will still allocate" if is_var
             else "compute"),
            node=jn.get("name"), location=target))
    return out


def check_json(text, shapes=None, hints=True, target=None):
    """Analyze a saved symbol JSON string: structural passes over the raw
    node table, then the Symbol passes over the loadable graph."""
    report = Report(target=target)
    try:
        graph = _json.loads(text)
    except ValueError as e:
        report.add(Finding("graph.names", "bad-json", ERROR,
                           f"not valid JSON: {e}", location=target))
        return report
    if not isinstance(graph, dict) or "nodes" not in graph:
        report.add(Finding("graph.names", "bad-json", ERROR,
                           "no 'nodes' table — not a symbol JSON",
                           location=target))
        return report
    report.extend(_json_structural(graph, target))
    try:
        from ..symbol.symbol import load_json
        sym = load_json(text)
    except Exception as e:
        report.add(Finding(
            "graph.names", "unloadable", ERROR,
            f"graph does not load ({str(e)[:160]}); only structural "
            "passes ran", location=target))
        return report
    # the structural pass already covered names over the WHOLE node table
    # (the Symbol walk sees only reachable nodes) — don't double-report
    sym_report = check(sym, shapes=shapes, hints=hints, target=target)
    report.extend(f for f in sym_report.findings
                  if f.pass_name != "graph.names")
    return report
