"""mxshard: static SPMD sharding analyzer (GSPMD-style propagation).

The dynamic half of the sharding story already exists — megatron rules
shard the TransformerLM (`parallel/tensor_parallel.py`), the pod fast
path exchanges gradients over the dp axis — but a mis-sharded param is
only discovered at run time: it silently replicates (per-device HBM
blowup) or GSPMD inserts a hidden all-gather that the mxcost collective
enumerator never models (cost.py only understands the dp bucket psum
plan).  mxshard closes that gap statically, before anything compiles:

* **propagation** — given a Symbol graph (or traced jaxpr), a mesh
  spec (`"dp=2,tp=2"` / axis dict / `jax.sharding.Mesh`) and a
  `ShardingRules` set, PartitionSpecs are seeded on the variables
  (step inputs ride the dp axis on dim 0, params get their rule's
  spec) and propagated forward through every op.  Dot-class ops carry
  the megatron algebra (column-parallel → output-dim sharded,
  row-parallel → contraction over a sharded dim → psum), embedding
  lookups over a vocab-sharded table psum, reduces over sharded dims
  psum, reshape/transpose/slice remap specs dimension-wise, and a
  dot-class handler back-infers the spec its operands need (the
  "backward" half: bias of a column-parallel FC is sliced, an
  activation feeding a row-parallel FC must arrive contraction-
  sharded).  An op with no handler falls back to **replicated
  outputs** and the fallback is recorded (`shard-fallback`) instead of
  silently propagating fiction.
* **findings** — `implicit-replication` (param/activation ≥
  `MXNET_SHARD_MIN_MB` fully replicated while a >1-device non-batch
  axis exists), `hidden-reshard` (an edge whose producer spec differs
  from what the consumer needs, classified all-gather / all-to-all /
  slice with statically computed bytes, naming both nodes),
  `rule-coverage` (a param matching zero or ≥2 rules of a rule set
  that clearly applies to the model — the static twin of the dynamic
  test_llm coverage test), and `dp-axis-leak` (a batch-led activation
  whose dim-0 dp sharding an op dropped past the input).
* **costs** — per-DEVICE peak HBM from sharded avals (the same
  liveness walk as `cost._liveness_pass`, buffer sizes divided by
  their shard counts), and the collective enumerator grows tp/GSPMD
  collectives alongside the dp bucket plan: `shard_collectives`
  returns the dp exchange (the SAME `kvstore.plan_buckets` rule —
  byte-exact against measured `KVStore.stats()` / `pod_stats`) plus
  the statically derived tp psums/reshards with per-collective ICI
  bytes (ring model, matching cost.py: all-reduce moves
  ``2*(n-1)/n * bytes`` per chip, all-gather ``(n-1)/n * bytes``).

Surfaced via `tools/mxlint.py --shard-report` (budget-gated against
COST_BUDGETS.json's ``sharding`` section; tests/test_sharding.py).
Findings are plain `analysis.findings` currency; every
code registers in CODE_TABLE.
"""
from __future__ import annotations

import numpy as np

from .findings import Finding, Report, ERROR, WARN, HINT
from .cost import _aval_bytes, DOT_CLASS

# every finding code this module emits (tests/test_analysis.py folds
# this into the no-orphan CODE_TABLE check)
CODES = ("implicit-replication", "hidden-reshard", "rule-coverage",
         "dp-axis-leak", "shard-fallback", "shard-summary")

_MB = float(1 << 20)

# default step-input heuristic shared with cost._liveness_pass
_STEP_INPUT_HINTS = ("data", "_label", "state")


# ---------------------------------------------------------------------------
# mesh / spec plumbing.  A spec is a plain tuple, one entry per tensor
# dim: a mesh-axis name (str) or None (replicated on that dim).
# ---------------------------------------------------------------------------

def _mesh_axes(mesh):
    """Normalize a mesh argument to ``{axis_name: size}``.

    Accepts a spec string (``"dp=2,tp=2"``, the `parallel.mesh`
    grammar), a dict, or anything with a Mesh-like ``.shape`` mapping.
    """
    if mesh is None:
        return {}
    if isinstance(mesh, str):
        from ..parallel.mesh import parse_spec
        return parse_spec(mesh)
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        return {str(k): int(v) for k, v in dict(shape).items()}
    raise TypeError(f"cannot derive mesh axes from {mesh!r}")


def _axis_size(ax, axes):
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= int(axes.get(str(a), 1))
        return n
    return int(axes.get(str(ax), 1))


def _spec_tuple(spec, ndim):
    """PartitionSpec / tuple / list -> padded plain tuple of len ndim."""
    entries = tuple(spec) if spec is not None else ()
    entries = entries[:ndim] + (None,) * (ndim - len(entries))
    return tuple(e if (e is None or isinstance(e, (tuple, list)))
                 else str(e) for e in entries)


def _clamp_spec(spec, shape, axes):
    """Drop spec axes absent from the mesh, of size 1, or that don't
    divide their dim — the same forgiveness `shard_params` applies."""
    out = []
    for dim, ax in zip(shape, _spec_tuple(spec, len(shape))):
        n = _axis_size(ax, axes)
        out.append(ax if (ax is not None and n > 1 and dim % n == 0)
                   else None)
    return tuple(out)


def _nshards(spec, axes):
    n = 1
    for ax in spec:
        n *= _axis_size(ax, axes)
    return n


def _sharded_bytes(aval, spec, axes):
    if aval is None:
        return 0
    return _aval_bytes(aval) // max(1, _nshards(spec, axes))


def _fmt_spec(spec):
    return "P(" + ", ".join("None" if a is None else repr(a)
                            for a in spec) + ")"


def _classify_reshard(src_spec, dst_spec):
    src_sh = any(a is not None for a in src_spec)
    dst_sh = any(a is not None for a in dst_spec)
    if src_sh and dst_sh:
        return "all-to-all"
    if src_sh:
        return "all-gather"
    return "slice"


def _reshard_ici_bytes(kind, full_bytes, n):
    """Per-chip ICI bytes for one reshard (ring model, n shards)."""
    if n <= 1:
        return 0
    if kind == "all-gather":
        return int(full_bytes * (n - 1) // n)
    if kind == "all-to-all":
        return int(full_bytes * (n - 1) // (n * n))
    return 0   # slice: drop local data, no wire traffic


# ---------------------------------------------------------------------------
# report currency
# ---------------------------------------------------------------------------

class ShardReport:
    """Everything the propagation derived for one program."""

    def __init__(self, target, axes):
        self.target = target
        self.mesh = dict(axes)
        self.findings = Report(target=target)
        self.specs = {}            # node name -> spec tuple (output 0)
        self.reshards = []         # [{src, dst, kind, bytes, ici_bytes}]
        self.collectives = []      # [{node, op, kind, axis, bytes, ici_bytes}]
        self.fallback_ops = {}     # op name -> node count
        self.per_device_peak_hbm_bytes = None
        self.replicated_peak_hbm_bytes = None

    @property
    def ici_bytes_per_step(self):
        """tp/GSPMD ICI bytes per chip per step (dp plan excluded —
        `shard_collectives` folds that in)."""
        return int(sum(c["ici_bytes"] for c in self.collectives) +
                   sum(r["ici_bytes"] for r in self.reshards))

    def as_dict(self):
        return {
            "target": self.target,
            "mesh": dict(self.mesh),
            "per_device_peak_hbm_bytes": self.per_device_peak_hbm_bytes,
            "replicated_peak_hbm_bytes": self.replicated_peak_hbm_bytes,
            "tp_collectives_per_step": len(self.collectives),
            "tp_ici_bytes_per_step": self.ici_bytes_per_step,
            "reshard_edges": len(self.reshards),
            "fallback_ops": dict(self.fallback_ops),
            "findings": [f.as_dict() for f in self.findings],
        }


# ---------------------------------------------------------------------------
# rule coverage — the static twin of test_llm's dynamic megatron check
# ---------------------------------------------------------------------------

def check_rule_coverage(param_shapes, rules, target=None, report=None):
    """Check a `ShardingRules` set against a model's parameter names.

    ``param_shapes``: {name: shape tuple or None}.  A param matching
    >=2 rule entries is ambiguous (first-match-wins hides the loser); a
    matrix param (ndim>=2) matching ZERO rules silently replicates.
    1-D params (biases, norm scales) are allowed to fall through to
    the replicated default.  If NO param matches ANY rule the set is
    considered not-applicable to this model and nothing is emitted
    (a convnet analyzed under megatron rules is not a coverage gap).
    """
    rep = report if report is not None else Report(target=target)
    matched = {name: [prog.pattern for prog, _ in rules.rules
                      if prog.search(name)]
               for name in param_shapes}
    if not any(matched.values()):
        return rep
    for name in sorted(matched):
        pats = matched[name]
        shape = param_shapes[name]
        ndim = len(shape) if shape is not None else 0
        if len(pats) >= 2:
            rep.add(Finding(
                "shard.rules", "rule-coverage", ERROR,
                f"param '{name}' matches {len(pats)} sharding rules "
                f"({', '.join(repr(p) for p in pats)}); first-match-wins "
                f"silently ignores the rest — tighten the regexes",
                node=name))
        elif not pats and ndim >= 2:
            rep.add(Finding(
                "shard.rules", "rule-coverage", ERROR,
                f"param '{name}' {tuple(shape) if shape else ''} matches "
                f"no sharding rule; it will silently replicate on every "
                f"device", node=name))
    return rep


# ---------------------------------------------------------------------------
# the propagation pass
# ---------------------------------------------------------------------------

# single-input ops whose output spec is the input spec (shape-preserving)
_PASS_THROUGH = frozenset([
    "Activation", "LeakyReLU", "Dropout", "Cast", "clip", "relu",
    "sigmoid", "tanh", "exp", "log", "sqrt", "square", "negative",
    "abs", "erf", "softsign", "identity", "_copy", "BlockGrad",
    "stop_gradient", "L2Normalization",
])

# single-input ops where a dim keeps its spec iff its SIZE is unchanged
# (pooling/padding change spatial dims but never batch/channel)
_SIZE_ALIGNED = frozenset([
    "Pooling", "UpSampling", "pad", "Pad", "slice", "slice_like",
    "Crop", "BilinearSampler", "_contrib_quantized_pooling",
])

# quantize/dequantize keep the data layout; the min/max outputs are
# replicated scalars
_QUANT_PASS = frozenset([
    "_contrib_quantize", "_contrib_quantize_v2", "_contrib_dequantize",
    "_contrib_requantize",
])

# multi-input elementwise/broadcast families -> dimension-wise join
_ELEMWISE_PREFIXES = ("broadcast_", "elemwise_", "_plus", "_minus",
                      "_mul", "_div", "_maximum", "_minimum", "_power")
_ELEMWISE = frozenset(["add_n", "where", "maximum", "minimum", "hypot"])

_REDUCE_OPS = frozenset(["sum", "mean", "max", "min", "prod", "nansum",
                         "nanprod", "norm", "argmax", "argmin"])


def _is_elemwise(opname):
    return opname in _ELEMWISE or \
        any(opname.startswith(p) for p in _ELEMWISE_PREFIXES)


def analyze_sharding(symbol, shapes=None, mesh="dp=8", rules=None,
                     dtypes=None, batch_axis="dp", step_inputs=None,
                     min_mb=None, name=None):
    """Propagate PartitionSpecs through a Symbol graph; return a
    `ShardReport` (findings + specs + reshards + tp collectives +
    per-device peak HBM).  Pure analysis: no devices touched, nothing
    compiled."""
    from . import graph_passes as gp
    from .. import config as _config

    axes = _mesh_axes(mesh)
    if min_mb is None:
        min_mb = float(_config.get("MXNET_SHARD_MIN_MB"))
    min_bytes = int(min_mb * _MB)
    topo = symbol._topo()
    env = gp._abstract_env(symbol, shapes, dtypes)
    rep = ShardReport(name or "symbol", axes)

    if step_inputs is None:
        step_inputs = {n.name for n in topo if n.is_variable and
                       (n.name.startswith("data") or
                        n.name.endswith("_label") or
                        "state" in n.name)}
    else:
        step_inputs = set(step_inputs)

    def avals_of(node):
        return env.get(id(node)) or (None,) * node.num_outputs()

    # ---- rule coverage (independent of propagation) --------------------
    if rules is not None:
        param_shapes = {}
        for n in topo:
            if not n.is_variable or n.name in step_inputs:
                continue
            a = avals_of(n)[0]
            param_shapes[n.name] = tuple(a.shape) if a is not None else \
                n._extra_attrs.get("__shape__")
        check_rule_coverage(param_shapes, rules, report=rep.findings)

    # ---- seed variable specs ------------------------------------------
    specs = {}   # id(node) -> tuple(spec per output)
    dp_size = _axis_size(batch_axis, axes)
    batch_size = None
    for n in topo:
        if not n.is_variable:
            continue
        a = avals_of(n)[0]
        ndim = len(a.shape) if a is not None else 0
        if n.name in step_inputs and ndim:
            sp = (batch_axis,) + (None,) * (ndim - 1)
            if batch_size is None and n.name.startswith("data"):
                batch_size = a.shape[0] if a is not None else None
        elif rules is not None and ndim:
            sp = _spec_tuple(rules.spec_for(n.name), ndim)
        else:
            sp = (None,) * ndim
        if a is not None:
            sp = _clamp_spec(sp, a.shape, axes)
        specs[id(n)] = (sp,)
        rep.specs[n.name] = sp

    def spec_of(src, idx):
        got = specs.get(id(src))
        if got is None or idx >= len(got):
            a = avals_of(src)[idx] if idx < len(avals_of(src)) else None
            return (None,) * (len(a.shape) if a is not None else 0)
        return got[idx]

    # ---- recording helpers --------------------------------------------
    def record_reshard(node, src, have, want, aval, why=""):
        """An edge whose producer spec differs from what the consumer
        needs: classify, cost, and (if big enough) surface."""
        if have == want or aval is None:
            return
        kind = _classify_reshard(have, want)
        full = _aval_bytes(aval)
        n = max(_nshards(have, axes), _nshards(want, axes))
        ici = _reshard_ici_bytes(kind, full, n)
        rep.reshards.append({
            "src": src.name, "dst": node.name, "kind": kind,
            "bytes": full, "ici_bytes": ici,
            "from": _fmt_spec(have), "to": _fmt_spec(want)})
        if full >= min_bytes:
            msg = (f"edge {src.name} -> {node.name}: producer spec "
                   f"{_fmt_spec(have)} != consumer spec {_fmt_spec(want)} "
                   f"— GSPMD inserts a hidden {kind} moving {full} bytes")
            if why:
                msg += f" ({why})"
            rep.findings.add(Finding("shard.propagate", "hidden-reshard",
                                     WARN, msg, node=node.name))

    def record_psum(node, ax, out_aval, out_spec, opname):
        """Contraction/reduction over a sharded axis -> all-reduce."""
        n = _axis_size(ax, axes)
        if n <= 1 or out_aval is None:
            return
        payload = _sharded_bytes(out_aval, out_spec, axes)
        rep.collectives.append({
            "node": node.name, "op": opname, "kind": "psum",
            "axis": ax if isinstance(ax, str) else str(ax),
            "bytes": payload,
            "ici_bytes": int(2 * (n - 1) * payload // n)})

    def fallback(node, opname, in_specs, in_avals, out_avals):
        """Unknown op: outputs replicate; the claim is recorded, and any
        sharded input is costed as an implied all-gather."""
        rep.fallback_ops[opname] = rep.fallback_ops.get(opname, 0) + 1
        for (src, idx), sp, a in zip(node.inputs, in_specs, in_avals):
            if any(e is not None for e in sp):
                record_reshard(node, src, sp,
                               (None,) * len(sp), a,
                               why=f"no propagation rule for op "
                                   f"'{opname}'; inputs gathered")
        out = []
        for a in out_avals:
            nd = len(a.shape) if a is not None else 0
            out.append((None,) * nd)
        return tuple(out)

    def join_specs(node, in_specs, in_avals, out_aval):
        """Dimension-wise union with trailing-dim broadcast alignment;
        conflicting inputs reshard to the first claimant's axis."""
        nd = len(out_aval.shape)
        out = [None] * nd
        for d in range(nd):
            for sp, a in zip(in_specs, in_avals):
                if a is None:
                    continue
                k = d - (nd - len(a.shape))
                if k < 0 or a.shape[k] != out_aval.shape[d]:
                    continue
                if sp[k] is not None:
                    out[d] = sp[k]
                    break
        out = _clamp_spec(tuple(out), out_aval.shape, axes)
        for (src, idx), sp, a in zip(node.inputs, in_specs, in_avals):
            if a is None or len(a.shape) == 0:
                continue
            off = nd - len(a.shape)
            want = tuple(out[off + k] if a.shape[k] == out_aval.shape[off + k]
                         else None for k in range(len(a.shape)))
            want = _clamp_spec(want, a.shape, axes)
            if sp != want:
                record_reshard(node, src, sp, want, a)
        return tuple(out)

    # ---- the walk ------------------------------------------------------
    for node in topo:
        if node.is_variable:
            continue
        opname = node.op.name
        in_specs = [spec_of(src, idx) for src, idx in node.inputs]
        in_avals = [avals_of(src)[idx] if idx < len(avals_of(src)) else None
                    for src, idx in node.inputs]
        out_avals = avals_of(node)
        out0 = out_avals[0]
        attrs = node.attrs

        out_specs = None
        nd_out = len(out0.shape) if out0 is not None else 0

        if opname in DOT_CLASS and opname in ("FullyConnected",
                                              "_contrib_quantized_fully_connected"):
            x_src, x_idx = node.inputs[0]
            xs, xa = in_specs[0], in_avals[0]
            ws = in_specs[1] if len(in_specs) > 1 else ()
            wa = in_avals[1] if len(in_avals) > 1 else None
            col = ws[0] if len(ws) > 0 else None   # (N, K): N sharded
            row = ws[1] if len(ws) > 1 else None   # (N, K): K sharded
            flatten = bool(attrs.get("flatten", True))
            if xa is not None and flatten and len(xa.shape) > 2 and \
                    any(e is not None for e in xs[1:]):
                # flatten folds dims 1.. into the contraction: any
                # sharding there must gather first
                want = (xs[0],) + (None,) * (len(xs) - 1)
                record_reshard(node, x_src, xs, want, xa,
                               why="flatten folds sharded dims into the "
                                   "contraction")
                xs = want
            batch_spec = tuple(xs[:-1]) if (xa is not None and
                                            len(xa.shape) > 1) else ()
            if flatten and nd_out == 2:
                batch_spec = (xs[0] if xs else None,)
            xk = xs[-1] if xs else None
            if row is not None:
                # row-parallel: contraction over the sharded K — the
                # operand must arrive K-sharded (backward inference),
                # and the partial products psum over the row axis
                want = batch_spec + (row,)
                if xa is not None and xs != want:
                    record_reshard(node, x_src, xs, want, xa,
                                   why="row-parallel contraction needs a "
                                       "K-sharded operand")
                out_spec = batch_spec + (col,)
                out_spec = _clamp_spec(out_spec, out0.shape, axes) \
                    if out0 is not None else out_spec
                record_psum(node, row, out0, out_spec, opname)
            else:
                if xk is not None and xk != row:
                    # contraction sharded on x but not on w: gather x
                    want = batch_spec + (None,)
                    record_reshard(node, x_src, xs, want, xa,
                                   why="contraction dim sharded on the "
                                       "operand but not the weight")
                out_spec = batch_spec + (col,)
                out_spec = _clamp_spec(out_spec, out0.shape, axes) \
                    if out0 is not None else out_spec
            # bias of a column-parallel FC is sliced along the output
            # dim (backward inference) — free, no finding
            out_specs = (out_spec,) + tuple(
                (None,) * len(a.shape) if a is not None else ()
                for a in out_avals[1:])

        elif opname in DOT_CLASS and opname in ("Convolution",
                                                "Deconvolution",
                                                "_contrib_quantized_conv"):
            xs, xa = in_specs[0], in_avals[0]
            ws = in_specs[1] if len(in_specs) > 1 else ()
            x_src, _ = node.inputs[0]
            if xa is not None and any(e is not None for e in xs[1:]):
                want = (xs[0],) + (None,) * (len(xs) - 1)
                record_reshard(node, x_src, xs, want, xa,
                               why="conv contracts channel/spatial dims")
                xs = want
            cout = ws[0] if len(ws) > 0 else None
            if len(ws) > 1 and any(e is not None for e in ws[1:]):
                w_src, _ = node.inputs[1]
                record_reshard(node, w_src, ws,
                               (ws[0],) + (None,) * (len(ws) - 1),
                               in_avals[1],
                               why="conv kernel contraction dims sharded")
            out_spec = ((xs[0] if xs else None, cout) +
                        (None,) * max(0, nd_out - 2))[:nd_out]
            out_spec = _clamp_spec(out_spec, out0.shape, axes) \
                if out0 is not None else tuple(out_spec)
            out_specs = (out_spec,)

        elif opname in DOT_CLASS:   # dot / batch_dot / linalg_gemm*
            xs = in_specs[0] if in_specs else ()
            ys = in_specs[1] if len(in_specs) > 1 else ()
            xk = xs[-1] if xs else None
            yk = ys[0] if ys else None
            out_spec = (tuple(xs[:-1]) + (ys[-1] if ys else None,)) \
                if nd_out else ()
            out_spec = out_spec[:nd_out] + (None,) * (nd_out - len(out_spec))
            out_spec = _clamp_spec(out_spec, out0.shape, axes) \
                if out0 is not None else out_spec
            if xk is not None and xk == yk:
                record_psum(node, xk, out0, out_spec, opname)
            elif xk is not None or yk is not None:
                for (src, idx), sp, a, want_last in (
                        (node.inputs[0], xs, in_avals[0], None),):
                    if sp and sp[-1] is not None:
                        record_reshard(node, src, sp,
                                       tuple(sp[:-1]) + (None,), a,
                                       why="mismatched contraction "
                                           "sharding")
            out_specs = (out_spec,)

        elif opname == "Embedding":
            tok_spec = in_specs[0] if in_specs else ()
            ws = in_specs[1] if len(in_specs) > 1 else ()
            vocab_ax = ws[0] if len(ws) > 0 else None
            feat_ax = ws[1] if len(ws) > 1 else None
            out_spec = tuple(tok_spec) + (feat_ax,)
            out_spec = out_spec[:nd_out] + (None,) * (nd_out - len(out_spec))
            out_spec = _clamp_spec(out_spec, out0.shape, axes) \
                if out0 is not None else out_spec
            if vocab_ax is not None:
                # vocab-sharded table: masked local lookup + psum
                record_psum(node, vocab_ax, out0, out_spec, opname)
            out_specs = (out_spec,)

        elif opname in ("Reshape", "Flatten", "reshape"):
            xs = in_specs[0] if in_specs else ()
            xa = in_avals[0] if in_avals else None
            out = [None] * nd_out
            if xa is not None and out0 is not None and len(xa.shape) and \
                    nd_out:
                in0, o0 = xa.shape[0], out0.shape[0]
                if o0 == in0 or (in0 and o0 % in0 == 0) or \
                        (o0 and in0 % o0 == 0):
                    out[0] = xs[0]   # merge/split keeps dim-0 sharding
                if nd_out > 1 and len(xa.shape) > 1 and \
                        out0.shape[-1] == xa.shape[-1]:
                    out[-1] = xs[-1]
                carried = {e for e in out if e is not None}
                lost = [e for e in xs if e is not None and e not in carried]
                if lost:
                    x_src, _ = node.inputs[0]
                    record_reshard(node, x_src, xs,
                                   tuple(out[:len(xs)]) +
                                   (None,) * max(0, len(xs) - nd_out), xa,
                                   why="reshape folds a sharded dim")
            out_spec = _clamp_spec(tuple(out), out0.shape, axes) \
                if out0 is not None else tuple(out)
            out_specs = (out_spec,)

        elif opname in ("transpose", "Transpose"):
            xs = in_specs[0] if in_specs else ()
            perm = attrs.get("axes") or tuple(reversed(range(len(xs))))
            out_spec = tuple(xs[p] if p < len(xs) else None for p in perm)
            out_specs = (_clamp_spec(out_spec, out0.shape, axes)
                         if out0 is not None else out_spec,)

        elif opname == "slice_axis":
            xs = list(in_specs[0]) if in_specs else []
            xa = in_avals[0] if in_avals else None
            ax = int(attrs.get("axis", 0))
            if xa is not None and ax < 0:
                ax += len(xa.shape)
            if 0 <= ax < len(xs) and xs[ax] is not None:
                x_src, _ = node.inputs[0]
                n = _axis_size(xs[ax], axes)
                if out0 is not None and out0.shape[ax] % n == 0:
                    # the slice re-partitions across the shard group
                    rep.reshards.append({
                        "src": x_src.name, "dst": node.name,
                        "kind": "slice", "bytes": _aval_bytes(out0),
                        "ici_bytes": 0,
                        "from": _fmt_spec(tuple(xs)),
                        "to": _fmt_spec(tuple(xs))})
                else:
                    record_reshard(node, x_src, tuple(xs),
                                   tuple(None if i == ax else e
                                         for i, e in enumerate(xs)), xa,
                                   why="slice boundary does not divide "
                                       "the shard grid")
                    xs[ax] = None
            out_spec = _clamp_spec(tuple(xs), out0.shape, axes) \
                if out0 is not None else tuple(xs)
            out_specs = (out_spec,)

        elif opname in _REDUCE_OPS:
            xs = in_specs[0] if in_specs else ()
            xa = in_avals[0] if in_avals else None
            ax_attr = attrs.get("axis")
            if ax_attr is None:
                reduced = set(range(len(xs)))
            else:
                ax_list = ax_attr if isinstance(ax_attr, (tuple, list)) \
                    else (ax_attr,)
                reduced = {a + len(xs) if a < 0 else a for a in
                           (int(a) for a in ax_list)}
            keepdims = bool(attrs.get("keepdims", False))
            out = []
            for i, e in enumerate(xs):
                if i in reduced:
                    if e is not None:
                        record_psum(node, e, out0,
                                    tuple(x for j, x in enumerate(xs)
                                          if j not in reduced), opname)
                    if keepdims:
                        out.append(None)
                else:
                    out.append(e)
            out_spec = tuple(out)[:nd_out] + \
                (None,) * max(0, nd_out - len(out))
            out_specs = (_clamp_spec(out_spec, out0.shape, axes)
                         if out0 is not None else out_spec,)

        elif opname == "BlockwiseAttention":
            joined = join_specs(node, in_specs, in_avals, out0) \
                if out0 is not None else ()
            out = list(joined)
            if len(out) >= 2 and out[1] is not None:
                # sequence-sharded attention needs ring attention; the
                # static model gathers instead
                q_src, _ = node.inputs[0]
                record_reshard(node, q_src, in_specs[0],
                               tuple(None if i == 1 else e
                                     for i, e in enumerate(in_specs[0])),
                               in_avals[0],
                               why="attention mixes the sequence dim")
                out[1] = None
            out_specs = (tuple(out),)

        elif opname in ("LayerNorm", "InstanceNorm", "L2Normalization",
                        "softmax", "log_softmax", "SoftmaxActivation"):
            xs = list(in_specs[0]) if in_specs else []
            xa = in_avals[0] if in_avals else None
            ax = int(attrs.get("axis", -1))
            if xa is not None and ax < 0:
                ax += len(xa.shape)
            if 0 <= ax < len(xs) and xs[ax] is not None:
                x_src, _ = node.inputs[0]
                record_reshard(node, x_src, tuple(xs),
                               tuple(None if i == ax else e
                                     for i, e in enumerate(xs)), xa,
                               why=f"{opname} normalizes over a sharded "
                                   f"dim")
                xs[ax] = None
            out_specs = tuple([tuple(xs)] +
                              [(None,) * len(a.shape) if a is not None
                               else () for a in out_avals[1:]])

        elif opname in ("SoftmaxOutput", "LinearRegressionOutput",
                        "LogisticRegressionOutput", "MAERegressionOutput",
                        "MakeLoss"):
            xs = list(in_specs[0]) if in_specs else []
            if opname == "SoftmaxOutput" and xs and xs[-1] is not None:
                # softmax normalizes over the class dim: vocab-sharded
                # logits gather first
                x_src, _ = node.inputs[0]
                record_reshard(node, x_src, tuple(xs),
                               tuple(xs[:-1]) + (None,), in_avals[0],
                               why="softmax normalizes over a sharded "
                                   "class dim")
                xs[-1] = None
            out_specs = (tuple(xs),)

        elif opname in ("BatchNorm", "BatchNorm_v1"):
            xs = in_specs[0] if in_specs else ()
            out_specs = tuple([tuple(xs)] +
                              [(None,) * len(a.shape) if a is not None
                               else () for a in out_avals[1:]])

        elif opname in _QUANT_PASS:
            xs = tuple(in_specs[0]) if in_specs else ()
            out_specs = tuple([_clamp_spec(xs, out0.shape, axes)
                               if out0 is not None else xs] +
                              [(None,) * len(a.shape) if a is not None
                               else () for a in out_avals[1:]])

        elif opname in _PASS_THROUGH:
            out_specs = tuple(tuple(in_specs[0]) if in_specs else ()
                              for _ in out_avals)

        elif opname in _SIZE_ALIGNED and in_avals and \
                in_avals[0] is not None and out0 is not None and \
                len(in_avals[0].shape) == nd_out:
            xs, xa = in_specs[0], in_avals[0]
            out_spec = tuple(xs[i] if xa.shape[i] == out0.shape[i] else None
                             for i in range(nd_out))
            out_specs = (_clamp_spec(out_spec, out0.shape, axes),)

        elif _is_elemwise(opname) and out0 is not None:
            out_specs = (join_specs(node, in_specs, in_avals, out0),)

        elif in_avals and in_avals[0] is not None and out0 is not None and \
                in_avals[0].shape == out0.shape and len(node.inputs) == 1:
            # shape-preserving unary op: specs survive
            out_specs = (tuple(in_specs[0]),)

        fell_back = out_specs is None
        if fell_back:
            out_specs = fallback(node, opname, in_specs, in_avals,
                                 out_avals)

        # pad/truncate to the real output count
        out_specs = tuple(out_specs)[:len(out_avals)]
        out_specs = out_specs + tuple(
            (None,) * (len(a.shape) if a is not None else 0)
            for a in out_avals[len(out_specs):])
        specs[id(node)] = out_specs
        rep.specs[node.name] = out_specs[0]

        # ---- dp-axis-leak: a batch-led output lost its dim-0 dp ------
        # (fallback nodes are already flagged shard-fallback; their
        # replication is a modeling upper bound, not a proven leak)
        if not fell_back and \
                dp_size > 1 and batch_size and out0 is not None and \
                len(out0.shape) and out0.shape[0] == batch_size and \
                out_specs[0] and out_specs[0][0] != batch_axis:
            fed_dp = any(sp and sp[0] == batch_axis and a is not None and
                         len(a.shape) and a.shape[0] == batch_size
                         for sp, a in zip(in_specs, in_avals))
            if fed_dp:
                rep.findings.add(Finding(
                    "shard.propagate", "dp-axis-leak", WARN,
                    f"op '{opname}' output is batch-led but dim 0 lost "
                    f"its '{batch_axis}' sharding; every device now "
                    f"computes the full batch downstream",
                    node=node.name))

    # ---- implicit replication -----------------------------------------
    nonbatch = any(sz > 1 for ax, sz in axes.items() if ax != batch_axis)
    if nonbatch:
        for n in topo:
            a = avals_of(n)[0]
            if a is None:
                continue
            sp = specs.get(id(n), ((None,) * len(a.shape),))[0]
            if any(e is not None for e in sp):
                continue
            nbytes = _aval_bytes(a)
            if nbytes < min_bytes:
                continue
            if n.is_variable and n.name not in step_inputs:
                rep.findings.add(Finding(
                    "shard.memory", "implicit-replication", WARN,
                    f"param '{n.name}' ({nbytes} bytes) is fully "
                    f"replicated while the mesh has a >1-device non-"
                    f"batch axis; every device holds a full copy",
                    node=n.name))
            elif not n.is_variable:
                rep.findings.add(Finding(
                    "shard.memory", "implicit-replication", WARN,
                    f"activation '{n.name}' ({nbytes} bytes) is fully "
                    f"replicated while the mesh has a >1-device non-"
                    f"batch axis", node=n.name))

    # ---- shard-fallback findings (one per op name) ---------------------
    for opname, count in sorted(rep.fallback_ops.items()):
        rep.findings.add(Finding(
            "shard.propagate", "shard-fallback", HINT,
            f"no propagation rule for op '{opname}' (x{count}); outputs "
            f"assumed replicated — per-device costs are upper bounds "
            f"there", node=opname))

    # ---- per-device peak HBM (sharded liveness) ------------------------
    rep.per_device_peak_hbm_bytes = _sharded_liveness(
        symbol, topo, env, specs, axes)
    rep.replicated_peak_hbm_bytes = _sharded_liveness(
        symbol, topo, env, None, axes)

    # ---- summary -------------------------------------------------------
    mesh_str = ",".join(f"{k}={v}" for k, v in axes.items())
    peak = rep.per_device_peak_hbm_bytes
    rep.findings.add(Finding(
        "shard.summary", "shard-summary", HINT,
        f"mesh {mesh_str or '(none)'}: per-device peak HBM "
        f"{(peak or 0) / _MB:.2f} MB "
        f"(replicated {(rep.replicated_peak_hbm_bytes or 0) / _MB:.2f} "
        f"MB), {len(rep.collectives)} tp/GSPMD collectives "
        f"({rep.ici_bytes_per_step} ICI bytes/step), "
        f"{len(rep.reshards)} reshard edges, "
        f"{sum(rep.fallback_ops.values())} fallback ops"))
    return rep


def _sharded_liveness(symbol, topo, env, specs, axes):
    """`cost._liveness_pass`'s walk with PER-DEVICE buffer sizes: every
    entry's bytes divide by its shard count (specs=None -> replicated
    sizes, i.e. the single-device peak)."""
    if any(env.get(id(n)) is None for n in topo):
        return None

    def nbytes(node, idx, aval):
        if aval is None:
            return 0
        if specs is None:
            return _aval_bytes(aval)
        sp = specs.get(id(node))
        spec = sp[idx] if sp is not None and idx < len(sp) else \
            (None,) * len(aval.shape)
        return _sharded_bytes(aval, spec, axes)

    pos = {id(n): i for i, n in enumerate(topo)}
    end = len(topo)
    last_use = {}
    for node in topo:
        for src, idx in node.inputs:
            key = (id(src), idx)
            last_use[key] = max(last_use.get(key, -1), pos[id(node)])
    for node, idx in symbol._entries:
        last_use[(id(node), idx)] = end

    entry_bytes = {}
    for node in topo:
        for i, a in enumerate(env[id(node)]):
            entry_bytes[(id(node), i)] = nbytes(node, i, a)

    var_ids = {id(n) for n in topo if n.is_variable}
    alive = sum(entry_bytes[(id(n), 0)] for n in topo if n.is_variable)
    peak = alive
    for i, node in enumerate(topo):
        if node.is_variable:
            continue
        alive += sum(entry_bytes[(id(node), k)]
                     for k in range(len(env[id(node)])))
        peak = max(peak, alive)
        for key, last in list(last_use.items()):
            if last == i:
                if key[0] not in var_ids:
                    alive -= entry_bytes.get(key, 0)
                del last_use[key]
    return int(peak)


# ---------------------------------------------------------------------------
# collectives: dp bucket plan + tp/GSPMD psums, one combined economy
# ---------------------------------------------------------------------------

def shard_collectives(symbol, shapes=None, mesh="dp=8", rules=None,
                      dtypes=None, cap_bytes=None, batch_axis="dp",
                      name=None, report=None):
    """The full per-step ICI economy of a sharded training step.

    The dp gradient exchange reuses `cost.enumerate_collectives` — the
    SAME `kvstore.plan_buckets` rule in the same reversed-parameter
    priority order, so the dp half is byte-exact against measured
    `KVStore.stats()` / `FusedTrainStep.pod_stats`.  Gradients of
    tp-sharded params exchange at their per-device shard size.  The
    tp/GSPMD half comes from the propagation pass (psums + reshard
    gathers).  Returns a dict; the ShardReport rides under "report"
    when the caller did not pass one in.
    """
    from .cost import enumerate_collectives
    rep = report if report is not None else analyze_sharding(
        symbol, shapes=shapes, mesh=mesh, rules=rules, dtypes=dtypes,
        batch_axis=batch_axis, name=name)
    axes = rep.mesh
    dp = _axis_size(batch_axis, axes)

    from . import graph_passes as gp
    topo = symbol._topo()
    env = gp._abstract_env(symbol, shapes, dtypes)
    step_inputs = {n.name for n in topo if n.is_variable and
                   (n.name.startswith("data") or n.name.endswith("_label")
                    or "state" in n.name)}
    grad_shapes, grad_dtypes = [], []
    for n in topo:
        if not n.is_variable or n.name in step_inputs:
            continue
        avals = env.get(id(n))
        a = avals[0] if avals else None
        if a is None:
            continue
        sp = rep.specs.get(n.name, (None,) * len(a.shape))
        shape = tuple(int(d) // _axis_size(ax, axes)
                      for d, ax in zip(a.shape, sp))
        grad_shapes.append(shape)
        grad_dtypes.append(np.dtype(a.dtype))

    dp_stats = None
    if dp > 1 and grad_shapes:
        dp_stats = enumerate_collectives(
            grad_shapes, dtypes=grad_dtypes, dp=dp, cap_bytes=cap_bytes,
            name=f"{rep.target}-dp")
    tp_ici = rep.ici_bytes_per_step
    total = tp_ici + (dp_stats["ici_bytes_per_chip"] if dp_stats else 0)
    return {
        "mesh": dict(axes),
        "dp": dp_stats,
        "tp": {"collectives_per_step": len(rep.collectives),
               "ici_bytes_per_step": tp_ici,
               "reshard_edges": len(rep.reshards)},
        "ici_bytes_per_step": int(total),
        "report": rep,
    }


# ---------------------------------------------------------------------------
# the bench set: what --shard-report and the budgets gate analyze
# ---------------------------------------------------------------------------

def lm_bench_symbol():
    """The committed LM bench program (small but tp-divisible)."""
    from ..llm.model import lm_symbol, LMConfig
    cfg = LMConfig(vocab_size=128, num_layers=2, num_heads=2, hidden=32,
                   max_len=32, eos_id=0)
    return lm_symbol(cfg), {"data": (8, 16), "softmax_label": (8, 16)}, \
        {"data": "int32", "softmax_label": "int32"}


def analyze_shard_bench_set(mesh="dp=2,tp=2", cap_bytes=None,
                            batch_axis="dp"):
    """Run mxshard over the committed bench programs: the three mxcost
    convnets under the mesh's dp axis (no rule set — a convnet under
    megatron rules is not a coverage gap, and dp params replicate by
    design) and the LM bench symbol under the full mesh with megatron
    rules.  Returns {name: result dict} ready for the budgets gate."""
    from .cost import bench_programs
    from ..parallel.tensor_parallel import ShardingRules
    axes = _mesh_axes(mesh)
    dp = _axis_size(batch_axis, axes)
    out = {}
    for pname, (sym, shapes, dtypes) in sorted(bench_programs().items()):
        stats = shard_collectives(
            sym, shapes=shapes, mesh={batch_axis: dp}, rules=None,
            dtypes=dtypes, cap_bytes=cap_bytes, batch_axis=batch_axis,
            name=pname)
        rep = stats.pop("report")
        entry = rep.as_dict()
        entry["collectives"] = stats
        entry["ici_bytes_per_step"] = stats["ici_bytes_per_step"]
        out[pname] = entry
    sym, shapes, dtypes = lm_bench_symbol()
    stats = shard_collectives(
        sym, shapes=shapes, mesh=axes,
        rules=ShardingRules.megatron(tp_axis="tp") if
        _axis_size("tp", axes) > 1 else None,
        dtypes=dtypes, cap_bytes=cap_bytes, batch_axis=batch_axis,
        name="llm.lm_micro")
    rep = stats.pop("report")
    entry = rep.as_dict()
    entry["collectives"] = stats
    entry["ici_bytes_per_step"] = stats["ici_bytes_per_step"]
    out["llm.lm_micro"] = entry
    return out


# ---------------------------------------------------------------------------
# budget gate (COST_BUDGETS.json "sharding" section)
# ---------------------------------------------------------------------------

_BUDGET_METRICS = ("per_device_peak_hbm_bytes", "ici_bytes_per_step")
# both metrics are fully static and deterministic: any growth is a real
# program change, so the tolerance is tight
_BUDGET_TOL = {"per_device_peak_hbm_bytes": 0.01,
               "ici_bytes_per_step": 0.01}


def snapshot_shard_budgets(results, mesh="dp=2,tp=2"):
    """The committed-baseline shape for COST_BUDGETS.json["sharding"]."""
    progs = {}
    for name, entry in sorted(results.items()):
        progs[name] = {m: int(entry.get(m) or 0) for m in _BUDGET_METRICS}
    return {"mesh": mesh if isinstance(mesh, str)
            else ",".join(f"{k}={v}" for k, v in _mesh_axes(mesh).items()),
            "programs": progs}


def check_shard_budgets(results, budgets):
    """Gate bench-set results against the committed baseline with the
    same `_compare` currency the mxcost budget gate uses."""
    from . import budgets as _budgets
    report = Report(target="shard-budgets")
    deltas = {}
    section = (budgets or {}).get("sharding", {})
    baseline = section.get("programs", {})
    for name, entry in sorted(results.items()):
        base = baseline.get(name)
        if base is None:
            report.add(Finding(
                "cost.budget", "budget-missing", HINT,
                f"no sharding baseline for program '{name}'; snapshot "
                f"with --write-budgets", node=name))
            continue
        for metric in _BUDGET_METRICS:
            if metric not in base:
                continue
            _budgets._compare(report, deltas, f"sharding.{name}", metric,
                              int(entry.get(metric) or 0), base[metric],
                              _BUDGET_TOL[metric], slack=False)
    return report, deltas


# ---------------------------------------------------------------------------
# measured cross-check: static dp plan vs a real KVStore push
# ---------------------------------------------------------------------------

def measured_ici_check(mesh="dp=4", cap_bytes=None, batch_axis="dp"):
    """Push the bench convnet's (per-device-sharded) gradients through a
    real device KVStore and compare the measured counters against the
    static dp plan.  Because `enumerate_collectives` applies the SAME
    `kvstore.plan_buckets` rule, the agreement is byte-exact — the
    returned ``agreement_pct`` is the CI gate (must be <= 10)."""
    import jax
    from .. import kvstore as _kvstore
    from .. import nd as _nd
    from ..context import tpu as _tpu
    from .cost import build_bench_convnet, BENCH_SHAPE

    axes = _mesh_axes(mesh)
    dp = _axis_size(batch_axis, axes)
    ndev = len(jax.devices())
    dp = max(1, min(dp, ndev))

    sym, shapes = build_bench_convnet("float32")
    kv = _kvstore.create("tpu")
    if cap_bytes is None:
        cap_bytes = kv._bucket_cap_bytes

    # the mesh the check runs under: the requested axes, with dp
    # clamped to the devices this host actually has
    axes = dict(axes)
    axes[batch_axis] = dp
    static = shard_collectives(sym, shapes=shapes, mesh=axes, rules=None,
                               dtypes=None, cap_bytes=cap_bytes,
                               batch_axis=batch_axis, name="convnet")
    rep = static["report"]

    # per-device gradient shapes (tp-sharded params exchange shards)
    arg_shapes, _, _ = sym.infer_shape(data=BENCH_SHAPE)
    grad_shapes = []
    for pname, shape in zip(sym.list_arguments(), arg_shapes):
        if pname == "data":
            continue
        sp = rep.specs.get(pname, (None,) * len(shape))
        grad_shapes.append(tuple(int(d) // _axis_size(ax, axes)
                                 for d, ax in zip(shape, sp)))
    devs = [_tpu(i) for i in range(dp)]
    keys = [str(i) for i in range(len(grad_shapes))]
    for k, s in zip(keys, grad_shapes):
        kv.init(k, _nd.zeros(s))
    vals = [[_nd.ones(s, ctx=d) for d in devs] for s in grad_shapes]
    kv.push(keys, vals)
    meas = kv.stats()
    dp_stats = static["dp"] or {}
    measured_bytes = int(meas["bytes_reduced"])
    static_bytes = int(dp_stats.get("bytes_per_step") or 0)
    agreement = abs(static_bytes - measured_bytes) * 100.0 / \
        max(1, measured_bytes)
    return {
        "mesh": dict(axes),
        "dp": dp,
        "static_bytes_per_step": static_bytes,
        "measured_bytes_per_step": measured_bytes,
        "static_collectives_per_step":
            int(dp_stats.get("collectives_per_step") or 0),
        "measured_allreduce_dispatches":
            int(meas["allreduce_dispatches"]),
        "agreement_pct": round(agreement, 3),
        "ok": agreement <= 10.0 and
            int(dp_stats.get("collectives_per_step") or 0) ==
            int(meas["allreduce_dispatches"]),
    }
