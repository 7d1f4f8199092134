"""mxcost — static graph cost & communication analysis.

The runtime only reveals cost problems after the fact: an int8 graph
that dequantizes before its dot computes in fp32, a blocking h2d copy
caps the input path, and the pod fast path's whole value is its
O(buckets) collective economy — yet none of those could be predicted
(or guarded) before a run.  mxcost is the predictive half: it
walks Symbol graphs and traced jaxprs and derives, per program,

* **per-op FLOPs / bytes-moved / arithmetic intensity** with a roofline
  classification against a device profile (TVM's per-op cost-model
  stance, PAPERS.md) — `analyze_symbol`, `analyze_callable`;
* a **dtype-flow pass** tracking precision through the graph: the
  ``dequantize → fp32 dot`` chains that are the static signature of the
  int8-slower-than-fp32 defect, quantized ops whose registered compute
  dtype is fp32, and f32 upcasts feeding fp32 dots inside bf16 graphs;
* a **collective enumerator** — `enumerate_collectives` applies the
  SAME `kvstore.plan_buckets` rule (and priority order) the runtime
  scheduler and the pod fast path use, so collectives-per-step and
  bytes-on-the-ICI are derived statically and cross-check against
  `KVStore.stats()` measured counters (the MLPerf-pods paper treats
  per-step communication bytes as a first-class budget);
* a **liveness / peak-HBM pass** with donation-opportunity findings
  (step-boundary buffers that die mid-program but are not donated);
* **hidden host-transfer detection** — callback primitives inside a
  traced program (the jaxpr side; `source_lint`'s
  ``host-transfer-in-graph`` is the AST side of the same hazard).

Results are ordinary `Finding`s/`Report`s, so they compose with every
other pass: ``tools/mxlint.py --cost-report`` renders them, and
`analysis/budgets.py` turns a committed ``COST_BUDGETS.json`` baseline
into hard CI failures on regression (new collectives, +bytes/step,
+peak HBM, new dequant chains).
"""
from __future__ import annotations

import functools
import math

import numpy as _np

from .findings import Finding, Report, ERROR, WARN, HINT

__all__ = ["DeviceProfile", "PROFILES", "get_profile", "OpCost",
           "ProgramCost", "analyze_symbol", "analyze_callable",
           "analyze_jaxpr", "jaxpr_dying_inputs", "enumerate_collectives",
           "analyze_executor", "build_bench_convnet", "bench_programs",
           "analyze_bench_set", "CODES"]

# every code the cost passes emit (the findings.CODE_TABLE cross-check)
CODES = ("cost-summary", "dequant-fp32-dot", "quantized-fp32-compute",
         "f32-upcast-in-bf16", "hidden-host-transfer",
         "donation-opportunity", "collective-summary",
         "collective-o-params")


# ---------------------------------------------------------------------------
# device profiles
# ---------------------------------------------------------------------------

class DeviceProfile:
    """Peak numbers the roofline classifies against.  Values are the
    published per-chip peaks (approximate by design: the classification
    needs the right order of magnitude, not the datasheet's third
    digit).  Note v3 has NO int8 MXU speedup — int8 peak == bf16 peak —
    which is exactly why a dequant/requant round trip makes int8
    slower, never faster, there."""

    __slots__ = ("name", "peak_flops", "hbm_bps", "ici_bps", "hbm_bytes")

    def __init__(self, name, peak_flops, hbm_bps, ici_bps, hbm_bytes):
        self.name = name
        self.peak_flops = dict(peak_flops)   # dtype name -> flops/s
        self.hbm_bps = float(hbm_bps)        # bytes/s
        self.ici_bps = float(ici_bps)        # bytes/s per link
        self.hbm_bytes = int(hbm_bytes)

    def peak(self, dtype):
        key = _dtype_key(dtype)
        if key in self.peak_flops:
            return self.peak_flops[key]
        if key.startswith("int") or key.startswith("uint"):
            return self.peak_flops.get("int8",
                                       self.peak_flops["float32"])
        if key == "float64":
            return self.peak_flops["float32"] / 10.0  # emulated
        return self.peak_flops.get("float32")

    def ridge(self, dtype):
        """Arithmetic intensity (flops/byte) above which `dtype` math is
        compute-bound on this device."""
        return self.peak(dtype) / self.hbm_bps

    def as_dict(self):
        return {"name": self.name, "peak_flops": dict(self.peak_flops),
                "hbm_gbps": self.hbm_bps / 1e9,
                "ici_gbps": self.ici_bps / 1e9,
                "hbm_gib": self.hbm_bytes / (1 << 30)}


PROFILES = {
    "tpu-v3": DeviceProfile(
        "tpu-v3",
        {"bfloat16": 123e12, "float32": 16e12, "int8": 123e12},
        hbm_bps=900e9, ici_bps=100e9, hbm_bytes=32 << 30),
    "tpu-v4": DeviceProfile(
        "tpu-v4",
        {"bfloat16": 275e12, "float32": 34e12, "int8": 275e12},
        hbm_bps=1200e9, ici_bps=100e9, hbm_bytes=32 << 30),
    # the CI host: classification sanity only, not a perf claim
    "cpu-host": DeviceProfile(
        "cpu-host", {"bfloat16": 100e9, "float32": 200e9, "int8": 400e9},
        hbm_bps=20e9, ici_bps=5e9, hbm_bytes=8 << 30),
}


def get_profile(name=None):
    if isinstance(name, DeviceProfile):
        return name
    if name is None:
        from .. import config as _config
        name = _config.get("MXNET_COST_PROFILE")
    try:
        return PROFILES[name]
    except KeyError:
        raise ValueError(f"unknown device profile {name!r} "
                         f"(have {sorted(PROFILES)})") from None


def _dtype_key(dtype):
    try:
        return _np.dtype(dtype).name
    except TypeError:
        return str(dtype)   # bfloat16 (ml_dtypes) has a numpy dtype; a
                            # bare string falls through unchanged


def _aval_bytes(aval):
    size = int(_np.prod(aval.shape)) if getattr(aval, "shape", ()) else 1
    try:
        item = _np.dtype(aval.dtype).itemsize
    except TypeError:
        item = 4
    return size * item


def _aval_elems(aval):
    return int(_np.prod(aval.shape)) if getattr(aval, "shape", ()) else 1


# ---------------------------------------------------------------------------
# per-op cost records
# ---------------------------------------------------------------------------

class OpCost:
    """One node's static cost: flops, bytes moved, intensity, bound."""

    __slots__ = ("node", "op", "flops", "bytes_in", "bytes_out",
                 "compute_dtype", "ai", "bound")

    def __init__(self, node, op, flops, bytes_in, bytes_out,
                 compute_dtype, ai, bound):
        self.node = node
        self.op = op
        self.flops = flops
        self.bytes_in = bytes_in
        self.bytes_out = bytes_out
        self.compute_dtype = compute_dtype
        self.ai = ai
        self.bound = bound   # "compute" | "memory" | "trivial" | "host"

    @property
    def bytes_moved(self):
        return self.bytes_in + self.bytes_out

    def as_dict(self):
        return {"node": self.node, "op": self.op, "flops": self.flops,
                "bytes_moved": self.bytes_moved,
                "compute_dtype": self.compute_dtype,
                "arithmetic_intensity": round(self.ai, 3),
                "bound": self.bound}


class ProgramCost:
    """The analyzer's result for one program: totals, the roofline
    classification, the counters the budget gate compares, and the
    findings (a plain `Report`, so it rides mxlint/runtime_report)."""

    def __init__(self, name, profile):
        self.name = name
        self.profile = profile
        self.per_op = []          # [OpCost]
        self.unknown_ops = 0      # nodes whose avals could not be solved
        self.param_bytes = 0
        self.peak_hbm_bytes = None
        self.collectives = None   # enumerate_collectives() dict
        self.counters = {"dequant_fp32_dot": 0, "quantized_fp32_compute": 0,
                         "f32_upcasts": 0, "host_transfers": 0}
        self.report = Report(target=name)

    # -- totals ---------------------------------------------------------------
    @property
    def flops(self):
        return sum(c.flops for c in self.per_op)

    @property
    def bytes_moved(self):
        return sum(c.bytes_moved for c in self.per_op)

    @property
    def arithmetic_intensity(self):
        b = self.bytes_moved
        return self.flops / b if b else 0.0

    def dominant_dtype(self):
        """Compute dtype carrying the most flops (the roofline row the
        program as a whole is judged against)."""
        by = {}
        for c in self.per_op:
            by[c.compute_dtype] = by.get(c.compute_dtype, 0) + c.flops
        return max(by, key=by.get) if by else "float32"

    def step_time_lb_s(self):
        """Roofline lower bound: the program can never run faster than
        max(flops at peak, bytes at HBM bandwidth)."""
        dt = self.dominant_dtype()
        t_flops = self.flops / self.profile.peak(dt)
        t_mem = self.bytes_moved / self.profile.hbm_bps
        return max(t_flops, t_mem)

    @property
    def bound(self):
        dt = self.dominant_dtype()
        t_flops = self.flops / self.profile.peak(dt)
        t_mem = self.bytes_moved / self.profile.hbm_bps
        if self.counters["host_transfers"]:
            return "host"
        return "compute" if t_flops >= t_mem else "memory"

    def bound_fracs(self):
        total = sum(c.flops for c in self.per_op) or 1
        out = {}
        for c in self.per_op:
            out[c.bound] = out.get(c.bound, 0) + c.flops
        return {k: round(v / total, 4) for k, v in out.items()}

    def as_dict(self, top=8):
        d = {
            "name": self.name,
            "profile": self.profile.name,
            "ops": len(self.per_op),
            "unknown_ops": self.unknown_ops,
            "flops": int(self.flops),
            "bytes_moved": int(self.bytes_moved),
            "param_bytes": int(self.param_bytes),
            "peak_hbm_bytes": (None if self.peak_hbm_bytes is None
                               else int(self.peak_hbm_bytes)),
            "arithmetic_intensity": round(self.arithmetic_intensity, 3),
            "dominant_dtype": self.dominant_dtype(),
            "bound": self.bound,
            "step_time_lb_ms": round(self.step_time_lb_s() * 1e3, 6),
            "bound_fracs": self.bound_fracs(),
            "counters": dict(self.counters),
            "top_ops": [c.as_dict() for c in sorted(
                self.per_op, key=lambda c: -c.flops)[:top]],
            "findings": [f.as_dict() for f in self.report],
        }
        if self.collectives is not None:
            d["collectives"] = dict(self.collectives)
        return d


# ---------------------------------------------------------------------------
# FLOPs rules (symbol ops).  An op can override via OpDef.cost_meta
# {"flops": fn(params, in_avals, out_avals) -> float,
#  "compute_dtype": "float32" | fn(...) -> str} — ops/quantization.py
# registers exactly that metadata (its int8 ops compute in fp32 on this
# design, which IS the defect mxcost exists to flag).
# ---------------------------------------------------------------------------

# ops that lower to MXU matmul/conv work — the roofline's compute rows,
# and the targets the dtype-flow chains are walked toward
DOT_CLASS = frozenset({
    "FullyConnected", "Convolution", "Deconvolution", "dot", "batch_dot",
    "linalg_gemm", "linalg_gemm2", "RNN",
    "_contrib_quantized_fully_connected", "_contrib_quantized_conv",
})

# ops the dequant/upcast chain walk treats as pass-through (everything
# that is not dot-class is traversed; this set exists only for docs)
_QUANT_OPS = frozenset({"_contrib_quantize", "_contrib_quantize_v2",
                        "quantize", "_contrib_requantize"})
_DEQUANT_OPS = frozenset({"_contrib_dequantize", "dequantize"})
_CAST_OPS = frozenset({"Cast", "cast", "amp_cast"})


def _sym_flops(node, in_avals, out_avals):
    """FLOPs of one symbol node from its solved input/output avals."""
    meta = getattr(node.op, "cost_meta", None) or {}
    rule = meta.get("flops")
    if rule is not None:
        try:
            return float(rule(node.attrs, in_avals, out_avals))
        except Exception:
            pass
    op = node.op.name
    out_elems = sum(_aval_elems(a) for a in out_avals if a is not None)
    if op in ("FullyConnected", "_contrib_quantized_fully_connected"):
        w = in_avals[1]
        return 2.0 * _aval_elems(out_avals[0]) * int(w.shape[-1])
    if op in ("Convolution", "Deconvolution", "_contrib_quantized_conv"):
        w = in_avals[1]
        # per output element: 2 * (in_features/group) * kernel volume
        return 2.0 * _aval_elems(out_avals[0]) * \
            (_aval_elems(w) / int(w.shape[0]))
    if op in ("dot", "batch_dot", "linalg_gemm", "linalg_gemm2"):
        k = int(in_avals[0].shape[-1]) if in_avals[0].shape else 1
        return 2.0 * _aval_elems(out_avals[0]) * k
    if op == "RNN":
        # 4 gate matmuls per step per direction, dominated by h*h
        try:
            h = int(node.attrs.get("state_size"))
            return 8.0 * out_elems * h
        except (TypeError, ValueError):
            return 8.0 * out_elems
    if op in ("Pooling", "_contrib_quantized_pooling"):
        kern = node.attrs.get("kernel") or ()
        kvol = int(_np.prod(kern)) if kern else 1
        if node.attrs.get("global_pool") and in_avals:
            kvol = max(1, _aval_elems(in_avals[0]) //
                       max(1, _aval_elems(out_avals[0])))
        return float(out_elems * kvol)
    if op in ("BatchNorm", "LayerNorm", "InstanceNorm", "L2Normalization",
              "RMSNorm"):
        return 8.0 * out_elems
    if op in ("softmax", "Softmax", "SoftmaxOutput", "SoftmaxActivation",
              "log_softmax"):
        return 4.0 * out_elems
    if op in _QUANT_OPS or op in _DEQUANT_OPS:
        return 3.0 * out_elems   # scale + clip/round per element
    return float(out_elems)      # elementwise default: 1 flop/element


def _compute_dtype(node, in_avals, out_avals):
    """The dtype the node's arithmetic actually runs in.  Op metadata
    wins (quantized ops DECLARE fp32 compute); otherwise the widest
    floating dtype among the solved avals, else the output dtype."""
    meta = getattr(node.op, "cost_meta", None) or {}
    declared = meta.get("compute_dtype")
    if callable(declared):
        try:
            declared = declared(node.attrs, in_avals, out_avals)
        except Exception:
            declared = None
    if declared:
        return str(declared)
    widest, width = None, -1
    for a in list(in_avals) + list(out_avals):
        if a is None:
            continue
        key = _dtype_key(a.dtype)
        if key.startswith(("float", "bfloat")):
            w = _np.dtype(a.dtype).itemsize if key != "bfloat16" else 2
            if w > width:
                widest, width = key, w
    if widest is not None:
        return widest
    return _dtype_key(out_avals[0].dtype) if out_avals and \
        out_avals[0] is not None else "float32"


_TRIVIAL_BYTES = 4 << 10   # below this, dispatch overhead dominates


def _classify(op_name, flops, bytes_moved, compute_dtype, profile):
    if bytes_moved <= _TRIVIAL_BYTES:
        return "trivial"
    ai = flops / max(1, bytes_moved)
    return "compute" if ai >= profile.ridge(compute_dtype) else "memory"


# ---------------------------------------------------------------------------
# symbol analysis
# ---------------------------------------------------------------------------

def analyze_symbol(symbol, shapes=None, dtypes=None, profile=None,
                   target=None, step_inputs=None):
    """Static cost analysis of a Symbol graph.

    Parameters
    ----------
    symbol : Symbol
    shapes : {var_name: shape} seeding abstract evaluation (same
        convention as `infer_shape` kwargs / `analysis.check`).
    dtypes : {var_name: dtype} — seeds variable dtypes that are not
        declared on the graph (a quantized model's int8 weights live in
        its params dict, not its variable attrs).
    profile : DeviceProfile or name (default: MXNET_COST_PROFILE).
    step_inputs : iterable of variable names refilled every step (data/
        label batches).  Default: ``data*`` and ``*_label`` variables.
        These are the donation-opportunity candidates — their buffers
        die inside the step by definition.
    """
    from .graph_passes import _abstract_env

    profile = get_profile(profile)
    topo = symbol._topo()
    name = target or "symbol"
    prog = ProgramCost(name, profile)
    try:
        env = _abstract_env(symbol, shapes, dtypes=dtypes)
    except Exception:
        env = {}

    def avals_of(node):
        return env.get(id(node))

    # -- per-op cost ---------------------------------------------------------
    for node in topo:
        if node.is_variable:
            avals = avals_of(node)
            if avals and avals[0] is not None:
                prog.param_bytes += _aval_bytes(avals[0])
            continue
        out_avals = avals_of(node)
        in_avals = []
        for src, idx in node.inputs:
            e = avals_of(src)
            in_avals.append(e[idx] if e and idx < len(e) else None)
        if out_avals is None or any(a is None for a in in_avals):
            prog.unknown_ops += 1
            continue
        flops = _sym_flops(node, in_avals, out_avals)
        b_in = sum(_aval_bytes(a) for a in in_avals)
        b_out = sum(_aval_bytes(a) for a in out_avals if a is not None)
        cdt = _compute_dtype(node, in_avals, out_avals)
        bound = _classify(node.op.name, flops, b_in + b_out, cdt, profile)
        prog.per_op.append(OpCost(node.name, node.op.name, flops, b_in,
                                  b_out, cdt, flops / max(1, b_in + b_out),
                                  bound))

    _dtype_flow_pass(symbol, topo, env, prog)
    _liveness_pass(symbol, topo, env, prog, step_inputs)
    prog.report.add(Finding(
        "cost.roofline", "cost-summary", HINT,
        "%s: %d op(s), %.3g GFLOPs, %.3g MB moved, AI %.1f flops/byte "
        "-> %s-bound on %s (%s); step >= %.3g ms; peak HBM %s"
        % (name, len(prog.per_op), prog.flops / 1e9,
           prog.bytes_moved / (1 << 20), prog.arithmetic_intensity,
           prog.bound, profile.name, prog.dominant_dtype(),
           prog.step_time_lb_s() * 1e3,
           "?" if prog.peak_hbm_bytes is None
           else "%.2f MB" % (prog.peak_hbm_bytes / (1 << 20))),
        location=name))
    return prog


# -- dtype flow --------------------------------------------------------------

def _consumer_map(topo):
    out = {}
    for node in topo:
        for src, idx in node.inputs:
            out.setdefault(id(src), []).append(node)
    return out


def _walk_to_dot(start, consumers):
    """BFS forward from `start` to the nearest dot-class node; returns
    (target_node, [path names start..target]) or (None, None).  The walk
    traverses everything that is NOT dot-class (quantize ops, pooling,
    reshapes, activations — the 'transparent' chain links)."""
    from collections import deque
    prev = {id(start): None}
    by_id = {id(start): start}
    q = deque([start])
    while q:
        node = q.popleft()
        for c in consumers.get(id(node), ()):
            if id(c) in prev:
                continue
            prev[id(c)] = id(node)
            by_id[id(c)] = c
            if not c.is_variable and c.op.name in DOT_CLASS:
                path, cur = [], id(c)
                while cur is not None:
                    path.append(by_id[cur].name)
                    cur = prev[cur]
                return c, list(reversed(path))
            q.append(c)
    return None, None


def _node_compute_dtype(node, env):
    in_avals = []
    for src, idx in node.inputs:
        e = env.get(id(src))
        in_avals.append(e[idx] if e and idx < len(e) else None)
    out_avals = env.get(id(node)) or ()
    return _compute_dtype(node, [a for a in in_avals if a is not None],
                          [a for a in out_avals if a is not None])


def _dtype_flow_pass(symbol, topo, env, prog):
    """Precision-lattice findings: dequantize chains that end in an
    fp32 dot, quantized ops that declare fp32 compute, and f32 upcasts
    feeding fp32 dots inside bf16-dominant graphs."""
    consumers = _consumer_map(topo)

    for node in topo:
        if node.is_variable:
            continue
        op = node.op.name
        # (1) the int8-slower-than-fp32 signature: int8 values round-trip
        # through fp32 on their way into the next dot
        if op in _DEQUANT_OPS:
            tgt, path = _walk_to_dot(node, consumers)
            if tgt is not None and \
                    _node_compute_dtype(tgt, env) == "float32":
                prog.counters["dequant_fp32_dot"] += 1
                prog.report.add(Finding(
                    "cost.dtype", "dequant-fp32-dot", WARN,
                    "dequantized values from '%s' reach '%s' (%s) which "
                    "computes in float32 (chain: %s): the int8 path "
                    "round-trips through fp32 before the next dot — the "
                    "static signature of the int8-slower-than-fp32 "
                    "defect; fuse the scale into the dot epilogue "
                    "instead of dequantizing between quantized ops"
                    % (node.name, tgt.name, tgt.op.name,
                       " -> ".join(path)), node=node.name))
        # (2) the defect's other half: an "int8" op whose registered
        # compute dtype is fp32 never sees int8 MXU throughput
        meta = getattr(node.op, "cost_meta", None) or {}
        if meta.get("quantized") and \
                _node_compute_dtype(node, env) == "float32" and \
                op in DOT_CLASS:
            prog.counters["quantized_fp32_compute"] += 1
            prog.report.add(Finding(
                "cost.dtype", "quantized-fp32-compute", WARN,
                "quantized op '%s' (%s) registers float32 compute: the "
                "int8 inputs are upcast and the matmul/conv runs at the "
                "fp32 MXU rate — int8 buys bandwidth here, never "
                "compute; lower to a native int8 dot with a fused "
                "scale/dequant epilogue" % (node.name, op),
                node=node.name))
        # (3) an explicit bf16 -> f32 upcast feeding an fp32 dot: the
        # producer already computed the value in bf16, so the MXU could
        # have run the downstream dot at the bf16 rate — the upcast
        # forces ~8x fp32 throughput (a clean bf16 graph has no such
        # cast, and a cast feeding only a head/loss never reaches a dot)
        if op in _CAST_OPS:
            in_aval = None
            src, idx = node.inputs[0]
            e = env.get(id(src))
            if e and idx < len(e):
                in_aval = e[idx]
            out_avals = env.get(id(node))
            if in_aval is not None and out_avals and \
                    out_avals[0] is not None and \
                    _dtype_key(in_aval.dtype) == "bfloat16" and \
                    _dtype_key(out_avals[0].dtype) == "float32":
                tgt, path = _walk_to_dot(node, consumers)
                if tgt is not None and \
                        _node_compute_dtype(tgt, env) == "float32":
                    prog.counters["f32_upcasts"] += 1
                    prog.report.add(Finding(
                        "cost.dtype", "f32-upcast-in-bf16", WARN,
                        "'%s' upcasts bfloat16 to float32 and the value "
                        "reaches '%s' (%s) as an fp32 dot (chain: %s): "
                        "that dot pays the fp32 MXU rate (~8x slower "
                        "than bf16) for a value the graph already "
                        "computed in bf16 — keep the chain bf16 or "
                        "cast after the dot"
                        % (node.name, tgt.name, tgt.op.name,
                           " -> ".join(path)), node=node.name))


# -- liveness / peak HBM -----------------------------------------------------

def _liveness_pass(symbol, topo, env, prog, step_inputs):
    """Allocate outputs in topo order, free TRANSIENTS after their last
    consumer, track the high-water mark.  Conservative on both sides:
    a node's outputs allocate before its inputs free (XLA cannot alias
    in general), and variable buffers (params, step inputs) are never
    freed — the caller holds them, so without donation they stay
    resident for the whole program even after their last graph use."""
    from .. import config as _config
    if any(env.get(id(n)) is None for n in topo):
        return   # partial inference: a peak claim would be fiction
    pos = {id(n): i for i, n in enumerate(topo)}
    end = len(topo)
    last_use = {}
    for node in topo:
        for src, idx in node.inputs:
            key = (id(src), idx)
            last_use[key] = max(last_use.get(key, -1), pos[id(node)])
    for node, idx in symbol._entries:       # heads live to the end
        last_use[(id(node), idx)] = end
    last_use_full = dict(last_use)

    entry_bytes = {}
    for node in topo:
        avals = env.get(id(node))
        for i, a in enumerate(avals):
            entry_bytes[(id(node), i)] = _aval_bytes(a)

    # every variable (params + step inputs) is resident at dispatch —
    # and stays resident: undonated caller-held buffers never free
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
                if key[0] not in var_ids:   # transients only
                    alive -= entry_bytes.get(key, 0)
                del last_use[key]
    prog.peak_hbm_bytes = peak

    # donation opportunities: step-boundary inputs whose buffer dies
    # mid-program but is re-staged from host every step anyway
    if step_inputs is None:
        step_inputs = {n.name for n in topo if n.is_variable and
                       (n.name.startswith("data") or
                        n.name.endswith("_label") or
                        "state" in n.name)}
    else:
        step_inputs = set(step_inputs)
    min_bytes = int(float(_config.get("MXNET_COST_DONATE_MIN_MB"))
                    * (1 << 20))
    for node in topo:
        if not node.is_variable or node.name not in step_inputs:
            continue
        nbytes = entry_bytes.get((id(node), 0), 0)
        died = last_use_full.get((id(node), 0), end) < end
        if nbytes >= min_bytes and died:
            prog.report.add(Finding(
                "cost.memory", "donation-opportunity", HINT,
                "step input '%s' (%.2f MB) dies inside the step but is "
                "re-staged from host every dispatch — donating its "
                "buffer lets XLA reuse the space in-place "
                "(donate_argnums / the fused step's donated carry)"
                % (node.name, nbytes / (1 << 20)), node=node.name))


# ---------------------------------------------------------------------------
# jaxpr analysis (traced fused steps / plain jax callables)
# ---------------------------------------------------------------------------

_COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_to_all", "ppermute", "pmax", "pmin",
    "reduce_scatter", "psum_scatter", "allreduce", "all_reduce"})
_HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_local_array_to_global_array", "outside_call"})
_DOT_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


def _subjaxprs(params):
    for v in params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def analyze_jaxpr(closed, name="jaxpr", profile=None, donated=()):
    """Walk a (Closed)Jaxpr's equations: per-primitive flops/bytes with
    the same roofline classification as the symbol side, collective
    binds counted with their payload bytes, and callback primitives
    flagged as hidden host transfers.  `scan` bodies multiply by trip
    count; `cond` branches all count (a deliberate upper bound)."""
    profile = get_profile(profile)
    prog = ProgramCost(name, profile)
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    coll = {"count": 0, "bytes": 0}

    def var_bytes(atoms):
        return sum(_aval_bytes(a.aval) for a in atoms
                   if hasattr(a, "aval"))

    def walk(jx, mult):
        for eqn in jx.eqns:
            p = eqn.primitive.name
            if p == "scan":
                length = int(eqn.params.get("length", 1))
                for sub in _subjaxprs(eqn.params):
                    walk(sub, mult * length)
                continue
            if p in ("while", "cond", "pjit", "closed_call", "core_call",
                     "custom_jvp_call", "custom_vjp_call",
                     "custom_vjp_call_jaxpr", "remat", "remat2",
                     "checkpoint", "shard_map", "named_call", "xla_call"):
                for sub in _subjaxprs(eqn.params):
                    walk(sub, mult)
                continue
            b_in = var_bytes(eqn.invars)
            b_out = var_bytes(eqn.outvars)
            out_elems = sum(_aval_elems(a.aval) for a in eqn.outvars
                            if hasattr(a, "aval"))
            if p in _HOST_PRIMS:
                prog.counters["host_transfers"] += mult
                prog.report.add(Finding(
                    "cost.host", "hidden-host-transfer", WARN,
                    "primitive '%s' inside traced program '%s' crosses "
                    "to the host (%.1f KB per call%s): the device "
                    "pipeline stalls on the round trip every step — "
                    "move the computation in-graph or hoist it out of "
                    "the traced region"
                    % (p, name, (b_in + b_out) / 1024.0,
                       ", x%d via scan" % mult if mult > 1 else ""),
                    location=name))
                prog.per_op.append(OpCost(p, p, 0.0, b_in * mult,
                                          b_out * mult, "float32", 0.0,
                                          "host"))
                continue
            if p in _COLLECTIVE_PRIMS:
                coll["count"] += mult
                coll["bytes"] += mult * b_in
                continue
            if p == "dot_general":
                (lc, _rc), _batch = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval
                k = int(_np.prod([lhs.shape[d] for d in lc])) or 1
                flops = 2.0 * out_elems * k
            elif p == "conv_general_dilated":
                rhs = eqn.invars[1].aval
                dn = eqn.params["dimension_numbers"]
                o_feat = rhs.shape[dn.rhs_spec[0]]
                flops = 2.0 * out_elems * (_aval_elems(rhs) /
                                           max(1, o_feat))
            elif p.startswith("reduce_") or p in ("argmax", "argmin"):
                flops = float(sum(_aval_elems(a.aval)
                                  for a in eqn.invars
                                  if hasattr(a, "aval")))
            else:
                flops = float(out_elems)
            flops *= mult
            cdt = "float32"
            for a in list(eqn.invars) + list(eqn.outvars):
                if hasattr(a, "aval"):
                    key = _dtype_key(a.aval.dtype)
                    if key.startswith(("float", "bfloat")):
                        cdt = key
                        break
            bound = _classify(p, flops, (b_in + b_out) * mult, cdt,
                              profile)
            prog.per_op.append(OpCost(p, p, flops, b_in * mult,
                                      b_out * mult, cdt,
                                      flops / max(1, (b_in + b_out) * mult),
                                      bound))

    walk(jaxpr, 1)
    if coll["count"]:
        prog.collectives = {"collectives_per_step": coll["count"],
                            "bytes_per_step": coll["bytes"]}
    # donation opportunities: an input aval that matches an output aval
    # and is not donated could carry the result in place
    donated = set(donated)
    out_avals = [v.aval for v in jaxpr.outvars if hasattr(v, "aval")]
    for i, v in enumerate(jaxpr.invars):
        if i in donated or not hasattr(v, "aval"):
            continue
        a = v.aval
        if _aval_bytes(a) < (1 << 20):
            continue
        if any(o.shape == a.shape and o.dtype == a.dtype
               for o in out_avals):
            prog.report.add(Finding(
                "cost.memory", "donation-opportunity", HINT,
                "input %d (%s%s, %.2f MB) matches an output aval but is "
                "not donated: the step pays a full extra buffer where "
                "donate_argnums would update in place"
                % (i, _dtype_key(a.dtype), list(a.shape),
                   _aval_bytes(a) / (1 << 20)), location=name))
    prog.report.add(Finding(
        "cost.roofline", "cost-summary", HINT,
        "%s: %d eqn(s), %.3g GFLOPs, %.3g MB moved, AI %.1f -> %s-bound"
        % (name, len(prog.per_op), prog.flops / 1e9,
           prog.bytes_moved / (1 << 20), prog.arithmetic_intensity,
           prog.bound), location=name))
    return prog


def jaxpr_dying_inputs(closed, indices=None):
    """Flat input positions whose buffers provably DIE inside the traced
    program: the invar is never aliased straight through to an outvar,
    so donating that argument lets XLA reuse its buffer for
    intermediates (lower peak HBM, no copy).  `indices` restricts the
    check to a candidate slice of the flattened inputs.

    This is the trace-time liveness oracle `fused.FusedTrainStep`
    consults for auto-donation (MXNET_FUSED_AUTODONATE): an input that
    IS returned — an echoed batch, a passthrough label — stays
    undonated, because its buffer must outlive the step."""
    jaxpr = closed.jaxpr
    live_out = {id(v) for v in jaxpr.outvars}
    rng = range(len(jaxpr.invars)) if indices is None else indices
    return [i for i in rng
            if 0 <= i < len(jaxpr.invars)
            and id(jaxpr.invars[i]) not in live_out]


def analyze_callable(fn, avals, name=None, profile=None,
                     donate_argnums=()):
    """Trace `fn` at `avals` (ShapeDtypeStructs or arrays) and analyze
    the jaxpr — the front door for fused-step cores and plain jax
    functions."""
    import jax
    closed = jax.make_jaxpr(fn)(*avals)
    return analyze_jaxpr(closed, name=name or getattr(fn, "__name__",
                                                      "callable"),
                         profile=profile, donated=donate_argnums)


def analyze_executor(exe, name=None, profile=None, is_train=False):
    """Analyze a bound `Executor`'s whole-graph program (the jaxpr the
    forward jit compiles): control-flow subgraphs cost their true
    scan-body work (body flops x trip count), which the symbol-side
    walk cannot see through a `_foreach` node."""
    import jax
    fn = exe._graph_fn(bool(is_train))
    args = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
            for a in exe.arg_arrays]
    aux = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
           for a in exe.aux_arrays]
    key = jax.ShapeDtypeStruct((2,), _np.uint32)
    return analyze_callable(lambda a, x, k: fn(a, x, k),
                            [args, aux, key],
                            name=name or "executor", profile=profile)


# ---------------------------------------------------------------------------
# collective enumeration (the kvstore/pod plan, statically)
# ---------------------------------------------------------------------------

def enumerate_collectives(shapes, dtypes=None, dp=8, cap_bytes=None,
                          order=None, extras=False, name=None):
    """Statically derive one training step's gradient-exchange economy
    for a dp-way mesh: the bucket plan (THE shared `kvstore.plan_buckets`
    rule, default priority order = reversed parameter order exactly as
    the scheduler and the pod fast path plan it), collectives per step,
    payload bytes per step (the number `KVStore.stats()['bytes_reduced']`
    measures), and the ring-model bytes each chip moves on the ICI.

    ``extras=True`` models the pod fast path's bundled extras psum: it
    folds into the first f32 bucket when one exists, else costs one
    extra collective.
    """
    shapes = list(shapes)
    n = len(shapes)
    if dtypes is None:
        dtypes = [_np.dtype("float32")] * n
    dtypes = [_np.dtype(d) if not isinstance(d, _np.dtype) else d
              for d in dtypes]
    if cap_bytes is None:
        from .. import config as _config
        cap_bytes = max(1, int(
            float(_config.get("MXNET_KVSTORE_BUCKET_MB")) * (1 << 20)))
    sizes = [(int(_np.prod(s)) if s else 1) * dt.itemsize
             for s, dt in zip(shapes, dtypes)]
    if order is None:
        order = list(reversed(range(n)))
    from ..kvstore import plan_buckets
    plan = plan_buckets(order, sizes, dtypes, cap_bytes)
    total = sum(sizes)
    collectives = len(plan)
    if extras and not any(dtypes[b[0]] == _np.dtype("float32")
                          for b in plan):
        collectives += 1
    # ideal plan size: dtype grouping + the size cap (the economy the
    # scheduler promises; O(params) single-item buckets break it)
    ndt = len({dt.name for dt in dtypes})
    ideal = max(1, int(math.ceil(total / cap_bytes))) + ndt - 1
    o_params = n > 2 and len(plan) >= n and len(plan) > 2 * ideal
    return {
        "name": name or "plan",
        "dp": int(dp),
        "params": n,
        "total_param_bytes": int(total),
        "bucket_cap_mb": cap_bytes / (1 << 20),
        "buckets": len(plan),
        "collectives_per_step": int(collectives),
        "bytes_per_step": int(total),
        "ici_bytes_per_chip": int(2 * (dp - 1) / max(1, dp) * total),
        "pull_broadcasts": len(plan),
        "dispatch_complexity": "O(params)" if o_params else "O(buckets)",
        "plan": [list(b) for b in plan],
    }


def collectives_report(stats, target=None):
    """Findings view of `enumerate_collectives` output."""
    report = Report(target=target or stats.get("name"))
    report.add(Finding(
        "cost.collectives", "collective-summary", HINT,
        "%s: dp=%d, %d param(s) -> %d bucket(s), %d collective(s)/step, "
        "%.2f MB/step payload (%.2f MB on the ICI per chip), %s dispatch"
        % (stats["name"], stats["dp"], stats["params"], stats["buckets"],
           stats["collectives_per_step"],
           stats["bytes_per_step"] / (1 << 20),
           stats["ici_bytes_per_chip"] / (1 << 20),
           stats["dispatch_complexity"]),
        location=stats.get("name")))
    if stats["dispatch_complexity"] == "O(params)":
        report.add(Finding(
            "cost.collectives", "collective-o-params", WARN,
            "%s: the plan dispatches %d collectives for %d params "
            "(every bucket single-item; ~%d would satisfy the %g MB "
            "cap): per-parameter dispatch is the pod-scale throughput "
            "killer the bucketed scheduler exists to prevent — check "
            "the push ordering/dtype interleaving"
            % (stats["name"], stats["collectives_per_step"],
               stats["params"],
               max(1, int(math.ceil(stats["total_param_bytes"] /
                                    (stats["bucket_cap_mb"] *
                                     (1 << 20))))),
               stats["bucket_cap_mb"]),
            location=stats.get("name")))
    return report


# ---------------------------------------------------------------------------
# the canonical bench program set (the mxlint --cost-report default and
# the COST_BUDGETS.json baseline)
# ---------------------------------------------------------------------------

BENCH_SHAPE = (8, 3, 32, 32)


def _fresh_names(build):
    """Compose `build`'s graph under auto-name counts that start at
    zero, so that a finding names the same nodes in any process."""
    @functools.wraps(build)
    def composed(*args, **kwargs):
        from ..symbol.symbol import _NameManager
        with _NameManager.fresh():
            return build(*args, **kwargs)
    return composed


@_fresh_names
def build_bench_convnet(dtype="float32"):
    """The quantization-battery convnet (conv3x3/16 + relu + maxpool +
    flatten + fc32), with every variable declared at `dtype` so the
    bf16 variant is bf16 end to end.  Returns (symbol, shapes)."""
    from .. import sym as S
    kw = {} if dtype == "float32" else {"dtype": dtype}
    # weight shapes are declared on the variables: a declared non-f32
    # dtype only takes effect in abstract evaluation when the shape is
    # known too (the param-shape solver would otherwise re-seed f32)
    c, hw = BENCH_SHAPE[1], BENCH_SHAPE[2]
    data = S.Variable("data", shape=BENCH_SHAPE, **kw)
    x = S.Convolution(data,
                      S.Variable("conv0_weight", shape=(16, c, 3, 3),
                                 **kw),
                      S.Variable("conv0_bias", shape=(16,), **kw),
                      kernel=(3, 3), num_filter=16, pad=(1, 1),
                      name="conv0")
    x = S.Activation(x, act_type="relu", name="relu0")
    x = S.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max",
                  name="pool0")
    x = S.Flatten(x, name="flatten0")
    fc_in = 16 * (hw // 2) * (hw // 2)
    out = S.FullyConnected(x,
                           S.Variable("fc0_weight", shape=(32, fc_in),
                                      **kw),
                           S.Variable("fc0_bias", shape=(32,), **kw),
                           num_hidden=32, name="fc0")
    return out, {"data": BENCH_SHAPE}


@_fresh_names
def build_bench_quantized_convnet():
    """quantize_model over the fp32 bench convnet: the int8 graph of
    the budget baseline (fixed rng seed for the weights).
    Returns (qsym, shapes, dtypes) where dtypes carries the int8 weight
    dtypes the variable attrs cannot."""
    import numpy as np
    from .. import nd
    from ..contrib.quantization import quantize_model

    sym, shapes = build_bench_convnet("float32")
    rng = np.random.RandomState(2)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=BENCH_SHAPE)
    args = {n: nd.array(rng.normal(0, 0.5, s).astype("f4"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n != "data"}
    auxs = {n: nd.zeros(s) for n, s in
            zip(sym.list_auxiliary_states(), aux_shapes)}
    qsym, qargs, _ = quantize_model(sym, args, auxs, calib_mode="none")
    dtypes = {n: str(a.dtype) for n, a in qargs.items()}
    return qsym, shapes, dtypes


def bench_programs():
    """{name: (symbol, shapes, dtypes)} — the program set the budget
    baseline covers.  Names match the COST_BUDGETS.json keys.  Both
    builders compose under fresh name counts."""
    fp32, shapes = build_bench_convnet("float32")
    bf16, _ = build_bench_convnet("bfloat16")
    qsym, qshapes, qdtypes = build_bench_quantized_convnet()
    return {
        "quantization.convnet_fp32": (fp32, shapes, None),
        "quantization.convnet_bf16": (bf16, shapes, None),
        "quantization.convnet_int8": (qsym, qshapes, qdtypes),
    }


def analyze_bench_set(profile=None, dp=8, cap_bytes=None):
    """Analyze the canonical bench set + the dp-way collective plan for
    its fp32 params: {name: ProgramCost}, plus the plan stats under the
    key ``__collectives__``.  This is what the mxlint --cost-report
    default run and the budget baseline share."""
    out = {}
    for name, (sym, shapes, dtypes) in sorted(bench_programs().items()):
        out[name] = analyze_symbol(sym, shapes=shapes, dtypes=dtypes,
                                   profile=profile, target=name)
    fp32, shapes = build_bench_convnet("float32")
    arg_shapes, _, _ = fp32.infer_shape(data=BENCH_SHAPE)
    pshapes = [s for n, s in zip(fp32.list_arguments(), arg_shapes)
               if n != "data"]
    stats = enumerate_collectives(pshapes, dp=dp, cap_bytes=cap_bytes,
                                  name="dp%d_bucketed_convnet" % dp)
    out["__collectives__"] = stats
    return out


# ---------------------------------------------------------------------------
# sparse embedding cost model (mxembed)
# ---------------------------------------------------------------------------

# flops per touched element for the lazy row-sparse update paths
# (optimizer.py _lazy_*_jit): rescale + clip + wd fold, then the
# update math; adam adds two moment EMAs, a square, a sqrt and a divide
_EMBED_UPDATE_FLOPS = {"lookup": 0, "scatter": 0, "sgd": 4,
                       "sgd_momentum": 7, "adam": 14}

# optimizer state rows moved per touched row (read + write each):
# momentum keeps one slot, adam two
_EMBED_STATE_ROWS = {"lookup": 0, "scatter": 0, "sgd": 0,
                     "sgd_momentum": 1, "adam": 2}


def analyze_embedding(num_rows, dim, rows_touched, dtype="float32",
                      kind="lookup", profile=None, name=None):
    """Static cost of one sparse-embedding op: the rows-touched x
    row-bytes model.

    The sparse path is host/wire-resident (ndarray/sparse.py design
    note), so there is no traced program to walk — but its cost is
    exactly determined by how many rows move: a ``lookup`` gathers
    ``rows_touched`` rows of ``dim * itemsize`` bytes (plus the int64
    id vector) and writes them back out; a ``scatter`` writes them; the
    optimizer kinds (``sgd``/``sgd_momentum``/``adam``) additionally
    read-modify-write the touched weight rows, the gradient rows, and
    the optimizer's state rows, at the lazy kernels' per-element flop
    counts.  Everything off the touched rows is free — that is the whole
    point of the lazy contract."""
    if kind not in _EMBED_UPDATE_FLOPS:
        raise ValueError(f"analyze_embedding: unknown kind {kind!r} "
                         f"(one of {sorted(_EMBED_UPDATE_FLOPS)})")
    profile = get_profile(profile)
    prog = ProgramCost(name or f"embedding.{kind}", profile)
    k = int(rows_touched)
    d = int(dim)
    isize = _np.dtype(dtype).itemsize
    row_bytes = d * isize
    idx_bytes = k * 8
    flops = _EMBED_UPDATE_FLOPS[kind] * k * d
    if kind == "lookup":
        bytes_in, bytes_out = k * row_bytes + idx_bytes, k * row_bytes
    elif kind == "scatter":
        bytes_in, bytes_out = k * row_bytes + idx_bytes, k * row_bytes
    else:
        state = _EMBED_STATE_ROWS[kind]
        # read: weight rows + grad rows + state rows + ids;
        # write: weight rows + state rows
        bytes_in = (2 + state) * k * row_bytes + idx_bytes
        bytes_out = (1 + state) * k * row_bytes
    dt = _dtype_key(dtype)
    bound = _classify(f"embedding.{kind}", flops, bytes_in + bytes_out,
                      dt, profile)
    prog.per_op.append(OpCost(
        node=f"embedding.{kind}", op=f"embedding.{kind}", flops=flops,
        bytes_in=bytes_in, bytes_out=bytes_out, compute_dtype=dt,
        ai=flops / max(1, bytes_in + bytes_out), bound=bound))
    prog.param_bytes = int(num_rows) * row_bytes
    return prog
