"""Device time by phase of the train step and by MXNet operator kind: the
trace's per-instruction sums (`by_op` of the busiest chip) joined with the
program's own map from HLO instruction to scope, `mx.compile.op_scopes()`.
Not a metric: the helper the `*_device_ms` readers share, loaded by path.

The rules of the classification live in the program (compile/scopes.py);
this file only sums.  An instruction the map does not know (another
program's op inside the window) counts under `other`, so the phases always
sum to `by_op`.  On a program without `op_scopes`, or without a live
executable, there is nothing to read and `split` returns None.
"""
import sys

KEY = "scope_join"      # where one run's split is kept in `ctx`


def split(ctx):
    """{"by_phase": {phase: ns}, "by_kind": {(op, phase): ns}, "mixed_ns",
    "inside_ns": {(phase, phases inside): ns}, "other_ops": {name: ns},
    "unmapped_ns", "total_ns"} of the window, computed (and printed on
    standard error) once per run."""
    if KEY not in ctx:
        ctx[KEY] = _split(ctx)
        if ctx[KEY] is not None:
            _print(ctx[KEY], ctx["steps"])
    return ctx[KEY]


def _split(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("steps"):
        return None
    import incubator_mxnet_tpu as mx
    op_scopes = getattr(mx.compile, "op_scopes", None)
    if op_scopes is None:
        return None
    by_op = max(tr["per_chip"], key=lambda c: c["busy_ns"])["by_op"]
    # the train block is the program whose instructions carry most of the
    # window's device time
    labels = {p["label"] for p in ctx.get("programs", ())} | {None}
    best, covered = None, 0.0
    for label in sorted(labels, key=str):
        scopes = op_scopes(label)
        ns = sum(t for n, t in by_op.items() if n in scopes)
        if ns > covered:
            best, covered = scopes, ns
    if best is None:
        return None
    out = {"by_phase": {}, "by_kind": {}, "mixed_ns": 0.0,
           "inside_ns": {}, "other_ops": {},
           "unmapped_ns": sum(by_op.values()) - covered,
           "total_ns": sum(by_op.values())}
    for name, ns in by_op.items():
        scope = best.get(name) or {"phase": "other", "op": None,
                                   "mixed": False}
        phase = scope["phase"]
        out["by_phase"][phase] = out["by_phase"].get(phase, 0.0) + ns
        kind = (scope["op"] or "-", phase)
        out["by_kind"][kind] = out["by_kind"].get(kind, 0.0) + ns
        if phase == "other":
            out["other_ops"][name] = ns
        if scope["mixed"]:
            out["mixed_ns"] += ns
            key = (phase, "+".join(scope.get("inside", ())))
            out["inside_ns"][key] = out["inside_ns"].get(key, 0.0) + ns
    return out


def _print(s, steps):
    def log(msg):
        print(f"[bench] scopes: {msg}", file=sys.stderr, flush=True)
    total = s["total_ns"] or 1.0
    log("device-op time by phase, s (ms a step): " + ", ".join(
        f"{p} {ns / 1e9:.4f} ({ns / steps / 1e6:.3f})"
        for p, ns in sorted(s["by_phase"].items(), key=lambda t: -t[1])))
    log(f"sum of phases {sum(s['by_phase'].values()) / 1e9:.6f} s against "
        f"by_op {s['total_ns'] / 1e9:.6f} s; in no map "
        f"{100 * s['unmapped_ns'] / total:.3f}%; in fusions whose insides "
        f"mix phases {100 * s['mixed_ns'] / total:.2f}%")
    for (phase, inside), ns in sorted(s["inside_ns"].items(),
                                      key=lambda t: -t[1])[:12]:
        log(f"  mixed, named {phase}, holding {inside}: {ns / 1e9:.4f} s")
    log("largest instructions under `other`: " + ", ".join(
        f"{n} {ns / 1e9:.4f} s" for n, ns in
        sorted(s["other_ops"].items(), key=lambda t: -t[1])[:8]))
    log("largest (operator kind, phase): " + ", ".join(
        f"{op} {phase} {ns / 1e9:.4f} s" for (op, phase), ns in
        sorted(s["by_kind"].items(), key=lambda t: -t[1])[:10]))


def phase_ms(ctx, phase):
    """Device time of one phase per step of the window, in ms."""
    s = split(ctx)
    if s is None:
        return None
    return s["by_phase"].get(phase, 0.0) / ctx["steps"] / 1e6
