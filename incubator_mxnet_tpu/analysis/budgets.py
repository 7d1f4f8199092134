"""Cost budgets: the committed baseline that turns mxcost regressions
into hard CI failures.

``COST_BUDGETS.json`` (repo root) records, per program in the canonical
bench set, the statically-derived flops / bytes-moved / peak-HBM numbers
and the dtype-flow defect counters (dequant chains, fp32-compute
quantized ops, f32 upcasts, hidden host transfers), plus the collective
economy of the dp-8 bucketed plan.  `check()` compares a fresh analysis
against the baseline:

* a counter above budget, a new collective, +bytes/step or +peak-HBM
  beyond tolerance  -> **ERROR** ``budget-regression`` (CI fails);
* a metric meaningfully below budget -> **HINT** ``budget-slack`` (an
  improvement landed: re-snapshot so the gate tightens behind it);
* a program with no baseline entry -> **HINT** ``budget-missing``.

Known, budgeted defects stay visible but do not fail CI: a WARN finding
whose counter is within budget is demoted to HINT ("budgeted"), so
``mxlint --cost-report --fail-on=warn`` passes on HEAD while any NEW
dequant chain / upcast / collective fails the build.  The workflow:

    python tools/mxlint.py --cost-report --budgets COST_BUDGETS.json
    # regress -> exit 1; improve -> budget-slack hints
    python tools/mxlint.py --cost-report --write-budgets COST_BUDGETS.json
    # re-baseline after an intentional change (commit the diff)
"""
from __future__ import annotations

import json

from .findings import Finding, Report, ERROR, WARN, HINT

__all__ = ["snapshot", "load", "save", "check", "DEFAULT_TOLERANCES",
           "CODES", "MEASURED_TOLERANCES", "snapshot_measured",
           "check_measured"]

# every code the budget gate emits (the findings.CODE_TABLE cross-check)
CODES = ("budget-regression", "budget-missing", "budget-slack")

# relative headroom for the continuous metrics; counters are exact
DEFAULT_TOLERANCES = {
    "flops": 0.05,
    "bytes_moved": 0.10,
    "peak_hbm_bytes": 0.10,
    "param_bytes": 0.05,
    "bytes_per_step": 0.10,
}

# measured (wall-clock / runtime-reported) metrics: only the keys
# listed HERE are gated — everything else the coldstart probe records
# (lower_s, trace_s, the pure-JAX control's own timings) is
# informational.  compile_s wall time varies with host load, so it gets
# wide headroom; peak_hbm_mb is the 15% envelope around the mxcost
# liveness prediction the baseline commits; jaxpr_eqns and the
# fused-vs-pure-JAX compile ratio are exact caps.
MEASURED_TOLERANCES = {
    "compile_s": 0.50,
    "peak_hbm_mb": 0.15,
    "jaxpr_eqns": 0.0,
    "compile_ratio_vs_jax": 0.0,
}

# snapshot floors: a measured value below the floor commits the FLOOR
# as the budget, so the gate stays the contract cap (fused-step compile
# <= 1.5x pure JAX) rather than chasing a lucky measurement down, and
# sub-second CPU compile times gate order-of-magnitude blowups instead
# of scheduler noise
_SNAPSHOT_FLOORS = {"compile_ratio_vs_jax": 1.5, "compile_s": 0.5}

# exact counters a program budget carries, and the finding code each one
# licenses (within budget -> that code's WARNs demote to HINT)
_COUNTER_CODES = {
    "dequant_fp32_dot": "dequant-fp32-dot",
    "quantized_fp32_compute": "quantized-fp32-compute",
    "f32_upcasts": "f32-upcast-in-bf16",
    "host_transfers": "hidden-host-transfer",
}
_SCALARS = ("flops", "bytes_moved", "peak_hbm_bytes", "param_bytes")
_COLL_COUNTERS = ("collectives_per_step", "buckets", "pull_broadcasts")


def snapshot(results):
    """Budget dict from an `analyze_bench_set`-style result map
    ({name: ProgramCost, '__collectives__': stats})."""
    budgets = {"version": 1, "tolerances": dict(DEFAULT_TOLERANCES),
               "programs": {}, "collectives": {}}
    for name, prog in sorted(results.items()):
        if name == "__collectives__":
            st = prog
            budgets["collectives"][st["name"]] = {
                "dp": st["dp"], "params": st["params"],
                "collectives_per_step": st["collectives_per_step"],
                "buckets": st["buckets"],
                "pull_broadcasts": st["pull_broadcasts"],
                "bytes_per_step": st["bytes_per_step"],
                "dispatch_complexity": st["dispatch_complexity"],
            }
            continue
        d = prog.as_dict()
        entry = {k: d[k] for k in _SCALARS if d.get(k) is not None}
        entry.update(d["counters"])
        budgets["programs"][name] = entry
    return budgets


def load(path):
    with open(path, encoding="utf-8") as f:
        budgets = json.load(f)
    if not isinstance(budgets, dict) or "programs" not in budgets:
        raise ValueError(f"{path}: not a COST_BUDGETS file "
                         "(no 'programs' table)")
    return budgets


def save(path, budgets):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(budgets, f, indent=1, sort_keys=True)
        f.write("\n")


def snapshot_measured(measured, budgets=None):
    """Fold a {program: {metric: value}} map of MEASURED numbers (the
    coldstart probe's compile_s / peak_hbm_mb) into a budget dict's
    'measured' section, returning the dict.  Unlike the static
    `snapshot`, this merges: programs not re-measured keep their
    committed entries."""
    if budgets is None:
        budgets = {"version": 1, "tolerances": dict(DEFAULT_TOLERANCES),
                   "programs": {}, "collectives": {}}
    section = budgets.setdefault("measured", {})
    budgets.setdefault("measured_tolerances", dict(MEASURED_TOLERANCES))
    for name, metrics in sorted(measured.items()):
        entry = section.setdefault(name, {})
        for k, v in sorted(metrics.items()):
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            entry[k] = round(max(float(v), _SNAPSHOT_FLOORS.get(k, 0.0)),
                             4)
    return budgets


def check_measured(measured, budgets):
    """Compare a {program: {metric: value}} map of measured coldstart
    numbers against the budget dict's 'measured' section.  Same finding
    codes and (report, deltas) contract as `check`."""
    report = Report(target="coldstart-budgets")
    deltas = {}
    tol = dict(MEASURED_TOLERANCES)
    tol.update(budgets.get("measured_tolerances") or {})
    baseline = budgets.get("measured") or {}
    for name, metrics in sorted(measured.items()):
        b = baseline.get(name)
        if b is None:
            report.add(Finding(
                "cost.budget", "budget-missing", HINT,
                "program '%s' has no measured baseline entry — snapshot "
                "it (tools/warmup.py --measure-budgets --write-budgets) so "
                "cold-start regressions become CI failures" % name,
                location=name))
            continue
        for metric, value in sorted(metrics.items()):
            if metric not in tol or \
                    not isinstance(value, (int, float)) or \
                    isinstance(value, bool):
                continue   # informational metric: recorded, not gated
            # a budget pinned at its snapshot floor is a contract cap,
            # not a measurement — running under it is not "slack"
            floor = _SNAPSHOT_FLOORS.get(metric)
            pinned = floor is not None and b.get(metric) == floor
            _compare(report, deltas, name, metric, value,
                     b.get(metric), tol[metric], slack=not pinned)
    return report, deltas


def _compare(report, deltas, scope, metric, value, budget, tol,
             slack=True):
    """One metric against its budget; returns True when in budget."""
    if value is None or budget is None:
        return True
    entry = {"value": value, "budget": budget, "ok": True}
    deltas.setdefault(scope, {})[metric] = entry
    if budget:
        entry["delta_pct"] = round(100.0 * (value - budget) / budget, 2)
    if value > budget * (1.0 + tol):
        entry["ok"] = False
        delta = "%+.1f%%" % entry["delta_pct"] if budget else \
            "was zero"   # a percentage of a 0 budget is meaningless
        report.add(Finding(
            "cost.budget", "budget-regression", ERROR,
            "%s: %s regressed to %s over budget %s (%s, tolerance "
            "%.0f%%) — a perf PR must either stay inside the committed "
            "budget or intentionally re-baseline COST_BUDGETS.json "
            "(mxlint --cost-report --write-budgets)"
            % (scope, metric, _fmt(value), _fmt(budget), delta,
               100 * tol),
            location=scope))
        return False
    band = tol if tol else 0.0
    if slack and (value < budget * (1.0 - max(band, 0.05)) or
                  (tol == 0.0 and value < budget)):
        report.add(Finding(
            "cost.budget", "budget-slack", HINT,
            "%s: %s improved to %s, well under budget %s — re-snapshot "
            "COST_BUDGETS.json so the gate tightens behind the win"
            % (scope, metric, _fmt(value), _fmt(budget)),
            location=scope))
    return True


def _fmt(v):
    if isinstance(v, float) and not v.is_integer():
        return "%.4g" % v
    v = int(v)
    if v >= (1 << 20):
        return "%.2f MB" % (v / (1 << 20))
    return str(v)


def check(results, budgets):
    """Compare {name: ProgramCost, '__collectives__': stats} against a
    budget dict.  Returns (report, deltas):

    * `report` carries the budget findings AND every program finding,
      with in-budget WARNs demoted to HINT ("budgeted") — feed it to
      the CLI severity gate;
    * `deltas` is the per-program {metric: {value, budget, delta_pct,
      ok}} map the parity artifact records.
    """
    report = Report(target="cost-budgets")
    deltas = {}
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(budgets.get("tolerances") or {})
    prog_budgets = budgets.get("programs") or {}
    coll_budgets = budgets.get("collectives") or {}

    for name, prog in sorted(results.items()):
        if name == "__collectives__":
            st = prog
            b = coll_budgets.get(st["name"])
            if b is None:
                report.add(Finding(
                    "cost.budget", "budget-missing", HINT,
                    "collective plan '%s' has no baseline entry — "
                    "snapshot it so new collectives become regressions"
                    % st["name"], location=st["name"]))
                continue
            for metric in _COLL_COUNTERS:
                _compare(report, deltas, st["name"], metric,
                         st.get(metric), b.get(metric), 0.0)
            _compare(report, deltas, st["name"], "bytes_per_step",
                     st.get("bytes_per_step"), b.get("bytes_per_step"),
                     tol["bytes_per_step"])
            if st.get("dispatch_complexity") == "O(params)" and \
                    b.get("dispatch_complexity") != "O(params)":
                report.add(Finding(
                    "cost.budget", "budget-regression", ERROR,
                    "%s: dispatch complexity regressed to O(params) "
                    "(every bucket single-item) from the budgeted "
                    "O(buckets) economy" % st["name"],
                    location=st["name"]))
            continue

        d = prog.as_dict()
        b = prog_budgets.get(name)
        if b is None:
            report.add(Finding(
                "cost.budget", "budget-missing", HINT,
                "program '%s' has no baseline entry in the budget file "
                "— snapshot it (mxlint --cost-report --write-budgets) "
                "so regressions become CI failures" % name,
                location=name))
            report.extend(prog.report)
            continue
        in_budget_codes = set()
        for counter, code in _COUNTER_CODES.items():
            ok = _compare(report, deltas, name, counter,
                          d["counters"].get(counter, 0),
                          b.get(counter, 0), 0.0)
            if ok:
                in_budget_codes.add(code)
        for metric in _SCALARS:
            _compare(report, deltas, name, metric, d.get(metric),
                     b.get(metric), tol.get(metric, 0.1))
        # known, budgeted defects stay visible but do not fail CI
        for f in prog.report:
            if f.severity == WARN and f.code in in_budget_codes:
                demoted = Finding(f.pass_name, f.code, HINT,
                                  f.message + " [budgeted: within the "
                                  "committed COST_BUDGETS baseline]",
                                  node=f.node, location=f.location)
                demoted.count = f.count
                report.add(demoted)
            else:
                report.add(f)
    return report, deltas
