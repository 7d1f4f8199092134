#!/usr/bin/env python
"""mxlint — static TPU-hazard linter for symbol graphs and scripts.

Front ends (analysis/ package):

* saved symbol JSON  — duplicate/empty names, unreachable nodes, dead
  outputs, aux races, f64 promotion, unbound inputs, TPU tile hints;
* python scripts     — AST lints: `.asnumpy()`/`.asscalar()`/
  `.wait_to_read()`/`waitall()` inside loops (host-sync-in-loop),
  literal ``kvstore='local'`` in TPU scripts, unbounded retry loops,
  swallowing excepts, unsupervised collectives, and direct
  `ServedModel.infer`/`ModelServer` use in router-configured scripts
  (router-bypass).

Usage:
    python tools/mxlint.py PATH [PATH ...]
        PATH: a .py script, a symbol .json, or a directory (scanned
        recursively for both).
    --hints            include perf hints (tpu-layout) in the output
    --shape name=d,... seed graph shape inference (repeatable), e.g.
                       --shape data=64,3,224,224
    --suppress codes   comma list of finding codes to drop
    --fail-on SEV      severity threshold for the exit status: exit 1
                       when any finding at/above SEV (one of error,
                       warn, hint) survives --suppress.  Default: warn
                       (hints never fail).  --fail-on=hint implies
                       --hints.
    --json             machine-readable summary (one JSON object)
    --tsan-report      concurrency report: the mxtsan AST lints
                       (unnamed-thread, bare-acquire, sleep-under-lock,
                       unjoined-thread-in-init) over PATHS (default:
                       the package), plus any MXNET_TSAN_LOG runtime
                       dump among PATHS rendered as the lock-order
                       graph + findings
    --cache-report DIR program-cache hit rates / churn from stats.json
    --cost-report      mxcost static cost analysis (analysis/cost.py):
                       the canonical bench program set (per-program
                       flops/bytes/roofline, dtype-flow defects, peak
                       HBM) plus the dp-N bucketed collective plan, and
                       any symbol-JSON PATHS as extra programs.
                       --budgets FILE compares against the committed
                       COST_BUDGETS baseline (in-budget defects demote
                       to hints; regressions are errors);
                       --write-budgets FILE re-snapshots the baseline;
                       --profile/--dp/--bucket-mb pick the device
                       profile and plan geometry.
    --shard-report     mxshard static SPMD sharding analysis
                       (analysis/sharding.py): PartitionSpec
                       propagation over the bench program set (and any
                       symbol-JSON PATHS) under --mesh — hidden
                       reshards, implicit replication, rule-coverage
                       gaps, dp-axis leaks, per-device peak HBM, and
                       the per-step ICI byte bill.  --budgets FILE
                       gates against the COST_BUDGETS "sharding"
                       section; --write-budgets FILE re-snapshots it;
                       --measured pushes the bench convnet's sharded
                       gradients through a real KVStore and fails on
                       >10% static-vs-measured disagreement.

Exit status (the CI contract): 0 — no finding at/above --fail-on
survived --suppress; 1 — at least one did; 2 — usage error (argparse).
Inline suppression: ``# mxlint: disable[=code]`` on the offending
source line, or a ``__lint__`` attr on a graph node.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _collect(paths):
    py, js = [], []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    full = os.path.join(root, f)
                    if f.endswith(".py"):
                        py.append(full)
                    elif f.endswith(".json"):
                        js.append(full)
        elif p.endswith(".py"):
            py.append(p)
        elif p.endswith(".json"):
            js.append(p)
        else:
            print(f"mxlint: skipping {p!r} (not a .py/.json or directory)",
                  file=sys.stderr)
    return py, js


def _looks_like_symbol_json(text):
    head = text.lstrip()[:1]
    return head == "{" and '"nodes"' in text


def _parse_shapes(items):
    shapes = {}
    for item in items or ():
        name, _, dims = item.partition("=")
        if not dims:
            raise SystemExit(f"mxlint: bad --shape {item!r} "
                             "(want name=d0,d1,...)")
        shapes[name] = tuple(int(d) for d in dims.split(",") if d)
    return shapes


def cache_report(cache_dir, as_json=False):
    """Program-cache report over a cache directory's ``stats.json``
    (written by the compile/ subsystem at process exit and by the
    warmup CLI): aggregate hit rates across recorded runs, per-program
    compile counts, and compiles attributed to churned signatures — a
    program compiled under more than one distinct signature paid a full
    XLA compile for each one, which is the shape-churn cost the
    recompile auditor diagnoses at runtime."""
    stats_path = os.path.join(cache_dir, "stats.json")
    try:
        with open(stats_path) as f:
            runs = json.load(f).get("runs", [])
    except (OSError, ValueError) as e:
        print(f"mxlint: no readable stats at {stats_path} ({e})",
              file=sys.stderr)
        return 1
    total = {"compiles": 0, "disk_hits": 0, "mem_hits": 0, "stores": 0,
             "corrupt": 0, "evicted": 0}
    by_label = {}
    sigs_by_label = {}
    for run in runs:
        for k in total:
            total[k] += run.get("counters", {}).get(k, 0)
        for ev in run.get("events", []):
            lab = ev.get("label", "?")
            by_label[lab] = by_label.get(lab, 0) + 1
            sigs_by_label.setdefault(lab, set()).add(ev.get("signature"))
    lookups = total["compiles"] + total["disk_hits"] + total["mem_hits"]
    churned = {lab: {"compiles": n,
                     "distinct_signatures": len(sigs_by_label[lab])}
               for lab, n in by_label.items()
               if len(sigs_by_label.get(lab, ())) > 1}
    report = {
        "runs": len(runs),
        **total,
        "hit_rate": round((total["disk_hits"] + total["mem_hits"]) /
                          lookups, 4) if lookups else None,
        "compiles_by_program": dict(sorted(by_label.items(),
                                           key=lambda kv: -kv[1])[:50]),
        "churned_signature_programs": churned,
    }
    if as_json:
        print(json.dumps(report, indent=1))
    else:
        print("program cache report (%d run(s)): %d compiles, %d disk "
              "hits, %d memory hits, hit rate %s"
              % (report["runs"], total["compiles"], total["disk_hits"],
                 total["mem_hits"],
                 "n/a" if report["hit_rate"] is None
                 else "%.1f%%" % (100 * report["hit_rate"])))
        if total["corrupt"] or total["evicted"]:
            print("  %d corrupt entries dropped, %d evicted"
                  % (total["corrupt"], total["evicted"]))
        for lab, n in sorted(by_label.items(), key=lambda kv: -kv[1]):
            mark = ""
            if lab in churned:
                mark = "  <- %d distinct signatures, one full XLA " \
                    "compile each (declared buckets or shape churn; " \
                    "MXNET_ANALYSIS=1 runtime report separates them)" \
                    % churned[lab]["distinct_signatures"]
            print("  %4d compile(s)  %s%s" % (n, lab, mark))
    return 0


def cost_report(paths, as_json=False, budgets_path=None,
                write_budgets=None, profile=None, dp=8, bucket_mb=None,
                suppress=(), fail_on="warn", shapes=None):
    """mxcost stage: analyze the canonical bench program set (plus any
    symbol-JSON PATHS) with analysis/cost.py, optionally gate against a
    COST_BUDGETS baseline, and exit per --fail-on (tests/test_cost.py
    drives it): a new dequant chain,
    f32 upcast, extra collective, +bytes/step or +peak-HBM beyond the
    committed budget exits 1."""
    from incubator_mxnet_tpu.analysis import Report
    from incubator_mxnet_tpu.analysis import cost as mxcost
    from incubator_mxnet_tpu.analysis import budgets as mxbudgets
    from incubator_mxnet_tpu.analysis.findings import severity_rank
    from incubator_mxnet_tpu.symbol.symbol import load_json

    cap = int(bucket_mb * (1 << 20)) if bucket_mb else None
    results = mxcost.analyze_bench_set(profile=profile, dp=dp,
                                       cap_bytes=cap)
    _py, json_files = _collect(paths)
    for path in json_files:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        if not _looks_like_symbol_json(text):
            continue
        name = os.path.basename(path)
        if name in results:       # same basename twice: keep both
            name = path
        try:
            sym = load_json(text)
        except Exception as e:
            print(f"mxlint: cannot load {path} ({str(e)[:120]})",
                  file=sys.stderr)
            continue
        results[name] = mxcost.analyze_symbol(
            sym, shapes=shapes or None, profile=profile, target=name)

    if write_budgets:
        mxbudgets.save(write_budgets, mxbudgets.snapshot(results))
        print(f"mxlint: cost budgets for {len(results) - 1} program(s) "
              f"written to {write_budgets}")
        return 0

    coll_report = mxcost.collectives_report(results["__collectives__"])
    deltas = {}
    if budgets_path:
        report, deltas = mxbudgets.check(results,
                                         mxbudgets.load(budgets_path))
    else:
        report = Report(target="cost")
        for name, prog in sorted(results.items()):
            if name != "__collectives__":
                report.extend(prog.report)
    report.extend(coll_report.findings)
    report = report.suppress(set(suppress))
    thr = severity_rank(fail_on)
    failing = [f for f in report
               if severity_rank(f.severity) <= thr]

    stats = {k: v for k, v in results["__collectives__"].items()
             if k != "plan"}
    summary = {
        "programs": {name: prog.as_dict()
                     for name, prog in sorted(results.items())
                     if name != "__collectives__"},
        "collectives": stats,
        "budgets": budgets_path,
        "budget_deltas": deltas,
        "findings": len(report),
        "failing": len(failing),
        "fail_on": fail_on,
    }
    if as_json:
        print(json.dumps(summary, indent=1))
    else:
        for name, prog in sorted(results.items()):
            if name == "__collectives__":
                continue
            d = prog.as_dict()
            print("%-34s %10.3f MFLOP %9.2f MB moved  AI %6.1f  "
                  "%s-bound (%s)"
                  % (name, d["flops"] / 1e6,
                     d["bytes_moved"] / (1 << 20),
                     d["arithmetic_intensity"], d["bound"],
                     d["dominant_dtype"]))
        for f in report:
            print(f.format())
        print("mxlint --cost-report: %d program(s), %d finding(s), "
              "%d failing at --fail-on=%s%s"
              % (len(results) - 1, len(report), len(failing), fail_on,
                 " (vs %s)" % budgets_path if budgets_path else ""))
    return 1 if failing else 0


def shard_report(paths, as_json=False, budgets_path=None,
                 write_budgets=None, mesh="dp=2,tp=2", measured=False,
                 bucket_mb=None, suppress=(), fail_on="warn",
                 shapes=None):
    """mxshard stage: propagate PartitionSpecs through the committed
    bench program set (plus any symbol-JSON PATHS) under --mesh with
    analysis/sharding.py, optionally gate per-device peak HBM and
    per-step ICI bytes against the COST_BUDGETS "sharding" section,
    and (with --measured) cross-check the static dp plan against a
    real KVStore push (tests/test_sharding.py drives it): a new
    hidden reshard, a silently-replicated matrix
    param, a rule-coverage gap, or +ICI/+HBM beyond budget exits 1."""
    from incubator_mxnet_tpu.analysis import Report
    from incubator_mxnet_tpu.analysis import sharding as mxshard
    from incubator_mxnet_tpu.analysis import budgets as mxbudgets
    from incubator_mxnet_tpu.analysis.findings import Finding, severity_rank
    from incubator_mxnet_tpu.parallel.tensor_parallel import ShardingRules
    from incubator_mxnet_tpu.symbol.symbol import load_json

    cap = int(bucket_mb * (1 << 20)) if bucket_mb else None
    results = mxshard.analyze_shard_bench_set(mesh=mesh, cap_bytes=cap)

    axes = mxshard._mesh_axes(mesh)
    rules = (ShardingRules.megatron(tp_axis="tp")
             if mxshard._axis_size("tp", axes) > 1 else None)
    _py, json_files = _collect(paths)
    for path in json_files:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        if not _looks_like_symbol_json(text):
            continue
        name = os.path.basename(path)
        if name in results:
            name = path
        try:
            sym = load_json(text)
        except Exception as e:
            print(f"mxlint: cannot load {path} ({str(e)[:120]})",
                  file=sys.stderr)
            continue
        stats = mxshard.shard_collectives(
            sym, shapes=shapes or None, mesh=mesh, rules=rules,
            cap_bytes=cap, name=name)
        rep = stats.pop("report")
        entry = rep.as_dict()
        entry["collectives"] = stats
        entry["ici_bytes_per_step"] = stats["ici_bytes_per_step"]
        results[name] = entry

    if write_budgets:
        try:
            budgets = mxbudgets.load(write_budgets)
        except (OSError, ValueError):
            budgets = {"version": 1, "programs": {}}
        budgets["sharding"] = mxshard.snapshot_shard_budgets(results,
                                                            mesh=mesh)
        mxbudgets.save(write_budgets, budgets)
        print(f"mxlint: sharding budgets for {len(results)} program(s) "
              f"written to {write_budgets}")
        return 0

    report = Report(target="sharding")
    for name, entry in sorted(results.items()):
        for d in entry.get("findings", ()):
            f = Finding(d["pass"], d["code"], d["severity"],
                        d["message"], node=d.get("node"),
                        location=d.get("location"))
            f.count = d.get("count", 1)
            report.add(f)
    deltas = {}
    if budgets_path:
        brep, deltas = mxshard.check_shard_budgets(
            results, mxbudgets.load(budgets_path))
        report.extend(brep.findings)
    report = report.suppress(set(suppress))
    thr = severity_rank(fail_on)
    failing = [f for f in report
               if severity_rank(f.severity) <= thr]

    meas = None
    if measured:
        meas = mxshard.measured_ici_check(mesh=mesh, cap_bytes=cap)

    summary = {
        "mesh": mesh if isinstance(mesh, str) else dict(axes),
        "programs": results,
        "budgets": budgets_path,
        "budget_deltas": deltas,
        "measured": meas,
        "findings": len(report),
        "failing": len(failing),
        "fail_on": fail_on,
    }
    if as_json:
        print(json.dumps(summary, indent=1))
    else:
        for name, entry in sorted(results.items()):
            print("%-24s %8.2f MB/device (replicated %8.2f MB)  "
                  "%2d tp collective(s)  %9d ICI B/step  %d reshard(s)"
                  % (name,
                     (entry.get("per_device_peak_hbm_bytes") or 0)
                     / (1 << 20),
                     (entry.get("replicated_peak_hbm_bytes") or 0)
                     / (1 << 20),
                     entry.get("tp_collectives_per_step") or 0,
                     entry.get("ici_bytes_per_step") or 0,
                     entry.get("reshard_edges") or 0))
        for f in report:
            print(f.format())
        if meas is not None:
            print("measured dp cross-check (dp=%d): static %d B/step vs "
                  "measured %d B/step, agreement %.3f%%, %s"
                  % (meas["dp"], meas["static_bytes_per_step"],
                     meas["measured_bytes_per_step"],
                     meas["agreement_pct"],
                     "OK" if meas["ok"] else "MISMATCH"))
        print("mxlint --shard-report: %d program(s) under mesh '%s', "
              "%d finding(s), %d failing at --fail-on=%s%s"
              % (len(results), mesh, len(report), len(failing), fail_on,
                 " (vs %s)" % budgets_path if budgets_path else ""))
    if meas is not None and not meas["ok"]:
        return 1
    return 1 if failing else 0


def tsan_report(paths, as_json=False):
    """Concurrency report: the mxtsan AST lint subset (unnamed-thread,
    bare-acquire, sleep-under-lock, unjoined-thread-in-init) over the
    given ``.py`` paths (default: the package), plus a render of any
    ``MXNET_TSAN_LOG`` JSON dumps passed in — the runtime sanitizer's
    findings and its lock-acquisition-order graph.  Exit 1 when any
    lint or runtime finding survives."""
    from incubator_mxnet_tpu import analysis
    from incubator_mxnet_tpu.analysis.source_lint import CONCURRENCY_CODES

    if not paths:
        paths = [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "incubator_mxnet_tpu")]
    py_files, json_files = _collect(paths)
    lint_findings = []
    scanned = 0
    for path in py_files:
        scanned += 1
        rep = analysis.check_source_file(path)
        lint_findings.extend(f for f in rep
                             if f.code in CONCURRENCY_CODES)

    runtime = {"findings": [], "lock_graph": None, "dumps": 0}
    payloads = []
    for path in json_files:
        try:
            with open(path, encoding="utf-8") as f:
                lines = [ln for ln in f.read().splitlines()
                         if ln.strip()]
        except OSError:
            continue
        for ln in lines:   # MXNET_TSAN_LOG: one json line per process
            try:
                p = json.loads(ln)
            except ValueError:
                break      # not a tsan dump (symbol JSON etc.)
            if isinstance(p, dict) and "lock_graph" in p:
                payloads.append(p)
    for payload in payloads:
        runtime["dumps"] += 1
        runtime["findings"].extend(payload.get("findings", []))
        graph = payload.get("lock_graph") or {}
        if runtime["lock_graph"] is None:
            runtime["lock_graph"] = graph
        else:   # merge multi-process dumps (chaos runs)
            seen = {lk["name"] for lk in runtime["lock_graph"]["locks"]}
            runtime["lock_graph"]["locks"].extend(
                lk for lk in graph.get("locks", ())
                if lk["name"] not in seen)
            have = {(e["from"], e["to"])
                    for e in runtime["lock_graph"]["edges"]}
            runtime["lock_graph"]["edges"].extend(
                e for e in graph.get("edges", ())
                if (e["from"], e["to"]) not in have)

    failing = len(lint_findings) + len(runtime["findings"])
    report = {
        "scanned": scanned,
        "lint_findings": len(lint_findings),
        "runtime_findings": len(runtime["findings"]),
        "failing": failing,
        "items": [f.as_dict() for f in lint_findings[:200]],
        "runtime": runtime if runtime["dumps"] else None,
    }
    if as_json:
        print(json.dumps(report, indent=1))
    else:
        for f in lint_findings:
            print(f.format())
        for f in runtime["findings"]:
            loc = f.get("location") or ""
            print(f"{loc}: {f.get('severity')} [{f.get('code')}] "
                  f"{f.get('message')}")
        graph = runtime["lock_graph"]
        if graph:
            print("lock-order graph: %d lock(s), %d edge(s)"
                  % (len(graph.get("locks", ())),
                     len(graph.get("edges", ()))))
            for e in graph.get("edges", ()):
                print("  %s -> %s  [%s; held at %s, acquired at %s]"
                      % (e["from"], e["to"], e.get("thread"),
                         e.get("held_at"), e.get("acquired_at")))
        print(f"mxlint --tsan-report: {scanned} file(s) scanned, "
              f"{failing} finding(s)")
    return 1 if failing else 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="mxlint", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*")
    ap.add_argument("--hints", action="store_true",
                    help="include perf hints (tpu-layout)")
    ap.add_argument("--shape", action="append", default=[],
                    metavar="NAME=D0,D1,...")
    ap.add_argument("--suppress", default="",
                    metavar="CODE[,CODE...]")
    ap.add_argument("--fail-on", choices=["error", "warn", "hint"],
                    default="warn", dest="fail_on",
                    help="exit 1 when any finding at/above this "
                         "severity survives --suppress (default: warn; "
                         "hint implies --hints)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--cache-report", metavar="CACHE_DIR",
                    help="report program-cache hit rates and churn-"
                         "attributed compiles from CACHE_DIR/stats.json")
    ap.add_argument("--tsan-report", action="store_true",
                    help="concurrency report: the mxtsan AST lints over "
                         "PATHS (default: the package) + any MXNET_TSAN_"
                         "LOG runtime dumps among PATHS rendered as the "
                         "lock-order graph and findings")
    ap.add_argument("--cost-report", action="store_true",
                    help="mxcost static cost analysis of the bench "
                         "program set + symbol-JSON PATHS; gate with "
                         "--budgets / re-baseline with --write-budgets")
    ap.add_argument("--shard-report", action="store_true",
                    help="mxshard static SPMD sharding analysis of the "
                         "bench program set + symbol-JSON PATHS under "
                         "--mesh: spec propagation, hidden reshards, "
                         "implicit replication, rule coverage, per-"
                         "device peak HBM and per-step ICI bytes; gate "
                         "with --budgets / re-baseline with "
                         "--write-budgets; --measured cross-checks the "
                         "static dp plan against a real KVStore push")
    ap.add_argument("--mesh", default="dp=2,tp=2", metavar="SPEC",
                    help="mesh spec for --shard-report, e.g. 'dp=8' or "
                         "'dp=2,tp=2' (default dp=2,tp=2)")
    ap.add_argument("--measured", action="store_true",
                    help="with --shard-report: also push the bench "
                         "convnet's sharded gradients through a device "
                         "KVStore and fail on >10%% static-vs-measured "
                         "ICI disagreement")
    ap.add_argument("--budgets", metavar="JSON",
                    help="COST_BUDGETS baseline to gate --cost-report "
                         "against (regressions become errors)")
    ap.add_argument("--write-budgets", metavar="JSON",
                    dest="write_budgets",
                    help="snapshot the --cost-report analysis as a new "
                         "budget baseline and exit")
    ap.add_argument("--profile", metavar="NAME",
                    help="mxcost device profile (tpu-v3/tpu-v4/"
                         "cpu-host; default MXNET_COST_PROFILE)")
    ap.add_argument("--dp", type=int, default=8,
                    help="data-parallel degree for the --cost-report "
                         "collective plan (default 8)")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    dest="bucket_mb",
                    help="bucket cap for the --cost-report collective "
                         "plan (default MXNET_KVSTORE_BUCKET_MB)")
    args = ap.parse_args(argv)

    if args.fail_on == "hint":
        args.hints = True
    if args.cache_report:
        return cache_report(args.cache_report, as_json=args.as_json)
    if args.tsan_report:
        return tsan_report(args.paths, as_json=args.as_json)
    if args.shard_report:
        return shard_report(
            args.paths, as_json=args.as_json, budgets_path=args.budgets,
            write_budgets=args.write_budgets, mesh=args.mesh,
            measured=args.measured, bucket_mb=args.bucket_mb,
            suppress={c.strip() for c in args.suppress.split(",")
                      if c.strip()},
            fail_on=args.fail_on, shapes=_parse_shapes(args.shape))
    if args.cost_report:
        return cost_report(
            args.paths, as_json=args.as_json, budgets_path=args.budgets,
            write_budgets=args.write_budgets, profile=args.profile,
            dp=args.dp, bucket_mb=args.bucket_mb,
            suppress={c.strip() for c in args.suppress.split(",")
                      if c.strip()},
            fail_on=args.fail_on, shapes=_parse_shapes(args.shape))
    if not args.paths:
        ap.error("paths required (or --cache-report DIR)")

    from incubator_mxnet_tpu import analysis
    shapes = _parse_shapes(args.shape)
    suppress = {c.strip() for c in args.suppress.split(",") if c.strip()}

    py_files, json_files = _collect(args.paths)
    reports = []
    scanned = 0
    for path in py_files:
        scanned += 1
        reports.append(analysis.check_source_file(path))
    for path in json_files:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        if not _looks_like_symbol_json(text):
            continue  # round artifacts etc., not graphs
        scanned += 1
        reports.append(analysis.check_json(text, shapes=shapes or None,
                                           hints=args.hints, target=path))

    findings = []
    for r in reports:
        r = r.suppress(suppress)
        if not args.hints:
            r = r.filter(max_severity=analysis.WARN)
        findings.extend(r.findings)

    by_code, by_pass = {}, {}
    for f in findings:
        by_code[f.code] = by_code.get(f.code, 0) + 1
        by_pass[f.pass_name] = by_pass.get(f.pass_name, 0) + 1
    from incubator_mxnet_tpu.analysis.findings import severity_rank
    thr = severity_rank(args.fail_on)
    failing = [f for f in findings if severity_rank(f.severity) <= thr]

    if args.as_json:
        print(json.dumps({
            "scanned": scanned,
            "findings": len(findings),
            "failing": len(failing),
            "by_code": by_code,
            "by_pass": by_pass,
            "items": [f.as_dict() for f in findings[:200]],
        }, indent=1))
    else:
        for f in findings:
            print(f.format())
        print(f"mxlint: {scanned} file(s) scanned, "
              f"{len(findings)} finding(s)"
              + (f" ({json.dumps(by_code)})" if findings else ""))
    return 1 if failing else 0


if __name__ == "__main__":
    sys.exit(main())
