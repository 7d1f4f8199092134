#!/usr/bin/env python
"""mxtop — live terminal status over the fleet's scrape plane.

Points at any set of transport endpoints that answer the ``metrics``
frame — replica workers, host daemons (``serving.hostd``), parameter
servers, or a standalone `obs.scrape.MetricsEndpoint` — and renders
one fleet-wide status view: per-replica QPS / p99 / queue depth /
shed, per-host liveness and worker counts, kvstore bytes/step and
bucket economy, guardian skip/rollback/quarantine counts, program
cache traffic.

Usage:
    python tools/mxtop.py ENDPOINT [ENDPOINT ...] [options]
        ENDPOINT: host:port / :port / port (transport spellings)
    --json           one snapshot as JSON ({"endpoints", "fleet"}) and
                     exit — the scriptable face (the obs CI stage and
                     dashboards consume this)
    --interval S     live refresh period (default 2.0)
    --once           render one text frame and exit (no ANSI loop)
    --timeout S      per-endpoint scrape timeout (default 5.0)

Aggregation: the ``fleet`` block sums numeric values that share a
dotted name across endpoints (counters add; point-in-time gauges add
too — a fleet-wide queue depth IS the sum of per-replica depths) and
keeps per-endpoint blocks verbatim for anything that must not be
summed.  Unreachable endpoints are listed, never fatal — a half-dead
fleet is exactly when you need the numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def snapshot(endpoints, timeout=5.0):
    """Scrape every endpoint once -> {"endpoints", "fleet", "unreachable"}."""
    from incubator_mxnet_tpu.obs.scrape import scrape
    per, unreachable = {}, []
    for ep in endpoints:
        try:
            per[str(ep)] = scrape(ep, timeout=timeout)["values"]
        except Exception as exc:
            unreachable.append({"endpoint": str(ep),
                                "error": f"{type(exc).__name__}: {exc}"})
    fleet = {}
    for values in per.values():
        for name, v in values.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                fleet[name] = fleet.get(name, 0) + v
    return {"endpoints": per, "fleet": fleet, "unreachable": unreachable,
            "time": round(time.time(), 3)}


def _namespace(values, prefix):
    pfx = prefix + "."
    return {k[len(pfx):]: v for k, v in values.items()
            if k.startswith(pfx)}


def _fmt(v, nd=1):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def render(snap):
    """One text frame over a snapshot (shared by --once and the loop)."""
    lines = []
    fleet = snap["fleet"]
    lines.append("mxtop — %d endpoint(s), %d unreachable    %s"
                 % (len(snap["endpoints"]), len(snap["unreachable"]),
                    time.strftime("%H:%M:%S")))
    for u in snap["unreachable"]:
        lines.append("  DOWN %-22s %s" % (u["endpoint"], u["error"][:60]))
    # -- serving: per-replica/model QPS, p99, queue depth --------------------
    serving = {}
    for ep, values in snap["endpoints"].items():
        for name, v in values.items():
            if not name.startswith("serving."):
                continue
            rest = name.split(".", 1)[1]
            model, _, field = rest.partition(".")
            serving.setdefault((ep, model), {})[field] = v
    if serving:
        lines.append("")
        lines.append("  %-18s %-14s %8s %9s %7s %7s %7s"
                     % ("SERVING", "endpoint", "qps", "p99_ms",
                        "queue", "shed", "resp"))
        for (ep, model), f in sorted(serving.items()):
            lines.append("  %-18s %-14s %8s %9s %7s %7s %7s"
                         % (model[:18], ep[-14:], _fmt(f.get("qps")),
                            _fmt(f.get("p99_ms")),
                            _fmt(f.get("queue_depth"), 0),
                            _fmt(f.get("shed"), 0),
                            _fmt(f.get("responses"), 0)))
    # -- router / fleet ------------------------------------------------------
    router = _namespace(fleet, "router")
    if router:
        lines.append("")
        lines.append("  ROUTER  inflight=%s failovers=%s lost=%s "
                     "dup_suppressed=%s swaps=%s"
                     % (_fmt(router.get("inflight"), 0),
                        _fmt(router.get("failovers"), 0),
                        _fmt(router.get("replicas_lost"), 0),
                        _fmt(router.get("duplicates_suppressed"), 0),
                        _fmt(router.get("swaps_committed"), 0)))
    fl = _namespace(fleet, "fleet")
    if fl:
        hosts_alive = sum(v for k, v in fl.items()
                          if k.startswith("hosts.") and k.endswith(".alive"))
        lines.append("  FLEET   live=%s target=%s ups=%s downs=%s "
                     "hosts_lost=%s hosts_alive=%s backfill_s=%s"
                     % (_fmt(fl.get("live_replicas"), 0),
                        _fmt(fl.get("target"), 0),
                        _fmt(fl.get("scale_ups"), 0),
                        _fmt(fl.get("scale_downs"), 0),
                        _fmt(fl.get("hosts_lost"), 0),
                        _fmt(hosts_alive, 0),
                        _fmt(fl.get("backfill_latency_s"))))
    hostd = _namespace(fleet, "hostd")
    if hostd:
        lines.append("  HOSTS   workers=%s spawns=%s"
                     % (_fmt(hostd.get("workers"), 0),
                        _fmt(hostd.get("spawns"), 0)))
    # -- kvstore -------------------------------------------------------------
    kv = _namespace(fleet, "kvstore")
    if kv:
        lines.append("")
        lines.append("  KVSTORE pushes=%s dispatches=%s buckets=%s "
                     "MB_reduced=%s fill=%s overlap=%s"
                     % (_fmt(kv.get("batched_pushes"), 0),
                        _fmt(kv.get("allreduce_dispatches"), 0),
                        _fmt(kv.get("buckets"), 0),
                        _fmt((kv.get("bytes_reduced") or 0) / (1 << 20)),
                        _fmt(kv.get("avg_bucket_fill"), 2),
                        _fmt(kv.get("overlap_ratio"), 2)))
    # -- guardian / supervisor ----------------------------------------------
    gd = _namespace(fleet, "guardian")
    if gd:
        lines.append("  GUARD   steps=%s skips=%s spikes=%s rollbacks=%s "
                     "quarantined=%s"
                     % (_fmt(gd.get("steps_observed"), 0),
                        _fmt(gd.get("skips"), 0),
                        _fmt(gd.get("spikes"), 0),
                        _fmt(gd.get("rollbacks"), 0),
                        _fmt(gd.get("quarantined"), 0)))
    sup = _namespace(fleet, "supervisor")
    if sup:
        lines.append("  SUPERV  step=%s heartbeats=%s hosts_lost=%s "
                     "watchdog_timeouts=%s stragglers=%s"
                     % (_fmt(sup.get("step"), 0),
                        _fmt(sup.get("heartbeats"), 0),
                        _fmt(sup.get("hosts_lost"), 0),
                        _fmt(sup.get("collective_timeouts"), 0),
                        _fmt(sup.get("stragglers_flagged"), 0)))
    cache = _namespace(fleet, "cache.counters")
    if cache:
        lines.append("  CACHE   compiles=%s disk_hits=%s disk_misses=%s "
                     "mem_hits=%s stores=%s lower_s=%s compile_s=%s"
                     % (_fmt(cache.get("compiles"), 0),
                        _fmt(cache.get("disk_hits"), 0),
                        _fmt(cache.get("disk_misses"), 0),
                        _fmt(cache.get("mem_hits"), 0),
                        _fmt(cache.get("stores"), 0),
                        _fmt(cache.get("lower_s_total"), 2),
                        _fmt(cache.get("compile_s_total"), 2)))
    # -- where start-up went: the phase tally, largest first ----------------
    phase = _namespace(fleet, "phase")
    seconds = {k[:-2]: v for k, v in phase.items()
               if k.endswith(".s") and ".jax." not in k}
    if seconds:
        compiles = sum(v for k, v in phase.items()
                       if k.endswith(".jax.compile.n"))
        lines.append("  PHASES  jax_compiles=%s  %s" % (
            _fmt(compiles, 0), "  ".join(
                "%s=%ss" % (k, _fmt(v, 2)) for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])[:6])))
    worker = _namespace(fleet, "worker")
    if worker:
        lines.append("  WORKER  executed=%s dedup_hits=%s outstanding=%s"
                     % (_fmt(worker.get("executed"), 0),
                        _fmt(worker.get("dedup_hits"), 0),
                        _fmt(worker.get("outstanding"), 0)))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxtop", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("endpoints", nargs="+",
                    help="transport endpoints answering 'metrics' frames")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print ONE snapshot as JSON and exit")
    ap.add_argument("--once", action="store_true",
                    help="render one text frame and exit")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)

    if args.as_json:
        print(json.dumps(snapshot(args.endpoints, timeout=args.timeout),
                         indent=1))
        return 0
    if args.once:
        print(render(snapshot(args.endpoints, timeout=args.timeout)))
        return 0
    try:
        while True:
            frame = render(snapshot(args.endpoints, timeout=args.timeout))
            # clear + home, then the frame (plain ANSI; no curses dep)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
