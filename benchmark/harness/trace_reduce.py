"""From a `jax.profiler` trace to numbers.

The reduction works on a neutral form, so that it can be checked against a
small recorded trace kept as JSON (tests/data/): a list of planes, each
`{"name": str, "lines": [{"name": str, "events": [[name, start_ns, dur_ns],
...]}]}`.  `load_xplane` makes that form from the profiler's `.xplane.pb`
with nothing but JAX.

What is read, on each device plane (`/device:TPU:<n>`):
  * line "XLA Ops": one event per executed HLO op.  Control-flow containers
    (`while`, `conditional`, `call`) span their bodies, whose ops are events
    of their own, so the busy time is the UNION of the intervals, and the
    per-op sums leave the containers out.
  * line "XLA Modules": one event per executed program.
The host plane (`/host:CPU`) carries the benchmark's `TraceAnnotation`s; the
one named `bench.window` bounds the measured window and ties the trace's
clock to the host's.
"""
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.window"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def load_xplane(log_dir):
    """Neutral form of the newest `.xplane.pb` under `log_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        keep_all = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if keep_all and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if keep_all or e.name.startswith("bench.")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals):
    """Merged, sorted [lo, hi) intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The part of merged `intervals` not covered by merged `holes`."""
    out = []
    j = 0
    for lo, hi in intervals:
        cur = lo
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < hi:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


def short_name(name):
    """An op event is named by its whole HLO line (`%fusion.3 = f32[...]
    fusion(...), kind=...`); what is kept is the instruction's own name,
    `fusion.3`."""
    return name.lstrip("%").split(" ", 1)[0]


def base_name(name):
    """`all-reduce-start.12` -> `all-reduce-start`."""
    return short_name(name).split(".")[0]


def is_container(name):
    return base_name(name) in CONTAINERS


def is_collective(name):
    base = base_name(name)
    return any(base.startswith(c) for c in COLLECTIVES)


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(planes):
    """Device planes in chip order; planes of other cores of a chip (names
    with a suffix after the number) are left out."""
    out = []
    for p in planes:
        tail = p["name"][len(DEVICE_PREFIX):]
        if p["name"].startswith(DEVICE_PREFIX) and tail.isdigit():
            out.append((int(tail), p))
    return [p for _, p in sorted(out, key=lambda t: t[0])]


def window_of(planes):
    """(lo_ns, hi_ns) of the `bench.window` annotation; without one, the
    span of all device events."""
    for p in planes:
        if p["name"].startswith(DEVICE_PREFIX):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_MARK:
                    return start, start + dur
    starts = [e[1] for p in device_planes(planes)
              for e in _line(p, OPS_LINE)]
    ends = [e[1] + e[2] for p in device_planes(planes)
            for e in _line(p, OPS_LINE)]
    if not starts:
        return None
    return min(starts), max(ends)


def annotations(planes):
    """`(name, lo_ns, hi_ns)` of the benchmark's own host annotations (the
    feed's `bench.feed`), the window mark left out."""
    return [(n, s, s + d) for p in planes
            if not p["name"].startswith(DEVICE_PREFIX)
            for line in p["lines"] for n, s, d in line["events"]
            if n != WINDOW_MARK]


def reduce(planes, host_spans=()):
    """All the numbers the per-layer readers take from a trace.

    `host_spans` are `(name, lo_ns, hi_ns)` on the trace's clock: what the
    host was doing, for naming the device's idle gaps.  Returns None where
    the trace holds no device plane or no device event in the window."""
    win = window_of(planes)
    chips = device_planes(planes)
    if win is None or not chips:
        return None
    lo, hi = win
    per_chip = []
    for p in chips:
        ops = [(n, s, s + d) for n, s, d in _line(p, OPS_LINE)
               if s + d > lo and s < hi]
        busy = clip(union([(s, e) for _, s, e in ops]), lo, hi)
        compute = clip(union([(s, e) for n, s, e in ops
                              if not is_container(n) and
                              not is_collective(n)]), lo, hi)
        coll = clip(union([(s, e) for n, s, e in ops if is_collective(n)]),
                    lo, hi)
        by_op = {}
        for n, s, e in ops:
            if not is_container(n):
                by_op[n] = by_op.get(n, 0.0) + (min(e, hi) - max(s, lo))
        modules = {}
        for n, s, d in _line(p, MODULES_LINE):
            if s >= lo and s + d <= hi:
                modules.setdefault(n, []).append(d)
        per_chip.append({
            "busy": busy, "busy_ns": total(busy),
            "collective_ns": total(coll),
            "collective_exposed_ns": total(subtract(coll, compute)),
            "by_op": by_op, "modules": modules})
    if not any(c["busy_ns"] > 0 for c in per_chip):
        return None
    busiest = max(per_chip, key=lambda c: c["busy_ns"])
    gaps = {}
    spans = sorted(host_spans, key=lambda t: t[1])
    for glo, ghi in subtract([[lo, hi]], busiest["busy"]):
        name, best = "host: outside any span", 0.0
        for sname, slo, shi in spans:
            if slo >= ghi:
                break
            cover = min(shi, ghi) - max(slo, glo)
            if cover > best:
                name, best = sname, cover
        gaps[name] = gaps.get(name, 0.0) + (ghi - glo)
    return {
        "window_ns": hi - lo,
        "chips": len(per_chip),
        "busy_ns_mean": sum(c["busy_ns"] for c in per_chip) / len(per_chip),
        "busy_ns_max": busiest["busy_ns"],
        "per_chip": per_chip,
        "idle_gaps": sorted(gaps.items(), key=lambda t: -t[1]),
        "device_ops": sorted(busiest["by_op"].items(), key=lambda t: -t[1]),
    }


def program_events(summary, chip=0):
    """(name, [durations_ns]) of the program that took most device time in
    the window on `chip`: the train block."""
    mods = summary["per_chip"][chip]["modules"]
    if not mods:
        return None, []
    name = max(mods, key=lambda n: sum(mods[n]))
    return name, mods[name]
