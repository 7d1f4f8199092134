"""Finding a cell's files by the names `BENCHMARK.json` gives.

    configuration <c>   its `file` (sizes), and beside it
                        <c>_reference.py (the plain reference) and
                        <c>_program.py (how the program composes it)
    traffic <t>         benchmark/traffic/<t>.json
    cell <w>            benchmark/limits/<w>.json: the limit of each number
                        that `correct` compares in that cell, with the
                        readings it was set from
    per-layer <m>       benchmark/metrics/<m>.py, or, for a name split by
                        the end-to-end metric it moves (`<m>.img`),
                        benchmark/metrics/<m without the last part>.py

A later PR adds a configuration, a mix, a cell or a per-layer metric by
adding such files and the entries that name them; nothing here is edited.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def load_module(path):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    def __init__(self, bench, name, tiny=False):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(by_name)}")
        self.spec = by_name[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.spec["config"])
        path = os.path.join(ROOT, conf["file"])
        with open(path) as f:
            self.cfg = json.load(f)
        stem = path[:-len(".json")]
        self.reference = load_module(stem + "_reference.py")
        self.adapter = load_module(stem + "_program.py")
        with open(os.path.join(BENCH_DIR, "traffic",
                               self.spec["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        path = os.path.join(BENCH_DIR, "limits", name + ".json")
        self.limits = {}       # a cell without limits is never `correct`
        if os.path.exists(path):
            with open(path) as f:
                self.limits = json.load(f)["limits"]
        for part in (self.cfg, self.traffic):
            small = part.pop("tiny", {})
            if tiny:        # the tests' size, stated in the file itself
                part.update(small)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name]) and
                          m["moves"] in reported]


def reader(metric_name):
    """The `read(ctx)` of a per-layer metric."""
    for stem in (metric_name, metric_name.rsplit(".", 1)[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(path).read
    raise FileNotFoundError(f"no reader for per-layer metric {metric_name!r} "
                            "under benchmark/metrics/")
