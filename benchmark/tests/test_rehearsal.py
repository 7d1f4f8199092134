"""CPU rehearsal: every cell's run, end to end at a tiny size, and the rules
of BENCHMARK.json that need no chip."""
import json
import os
import re
import subprocess
import sys

import pytest

import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_SOURCES = {"device_trace", "host_clock"}


def test_names_units_and_arrows():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    cells = [w["name"] for w in BENCH["workloads"]]
    reports = {c: {m["name"] for m in BENCH["end_to_end"]
                   if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
    for m in BENCH["per_layer"]:
        for c in m.get("workloads", cells):
            assert m["moves"] in reports[c], (m["name"], c)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1


def test_every_per_layer_metric_has_a_reader():
    from benchmark.harness import cells
    for m in BENCH["per_layer"]:
        assert callable(cells.reader(m["name"]))


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable] + BENCH["command"][1:] +
        ["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None)
    assert not proc.stdout.strip()


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_cell_runs_at_a_tiny_size(workload, trace):
    rc, result, err = tiny.run_cell(workload, seed=2147483659, trace=trace)
    assert rc == 0, err[-3000:]
    assert RESULT_KEYS <= set(result), sorted(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    source = {m["name"]: m["source"]
              for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    # a CPU run prints counts and the program's own spans, never a number
    # that only a chip can give
    for name in result["metrics"]:
        assert source[name] not in DEVICE_SOURCES, name
    assert "busy_s" not in result["device"]
    if trace:
        assert any(n.startswith("compiles_in_window")
                   for n in result["metrics"])
