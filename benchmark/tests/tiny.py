"""One helper for the tests: a whole run of a cell in a child process on the
CPU, at the `tiny` sizes the cell's own configuration and traffic files
state (widths cut -- never done in a cell)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_CHILD = """
import json, sys, time
t0 = time.perf_counter()
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
from benchmark.harness import runner
import sabotage
spec = json.loads({spec!r})
rc = runner.run(spec["workload"], spec["seed"], spec["seconds"],
                spec["trace"], t0, tiny=True,
                sabotage=sabotage.make(spec.get("sabotage")))
sys.exit(rc)
"""


def run_cell(workload, seed=7, seconds=1.0, trace=False, sabotage=None):
    """(exit code, last stdout line as a dict or None, stderr)."""
    spec = json.dumps({"workload": workload, "seed": seed,
                       "seconds": seconds, "trace": trace,
                       "sabotage": sabotage})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(
            root=ROOT, tests=os.path.dirname(os.path.abspath(__file__)),
            spec=spec)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc.returncode, result, proc.stderr
