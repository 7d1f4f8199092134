#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

A new process loads, warms up, measures for `--seconds`, compares what the
timed path produced with the plain reference, prints one JSON object as the
last line of standard output and exits.  Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result; it never falls
back.  See benchmark/harness/runner.py.
"""
import time
T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import runner
    return runner.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
