#!/usr/bin/env python3
"""The readings a cell's `correct` limits are set from, taken on the chip at
the cell's own size.  Not part of a benchmark run.

    python3 benchmark/control.py --workload <name> --seeds 12 --controls 3

For each seed: the program's first block against the plain reference (the
lower reading of every number).  For the first `--controls` seeds also the
control -- the reference put in the program's place and computed in the
configuration's `control_numerics`, the nearest precision below the one it
states -- and the planted faults (a state left unchanged; half of the batch
left out, the mean taken over the rest), each against the same reference.
Every reading goes through `compare.judge` with the cell's committed limits,
as a run's does: its line says `correct` and which numbers were over.  One
JSON line per reading on standard output.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--numerics", default=None,
                    help="comma-separated; default: the configuration's "
                         "control_numerics")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import cells, compare, runner
    import jax
    cell = cells.Cell(cells.benchmark_json(), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control: needs a TPU chip", file=sys.stderr)
        return 2
    cfg, ref = cell.cfg, cell.reference
    faults = ["state_unchanged", "half_batch"]

    def say(kind, seed, nums, seconds):
        ok, compared = compare.judge(nums, cell.limits)
        over = [n for n, c in compared.items() if c["limit"] is not None
                and not (c["value"] is not None and c["value"] <= c["limit"])]
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          "seconds": round(seconds, 1), "correct": ok,
                          "over": over,
                          **{n: v for n, (v, _) in nums.items()},
                          "at": {n: w for n, (_, w) in nums.items()}}),
              flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        program = runner.Program(cell, seed)
        prog, unfused = program.prog, program.unfused
        key, pool, k = program.key, program.pool, program.k
        program.close()
        if unfused:
            print(f"control: seed {seed}: the fused step is not engaged",
                  file=sys.stderr)
            return 1
        reference = compare.run_reference(ref, cfg, key, pool, k)
        say("program", seed, compare.numbers(prog, reference),
            time.perf_counter() - t)
        if i >= args.controls:
            continue
        for numerics in (args.numerics or cfg["control_numerics"]).split(","):
            t = time.perf_counter()
            low = compare.run_reference(ref, cfg, key, pool, k,
                                        numerics=numerics)
            say("control:" + numerics, seed, compare.numbers(low, reference),
                time.perf_counter() - t)
        for fault in faults:
            t = time.perf_counter()
            broken = compare.run_reference(ref, cfg, key, pool, k,
                                           fault=fault)
            say("fault:" + fault, seed, compare.numbers(broken, reference),
                time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
