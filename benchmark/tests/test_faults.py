"""`correct` has to come out false when the timed path is broken underneath:
the whole of a run (minus the look for a chip), at a tiny size, with each
fault a training cell can have planted in turn -- and the control, the
reference computed one precision lower in the program's place."""
import json
import os

import pytest

import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
ONE_CHIP = {}      # configuration -> its first cell
for w in BENCH["workloads"]:
    ONE_CHIP.setdefault(w["config"], w["name"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", sorted(ONE_CHIP.values()))
def test_planted_fault_is_not_correct(workload, fault):
    rc, result, err = tiny.run_cell(workload, sabotage=fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False, result["compared"]
    over = [n for n, c in result["compared"].items()
            if c["limit"] is not None and
            (c["value"] is None or c["value"] > c["limit"])]
    assert over, result["compared"]


@pytest.mark.parametrize("workload", sorted(ONE_CHIP.values()))
def test_sound_run_is_correct(workload):
    rc, result, err = tiny.run_cell(workload)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["compared"]


def _readings(config, numerics="float32", fault=None):
    from benchmark.harness import cells, compare, traffic
    cell = cells.Cell(BENCH, ONE_CHIP[config], tiny=True)
    batch = cell.traffic["batch_per_chip"] * 4
    pool = traffic.make_pool(cell.traffic, cell.cfg, cell.adapter, batch, 11)
    return cell, compare.run_reference(
        cell.reference, cell.cfg, compare.program_key(11), pool,
        cell.cfg["fused_step_block"], numerics=numerics, fault=fault)


@pytest.mark.parametrize("config", sorted(ONE_CHIP))
def test_control_is_not_correct(config):
    """The reference one precision lower, in the program's place."""
    from benchmark.harness import compare
    cell, ref = _readings(config)
    _, low = _readings(config, numerics=cell.cfg["control_numerics"])
    ok, compared = compare.judge(compare.numbers(low, ref),
                                 cell.limits)
    assert not ok, compared
    ok, compared = compare.judge(compare.numbers(ref, ref),
                                 cell.limits)
    assert ok, compared
