"""The trace reduction against hand counts: on a small hand-made trace that
has every case (nesting, two chips, collectives hidden and exposed, gaps under
named host spans), and on a slice of a trace recorded on the v5e."""
import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

MS = 1e6


def _hand_made():
    """Window [0, 100 ms).  Chip 0: a `while` container 10..90 whose body is
    fusion.1 10..40, all-reduce.1 35..55 (5 ms hidden under fusion.1, 15 ms
    exposed), fusion.2 60..90.  Chip 1: fusion.1 20..50, all-reduce.1 50..60
    (all exposed).  One program event per chip."""
    def dev(ops, mods):
        return [{"name": tr.OPS_LINE, "events": ops},
                {"name": tr.MODULES_LINE, "events": mods}]
    chip0 = dev([["while.3", 10 * MS, 80 * MS],
                 ["fusion.1", 10 * MS, 30 * MS],
                 ["all-reduce.1", 35 * MS, 20 * MS],
                 ["fusion.2", 60 * MS, 30 * MS]],
                [["jit_stepkd(1)", 10 * MS, 80 * MS]])
    chip1 = dev([["fusion.1", 20 * MS, 30 * MS],
                 ["all-reduce.1", 50 * MS, 10 * MS]],
                [["jit_stepkd(1)", 20 * MS, 40 * MS]])
    host = [{"name": "python3", "events": [
        [tr.WINDOW_MARK, 0.0, 100 * MS], ["bench.feed", 0.0, 8 * MS]]}]
    return [{"name": "/device:TPU:0", "lines": chip0},
            {"name": "/device:TPU:1", "lines": chip1},
            {"name": "/device:TPU:0 SparseCore", "lines": chip1},
            {"name": "/host:CPU", "lines": host}]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.total([[0, 3], [5, 7]]) == 5
    assert tr.clip([[0, 3], [5, 7]], 2, 6) == [[2, 3], [5, 6]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.is_collective("%all-reduce-start.12 = f32[]")
    assert tr.is_container("while.3") and not tr.is_container("fusion.1")


def test_hand_made_trace():
    planes = _hand_made()
    spans = [(n, s, s + d) for n, s, d in planes[-1]["lines"][0]["events"]
             if n != tr.WINDOW_MARK] + [("fit.step_block", 90 * MS, 97 * MS)]
    out = tr.reduce(planes, spans)
    assert out["chips"] == 2                   # the SparseCore plane is out
    assert out["window_ns"] == 100 * MS
    c0, c1 = out["per_chip"]
    assert c0["busy_ns"] == 80 * MS            # the union, container and all
    assert c1["busy_ns"] == 40 * MS
    assert out["busy_ns_max"] == 80 * MS
    assert out["busy_ns_mean"] == 60 * MS
    assert c0["collective_ns"] == 20 * MS
    assert c0["collective_exposed_ns"] == 15 * MS
    assert c1["collective_exposed_ns"] == 10 * MS
    assert "while.3" not in c0["by_op"]        # per-op sums skip containers
    assert c0["by_op"]["fusion.2"] == 30 * MS
    assert c0["modules"] == {"jit_stepkd(1)": [80 * MS]}
    # idle on the busiest chip: 0..10 (8 ms of it under bench.feed) and
    # 90..100 (7 ms under fit.step_block)
    assert dict(out["idle_gaps"]) == {"bench.feed": 10 * MS,
                                      "fit.step_block": 10 * MS}
    assert out["device_ops"][0][0] in ("fusion.1", "fusion.2")
    name, durs = tr.program_events(out)
    assert name == "jit_stepkd(1)" and durs == [80 * MS]


def test_no_device_plane_reads_nothing():
    host_only = [p for p in _hand_made()
                 if not p["name"].startswith(tr.DEVICE_PREFIX)]
    assert tr.reduce(host_only) is None


def test_readers_on_the_hand_made_trace():
    from benchmark.harness import cells
    out = tr.reduce(_hand_made())
    ctx = {"trace": out, "steps": 8}
    assert cells.reader("device_idle_pct.img")(ctx) == pytest.approx(20.0)
    assert cells.reader("step_device_ms.img")(ctx) == pytest.approx(
        (80 + 40) / 2 / 8)
    none = {"trace": None, "steps": 8}
    for name in ("device_idle_pct.img", "step_device_ms.tok"):
        assert cells.reader(name)(none) is None


def test_recorded_v5e_trace():
    """One K=8 block of `lstm_ptb_train`, recorded on a TPU v5 lite (my
    chip run, PR 26): the window mark, the window's first 307.28 ms of
    device events (11,477 op events, names cut to the instruction's own),
    and the feed's annotations.  Expected values were counted apart from
    the reduction, by a sweep over the sorted end points."""
    import gzip
    with gzip.open(os.path.join(HERE, "data", "lstm_block_v5e.json.gz"),
                   "rt") as f:
        planes = json.load(f)
    out = tr.reduce(planes)
    assert out["chips"] == 1
    assert out["window_ns"] == 307279879.0
    assert out["busy_ns_max"] == pytest.approx(284138735.0, rel=1e-9)
    assert 100 * (1 - out["busy_ns_max"] / out["window_ns"]) == \
        pytest.approx(7.530966, rel=1e-6)
    name, durs = tr.program_events(out)
    assert name.startswith("jit_stepkd") and durs == [283336663.0]
    # `while.200` is the K-step scan: a container, 281.7 ms of the block,
    # and so in the union but in no per-op sum
    ops = dict(out["device_ops"])
    assert not any(n.startswith("while") for n in ops)
    assert sum(ops.values()) <= out["busy_ns_max"] * 1.0000001
    # no host span was given, so every gap is unnamed
    assert [n for n, _ in out["idle_gaps"]] == ["host: outside any span"]
    assert out["idle_gaps"][0][1] == pytest.approx(
        307279879.0 - 284138735.0, rel=1e-9)
