"""The readers of PR 27's per-layer metrics against hand counts: the join of
a trace's per-instruction sums with a hand-made `mx.compile.op_scopes()` map
(on the hand-made trace and on the block recorded on the v5e), the ring's
stage counters, and the fit loop's work and wait spans.  On a program that
has none of these (the parent of PR 27), every reader reads nothing."""
import gzip
import json
import os

import pytest

import incubator_mxnet_tpu as mx
from benchmark.harness import cells, trace_reduce as tr
from test_trace_reduce import HERE, MS, _hand_made

PHASES = ("fwd", "bwd", "optimizer", "guardian")
NEW = [p + "_device_ms" for p in PHASES] + [
    "ring_source_ms", "ring_stage_ms", "ring_put_ms", "fit_block_tail_ms",
    "guardian_poll_wait_ms"]


def _scope(phase, op=None, mixed=False):
    return {"phase": phase, "op": op, "node": op and op.lower() + "0",
            "mixed": mixed, **({"inside": ["guardian", "optimizer"]}
                               if mixed else {})}


def _ctx(trace, steps, scopes, monkeypatch, label="FusedTrainStep#1"):
    asked = []

    def op_scopes(wanted=None):
        asked.append(wanted)
        return scopes if wanted in (None, label) else {}
    monkeypatch.setattr(mx.compile, "op_scopes", op_scopes, raising=False)
    return {"trace": trace, "steps": steps,
            "programs": [{"label": label}, {"label": "another"}]}, asked


def test_every_new_metric_is_declared_with_its_cells():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for stem in NEW:
        suffixes = (".img",) if stem.startswith("ring_") else (".img", ".tok")
        for suffix in suffixes:
            m = declared[stem + suffix]
            assert m["unit"] == "ms" and m["better"] == "lower"
            assert m["moves"] == {".img": "img_per_s",
                                  ".tok": "tokens_per_s"}[suffix]
            assert len(m["workloads"]) == 1
    assert "ring_source_ms.tok" not in declared


def test_phase_readers_on_the_hand_made_trace(monkeypatch, capsys):
    """Busiest chip 0: fusion.1 30 ms, all-reduce.1 20 ms, fusion.2 30 ms
    (the `while` container is in no per-op sum), 8 steps."""
    scopes = {"fusion.1": _scope("fwd", "Convolution"),
              "fusion.2": _scope("guardian", mixed=True),
              "all-reduce.1": _scope("exchange"),
              "never.ran": _scope("optimizer")}
    ctx, asked = _ctx(tr.reduce(_hand_made()), 8, scopes, monkeypatch)
    got = {p: cells.reader(p + "_device_ms.img")(ctx) for p in PHASES}
    assert got == {"fwd": pytest.approx(30 / 8), "bwd": 0.0,
                   "optimizer": 0.0, "guardian": pytest.approx(30 / 8)}
    split = ctx["scope_join"]
    assert sum(split["by_phase"].values()) == pytest.approx(80 * MS)
    assert split["by_phase"]["exchange"] == 20 * MS
    assert split["mixed_ns"] == 30 * MS and split["unmapped_ns"] == 0
    assert split["by_kind"][("Convolution", "fwd")] == 30 * MS
    # the map was asked for once per label, and the lines printed once
    assert sorted(asked, key=str) == ["FusedTrainStep#1", None, "another"]
    err = capsys.readouterr().err
    assert err.count("device-op time by phase") == 1
    assert "named guardian, holding guardian+optimizer: 0.0300 s" in err
    assert "Convolution fwd 0.0300 s" in err
    assert cells.reader("fwd_device_ms.tok")(ctx) == pytest.approx(30 / 8)


def test_an_instruction_in_no_map_counts_as_other(monkeypatch):
    ctx, _ = _ctx(tr.reduce(_hand_made()), 8,
                  {"fusion.1": _scope("bwd", "BatchNorm")}, monkeypatch)
    assert cells.reader("bwd_device_ms.img")(ctx) == pytest.approx(30 / 8)
    split = ctx["scope_join"]
    assert split["by_phase"]["other"] == 50 * MS
    assert split["unmapped_ns"] == 50 * MS
    assert sum(split["by_phase"].values()) == split["total_ns"] == 80 * MS


def test_phase_readers_on_the_recorded_v5e_block(monkeypatch):
    """`lstm_block_v5e.json.gz` (my chip run, PR 26) with a map made by
    hand from its instruction names: the guardian's reduction, the two
    scans' fusions as `RNN`, the rest left out of the map."""
    with gzip.open(os.path.join(HERE, "data", "lstm_block_v5e.json.gz"),
                   "rt") as f:
        out = tr.reduce(json.load(f))
    by_op = out["per_chip"][0]["by_op"]
    scopes = {n: _scope("guardian", mixed=True) for n in by_op
              if n.startswith("is-finite")}
    scopes.update({n: _scope("fwd", "RNN") for n in by_op
                   if n.startswith("fusion.19")})
    assert scopes and len(scopes) < len(by_op)
    ctx, _ = _ctx(out, 8, scopes, monkeypatch)
    guardian = sum(t for n, t in by_op.items() if n.startswith("is-finite"))
    rnn = sum(t for n, t in by_op.items() if n.startswith("fusion.19"))
    assert cells.reader("guardian_device_ms.tok")(ctx) == \
        pytest.approx(guardian / 8 / 1e6, rel=1e-9)
    assert cells.reader("fwd_device_ms.tok")(ctx) == \
        pytest.approx(rnn / 8 / 1e6, rel=1e-9)
    split = ctx["scope_join"]
    assert sum(split["by_phase"].values()) == pytest.approx(
        sum(by_op.values()), rel=1e-9)
    assert split["by_kind"][("RNN", "fwd")] == pytest.approx(rnn, rel=1e-9)


def test_ring_stage_readers(capsys):
    ctx = {"window_s": 0.5,
           "io": {"batches": 4, "source_s": 0.002, "stage_s": 0.1,
                  "put_s": 0.02, "h2d_s": 0.121}}
    assert cells.reader("ring_source_ms.img")(ctx) == pytest.approx(0.5)
    assert cells.reader("ring_stage_ms.img")(ctx) == pytest.approx(25.0)
    assert cells.reader("ring_put_ms.img")(ctx) == pytest.approx(5.0)
    assert ("source + stage + put 30.500 ms a batch; h2d_s + source_s "
            "30.750 ms a batch; the feeder worked 24.4% of the window") in \
        capsys.readouterr().err


def _span(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


def test_fit_loop_readers(capsys):
    """Two blocks.  Work between them: callbacks 300 + 500 us, polls
    (1000 - 900) + (2000 - 1950) us; waits 900 + 1950 us."""
    ctx = {"spans": [
        _span("fit.step_block", 0, 5000, k=8),
        _span("fit.callbacks", 5000, 300, k=8),
        _span("fit.guardian", 5300, 1000, wait_us=900, steps=8),
        _span("io.h2d", 0, 7000, bytes=1),
        _span("fit.step_block", 10000, 5000, k=8),
        _span("fit.callbacks", 15000, 500, k=8),
        _span("fit.guardian", 15500, 2000, wait_us=1950, steps=8),
        _span("fit.epoch_end", 18000, 4000, wait_us=100, epoch=0)]}
    assert cells.reader("fit_block_tail_ms.img")(ctx) == pytest.approx(
        (300 + 500 + 100 + 50) / 2 / 1e3)
    assert cells.reader("guardian_poll_wait_ms.tok")(ctx) == pytest.approx(
        (900 + 1950) / 2 / 1e3)
    err = capsys.readouterr().err
    assert "fit.callbacks 0.400 ms a block, fit.guardian 0.075 ms" in err
    assert "fit.epoch_end: 4.000 ms, of which 0.100 waiting" in err


def test_a_program_without_the_names_reads_nothing(monkeypatch):
    """What the parent of PR 27 gives: no `op_scopes`, no stage counters,
    no `fit.guardian` span.  Each reader returns None and raises nothing."""
    monkeypatch.delattr(mx.compile, "op_scopes")
    ctx = {"trace": tr.reduce(_hand_made()), "steps": 8, "programs": [],
           "io": {"batches": 4, "h2d_s": 0.1, "stall_s": 0.0, "bytes": 9},
           "spans": [_span("fit.step_block", 0, 5000, k=8),
                     _span("io.h2d", 0, 7000, bytes=1)]}
    bench = cells.benchmark_json()
    names = [m["name"] for m in bench["per_layer"]
             if m["name"].rsplit(".", 1)[0] in NEW]
    assert len(names) == 15
    for name in names:
        assert cells.reader(name)(ctx) is None, name
    # nor a trace without a device plane, nor a live program with no map
    monkeypatch.setattr(mx.compile, "op_scopes", lambda label=None: {},
                        raising=False)
    for trace in (None, tr.reduce(_hand_made())):
        ctx = {"trace": trace, "steps": 8, "programs": []}
        assert cells.reader("fwd_device_ms.img")(ctx) is None
