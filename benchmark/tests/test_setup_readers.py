"""The readers of PR 38's set-up and epoch-end metrics against a made-up
phase tally and window: the window's own spans come off, a program with no
tally (the parent of PR 38) or a window that compiled reads nothing, and a
tiny run of a cell prints every one of them."""
import pytest

from benchmark.harness import cells
from incubator_mxnet_tpu.obs import trace as obs_trace
from tiny import run_cell

SETUP = ("setup_import_s", "setup_module_s", "setup_jax_trace_s",
         "setup_jax_compile_s", "setup_jax_compiles", "setup_cache_load_s",
         "setup_reown_s", "setup_epoch_end_s")


def _phase(n, s, **jax):
    return {"n": n, "s": s, "jax": {k: {"n": e[0], "s": e[1]}
                                     for k, e in jax.items()}}


TALLY = {
    "": _phase(0, 0.0, trace=(40, 0.5), lower=(9, 0.25),
               compile=(12, 2.0), cache_hit=(2, 0), cache_load=(2, 0.5)),
    "mx.import": _phase(1, 4.0),
    "fit.bind": _phase(3, 0.75, trace=(3, 0.125)),
    "fit.init_params": _phase(3, 1.5),
    "fit.init_optimizer": _phase(3, 0.5),
    "fused.trace": _phase(1, 2.0, trace=(1, 1.75)),
    "compile.lower": _phase(1, 1.0, lower=(1, 0.75)),
    "compile.compile": _phase(1, 3.0, compile=(1, 3.0), cache_hit=(1, 0),
                              cache_load=(1, 2.5)),
    "compile.load": _phase(1, 0.25),
    "fused.reown": _phase(3, 0.625, compile=(1, 0.125)),
    "fit.epoch_end": _phase(3, 1.5),
}


def _span(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


WINDOW = [_span("fit.bind", 0, 250000), _span("fit.init_params", 0, 500000),
          _span("fused.reown", 0, 125000), _span("fit.step_block", 0, 10),
          _span("fit.get_params", 10, 300000, bytes=4096),
          _span("fit.set_params", 300010, 150000, bytes=4096),
          _span("fit.op_counters", 450010, 1000),
          _span("fit.epoch_end", 0, 500000, wait_us=20000)]


@pytest.fixture
def tally(monkeypatch):
    monkeypatch.setattr(obs_trace, "phases", lambda: TALLY)


def _read(name, **ctx):
    return cells.reader(name)(dict({"spans": WINDOW,
                                    "compiles_in_window": 0.0}, **ctx))


def test_setup_readers_take_the_windows_spans_off(tally, capsys):
    assert _read("setup_import_s") == 4.0
    assert _read("setup_module_s") == pytest.approx(2.75 - 0.75)
    assert _read("setup_reown_s") == pytest.approx(0.5)
    assert _read("setup_epoch_end_s") == pytest.approx(1.0)
    assert _read("setup_jax_trace_s") == pytest.approx(3.375)
    # a persistent-cache hit's retrieval is the cache's, not XLA's
    assert _read("setup_jax_compile_s") == pytest.approx(1.5 + 0.5 + 0.125)
    assert _read("setup_jax_compiles") == 12 - 2 + 0 + 1
    assert _read("setup_cache_load_s") == pytest.approx(0.5 + 2.5 + 0.25)
    err = capsys.readouterr().err
    assert 'setup_jax_compile_s by phase: "" 1.500 s, compile.compile ' \
        '0.500 s, fused.reown 0.125 s' in err
    assert "phase mx.import: n 1, 4.000 s" in err


def test_jax_readers_read_nothing_where_the_window_compiled(tally):
    for name in ("setup_jax_trace_s", "setup_jax_compile_s",
                 "setup_jax_compiles", "setup_cache_load_s"):
        assert _read(name, compiles_in_window=1.0) is None, name
    assert _read("setup_module_s", compiles_in_window=1.0) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("phases", [None, {}])
def test_no_tally_reads_nothing(monkeypatch, phases):
    if phases is None:      # the parent of PR 38 has no `phases`
        monkeypatch.delattr(obs_trace, "phases")
    else:
        monkeypatch.setattr(obs_trace, "phases", lambda: phases)
    for name in SETUP:
        assert _read(name) is None, name


def test_epoch_end_from_the_windows_span(capsys):
    assert _read("epoch_end_ms.tok") == 500.0
    err = capsys.readouterr().err
    assert "epoch_end_ms over 1: wait 20.000 ms, fit.get_params 300.000 " \
        "ms, fit.set_params 150.000 ms, fit.op_counters 1.000 ms, other " \
        "29.000 ms; fit.get_params 4096 bytes; fit.set_params 4096 bytes" \
        in err
    assert _read("epoch_end_ms.img", spans=WINDOW[:4]) is None


def test_every_new_metric_is_declared_for_the_cells_that_report_it():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    every = [w["name"] for w in bench["workloads"]]
    for name in SETUP:
        m = declared[name]
        assert (m["moves"], m["layer"], m["source"]) == \
            ("setup_s", "set-up", "program_counter")
        assert m["workloads"] == every
    assert declared["epoch_end_ms.img"]["workloads"] == \
        ["resnet50_train_hostfed"]
    assert declared["epoch_end_ms.tok"]["workloads"] == every[1:]


def test_a_tiny_traced_run_prints_every_new_metric():
    rc, result, err = run_cell("lstm_ptb_train", seed=2147483999,
                               trace=True)
    assert rc == 0, err[-3000:]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    print({n: got.get(n) for n in SETUP + ("epoch_end_ms.tok",)})
    for name in SETUP + ("epoch_end_ms.tok",):
        assert name in got, name
    assert got["setup_import_s"] > 0 and got["setup_jax_compiles"] > 0
    assert got["epoch_end_ms.tok"] > 0 and result["correct"]
