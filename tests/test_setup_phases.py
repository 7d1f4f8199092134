"""Phases (PR 38): a span that is also tallied, on or off, with JAX's compile
events put down to the innermost phase open on the calling thread -- the
tally that `benchmark/metrics/setup_*.py` read set-up from."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import compile as mxc
from incubator_mxnet_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 4


@pytest.fixture(autouse=True)
def _clean():
    """Tracing off, no spans and an empty tally around every test."""
    obs_trace.disable()
    obs_trace.reset()
    obs_trace.reset_phases()
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(K)
    yield
    os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)
    obs_trace.disable()
    obs_trace.reset()
    obs_trace.reset_phases()
    mxc.reset_for_tests()


def _annotations(monkeypatch):
    """The names `TraceAnnotation`s were entered with."""
    entered = []
    plain = obs_trace._annotate
    monkeypatch.setattr(obs_trace, "_annotate",
                        lambda name: entered.append(name) or plain(name))
    return entered


def _run(fn, n):
    """A new `jax.jit` of `fn` on a host array (nothing eager compiles)."""
    jax.jit(fn)(np.ones(n, np.float32)).block_until_ready()


def _compiles(name):
    return obs_trace.phases().get(name, {}).get("jax", {}).get(
        "compile", {}).get("n", 0)


def test_phase_with_tracing_off_is_tallied_and_opens_no_span(monkeypatch):
    entered = _annotations(monkeypatch)
    with obs_trace.phase("t.off", cat="train", x=1) as ph:
        ph.note(y=2)
    with obs_trace.phase("t.off"):
        pass
    assert obs_trace.buffered() == [] and entered == []
    got = obs_trace.phases()["t.off"]
    assert got["n"] == 2 and got["s"] >= ph.s > 0 and got["jax"] == {}


def test_phase_with_tracing_on_is_one_span(monkeypatch):
    entered = _annotations(monkeypatch)
    obs_trace.enable()
    with obs_trace.phase("t.on", cat="train", x=1) as ph:
        ph.note(y=2)
        with obs_trace.span("t.child"):
            pass
    spans = obs_trace.buffered()
    (rec,) = [s for s in spans if s["name"] == "t.on"]
    (child,) = [s for s in spans if s["name"] == "t.child"]
    assert entered == ["t.on", "t.child"]
    assert rec["cat"] == "train" and rec["args"] == {"x": 1, "y": 2}
    assert child["pa"] == rec["sp"]
    assert rec["dur"] == pytest.approx(ph.s * 1e6, abs=1000)
    assert obs_trace.phases()["t.on"]["n"] == 1


def test_compile_inside_a_phase_is_its_and_outside_is_nobodys():
    with obs_trace.phase("t.jit"):
        _run(lambda x: x * 3.0 + 1.0, 3)
    _run(lambda x: x * 5.0 - 1.0, 3)
    got = obs_trace.phases()
    assert _compiles("t.jit") == 1 and _compiles("") == 1
    inside = got["t.jit"]["jax"]
    assert set(inside) >= {"trace", "lower", "compile"}
    # JAX's times lie inside the phase's own
    assert sum(e["s"] for e in inside.values()) <= got["t.jit"]["s"]
    assert got[""]["n"] == 0 and got[""]["s"] == 0.0


def test_a_jit_traced_inside_another_jits_trace_counts_once():
    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)
    with obs_trace.phase("t.nest") as ph:
        _run(lambda x: inner(x) + inner(x + 1.0), 4)
    ev = obs_trace.phases()["t.nest"]["jax"]
    assert ev["trace"]["n"] == 1 and ev["trace"]["s"] <= ph.s


def test_nested_phases_and_two_threads_keep_their_own_innermost():
    opened, release = threading.Barrier(2), threading.Event()

    def other():
        with obs_trace.phase("t.thread"):
            opened.wait()
            release.wait()

    t = threading.Thread(target=other)
    t.start()
    with obs_trace.phase("t.outer"):
        with obs_trace.phase("t.inner"):
            opened.wait()    # the other thread's phase is open meanwhile
            _run(lambda x: x - 7.0, 2)
        _run(lambda x: x * 9.0, 2)
    release.set()
    t.join()
    assert _compiles("t.inner") == 1 and _compiles("t.outer") == 1
    assert _compiles("t.thread") == 0 and _compiles("") == 0
    got = obs_trace.phases()
    assert got["t.outer"]["s"] >= got["t.inner"]["s"]
    assert got["t.thread"]["n"] == 1


def test_no_phase_is_lost_between_threads():
    """More threads than cores closing phases at a short switch interval:
    the tally's read-modify-write holds its lock, so no count is lost."""
    threads, each = 4 * (os.cpu_count() or 4), 200
    start = threading.Barrier(threads)

    def work():
        start.wait()
        for _ in range(each):
            with obs_trace.phase("t.many"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert obs_trace.phases()["t.many"]["n"] == threads * each


def test_reset_leaves_the_tally_and_reset_phases_clears_it():
    obs_trace.enable()
    with obs_trace.phase("t.keep"):
        pass
    obs_trace.reset()
    assert obs_trace.buffered() == []
    assert obs_trace.phases()["t.keep"]["n"] == 1
    obs_trace.reset_phases()
    assert obs_trace.phases() == {}


def _fit():
    rng = np.random.default_rng(0)
    x = rng.random((3 * K * 8, 6), dtype=np.float32)
    y = rng.integers(0, 4, (len(x),)).astype(np.float32)
    d = mx.sym.Variable("data")
    f = mx.sym.FullyConnected(d, num_hidden=4, name="fc1")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(f, name="softmax"),
                        context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=8), num_epoch=1,
            optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    assert mod._fused_step is not None and not mod._fused_step.broken
    return mod


SET_UP = ("fit.bind", "fit.init_params", "fit.init_optimizer", "fused.trace",
          "compile.lower", "compile.compile", "fused.reown", "fit.epoch_end",
          "fit.get_params", "fit.set_params", "fit.op_counters")


def test_fit_with_tracing_off_records_no_span_and_tallies_every_phase():
    mod = _fit()
    assert obs_trace.buffered() == []
    got = obs_trace.phases()
    for name in SET_UP:
        assert got[name]["n"] >= 1, name
    ep = got["fit.epoch_end"]["s"]
    assert got["fit.get_params"]["s"] + got["fit.set_params"]["s"] <= ep
    # the trace's and the program cache's seconds are the phases' own
    stats = mod._fused_step.compile_phase_stats()
    assert stats["trace_s"] == got["fused.trace"]["s"]
    assert sum(p["lower_s"] for p in stats["programs"]) == pytest.approx(
        got["compile.lower"]["s"])
    assert _compiles("compile.compile") >= 1


def test_fit_with_tracing_on_notes_the_bytes_each_way():
    obs_trace.enable()
    mod = _fit()
    spans = {s["name"]: s for s in obs_trace.buffered()}
    for name in SET_UP:
        assert name in spans, name
    nbytes = sum(a.size * a.dtype.itemsize
                 for d in mod.get_params() for a in d.values())
    assert spans["fit.get_params"]["args"]["bytes"] == nbytes
    assert spans["fit.set_params"]["args"]["bytes"] == nbytes
    end = spans["fit.epoch_end"]
    for name in ("fit.get_params", "fit.set_params", "fit.op_counters"):
        assert end["ts"] <= spans[name]["ts"] and \
            spans[name]["ts"] + spans[name]["dur"] <= end["ts"] + end["dur"]
    reown = spans["fused.reown"]["args"]
    assert reown["mode"] == "whole" and reown["leaves"] >= 4
    assert reown["bytes"] >= 2 * 4 * (6 * 4 + 4)   # fc weight, bias, momenta


def test_the_package_import_is_a_phase():
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, incubator_mxnet_tpu as mx; "
         "print(json.dumps(mx.obs.trace.phases()['mx.import']))"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=300, cwd=REPO)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["n"] == 1 and got["s"] > 0


def test_the_tally_is_the_phase_namespace_of_a_scrape():
    with obs_trace.phase("t.scrape"):
        _run(lambda x: x + 2.5, 2)
    _run(lambda x: x + 3.5, 2)
    text = mx.obs.render_prometheus()
    parsed = mx.obs.parse_prometheus(text)
    names = {n for n, _ in parsed}
    assert parsed[("mx_phase_t_scrape_n", ())] == 1
    assert "mx_phase_t_scrape_jax_compile_s" in names
    assert parsed[("mx_phase_outside_jax_compile_n", ())] == 1


def test_mxtop_shows_the_largest_phases():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import mxtop
    with obs_trace.phase("t.top"):
        _run(lambda x: x / 3.0, 2)
    frame = mxtop.render({"endpoints": {}, "unreachable": [],
                          "fleet": mx.obs.registry().collect()})
    (line,) = [ln for ln in frame.splitlines() if "PHASES" in ln]
    assert "jax_compiles=1" in line and "t.top=" in line
