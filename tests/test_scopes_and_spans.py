"""The names this framework gives a profile: `jax.named_scope`s inside the
fused train step and around every graph node (read back per HLO instruction
by `mx.compile.op_scopes`), and the obs spans around the host's WORK in the
input ring's feeder and in the fit loop, each beside its always-on counter
and each on the profiler's own clock as well."""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import compile as mxc
from incubator_mxnet_tpu import io_plane
from incubator_mxnet_tpu.compile import scopes
from incubator_mxnet_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 4


@pytest.fixture(autouse=True)
def _clean():
    """Tracing off and an empty span buffer around every test; K steps a
    block; the program cache's singleton dropped afterwards."""
    obs_trace.enabled()
    obs_trace.reset()
    os.environ["MXNET_FUSED_STEP_BLOCK"] = str(K)
    yield
    os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)
    obs_trace.disable()
    obs_trace.reset()
    mxc.reset_for_tests()


def _symbol():
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           name="conv1")
    b = mx.sym.BatchNorm(c, name="bn1")
    a = mx.sym.Activation(b, act_type="relu", name="relu1")
    p = mx.sym.Pooling(a, global_pool=True, pool_type="avg", kernel=(1, 1),
                       name="pool1")
    f = mx.sym.FullyConnected(mx.sym.Flatten(p), num_hidden=4, name="fc1")
    return mx.sym.SoftmaxOutput(f, name="softmax")


def _fit(blocks=2, batch=8, side=8, source_sleep=0.0, callback=None,
         one_epoch=False):
    """One `Module.fit` of `blocks` K-step blocks over a host iterator.
    `one_epoch`: the iterator has this epoch and no other.  The reset at the
    epoch's end starts the feeder reading ahead into the next epoch, and
    how many of ITS batches get staged before `fit` returns and closes the
    ring is a race (none on an idle machine, one to three under six test
    workers); with no next epoch every count is of this epoch alone."""
    rng = np.random.default_rng(0)
    n = blocks * K * batch
    x = rng.random((n, 3, side, side), dtype=np.float32)
    y = rng.integers(0, 4, (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=batch)
    if source_sleep:
        plain_next = it.next

        def slow_next():
            time.sleep(source_sleep)
            return plain_next()
        it.next = slow_next
    if one_epoch:
        it.reset = lambda: None         # exhausted once, exhausted for good
    mod = mx.mod.Module(_symbol(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            eval_metric="ce", batch_end_callback=callback)
    assert mod._fused_step is not None and not mod._fused_step.broken
    return mod


_CHILD = """
import json, os, sys
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import conftest                                   # the 8-device CPU mesh
import incubator_mxnet_tpu as mx
import test_scopes_and_spans as t
os.environ["MXNET_FUSED_STEP_BLOCK"] = str(t.K)
mod = t._fit()
label = mod._fused_step._audit_key
print(json.dumps({"scopes": mx.compile.op_scopes(label), "label": label,
                  "programs": mx.compile.stats()["programs"]}))
"""


# -- (a) scopes in the compiled step ----------------------------------------

def test_classify_rules():
    c = scopes.classify
    assert c("jit(stepk)/while/body/closed_call/fwd/jvp(Convolution:conv1)"
             "/conv_general_dilated") == \
        {"phase": "fwd", "op": "Convolution", "node": "conv1", "loop": None}
    assert c("jit(stepk)/while/body/closed_call/bwd/transpose(jvp("
             "Convolution:conv1))/conv_general_dilated")["phase"] == "bwd"
    # a transpose that kept the forward's scope is the backward pass
    assert c("fwd/transpose(jvp(BatchNorm:bn1))/mul") == \
        {"phase": "bwd", "op": "BatchNorm", "node": "bn1", "loop": None}
    # XLA joins merged names with ';': the first speaks
    assert c("a/optimizer/mul;a/guardian/select_n")["phase"] == "optimizer"
    assert c("jit(stepk)/while/body/add") == \
        {"phase": "other", "op": None, "node": None, "loop": None}
    assert c("") == {"phase": "other", "op": None, "node": None,
                     "loop": None}
    # inside a loop's body the body's node speaks, and the loop is named
    assert c("jit(stepk)/while/body/closed_call/bwd/transpose(jvp(_foreach:"
             "lm_loop1))/while/body/transpose(jvp(FullyConnected:lm_fc))/"
             "dot_general") == {"phase": "bwd", "op": "FullyConnected",
                                "node": "lm_fc", "loop": "lm_loop1"}
    assert c("fwd/jvp(_foreach:outer)/while/body/jvp(_while_loop:inner)/"
             "while/body/add") == {"phase": "fwd", "op": "_while_loop",
                                   "node": "inner", "loop": "outer"}


def test_parse_fusions_and_mixed():
    text = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %s = f32[4]{0} select(%m, %m, %p0), metadata={op_name="jit(f)/guardian/select_n"}
}

%fused_computation.1 (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  %i = f32[4]{0} add(%p0.1, %p0.1), metadata={op_name="jit(f)/while/body/add"}
  ROOT %t = f32[4]{0} tanh(%i), metadata={op_name="jit(f)/fwd/jvp(Activation:act0)/tanh"}
}

%fused_computation.2 (p0.2: f32[4]) -> f32[4] {
  %p0.2 = f32[4]{0} parameter(0)
  %u = f32[4]{0} tanh(%p0.2), metadata={op_name="jit(f)/fwd/jvp(Activation:act0)/tanh"}
  ROOT %d = f32[4]{0} copy(%u), metadata={op_name="jit(f)/while/body/dynamic_update_slice"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(f)/metric/reduce_sum"}
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/guardian/select_n"}
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/fwd/jvp(Activation:act0)/tanh"}
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/dynamic_update_slice"}
  %reduce.2 = f32[] reduce(%fusion.1, %x), to_apply=%region_0.1, metadata={op_name="jit(f)/metric/reduce_sum"}
  ROOT %copy.1 = f32[4]{0} copy(%fusion.1)
}
"""
    got = scopes.parse(text)
    # the insides of the fusion and of the reducer are no events
    assert set(got) == {"x", "fusion.1", "fusion.2", "fusion.3", "reduce.2",
                        "copy.1"}
    assert got["fusion.1"] == {"phase": "guardian", "op": None, "node": None,
                               "loop": None, "mixed": True,
                               "inside": ["guardian", "optimizer"]}
    # an instruction under no phase (the scan's indexing) mixes nothing ...
    assert got["fusion.2"] == {"phase": "fwd", "op": "Activation",
                               "node": "act0", "loop": None, "mixed": False}
    # ... but a phase inside a fusion that XLA named for none does
    assert got["fusion.3"] == {"phase": "other", "op": None, "node": None,
                               "loop": None, "mixed": True, "inside": ["fwd"]}
    assert got["reduce.2"]["phase"] == "metric" and \
        not got["reduce.2"]["mixed"]
    assert got["copy.1"]["phase"] == "other"


def test_op_scopes_of_a_fused_block_and_after_the_disk_tier(tmp_path):
    mxc.reset_for_tests()
    mxc.set_cache_dir(str(tmp_path))
    mod = _fit()
    label = mod._fused_step._audit_key
    got = mxc.op_scopes(label)
    assert got and got == mxc.op_scopes()
    phases = {v["phase"] for v in got.values()}
    assert {"fwd", "bwd", "guardian", "metric", "other"} <= phases
    # XLA fuses the optimizer's update into the guardian's keeps and
    # reductions; where no instruction is the optimizer's own, the mixed
    # fusions say that they hold it
    inside = {p for v in got.values() for p in v.get("inside", ())}
    assert "optimizer" in phases | inside
    assert all(v["mixed"] == ("inside" in v) for v in got.values())
    conv = {v["phase"] for v in got.values()
            if v["op"] == "Convolution" and v["node"] == "conv1"}
    assert {"fwd", "bwd"} <= conv
    assert {"BatchNorm", "FullyConnected"} <= \
        {v["op"] for v in got.values()}
    # the executable outlives the module in the cache's live tier
    del mod
    assert mxc.op_scopes(label) == got
    assert mxc.op_scopes("no such program") == {}
    # another process loads the executable from the disk tier (no
    # compile) and reads the same map from it
    env = dict(os.environ, MXNET_PROGRAM_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", _CHILD % {"repo": REPO}],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    child = json.loads(proc.stdout.strip().splitlines()[-1])
    block = [p for p in child["programs"] if p["label"] == child["label"]]
    assert block and all(p["compiles"] == 0 for p in block)
    assert sum(p["disk_hits"] for p in block) >= 1
    assert child["scopes"] == got


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The optimized HLO of the K-step block with the scopes and with
    every `jax.named_scope` made a no-op: the same instructions in the
    same order, once the metadata and the instructions' names (which XLA
    derives from it) are taken out."""
    import jax

    def text():
        mod = _fit(blocks=1)
        (exe,) = [e for p in mod._fused_step.cached_programs()
                  for e in p.executables()]
        body = exe.as_text()
        body = body[body.index("\n\n%"):]        # past the file tables
        body = re.sub(r",? ?metadata=\{[^}]*\}", "", body)
        return re.sub(r"%[\w.\-]+", "%", body)

    with_scopes = text()
    assert "fusion" in with_scopes
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    mxc.reset_for_tests()
    assert text() == with_scopes


# -- (b), (c) work spans and stage counters ---------------------------------

def _by_name(spans, name):
    return sorted((s for s in spans if s["name"] == name),
                  key=lambda s: s["ts"])


def _overlap(a, b):
    return min(a["ts"] + a["dur"], b["ts"] + b["dur"]) - max(a["ts"],
                                                             b["ts"])


def _stage_timing_disagrees(spans, io0, io1):
    """None where each stage counter timed the same interval as its span
    and h2d_s is stage + put (and the adoption check), else what did not."""
    for span_name, key in (("io.source", "source_s"),
                           ("io.stage", "stage_s"), ("io.h2d", "put_s")):
        durs = [s["dur"] for s in spans if s["name"] == span_name]
        if sum(durs) / 1e6 != pytest.approx(
                io1[key] - io0[key], rel=0.10, abs=30e-6 * len(durs)):
            return f"{key}: spans {sum(durs) / 1e6} s, counter " \
                   f"{io1[key] - io0[key]} s"
    h2d = io1["h2d_s"] - io0["h2d_s"]
    parts = io1["stage_s"] - io0["stage_s"] + io1["put_s"] - io0["put_s"]
    if not parts <= h2d <= parts * 1.5 + 1e-3:
        return f"h2d_s {h2d} s against stage + put {parts} s"
    return None


def test_fit_spans_and_stage_counters():
    obs_trace.enable()
    calls = []
    io0 = io_plane.stats()
    mod = _fit(blocks=3, batch=32, side=32, source_sleep=0.002,
               callback=lambda param: calls.append(param.nbatch),
               one_epoch=True)
    io1 = io_plane.stats()
    spans = list(obs_trace.buffered())
    assert calls == list(range(3 * K))

    # the feeder: three sibling leaf spans a batch, one after another
    feeder = [s for s in spans if s["thread"] == "mx-io-h2d"]
    assert {s["name"] for s in feeder} == {"io.source", "io.stage", "io.h2d"}
    feeder.sort(key=lambda s: s["ts"])
    for a, b in zip(feeder, feeder[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1, (a, b)
    batches = io1["batches"] - io0["batches"]
    assert batches == 3 * K
    assert len(_by_name(spans, "io.stage")) == batches
    assert len(_by_name(spans, "io.h2d")) == batches
    # more pulls than batches: the one that found the source dry (and
    # the one the reset at the epoch's end set off, dry as well)
    assert len(_by_name(spans, "io.source")) >= batches + 1
    stage = _by_name(spans, "io.stage")[0]
    assert stage["args"]["bytes"] == 32 * 3 * 32 * 32 * 4 + 32 * 4
    assert stage["args"]["copies"] == 2
    # each counter times the same interval as its span, and h2d_s stays
    # what it was: stage + put (and the adoption check).  A counter's two
    # clock reads stand AROUND its span's, a few microseconds apart: under
    # six test workers the feeder thread can lose the interpreter between
    # them for longer than the whole tolerance.  The tolerance stays as it
    # is; a run that fails it is measured again (a misplaced counter fails
    # every time, a descheduled thread does not)
    why = _stage_timing_disagrees(spans, io0, io1)
    for _ in range(3):
        if why is None:
            break
        obs_trace.reset()
        again0 = io_plane.stats()
        _fit(blocks=3, batch=32, side=32, source_sleep=0.002, one_epoch=True)
        why = _stage_timing_disagrees(obs_trace.buffered(), again0,
                                      io_plane.stats())
    assert why is None, why
    assert io1["source_s"] - io0["source_s"] >= 0.002 * batches

    # the fit loop: work between two blocks, and the epoch's end
    main = threading.current_thread().name
    blocks = _by_name(spans, "fit.step_block")
    assert len(blocks) == 3
    callbacks = _by_name(spans, "fit.callbacks")
    assert len(callbacks) == 3 and callbacks[0]["args"]["k"] == K
    assert [s["args"]["nbatch"] for s in callbacks] == [0, K, 2 * K]
    # a poll once the blocks BEFORE the newest cover
    # MXNET_GUARDIAN_INTERVAL (8) steps, so after the third block with
    # that block in flight, and the forced one that drains it at the
    # epoch's end
    polls = _by_name(spans, "fit.guardian")
    assert [s["args"]["steps"] for s in polls] == [2 * K, K]
    assert [s["args"]["in_flight"] for s in polls] == [K, 0]
    assert blocks[2]["ts"] < polls[0]["ts"]
    (epoch_end,) = _by_name(spans, "fit.epoch_end")
    assert epoch_end["args"]["nbatch"] == 3 * K
    for s in callbacks + polls + [epoch_end]:
        assert s["thread"] == main and s["pa"] is None
        assert all(_overlap(s, b) <= 1 for b in blocks), s
    # a wait FOR the device inside a work span is told apart: the poll's
    # gather, the epoch end's metric read, and what a cursor move stood
    # in the runtime's enqueue beyond the typical move
    for s in callbacks + polls + [epoch_end]:
        assert 0 <= s["args"]["wait_us"] <= s["dur"] + 1
    waited = mod._guardian.stats()["poll_wait_s"]
    assert mod._guardian.stats()["polls"] == len(polls)
    assert sum(s["args"]["wait_us"] for s in polls) == pytest.approx(
        waited * 1e6, abs=len(polls) + 1)
    # set-up's spans, once a fit
    for name in ("fit.bind", "fit.init_params", "fit.init_optimizer",
                 "fused.trace", "compile.lower", "compile.compile"):
        assert _by_name(spans, name), name


def test_cursor_move_that_stands_in_the_enqueue_is_a_wait(monkeypatch):
    """The K cursor moves are the same host work K times.  One that takes
    longer stood in the runtime's enqueue behind a busy device: that time
    is the `fit.callbacks` span's `wait_us`, the rest of the span its
    work, and a callback's own time is never counted as a wait."""
    stall, slow_callback = 0.15, 0.02
    move = mx.mod.Module._fit_block_cursor

    def stalled(self, j):
        if j == 1:
            time.sleep(stall)
        return move(self, j)

    monkeypatch.setattr(mx.mod.Module, "_fit_block_cursor", stalled)
    obs_trace.enable()
    _fit(blocks=2, callback=lambda param: time.sleep(
        slow_callback if param.nbatch % K == 2 else 0))
    callbacks = _by_name(obs_trace.buffered(), "fit.callbacks")
    assert len(callbacks) == 2
    for s in callbacks:
        work = s["dur"] - s["args"]["wait_us"]
        assert stall * 1e6 - 2000 <= s["args"]["wait_us"] <= s["dur"]
        assert slow_callback * 1e6 <= work < stall * 1e6


def test_tracing_off_opens_no_span_and_counters_still_advance(monkeypatch):
    obs_trace.disable()
    entered = []
    monkeypatch.setattr(obs_trace, "_annotate",
                        lambda name: entered.append(name))
    ring = io_plane.H2DRing(io_plane.RingPlacement(), name="test")
    batch = [np.ones((64, 64), np.float32), np.zeros((64,), np.float32)]
    ended0, io0 = obs_trace.stats()["ended"], io_plane.stats()
    for _ in range(4):
        assert ring.timed_source(lambda: batch) is batch
        assert ring.put(batch)
        ring.get()
    assert obs_trace.stats()["ended"] == ended0 == 0
    assert not entered and not obs_trace.buffered()
    io1, own = io_plane.stats(), ring.ring_stats()
    for key in ("source_s", "stage_s", "put_s", "h2d_s"):
        assert io1[key] > io0[key] and own[key] > 0, key
    assert own["batches"] == 4 and own["stage_s"] + own["put_s"] <= \
        own["h2d_s"]
    # one record per number: the ring's own producer, no second counter
    from incubator_mxnet_tpu.obs import metrics as obs_metrics
    scraped = obs_metrics.registry().collect()
    assert scraped["io.batches"] == io1["batches"]
    assert not any(name in scraped for name in (
        "io.h2d.batches", "io.h2d.bytes", "io.ring.stalls"))


# -- (d) one clock -----------------------------------------------------------

def test_an_open_span_is_an_annotation_in_a_profile(tmp_path):
    import jax
    obs_trace.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("test.on_the_profilers_clock", cat="test"):
            jax.block_until_ready(jax.numpy.ones((8, 8)) + 1)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name == "test.on_the_profilers_clock"]
    assert len(events) == 1
    (span,) = [s for s in obs_trace.buffered()
               if s["name"] == "test.on_the_profilers_clock"]
    assert events[0].duration_ns >= 10e6
    assert events[0].duration_ns / 1e3 == pytest.approx(span["dur"], rel=0.2)
