"""mxtsan concurrency sanitizer (the ISSUE-9 acceptance gates).

Seeded defect fixtures — a forced A->B / B->A lock-order inversion, an
unsynchronized shared-dict write race, a leaked unjoined thread, a
blocking sleep under a contended lock, a thread outliving its owner's
close() — each asserting the finding names the exact locks/objects,
threads, and ``file:line`` sites.  Plus: the zero-overhead contract
(flag unset -> the shims ARE the plain threading objects), the
MXNET_TSAN_RAISE escalation, the concurrency AST lints, regression
locks for the two real races the sanitizer surfaced (router slot
bookkeeping, supervisor stats counters), and the zero-false-positive
gate over a tier-1-representative workload (fit step, serving
round-trip, dist push/pull) with the sanitizer on.
"""
import os
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import analysis, io, sym
from incubator_mxnet_tpu.analysis import locks as alocks
from incubator_mxnet_tpu.analysis import tsan
from incubator_mxnet_tpu.base import MXNetError


@pytest.fixture
def tsan_on():
    """Sanitizer on for this test, restored (and wiped) afterwards."""
    was = tsan.enabled()
    tsan.reset()
    tsan.enable()
    yield tsan
    if not was:
        tsan.disable()
    tsan.reset()


def _by_code(code):
    return [f for f in tsan.findings() if f.code == code]


# -- zero-overhead contract ---------------------------------------------------

def test_shims_are_plain_threading_objects_when_off():
    """With the sanitizer off, make_lock/make_rlock/make_condition hand
    back the stock threading primitives — not wrappers."""
    if tsan.enabled():   # MXNET_TSAN=1 came in with the environment
        pytest.skip("MXNET_TSAN=1 in this process")
    lk = alocks.make_lock("x")
    assert type(lk) is type(threading.Lock())
    rk = alocks.make_rlock("x")
    assert type(rk) is type(threading.RLock())
    cond = alocks.make_condition(name="x")
    assert isinstance(cond, threading.Condition)
    assert type(cond._lock) is type(threading.RLock())
    d = tsan.shared_dict("x")
    assert type(d) is dict
    class Obj:
        pass
    o = Obj()
    assert tsan.instrument(o, "x") is o and type(o) is Obj


# -- seeded defect fixtures ---------------------------------------------------

def test_lock_order_inversion_fixture(tsan_on):
    """A->B in one thread, B->A in another: the sanitizer reports the
    potential deadlock naming both locks, both threads, and the two
    acquisition sites — before anything hangs."""
    a = alocks.make_lock("fixture.A")
    b = alocks.make_lock("fixture.B")

    def forward():
        with a:
            with b:       # A -> B
                pass

    def backward():
        with b:
            with a:       # B -> A: closes the cycle
                pass

    t1 = threading.Thread(target=forward, name="fix-forward")
    t1.start(); t1.join(5)
    t2 = threading.Thread(target=backward, name="fix-backward")
    t2.start(); t2.join(5)

    found = _by_code("lock-order-inversion")
    assert found, tsan.findings()
    msg = found[0].message
    assert "fixture.A" in msg and "fixture.B" in msg
    assert "fix-forward" in msg and "fix-backward" in msg
    # both with-blocks above are named by file:line in this test file
    assert msg.count("test_tsan.py") >= 2
    assert found[0].severity == "error"
    # the graph artifact carries both edges
    graph = tsan.lock_graph()
    pairs = {(e["from"], e["to"]) for e in graph["edges"]}
    assert ("fixture.A", "fixture.B") in pairs
    assert ("fixture.B", "fixture.A") in pairs


def test_lock_order_raise_escalation(tsan_on):
    """MXNET_TSAN_RAISE=1 turns the inversion into an MXNetError at the
    acquisition site, with the lock released behind it."""
    os.environ["MXNET_TSAN_RAISE"] = "1"
    try:
        a = alocks.make_lock("raise.A")
        b = alocks.make_lock("raise.B")
        with a:
            with b:
                pass
        with b:
            with pytest.raises(MXNetError, match="raise.A"):
                with a:
                    pass
        # the failed acquisition did not leak the lock
        assert a.acquire(blocking=False)
        a.release()
    finally:
        os.environ.pop("MXNET_TSAN_RAISE", None)


def test_shared_dict_write_race_fixture(tsan_on):
    """Two threads writing one key with no common lock: attributed to
    both sites, both threads, named state."""
    d = tsan.shared_dict("fixture.table")

    def writer():
        d["hot"] = 1      # no lock held

    t = threading.Thread(target=writer, name="fix-writer")
    t.start(); t.join(5)
    d["hot"] = 2          # MainThread, no lock held

    found = _by_code("shared-state-race")
    assert found, tsan.findings()
    msg = found[0].message
    assert "fixture.table['hot']" in msg
    assert "write/write" in msg
    assert "fix-writer" in msg and "MainThread" in msg
    assert msg.count("test_tsan.py") >= 2


def test_shared_dict_guarded_writes_are_clean(tsan_on):
    """The same access pattern under a common lock produces nothing."""
    lk = alocks.make_lock("fixture.guard")
    d = tsan.shared_dict("fixture.guarded")

    def writer():
        with lk:
            d["hot"] = 1

    t = threading.Thread(target=writer, name="fix-guarded-writer")
    t.start(); t.join(5)
    with lk:
        d["hot"] = 2
        assert d["hot"] == 2
    assert not _by_code("shared-state-race"), tsan.findings()


def test_instrumented_attribute_race_fixture(tsan_on):
    """Attribute writes on a registered object race across threads."""
    class Stats:
        def __init__(self):
            self.count = 0

    s = tsan.instrument(Stats(), "fixture.stats")

    def bump():
        s.count += 1

    t = threading.Thread(target=bump, name="fix-bumper")
    t.start(); t.join(5)
    s.count += 1
    found = _by_code("shared-state-race")
    assert found, tsan.findings()
    assert "fixture.stats['count']" in found[0].message


def test_leaked_thread_fixture(tsan_on):
    """A started, never-joined non-daemon thread is reported with its
    creation site."""
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="fix-leaker",
                         daemon=False)
    t.start()
    try:
        found = _by_code("leaked-thread")
        assert found, tsan.findings()
        msg = found[0].message
        assert "fix-leaker" in msg and "test_tsan.py" in msg
    finally:
        stop.set()
        t.join(5)


def test_blocking_sleep_under_contended_lock_fixture(tsan_on):
    """time.sleep while holding a lock another thread uses: flagged with
    the lock name and the blocking site."""
    lk = alocks.make_lock("fixture.hot-lock")

    def toucher():
        with lk:
            pass

    t = threading.Thread(target=toucher, name="fix-toucher")
    t.start(); t.join(5)
    with lk:                      # now contended (two threads used it)
        time.sleep(0.005)
    found = _by_code("blocking-under-lock")
    assert found, tsan.findings()
    msg = found[0].message
    assert "fixture.hot-lock" in msg and "time.sleep" in msg
    assert "test_tsan.py" in msg


def test_thread_outlives_close_fixture(tsan_on):
    """The audited close-path join flags a worker that survives it."""
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="fix-wedged",
                         daemon=True)
    t.start()
    try:
        assert tsan.join_thread(t, 0.05, owner="FixtureOwner") is False
        found = _by_code("thread-outlives-close")
        assert found, tsan.findings()
        msg = found[0].message
        assert "fix-wedged" in msg and "FixtureOwner" in msg
    finally:
        stop.set()
        t.join(5)


# -- AST lints (the static half) ---------------------------------------------

def test_concurrency_ast_lints():
    src = '''
import threading, time
lock = threading.Lock()

class Pool:
    def __init__(self):
        self.t = threading.Thread(target=print)   # unnamed + unjoined
        self.t.start()

def drain():
    lock.acquire()
    with lock:
        time.sleep(0.5)
    lock.release()
'''
    rep = analysis.check_source(src, filename="fixture.py")
    codes = {f.code for f in rep}
    assert "unnamed-thread" in codes
    assert "unjoined-thread-in-init" in codes
    assert "bare-acquire" in codes
    assert "sleep-under-lock" in codes
    # named thread + lifecycle method + with-scope: all clean
    clean = '''
import threading, time

class Pool:
    def __init__(self):
        self.t = threading.Thread(target=print, name="mx-pool-worker")
        self.t.start()

    def close(self):
        self.t.join(timeout=5)

def drain(lock):
    with lock:
        pass
    time.sleep(0.5)
'''
    rep = analysis.check_source(clean, filename="clean.py")
    from incubator_mxnet_tpu.analysis.source_lint import CONCURRENCY_CODES
    assert not [f for f in rep if f.code in CONCURRENCY_CODES], list(rep)


def test_package_is_clean_under_concurrency_lints():
    """Zero findings over the package source (what `mxlint
    --tsan-report` exits 1 on)."""
    from incubator_mxnet_tpu.analysis.source_lint import CONCURRENCY_CODES
    pkg = os.path.dirname(analysis.__file__)
    pkg = os.path.dirname(pkg)   # incubator_mxnet_tpu/
    bad = []
    for root, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            rep = analysis.check_source_file(os.path.join(root, f))
            bad.extend(f2 for f2 in rep if f2.code in CONCURRENCY_CODES)
    assert not bad, "\n".join(f.format() for f in bad)


# -- regression locks for the races the sanitizer surfaced -------------------

def _mlp_net():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=8, name="fc0")
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=3, name="head")
    return sym.SoftmaxOutput(net, name="softmax")


def _served_model(name, batch=4):
    np.random.seed(0)
    net = _mlp_net()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[io.DataDesc("data", (batch, 6))],
             label_shapes=[io.DataDesc("softmax_label", (batch,))],
             for_training=False, grad_req="null")
    mod.init_params(mx.initializer.Xavier())
    args, auxs = mod.get_params()
    return mx.serving.ServedModel(net, args, auxs,
                                  data_shapes=[("data", (1, 6))],
                                  buckets=(1, 2, 4), ctx=mx.cpu(),
                                  name=name)


def test_router_health_and_dispatch_race_free(tsan_on):
    """Regression for the health-loop race: slot bookkeeping (probes,
    state, last_ok) is now written under the router lock, so a fast
    health loop concurrent with dispatch threads and a weight-state
    flip produces ZERO shared-state findings on the slot objects."""
    from incubator_mxnet_tpu.serving.replica import LocalReplica
    from incubator_mxnet_tpu.serving.router import ReplicaRouter

    model = _served_model("tsan-router")
    model.warmup()
    router = ReplicaRouter(
        [LocalReplica(model, replica_id="r0")],
        name="tsan-router", health_interval_s=0.01, deepcheck_every=3)
    try:
        x = np.random.randn(2, 6).astype(np.float32)
        stop = threading.Event()

        def client():
            while not stop.is_set():
                router.predict({"data": x}, timeout_ms=2000)

        threads = [threading.Thread(target=client,
                                    name=f"tsan-client-{i}")
                   for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.4)   # dozens of health probes + dispatches overlap
        stop.set()
        for t in threads:
            t.join(10)
    finally:
        router.shutdown(drain=False)
    races = [f for f in _by_code("shared-state-race")
             if "router" in f.message]
    assert not races, "\n".join(f.format() for f in races)
    assert not _by_code("lock-order-inversion"), tsan.findings()


def test_supervisor_stats_race_free(tsan_on):
    """Regression for the stats-counter race: every `_stats` update now
    holds the view lock, so heartbeat-thread counters concurrent with
    fit-thread collectives produce zero findings."""
    from incubator_mxnet_tpu.resilience.supervisor import JobSupervisor

    sup = JobSupervisor(rank=0, num_workers=2)
    view = {"epoch": 0, "alive": [0, 1], "dead": [], "age": {},
            "steps": {0: 1, 1: 1}, "ewma": {}}
    stop = threading.Event()

    def beat():
        while not stop.is_set():
            sup._on_view(view)
            with sup._view_lock:
                sup._stats["heartbeats"] += 1

    t = threading.Thread(target=beat, name="tsan-hb")
    t.start()
    for _ in range(50):
        sup.collective("noop", lambda: 1)
        sup.record_step(0.001)
    stop.set()
    t.join(10)
    sup.stop()
    races = [f for f in _by_code("shared-state-race")
             if "supervisor" in f.message]
    assert not races, "\n".join(f.format() for f in races)
    assert sup.stats()["collectives"] == 50


# -- the zero-false-positive gate ---------------------------------------------

def test_zero_false_positives_on_tier1_workload(tsan_on):
    """A tier-1-representative workload under the sanitizer — a fit
    step, a serving round-trip through the micro-batcher, and a dist
    push/pull over the socket server — must produce ZERO findings: the
    sanitizer earns its place only if a clean system reads clean."""
    # 1. fit step (module data plane, engine, compile cache, storage)
    np.random.seed(0)
    X = np.random.randn(64, 6).astype(np.float32)
    y = np.random.randint(0, 3, 64)
    train = io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp_net(), context=mx.cpu())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(), num_epoch=1)

    # 2. serving round-trip (batcher worker + metrics + breaker)
    model = _served_model("tsan-gate")
    server = mx.serving.ModelServer()
    server.load_model("tsan-gate", model=model)
    outs = [server.submit("tsan-gate",
                          {"data": np.random.randn(2, 6).astype(
                              np.float32)})
            for _ in range(8)]
    for f in outs:
        f.result(30)
    server.shutdown(drain=True)

    # 3. dist push/pull (transport, parameter server, membership-free)
    from incubator_mxnet_tpu.dist.server import ParameterServer
    from incubator_mxnet_tpu.dist.kvstore_dist import KVStoreDist
    from incubator_mxnet_tpu import nd

    psrv = ParameterServer(num_workers=1).start()
    old = {k: os.environ.get(k) for k in
           ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_RANK",
            "DMLC_NUM_WORKER")}
    os.environ.update(DMLC_PS_ROOT_URI="127.0.0.1",
                      DMLC_PS_ROOT_PORT=str(psrv.port),
                      DMLC_RANK="0", DMLC_NUM_WORKER="1")
    try:
        kv = KVStoreDist("dist_async")
        kv.init("w", nd.zeros((4,)))
        kv.push("w", nd.ones((4,)))
        out = nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)
        kv.close()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        psrv.shutdown()

    found = tsan.findings()
    assert not found, "\n".join(f.format() for f in found)


def test_findings_flow_into_runtime_report(tsan_on):
    """tsan findings ride the same Report machinery as every other
    analysis pass."""
    d = tsan.shared_dict("report.state")
    t = threading.Thread(target=lambda: d.__setitem__("k", 1),
                         name="report-writer")
    t.start(); t.join(5)
    d["k"] = 2
    rep = analysis.runtime_report()
    assert any(f.code == "shared-state-race" for f in rep), list(rep)


def test_dump_artifact_roundtrip(tsan_on, tmp_path):
    """The MXNET_TSAN_LOG artifact carries findings + the lock graph,
    and mxlint --tsan-report renders it."""
    a = alocks.make_lock("dump.A")
    b = alocks.make_lock("dump.B")
    with a:
        with b:
            pass
    path = tmp_path / "tsan.json"
    payload = tsan.dump(str(path))
    assert path.exists()
    names = {e["name"] for e in payload["lock_graph"]["locks"]}
    assert {"dump.A", "dump.B"} <= names
    pairs = {(e["from"], e["to"]) for e in payload["lock_graph"]["edges"]}
    assert ("dump.A", "dump.B") in pairs

    import subprocess, sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "mxlint.py"),
         "--tsan-report", str(path), "--json"],
        capture_output=True, text=True, timeout=300)
    import json
    summary = json.loads(out.stdout)
    assert summary["runtime"]["dumps"] == 1
    assert summary["runtime"]["lock_graph"]["edges"]
