#!/usr/bin/env python
"""Chaos runner: the tier-1 dist + serving tests under canned fault
schedules, with a JSON artifact of what was injected and what survived.

Each schedule sets ``MXNET_FAULTS`` (a seeded, deterministic fault spec —
see resilience/faults.py) and ``MXNET_FAULTS_LOG`` for the pytest process
AND every worker subprocess it spawns, runs the selected tests, then
aggregates the fault log: faults fired by site/kind, retries, reconnects,
and the final pass/fail counts.  The tests are the SAME tests that gate
normal PRs — the chaos claim is exactly "the functional contract holds
while the transport is being actively sabotaged".

Pod mode (``--pod``) runs the ELASTIC schedules instead: a root
parameter server (the pod coordinator) plus three real worker processes
mid-`Module.fit` under the supervisor, sabotaged per rank — heartbeat
drops that must NOT trip false host loss, one host SIGKILLed mid-fit
(survivors must detect it, shrink, and resume from the checkpoint), and
one hung collective (the watchdog must convert the stall into a
`CollectiveTimeoutError` and the whole pod must recover).  The artifact
(``CHAOS_POD.json``) embeds every surviving worker's
`JobSupervisor.stats()` dict — heartbeats, watchdog timeouts, hosts
lost, and the PR 5 kvstore retry/breaker counters.

Serving mode (``--serving``) runs the MULTI-REPLICA schedules over a
real `ReplicaRouter` fronting three subprocess replica workers (spawned
with a shared program-cache dir, so replicas 2-3 must spin up with ZERO
XLA compiles): one worker SIGKILLed mid-flight (zero accepted requests
lost, zero duplicate executions — certified from the survivors'
executed-rid logs), a health-probe drop burst (suspicion, never a false
eviction), a full rolling weight-swap under traffic (zero dropped
requests, zero post-warmup compiles — certified via worker compile-
cache stats), and a torn swap (clean abort, fleet keeps serving,
re-issue completes).  The artifact is ``CHAOS_SERVING.json``.

Training-guardian mode (``--train``) runs the NUMERICAL-HEALTH
schedules: an injected non-finite gradient (the guardian must refuse
the update in-graph and continue deterministically — two identical
seeded runs end bit-identical), an injected loss spike (the guardian
must roll back to the last healthy checkpoint and end bit-identical to
a clean reference run that skipped the same quarantined window), and an
injected corrupt record (the io tier must substitute/skip it, count it,
and quarantine it so a resumed iterator never reads it again).  Every
schedule additionally certifies ZERO unified-program-cache compiles
during recovery (the live/in-memory tier serves every rebuilt program).
The artifact is ``CHAOS_TRAIN.json``.

Decode mode (``--decode``) runs the CONTINUOUS-BATCHING schedules over
a real `ReplicaRouter` fronting two in-process `DecodeReplica`s (one
shared cached-jit program space, so the second replica warms with ZERO
compiles): a steady-state mixed-ladder sweep (zero compiles, zero
recompile-auditor findings across arbitrary prompt/budget arrival
orders) and one replica SIGKILLed mid-decode — every admitted sequence
must be replayed on the survivor (the prefill re-derives the lost KV
state from the prompt) with zero losses and zero duplicate deliveries.
The artifact is ``CHAOS_DECODE.json``.

Loop mode (``--loop``) runs the CONTINUOUS TRAIN-TO-SERVE schedules: a
real trainer process (tools/loop_trainer.py) publishing guardian-healthy
checkpoints into a shared `ModelRegistry` while a 2-replica remote fleet
promotes them through the `LoopController`'s canary gate under live
traffic.  One schedule corrupts a training shard mid-loop
(``io.corrupt_record`` payload damage + an injected loss spike: the
guardian rolls back, the publisher fences the disowned window, and the
fleet must NEVER serve a fenced or rejected version, lose zero admitted
requests, compile nothing during swaps, and go live on the next clean
version inside the freshness SLO); one publishes a healthy-stamped but
weight-sabotaged checkpoint (the serving-side canary must reject it,
swap the canary replica back, stamp it rejected — durably, never
retried); one tears a publish mid-commit (the truncated manifest must
be invisible and a clean re-publish must promote).  The artifact is
``CHAOS_LOOP.json``.

Usage: python tools/run_chaos.py [--quick] [--pod] [--serving] [--train]
                                 [--decode] [--loop] [--json] [--out PATH]
    --quick   bounded test selection
    --pod     run the elastic pod schedules (writes CHAOS_POD.json)
    --serving run the multi-replica router schedules
              (writes CHAOS_SERVING.json)
    --train   run the training-guardian schedules
              (writes CHAOS_TRAIN.json)
    --decode  run the continuous-batching decode schedules
              (writes CHAOS_DECODE.json)
    --loop    run the train-to-serve loop schedules
              (writes CHAOS_LOOP.json)
    --json    print only the JSON artifact on stdout
    --out     also write the artifact to PATH (default CHAOS_REPORT.json,
              CHAOS_POD.json with --pod, CHAOS_SERVING.json with
              --serving, CHAOS_TRAIN.json with --train)

Exit status: 0 when every schedule's tests passed.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# seeded schedules: same seed -> same per-process fault sequence, so a
# red chaos run reproduces locally with the spec string alone
SCHEDULES = {
    "flaky-connect": "seed=11;transport.connect:refuse(n=2)",
    "dropped-pushes": "seed=12;transport.send:drop(p=0.3,cmd=push,n=4)",
    "slow-peers": ("seed=13;server.dispatch:slow(ms=30,p=0.05);"
                   "serving.execute:slow(ms=10,p=0.2)"),
}

QUICK_TESTS = [
    "tests/test_dist.py::test_dist_sync_multiprocess[2-0]",
    "tests/test_dist.py::test_dist_sync_sharded_servers",
    "tests/test_serving.py::test_concurrent_clients_correct_and_ordered",
    "tests/test_serving.py::test_unload_drains_without_dropping",
]

FULL_TESTS = QUICK_TESTS + [
    "tests/test_dist.py::test_dist_sync_multiprocess[4-0]",
    "tests/test_dist.py::test_dist_sync_three_servers_uneven_ranges",
    "tests/test_dist.py::test_dist_compression_packs_the_wire",
    "tests/test_serving.py::test_drain_on_shutdown_completes_in_flight",
    "tests/test_serving.py::test_backpressure_bounded_queue",
]


def _counts(output):
    counts = {"passed": 0, "failed": 0, "errors": 0}
    for key, word in (("passed", "passed"), ("failed", "failed"),
                      ("errors", "errors?")):
        m = re.search(r"(\d+) %s\b" % word, output)
        if m:
            counts[key] = int(m.group(1))
    return counts


def _read_fault_log(path):
    """Aggregate one schedule's MXNET_FAULTS_LOG (all processes append)."""
    agg = {"faults": 0, "by_site_kind": {}, "retries": 0, "reconnects": 0}
    try:
        with open(path) as f:
            for line in f:
                try:
                    event = json.loads(line)
                except ValueError:
                    continue
                kind = event.get("event")
                if kind == "fault":
                    agg["faults"] += 1
                    key = "%s:%s" % (event.get("site"), event.get("kind"))
                    agg["by_site_kind"][key] = \
                        agg["by_site_kind"].get(key, 0) + 1
                elif kind == "retry":
                    agg["retries"] += 1
                elif kind == "reconnect":
                    agg["reconnects"] += 1
    except OSError:
        pass
    return agg


def run_schedule(name, spec, tests, quiet=False):
    log_fd, log_path = tempfile.mkstemp(prefix="chaos-%s-" % name,
                                        suffix=".jsonl")
    os.close(log_fd)
    env = dict(os.environ, MXNET_FAULTS=spec, MXNET_FAULTS_LOG=log_path,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "--tb=line",
             "-p", "no:cacheprovider"] + tests,
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=1200)
        rc, output = proc.returncode, proc.stdout + proc.stderr
    except subprocess.TimeoutExpired as exc:
        # a hung schedule is a RESULT (the worst one) — record it with
        # whatever the fault log captured instead of crashing the run
        rc = -1
        output = "TIMEOUT after %ds\n%s" % (exc.timeout,
                                            (exc.stdout or "")[-1200:])
    result = {
        "schedule": name,
        "spec": spec,
        "rc": rc,
        **_counts(output),
        "duration_s": round(time.time() - t0, 1),
        **_read_fault_log(log_path),
        "tail": "\n".join(output.strip().splitlines()[-6:])[-1200:],
    }
    os.unlink(log_path)
    if not quiet:
        print("chaos[%s]: rc=%d passed=%d failed=%d faults=%d retries=%d "
              "reconnects=%d (%.1fs)" %
              (name, result["rc"], result["passed"], result["failed"],
               result["faults"], result["retries"], result["reconnects"],
               result["duration_s"]), file=sys.stderr)
    return result


# -- pod schedules: elastic multi-host supervision under sabotage -------------
# three workers mid-Module.fit; faults are injected PER RANK so each
# schedule is one deterministic pod failure story
POD_SCHEDULES = {
    # lossy control network: a burst of 3 consecutive dropped heartbeats
    # per host (0.6s silence under the 1.2s deadline) must not trip
    # false host loss — and the drops must verifiably fire
    "pod-hb-drops": {"faults": {"*": "seed=21;heartbeat.send:drop(at=2-4)"},
                     "killed": None, "min_faults": 3},
    # whole-host SIGKILL mid-fit: survivors must detect the loss within
    # the heartbeat deadline, convert the stalled round into a
    # CollectiveTimeoutError, shrink to world 2, and resume
    "pod-host-crash": {"faults": {"2": "seed=22;host.step:kill(at=4)"},
                       "killed": 2},
    # hung collective on one rank: every watchdog fires (no host is
    # dead), the full pod shrinks-in-place and resumes — no indefinite
    # hang anywhere
    "pod-hung-collective": {
        "faults": {"1": "seed=23;collective.dispatch:hang(at=9)"},
        "killed": None},
}

# the worker subprocess body is tools/pod_worker.py — ONE copy shared
# with tests/test_supervisor.py so the chaos artifact and the acceptance
# test exercise the identical protocol
POD_WORKER_PATH = os.path.join(REPO, "tools", "pod_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_pod_schedule(name, schedule, quiet=False):
    """One pod schedule: root server (coordinator) + 3 supervised workers
    mid-fit, faults injected per rank.  Returns the result dict with
    per-worker outcomes and every survivor's JobSupervisor.stats()."""
    n_workers = 3
    log_fd, log_path = tempfile.mkstemp(prefix="chaos-%s-" % name,
                                        suffix=".jsonl")
    os.close(log_fd)
    ckpt_dir = tempfile.mkdtemp(prefix="chaos-%s-ckpt-" % name)
    port = _free_port()
    base_env = dict(
        os.environ,
        DMLC_PS_ROOT_URI="127.0.0.1", DMLC_PS_ROOT_PORT=str(port),
        DMLC_NUM_WORKER=str(n_workers), DMLC_ROLE="worker",
        MXNET_KVSTORE_COLLECTIVE="0",
        # fast pod clocks: detection in ~1s, watchdog in 3s, so a whole
        # schedule (including shrink + resume) fits a CI budget
        MXNET_SUPERVISOR_HEARTBEAT_S="0.2",
        MXNET_SUPERVISOR_DEADLINE_S="1.2",
        MXNET_SUPERVISOR_COLLECTIVE_TIMEOUT_S="3.0",
        MXNET_SUPERVISOR_SHRINK_BARRIER_S="10.0",
        MXNET_PS_RECONNECT_WAIT="1.0",
        MXNET_FAULTS_LOG=log_path,
        POD_CKPT_DIR=ckpt_dir,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    base_env.pop("MXNET_FAULTS", None)
    t0 = time.time()
    server = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.dist.server"],
        env=dict(base_env), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, cwd=REPO)
    procs = []
    for r in range(n_workers):
        env = dict(base_env, DMLC_RANK=str(r))
        spec = schedule["faults"].get(str(r)) or schedule["faults"].get("*")
        if spec:
            env["MXNET_FAULTS"] = spec
        procs.append(subprocess.Popen(
            [sys.executable, POD_WORKER_PATH], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO))
    workers = []
    hung = False
    for r, p in enumerate(procs):
        try:
            out = p.communicate(timeout=240)[0].decode()
        except subprocess.TimeoutExpired:
            # a hung worker is the exact failure this subsystem exists to
            # prevent — record it as the worst result, don't hang the run
            hung = True
            p.kill()
            out = (p.communicate()[0] or b"").decode() + "\nHUNG (killed)"
        sup_stats = None
        sha = None
        for line in out.splitlines():
            if line.startswith("SUPSTATS "):
                try:
                    sup_stats = json.loads(line[len("SUPSTATS "):])
                except ValueError:
                    pass
            elif line.startswith("PARAMS_SHA "):
                sha = line.split()[1]
        workers.append({"rank": r, "rc": p.returncode,
                        "params_sha": sha, "supervisor": sup_stats,
                        "tail": "\n".join(out.strip().splitlines()[-5:])
                                [-800:]})
    server.kill()
    server.communicate()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    killed = schedule["killed"]
    survivors = [w for w in workers if w["rank"] != killed]
    fault_agg = _read_fault_log(log_path)
    passed = (not hung
              and all(w["rc"] == 0 for w in survivors)
              and all(w["params_sha"] is not None for w in survivors)
              and len({w["params_sha"] for w in survivors}) == 1
              and (killed is None or workers[killed]["rc"] == 137)
              and fault_agg["faults"] >= schedule.get("min_faults", 1))
    result = {
        "schedule": name,
        "specs": schedule["faults"],
        "killed_rank": killed,
        "workers": workers,
        "duration_s": round(time.time() - t0, 1),
        **fault_agg,
        "passed": passed,
    }
    os.unlink(log_path)
    if not quiet:
        print("chaos[%s]: passed=%s rcs=%s faults=%d (%.1fs)" %
              (name, passed, [w["rc"] for w in workers],
               result["faults"], result["duration_s"]), file=sys.stderr)
    return result


def _spawn_pod(port, n_workers, ckpt_dir, log_path, faults_by_rank=None,
               resume=False, scaling=True):
    """Launch n supervised pod workers against the coordinator at
    `port`; returns (procs, outs).  One copy of the env recipe shared
    by the scaling schedule's chaos and control lanes."""
    base_env = dict(
        os.environ,
        DMLC_PS_ROOT_URI="127.0.0.1", DMLC_PS_ROOT_PORT=str(port),
        DMLC_NUM_WORKER=str(n_workers), DMLC_ROLE="worker",
        MXNET_KVSTORE_COLLECTIVE="0",
        MXNET_SUPERVISOR_HEARTBEAT_S="0.2",
        MXNET_SUPERVISOR_DEADLINE_S="1.2",
        MXNET_SUPERVISOR_COLLECTIVE_TIMEOUT_S="3.0",
        MXNET_SUPERVISOR_SHRINK_BARRIER_S="10.0",
        MXNET_PS_RECONNECT_WAIT="1.0",
        MXNET_FAULTS_LOG=log_path,
        POD_CKPT_DIR=ckpt_dir,
        POD_RESUME="1" if resume else "0",
        POD_SCALING="1" if scaling else "0",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    base_env.pop("MXNET_FAULTS", None)
    base_env.pop("MXNET_SUPERVISOR_EPOCH", None)
    procs = []
    for r in range(n_workers):
        env = dict(base_env, DMLC_RANK=str(r))
        spec = (faults_by_rank or {}).get(str(r))
        if spec:
            env["MXNET_FAULTS"] = spec
        procs.append(subprocess.Popen(
            [sys.executable, POD_WORKER_PATH], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO))
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=240)[0].decode())
        except subprocess.TimeoutExpired:
            p.kill()
            outs.append((p.communicate()[0] or b"").decode()
                        + "\nHUNG (killed)")
    return procs, outs


def _pod_server(port, n_workers):
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1", DMLC_PS_ROOT_PORT=str(port),
               DMLC_NUM_WORKER=str(n_workers),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    return subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.dist.server"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=REPO)


def run_pod_scaling_schedule(quiet=False):
    """The scale-meets-resilience composition gate: a 3-worker
    SUPERVISED scaling sweep (per-world-size throughput curve recorded
    by every worker), one host SIGKILLed mid-sweep — survivors must
    shrink to world 2, resume from the last committed checkpoint, and
    COMPLETE the curve (points at world 3 AND world 2) — then a control
    lane: an uninterrupted 2-worker run resumed from the same
    checkpoint must end with bit-identical params."""
    t0 = time.time()
    checks = {}
    log_fd, log_path = tempfile.mkstemp(prefix="chaos-pod-scaling-",
                                        suffix=".jsonl")
    os.close(log_fd)
    ckpt_dir = tempfile.mkdtemp(prefix="chaos-pod-scaling-ckpt-")
    control_dir = ckpt_dir + "-control"
    curves = []
    try:
        # lane 1 — chaos: rank 2 dies at its 4th step, mid-sweep
        port = _free_port()
        server = _pod_server(port, 3)
        procs, outs = _spawn_pod(
            port, 3, ckpt_dir, log_path,
            faults_by_rank={"2": "seed=24;host.step:kill(at=4)"})
        server.kill()
        server.communicate()
        shas, resume_step = set(), None
        for r in (0, 1):
            m = re.search(r"PARAMS_SHA (\w+)", outs[r])
            shas.add(m.group(1) if m else None)
            m = re.search(r"SCALING (.*)", outs[r])
            curves.append(json.loads(m.group(1)) if m else {})
            m = re.search(r"resuming from .*\(step (\d+),", outs[r])
            if m:
                resume_step = int(m.group(1))
        checks["killed_host_rc_137"] = procs[2].returncode == 137
        checks["survivors_completed"] = all(
            p.returncode == 0 for p in procs[:2])
        checks["survivors_agree"] = len(shas) == 1 and None not in shas
        # the curve COMPLETED across the shrink: every survivor holds a
        # world-3 point (pre-kill) and a world-2 point (post-resume)
        checks["curve_spans_shrink"] = all(
            set(c) >= {"2", "3"} and
            all(pt["steps"] > 0 for pt in c.values())
            for c in curves)
        # lane 2 — control: clean 2-worker resume from the SAME
        # checkpoint the survivors resumed from (prune newer snapshots)
        checks["resume_step_found"] = resume_step is not None
        if resume_step is not None:
            shutil.copytree(ckpt_dir, control_dir)
            for entry in os.listdir(control_dir):
                cm = re.match(r"ckpt-(\d+)$", entry)
                if cm and int(cm.group(1)) > resume_step:
                    shutil.rmtree(os.path.join(control_dir, entry))
            port = _free_port()
            server = _pod_server(port, 2)
            cprocs, couts = _spawn_pod(port, 2, control_dir, log_path,
                                       resume=True)
            server.kill()
            server.communicate()
            cshas = set()
            for r in (0, 1):
                m = re.search(r"PARAMS_SHA (\w+)", couts[r])
                cshas.add(m.group(1) if m else None)
            checks["control_completed"] = all(
                p.returncode == 0 for p in cprocs)
            checks["bit_identical_vs_clean_shrunk"] = (
                len(cshas) == 1 and None not in cshas and cshas == shas)
    finally:
        fault_agg = _read_fault_log(log_path)
        os.unlink(log_path)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        shutil.rmtree(control_dir, ignore_errors=True)
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": "pod-scaling",
        "specs": {"2": "seed=24;host.step:kill(at=4)"},
        "killed_rank": 2,
        "checks": checks,
        "curves": curves,
        "workers": [],
        **fault_agg,
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[pod-scaling]: passed=%s checks=%s (%.1fs)" %
              (result["passed"], checks, result["duration_s"]),
              file=sys.stderr)
    return result


def run_pod(as_json=False, out_path=None):
    runs = [run_pod_schedule(name, sched, quiet=as_json)
            for name, sched in POD_SCHEDULES.items()]
    try:
        runs.append(run_pod_scaling_schedule(quiet=as_json))
    except Exception as exc:
        runs.append({"schedule": "pod-scaling", "passed": False,
                     "workers": [], "error": repr(exc)})
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
        "supervisor_stats": {
            r["schedule"]: [w["supervisor"] for w in r["workers"]
                            if w["supervisor"] is not None]
            for r in runs},
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        slim = {"all_passed": artifact["all_passed"],
                "schedules": [{k: v for k, v in r.items()
                               if k not in ("workers",)}
                              for r in runs],
                "supervisor_stats": artifact["supervisor_stats"]}
        print(json.dumps(slim))
    else:
        print("chaos pod: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- serving schedules: the replica router under sabotage ---------------------
# a real 3-replica fleet (subprocess workers) behind an in-process
# ReplicaRouter; router-side faults are seeded so every run replays the
# same story.  Each schedule returns the acceptance verdicts the README
# failure matrix promises.

def _export_mlp(tmp):
    """One tiny served model exported as a classic checkpoint pair;
    returns (module, prefix, worker env with a shared program-cache
    dir).  Shared by the serving and fleet schedules."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import sym, io
    np.random.seed(0)
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=64, name="fc0")
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=8, name="head")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[io.DataDesc("data", (4, 16))],
             label_shapes=[io.DataDesc("softmax_label", (4,))],
             for_training=False, grad_req="null")
    mod.init_params(mx.initializer.Xavier())
    prefix = os.path.join(tmp, "m")
    mod.save_checkpoint(prefix, 0)
    env = {"MXNET_PROGRAM_CACHE_DIR": os.path.join(tmp, "pcache"),
           "JAX_PLATFORMS": "cpu"}
    return mod, prefix, env


def _serving_fleet(tmp, n=3, buckets=(1, 2, 4), health_deadline_s=3.0):
    """(router, replicas, model artifacts) — a spawned remote fleet
    warming from one shared program-cache dir."""
    import incubator_mxnet_tpu as mx
    mod, prefix, env = _export_mlp(tmp)
    reps = [mx.serving.RemoteReplica.spawn(
        prefix=prefix, epoch=0, data_shapes=[("data", (1, 16))],
        buckets=buckets, name="m", replica_id="w%d" % i, env=env)
        for i in range(n)]
    router = mx.serving.ReplicaRouter(
        reps, health_interval_s=0.2, health_deadline_s=health_deadline_s)
    return router, reps, (mod, prefix)


def _drive_router(router, n_threads=4, per=40, kill_at=None,
                  kill_fn=None, priority="interactive", timeout_ms=30000):
    """Closed-loop traffic; optionally fire `kill_fn` once `kill_at`
    requests were accepted.  Returns (ok_count, errors)."""
    results, errors = [], []
    accepted = [0]
    fired = [False]
    lock = threading.Lock()

    def client():
        for _ in range(per):
            try:
                f = router.submit({"data": _drive_router._x},
                                  timeout_ms=timeout_ms,
                                  priority=priority)
                with lock:
                    accepted[0] += 1
                    if kill_at is not None and accepted[0] == kill_at \
                            and not fired[0]:
                        fired[0] = True
                        kill_fn()
                results.append(f.result(60))
            except Exception as exc:   # a lost request is the FINDING
                errors.append(repr(exc))

    import numpy as np
    _drive_router._x = np.random.default_rng(5).standard_normal(
        (2, 16)).astype(np.float32)
    threads = [threading.Thread(target=client,
                                name=f"mx-chaos-client-{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return len(results), errors


def _survivor_rids(reps, skip=()):
    rids = []
    for r in reps:
        if r.replica_id in skip:
            continue
        rids += r.stats().get("executed_rids", [])
    return rids


def run_serving_schedule(name, tmp, quiet=False):
    """One serving schedule; returns a result dict with `passed`."""
    from incubator_mxnet_tpu.resilience import faults as _f
    import incubator_mxnet_tpu as mx
    t0 = time.time()
    checks = {}
    router, reps, (mod, prefix) = _serving_fleet(tmp)
    try:
        # zero-compile fleet spin-up evidence (all schedules)
        checks["spinup_zero_compiles"] = all(
            r.ready_info.get("compiles") == 0 for r in reps[1:])
        if name == "replica-kill":
            _f.configure("seed=41")   # trace/log only; the kill is real
            ok, errors = _drive_router(router, kill_at=60,
                                       kill_fn=reps[1].kill)
            rids = _survivor_rids(reps, skip=("w1",))
            st = router.stats()
            checks.update(
                zero_lost=(ok == 160 and not errors),
                zero_duplicate_execution=(len(rids) == len(set(rids))
                                          and st["duplicates_suppressed"]
                                          == 0),
                replica_declared_dead=(st["replicas_lost"] == 1),
                failovers=st["failovers"])
        elif name == "probe-drop-burst":
            _f.configure("seed=42;replica.health:drop(at=2-6)")
            ok, errors = _drive_router(router, per=30)
            time.sleep(1.0)   # let the probe schedule play out
            st = router.stats()
            drops = [e for e in _f.trace()
                     if e.get("site") == "replica.health"]
            checks.update(
                zero_lost=(ok == 120 and not errors),
                drops_fired=(len(drops) >= 3),
                no_false_eviction=(st["replicas_lost"] == 0))
        elif name in ("rolling-swap", "torn-swap"):
            args, auxs = mod.get_params()
            ckroot = os.path.join(tmp, "ckpts-" + name)
            mgr = mx.checkpoint.CheckpointManager(ckroot,
                                                  async_snapshots=False)
            arrays = {"arg:%s" % k: v.asnumpy() * 2.0
                      for k, v in args.items()}
            arrays.update({"aux:%s" % k: v.asnumpy()
                           for k, v in auxs.items()})
            mgr.snapshot(arrays=arrays, step=1)
            mgr.close()
            if name == "torn-swap":
                _f.configure("seed=43;replica.swap:torn(at=2)")
            else:
                _f.configure("seed=44")
            base = [r.stats() for r in reps]
            swap_err = [None]

            def do_swap():
                try:
                    router.swap_weights(checkpoint_dir=ckroot)
                except Exception as exc:
                    swap_err[0] = repr(exc)

            swapper = threading.Thread(target=do_swap,
                                       name="mx-chaos-swapper")
            swapper.start()
            ok, errors = _drive_router(router, per=30)
            swapper.join(120)
            if name == "torn-swap":
                # the roll must ABORT cleanly with the fleet serving;
                # clearing the fault and re-issuing finishes it
                checks["aborted_cleanly"] = (
                    swap_err[0] is not None and "ABORTED" in swap_err[0])
                _f.configure("seed=44")
                router.swap_weights(checkpoint_dir=ckroot)
            else:
                checks["swap_completed"] = swap_err[0] is None
            after = [r.stats() for r in reps]
            versions = [s.get("version") for s in after]
            compiles = [
                (s.get("cache") or {}).get("compiles", 0) -
                (b.get("cache") or {}).get("compiles", 0)
                for b, s in zip(base, after)]
            checks.update(
                zero_lost=(ok == 120 and not errors),
                all_swapped=(all(v and v >= 1 for v in versions)),
                zero_swap_compiles=(all(c == 0 for c in compiles)),
                # the compiled ladder is untouched by the swap (the
                # program-count face of the recompile-auditor claim)
                programs_stable=(all(s.get("programs") == 3
                                     for s in after)),
                versions=versions)
        else:
            raise ValueError("unknown serving schedule %r" % name)
        errs = errors[:5] if errors else []
    finally:
        try:
            router.shutdown(drain=False)
        except Exception:
            pass
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass
        _f.clear()
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": name,
        "checks": checks,
        "errors": errs,
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[serving/%s]: passed=%s checks=%s (%.1fs)" %
              (name, result["passed"], checks, result["duration_s"]),
              file=sys.stderr)
    return result


def run_serving(as_json=False, out_path=None):
    runs = []
    for name in ("replica-kill", "probe-drop-burst", "rolling-swap",
                 "torn-swap"):
        tmp = tempfile.mkdtemp(prefix="chaos-serving-%s-" % name)
        try:
            runs.append(run_serving_schedule(name, tmp, quiet=as_json))
        except Exception as exc:
            runs.append({"schedule": name, "passed": False,
                         "error": repr(exc)})
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos serving: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- fleet schedule: a whole HOST dies under mixed-priority load --------------
# two real host daemons (serving.hostd process groups), two replicas
# each behind a FleetManager; one host's ENTIRE process group is
# SIGKILLed mid-ramp.  The acceptance story: zero admitted interactive
# requests lost, interactive p99 inside its SLO band while best-effort
# sheds first, the fleet backfilled to target on the surviving host,
# and every backfill spinup certified zero-compile off the shared
# program cache.

def run_fleet_schedule(tmp, quiet=False, slo_ms=150.0):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.resilience import faults as _f
    from incubator_mxnet_tpu.serving import AgentHost, FleetManager, \
        ReplicaSpec
    t0 = time.time()
    checks = {}
    detail = {}
    errs = []
    _f.configure("seed=61")   # trace/log only; the host kill is real
    _mod, prefix, env = _export_mlp(tmp)
    spec = ReplicaSpec(data_shapes=[("data", (1, 16))], name="m",
                       prefix=prefix, epoch=0, buckets=(1, 2, 4), env=env)
    x = np.random.default_rng(6).standard_normal((2, 16)).astype(
        np.float32)
    # setup INSIDE the try: the daemons are their own process groups
    # (start_new_session), so a host-b launch or FleetManager failure
    # must still reach the finally that kills host-a — an orphaned
    # daemon would outlive the whole chaos run
    hosts = []
    fleet = None
    try:
        hosts.append(AgentHost.launch_local("host-a", env=env))
        hosts.append(AgentHost.launch_local("host-b", env=env))
        # max == target: this schedule certifies host-loss BACKFILL
        # (autoscale growth is tests/test_fleet.py's), and every extra
        # breach-driven cold spawn during the measured window is a
        # python+jax import storm polluting the p99 the gate is about
        fleet = FleetManager(
            hosts, spec, name="chaos-fleet", target_replicas=4,
            min_replicas=4, max_replicas=4, slo_ms=slo_ms, tick_s=0.1,
            up_after_s=0.3, down_after_s=600.0, cooldown_s=0.5,
            host_heartbeat_s=0.2, host_deadline_s=1.5)
        router = fleet.router
        # the degradation policy under capacity loss: best-effort is
        # the shock absorber, interactive sheds only at queue collapse
        router.shed_ms = {"best_effort": slo_ms / 4.0, "batch": slo_ms,
                          "interactive": slo_ms * 100.0}
        st = fleet.stats()
        checks["spread_over_hosts"] = (
            sorted(set(st["placement"].values())) == ["host-a", "host-b"])
        # initial spinup: first worker compiles the ladder cold, every
        # later one loads it from the shared disk tier
        ups = [e for e in st["events"] if e["action"] == "scale_up"]
        checks["spinup_zero_compiles_after_first"] = all(
            e.get("spinup_compiles") == 0 for e in ups[1:])

        # phase 0 — flood-free interactive baseline (the SLO band is
        # relative to what THIS machine can deliver, not an absolute
        # latency)
        def interactive_client(n, out):
            for _ in range(n):
                t1 = time.monotonic()
                try:
                    router.predict({"data": x}, timeout_ms=30000,
                                   priority="interactive")
                    out["lat_ms"].append((time.monotonic() - t1) * 1e3)
                except Exception as exc:
                    out["errors"].append(repr(exc))

        base = {"lat_ms": [], "errors": []}
        base_threads = [threading.Thread(target=interactive_client,
                                         args=(40, base),
                                         name="mx-chaos-fleet-base-%d" % i)
                        for i in range(3)]
        for t in base_threads:
            t.start()
        for t in base_threads:
            t.join()
        baseline_p99 = float(np.percentile(base["lat_ms"], 99)) \
            if base["lat_ms"] else None
        bound_ms = max(slo_ms, 4.0 * baseline_p99) \
            if baseline_p99 else slo_ms

        # phase 1 — mixed-priority ramp with the host kill mid-flight
        from incubator_mxnet_tpu.serving import ServingMetrics
        router.metrics = ServingMetrics(router.name)   # fresh reservoirs
        inter = {"lat_ms": [], "errors": []}
        be_done, be_shed = [0], [0]
        stop_be = threading.Event()
        accepted = [0]
        killed = [False]
        lock = threading.Lock()

        def interactive_ramp(n):
            for _ in range(n):
                t1 = time.monotonic()
                try:
                    f = router.submit({"data": x}, timeout_ms=30000,
                                      priority="interactive")
                except Exception as exc:
                    inter["errors"].append("admit: " + repr(exc))
                    continue
                with lock:
                    accepted[0] += 1
                    if accepted[0] == 60 and not killed[0]:
                        killed[0] = True
                        hosts[1].kill()   # SIGKILL the host process group
                try:
                    f.result(60)
                    inter["lat_ms"].append((time.monotonic() - t1) * 1e3)
                except Exception as exc:   # an admitted loss is a FINDING
                    inter["errors"].append(repr(exc))

        def best_effort_flood():
            # PIPELINED (open-loop) flood: a deep async window per
            # client is
            # what builds real queue pressure on a fast model — a
            # closed-loop client could never push est-wait over the
            # best-effort shed threshold
            window = []

            def reap(f):
                try:
                    f.result(60)
                    with lock:
                        be_done[0] += 1
                except Exception:
                    with lock:
                        be_shed[0] += 1

            while not stop_be.is_set():
                try:
                    window.append(router.submit({"data": x},
                                                timeout_ms=30000,
                                                priority="best_effort"))
                except Exception:
                    with lock:
                        be_shed[0] += 1
                    time.sleep(0.002)   # a shed reply means BACK OFF
                if len(window) >= 64:
                    reap(window.pop(0))
            for f in window:
                reap(f)

        # 1000 interactive samples: at most ~4-8 requests can be caught
        # in the kill's failover window (closed loop, 4 threads), and
        # the p99 of a 1000-sample run has its cutoff at 10 — so the
        # gate measures the steady degraded tail, not the coin-flip of
        # whether a ~300ms failover spike lands inside a 2.8-request
        # p99 cutoff (bimodal flake at 280 samples)
        threads = [threading.Thread(target=interactive_ramp, args=(250,),
                                    name="mx-chaos-fleet-inter-%d" % i)
                   for i in range(4)]
        threads += [threading.Thread(target=best_effort_flood,
                                     name="mx-chaos-fleet-be-%d" % i)
                    for i in range(2)]
        for t in threads:
            t.start()
        for t in threads[:4]:
            t.join()
        # keep the flood up until the fleet has backfilled, so the SLO
        # claim covers the degraded window end to end
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = fleet.stats()
            if st["hosts_lost"] == 1 and st["backfills"] >= 1:
                break
            time.sleep(0.1)
        stop_be.set()
        for t in threads[4:]:
            t.join()

        st = fleet.stats()
        snap = router.stats()
        classes = snap.get("classes", {})
        p99 = float(np.percentile(inter["lat_ms"], 99)) \
            if inter["lat_ms"] else None
        backfill_ups = [e for e in st["events"]
                        if e["action"] == "scale_up"
                        and "backfill" in str(e.get("reason"))]
        checks.update(
            host_declared_dead=(st["hosts_lost"] == 1
                                and st["hosts"]["host-b"]["alive"]
                                is False),
            zero_lost_interactive=(not inter["errors"]
                                   and len(inter["lat_ms"]) == 1000),
            interactive_slo_held=(p99 is not None and p99 <= bound_ms),
            interactive_not_shed=(classes.get("interactive", {})
                                  .get("shed", 0) == 0),
            best_effort_shed_first=(be_shed[0] > 0),
            backfilled_to_target=(st["backfills"] >= 1
                                  and st["live_replicas"] == st["target"]
                                  and set(st["placement"].values())
                                  == {"host-a"}),
            backfill_zero_compiles=(bool(backfill_ups) and all(
                e.get("spinup_compiles") == 0 for e in backfill_ups)))
        detail = {
            "interactive_baseline_p99_ms": baseline_p99,
            "interactive_p99_ms": p99,
            "interactive_p99_bound_ms": round(bound_ms, 3),
            "interactive_completed": len(inter["lat_ms"]),
            "best_effort_completed": be_done[0],
            "best_effort_shed": be_shed[0],
            "backfill_latency_s": st["backfill_latency_s"],
            "fleet": {k: st[k] for k in
                      ("target", "live_replicas", "scale_ups",
                       "hosts_lost", "backfills", "placement")},
            "router": {k: snap.get(k) for k in
                       ("failovers", "replicas_lost",
                        "duplicates_suppressed")},
        }
        errs = inter["errors"][:5]
    finally:
        if fleet is not None:
            try:
                fleet.shutdown(drain=False, close_hosts=True)
            except Exception:
                pass
        for h in hosts:
            try:
                h.kill()
            except Exception:
                pass
        _f.clear()
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": "fleet-host-kill",
        "checks": checks,
        **detail,
        "errors": errs,
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[fleet/host-kill]: passed=%s checks=%s (%.1fs)" %
              (result["passed"], checks, result["duration_s"]),
              file=sys.stderr)
    return result


def run_fleet(as_json=False, out_path=None):
    tmp = tempfile.mkdtemp(prefix="chaos-fleet-")
    try:
        runs = [run_fleet_schedule(tmp, quiet=as_json)]
    except Exception as exc:
        runs = [{"schedule": "fleet-host-kill", "passed": False,
                 "error": repr(exc)}]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos fleet: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- decode schedules: continuous-batching LM serving under sabotage ----------
# two in-process `DecodeReplica`s (one shared cached-jit program space,
# so replica 2 must warm with ZERO compiles) behind a real
# ReplicaRouter; one replica SIGKILLed mid-decode.  The acceptance
# story: a decode request is REPLAYABLE (prompt + budget re-derive the
# lost KV state via prefill on a survivor), so zero admitted sequences
# are lost, none is delivered twice, and the steady state never
# presents XLA a novel shape.

def _decode_cfg():
    from incubator_mxnet_tpu.llm import LMConfig
    return LMConfig(vocab_size=48, num_layers=2, num_heads=2, hidden=16,
                    ffn_mult=2, max_len=32, eos_id=0)


def _decode_params(cfg, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    c, f = cfg.hidden, cfg.hidden * cfg.ffn_mult
    mk = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.1  # noqa: E731
    p = {"lm_embed_weight": mk(cfg.vocab_size, c),
         "lm_final_ln_gamma": np.ones((c,), np.float32),
         "lm_final_ln_beta": np.zeros((c,), np.float32)}
    for i in range(cfg.num_layers):
        pre = "lm_block%d_" % i
        for suffix, shape in (("ln1_gamma", (c,)), ("ln1_beta", (c,)),
                              ("qkv_weight", (3 * c, c)),
                              ("qkv_bias", (3 * c,)),
                              ("out_proj_weight", (c, c)),
                              ("out_proj_bias", (c,)),
                              ("ln2_gamma", (c,)), ("ln2_beta", (c,)),
                              ("fc1_weight", (f, c)), ("fc1_bias", (f,)),
                              ("fc2_weight", (c, f)), ("fc2_bias", (c,))):
            p[pre + suffix] = np.ones(shape, np.float32) \
                if suffix.endswith("gamma") else (
                mk(*shape) if "weight" in suffix
                else np.zeros(shape, np.float32))
    return p


def _drive_decode(router, rng_seed, n_threads=4, per=20, kill_at=None,
                  kill_fn=None):
    """Closed-loop mixed-length decode traffic with caller-owned
    request ids; optionally fire `kill_fn` after `kill_at` admissions.
    Returns (ok results, errors, submitted rids)."""
    import numpy as np
    rng = np.random.default_rng(rng_seed)
    prompts = [[int(t) for t in rng.integers(1, 40, int(n))]
               for n in rng.choice([2, 3, 5, 7, 8], n_threads * per)]
    results, errors, rids = [], [], []
    accepted = [0]
    fired = [False]
    lock = threading.Lock()

    def client(tid):
        for j in range(per):
            idx = tid * per + j
            rid = "dec-%d" % idx
            try:
                f = router.submit(
                    {"tokens": prompts[idx],
                     "max_new_tokens": 4 + idx % 5},
                    timeout_ms=60000,
                    priority=("interactive", "batch",
                              "best_effort")[idx % 3],
                    request_id=rid)
                with lock:
                    rids.append(rid)
                    accepted[0] += 1
                    if kill_at is not None and accepted[0] == kill_at \
                            and not fired[0]:
                        fired[0] = True
                        kill_fn()
                results.append(f.result(120))
            except Exception as exc:   # a lost admitted request = FINDING
                errors.append(repr(exc))

    threads = [threading.Thread(target=client, args=(i,),
                                name="mx-chaos-decode-client-%d" % i)
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, errors, rids


def run_decode_schedule(name, quiet=False):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import analysis
    from incubator_mxnet_tpu.resilience import faults as _f
    t0 = time.time()
    checks = {}
    errs = []
    _f.configure("seed=51")   # trace/log only; the kill is real
    analysis.recompile.reset()
    cfg = _decode_cfg()
    reps = [mx.serving.DecodeReplica(
        cfg, _decode_params(cfg), replica_id="dec%d" % i,
        slots=4, buckets=(4, 8)) for i in range(2)]
    router = mx.serving.ReplicaRouter(reps, name="chaos-decode",
                                      health_interval_s=0.1,
                                      max_dispatches=4)
    try:
        # replica 2 warms off replica 1's live programs: same graph
        # keys through one cached-jit space, so spinup is compile-free
        checks["spinup_zero_compiles"] = \
            reps[1].ready_info.get("compiles") == 0
        base_compiles = [r.engine.programs.compile_count() for r in reps]
        if name == "decode-replica-kill":
            results, errors, rids = _drive_decode(
                router, rng_seed=51, kill_at=30, kill_fn=reps[0].kill)
            st = router.stats()
            survivors = [r for r in reps if r.replica_id != "dec0"]
            executed = [rid for r in survivors
                        for rid in r.engine.stats()["executed_rids"]]
            answered = {r["rid"] for r in results if isinstance(r, dict)}
            checks.update(
                zero_lost=(len(results) == len(rids) == 80
                           and not errors),
                every_sequence_generated=(all(
                    isinstance(r, dict) and r["tokens"]
                    for r in results)),
                zero_duplicate_execution=(
                    len(executed) == len(set(executed))
                    and st["duplicates_suppressed"] == 0),
                replica_declared_dead=(st["replicas_lost"] >= 1),
                every_rid_delivered_once=(len(answered) == 80),
                failovers=st["failovers"])
            errs = errors[:5]
        elif name == "decode-steady-state":
            results, errors, rids = _drive_decode(router, rng_seed=52)
            after = [r.engine.programs.compile_count() for r in reps]
            churn = [f for f in analysis.recompile.findings()
                     if str(f.get("key", "")).startswith("decode:")]
            checks.update(
                zero_lost=(len(results) == 80 and not errors),
                zero_steady_state_compiles=(after == base_compiles),
                zero_recompile_findings=(not churn),
                programs_stable=(all(
                    r.engine.programs.program_count() == 3
                    for r in reps)))
            errs = errors[:5]
        else:
            raise ValueError("unknown decode schedule %r" % name)
    finally:
        try:
            router.shutdown(drain=False)
        except Exception:
            pass
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass
        _f.clear()
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": name,
        "checks": checks,
        "errors": errs,
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[decode/%s]: passed=%s checks=%s (%.1fs)" %
              (name, result["passed"], checks, result["duration_s"]),
              file=sys.stderr)
    return result


def run_decode(as_json=False, out_path=None):
    runs = []
    for name in ("decode-steady-state", "decode-replica-kill"):
        try:
            runs.append(run_decode_schedule(name, quiet=as_json))
        except Exception as exc:
            runs.append({"schedule": name, "passed": False,
                         "error": repr(exc)})
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos decode: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- training-guardian schedules: silent-failure recovery ---------------------
# in-process seeded schedules over small Module.fit runs; every recovery
# path is certified with zero unified-program-cache compiles

def _train_model():
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import sym
    np.random.seed(0)
    mx.random.seed(0)
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _train_iter(n=128, bs=8):
    import numpy as np
    from incubator_mxnet_tpu import io
    rng = np.random.RandomState(3)
    x = rng.standard_normal((n, 10)).astype("float32")
    y = rng.randint(0, 4, n).astype("float32")
    return io.NDArrayIter(x, y, batch_size=bs, shuffle=False)


def _train_fit(mod, ckpt_dir=None):
    import incubator_mxnet_tpu as mx
    mod.fit(_train_iter(), num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, eval_metric="acc",
            initializer=mx.initializer.Xavier(),
            checkpoint_dir=ckpt_dir, checkpoint_period=4)


def _params_sha(mod):
    import hashlib
    args, auxs = mod.get_params()
    h = hashlib.sha256()
    for k in sorted(args):
        h.update(args[k].asnumpy().tobytes())
    for k in sorted(auxs):
        h.update(auxs[k].asnumpy().tobytes())
    return h.hexdigest()


def run_train_schedule(name, tmp, quiet=False):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import compile as _compile
    from incubator_mxnet_tpu.resilience import faults as _f
    t0 = time.time()
    checks = {}
    os.environ["MXNET_GUARDIAN_INTERVAL"] = "4"
    os.environ["MXNET_GUARDIAN_SPIKE_WINDOW"] = "4"

    def compiles():
        return _compile.stats()["counters"]["compiles"]

    if name == "warmup":
        # pays the process's cold compiles so every REAL schedule can
        # gate on zero-compile recovery (the live tier serves rebuilt
        # programs); also the fault-free baseline sha.  The K-step scan
        # AND the 1-step program both warm here — a post-rollback resume
        # trains partial blocks (the quarantined window breaks block
        # collection), so recovery dispatches the 1-step program too.
        _f.clear()
        mod = _train_model()
        _train_fit(mod)
        prev = os.environ.get("MXNET_FUSED_STEP_BLOCK")
        os.environ["MXNET_FUSED_STEP_BLOCK"] = "1"
        try:
            _train_fit(_train_model())
        finally:
            if prev is None:
                os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)
            else:
                os.environ["MXNET_FUSED_STEP_BLOCK"] = prev
        checks["completed"] = True
        checks["baseline_sha"] = _params_sha(mod)
        checks["guardian_active"] = mod._guardian is not None and \
            mod._guardian.stats()["steps_observed"] > 0
    elif name == "nonfinite-skip":
        # injected NaN gradient -> in-graph skip, deterministic
        # continuation: two identical seeded runs end bit-identical
        def one_run():
            _f.configure("seed=31;grad.nonfinite:error(at=5)")
            mod = _train_model()
            c0 = compiles()
            _train_fit(mod)
            st = mod._guardian.stats()
            _f.clear()
            return _params_sha(mod), st, compiles() - c0
        sha1, st1, d1 = one_run()
        sha2, st2, d2 = one_run()
        checks.update(
            skip_fired=(st1["skips"] == 1 and st1["injected_nonfinite"] == 1),
            batch_quarantined=(st1["quarantined"] == 1),
            deterministic_continuation=(sha1 == sha2),
            zero_recovery_compiles=(d1 == 0 and d2 == 0))
    elif name == "spike-rollback":
        # injected loss spike -> rollback-to-last-good; final params
        # bit-identical to a clean reference that skipped the same
        # quarantined window from the same healthy checkpoint state
        ck_a = os.path.join(tmp, "ck-spike")
        ck_b = os.path.join(tmp, "ck-ref")
        _f.configure("seed=32;loss.spike:error(at=10)")
        mod = _train_model()
        c0 = compiles()
        _train_fit(mod, ck_a)
        st = mod._guardian.stats()
        sha_rb = _params_sha(mod)
        d_rb = compiles() - c0
        _f.clear()
        os.makedirs(ck_b, exist_ok=True)
        shutil.copyfile(os.path.join(ck_a, "quarantine.jsonl"),
                        os.path.join(ck_b, "quarantine.jsonl"))
        ref = _train_model()
        c1 = compiles()
        _train_fit(ref, ck_b)
        checks.update(
            rollback_fired=(st["rollbacks"] == 1 and st["spikes"] == 1),
            window_quarantined=(st["quarantined"] >= 1),
            bit_identical_vs_clean=(sha_rb == _params_sha(ref)),
            zero_recovery_compiles=(d_rb == 0 and compiles() - c1 == 0))
    elif name == "corrupt-record":
        # injected record corruption -> substituted + counted +
        # quarantined; a resumed iterator skips the record entirely
        import numpy as np
        import cv2
        from incubator_mxnet_tpu import recordio
        from incubator_mxnet_tpu.image import ImageRecordIterImpl
        from incubator_mxnet_tpu.resilience.guardian import QuarantineLog
        rec = os.path.join(tmp, "c.rec")
        rng = np.random.RandomState(0)
        w = recordio.MXRecordIO(rec, "w")
        for i in range(24):
            ok, enc = cv2.imencode(
                ".png", rng.randint(0, 255, (40, 40, 3), dtype=np.uint8))
            w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                  enc.tobytes()))
        w.close()
        qlog = QuarantineLog(os.path.join(tmp, "quarantine.jsonl"))
        # record= targeting: hit-count (at=) ordering is thread-schedule
        # dependent under the multi-threaded batch builders
        _f.configure("seed=33;io.corrupt_record:corrupt(record=6)")
        it = ImageRecordIterImpl(path_imgrec=rec, data_shape=(3, 32, 32),
                                 batch_size=4, preprocess_threads=2)
        it.set_quarantine(qlog)
        n1 = sum(b.data[0].shape[0] - b.pad for b in it)
        corrupt_first = it.corrupt_records
        it.close()
        _f.clear()
        entries = qlog.load()
        # "resume": a fresh iterator with the quarantine applied never
        # reads the poisoned record again (no fault clause configured)
        it2 = ImageRecordIterImpl(path_imgrec=rec, data_shape=(3, 32, 32),
                                  batch_size=4, preprocess_threads=2)
        it2.apply_quarantine(entries)
        labels = []
        for b in it2:
            labels.extend(
                b.label[0].asnumpy()[:b.data[0].shape[0] - b.pad].tolist())
        it2.close()
        bad = {int(e["record"]) for e in entries
               if e.get("record") is not None}
        checks.update(
            corrupt_detected=(corrupt_first == 1 and n1 == 24),
            quarantine_logged=(bad == {6}),
            skipped_on_resume=(it2.corrupt_records == 0 and
                               len(labels) == 23 and
                               not any(float(r) in labels for r in bad)))
    else:
        raise ValueError("unknown train schedule %r" % name)
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": name,
        "checks": {k: v for k, v in checks.items() if k != "baseline_sha"},
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[train/%s]: passed=%s checks=%s (%.1fs)" %
              (name, result["passed"], result["checks"],
               result["duration_s"]), file=sys.stderr)
    return result


def run_train(as_json=False, out_path=None):
    runs = []
    for name in ("warmup", "nonfinite-skip", "spike-rollback",
                 "corrupt-record"):
        tmp = tempfile.mkdtemp(prefix="chaos-train-%s-" % name)
        try:
            runs.append(run_train_schedule(name, tmp, quiet=as_json))
        except Exception as exc:
            runs.append({"schedule": name, "passed": False,
                         "error": repr(exc)})
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos train: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- sharded-embedding chaos: SIGKILL a row shard mid-traffic -----------------
#
# The mxembed failure matrix (embedding/sharded.py): a shard server dying
# becomes a structured ServerLostError naming the shard and its rows;
# training recovers by restoring the checkpointed table and replaying
# from the checkpoint (bit-identical, since the lazy updates are
# deterministic); serving recovers through the on_shard_lost hook
# (respawn + replace_shard) with ZERO lost admitted requests.

def _spawn_shard_proc(port):
    """One embedding row-shard server as a real subprocess, so the
    schedule can SIGKILL it (not a polite in-process shutdown)."""
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1", DMLC_PS_ROOT_PORT=str(port),
               DMLC_NUM_WORKER="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.pop("MXNET_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.dist.server"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=REPO)
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return proc
        except OSError:
            if proc.poll() is not None:
                break
            time.sleep(0.05)
    proc.kill()
    raise RuntimeError("embedding shard server on port %d never came up"
                       % port)


def _table_sha(table):
    import hashlib
    return hashlib.sha256(table.checkpoint_rows().tobytes()).hexdigest()


def _embed_fit_model(rows, dim, table, n=96, bs=16, seed=0):
    """The wide-and-deep fixture: deterministic id stream + tower,
    bound with inputs_need_grad so fit's classic loop exposes the
    embedding gradient (examples/recommender/wide_deep.py)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import embedding as mxembed, io, sym
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, rows, size=(n, 2)).astype("int64")
    dense = rng.standard_normal((n, 4)).astype("float32")
    label = ((ids[:, 0] + ids[:, 1]) % 2).astype("float32")
    base = io.NDArrayIter({"emb": ids.astype("float32"), "dense": dense},
                          {"softmax_label": label}, batch_size=bs)
    adapter = mxembed.EmbeddingFitAdapter(table, base, id_field=0)
    emb = sym.Variable("emb")
    den = sym.Variable("dense")
    deep = sym.FullyConnected(emb, num_hidden=8, name="deep1")
    deep = sym.Activation(deep, act_type="relu")
    wide = sym.FullyConnected(den, num_hidden=8, name="wide1")
    out = sym.FullyConnected(deep + wide, num_hidden=2, name="head")
    net = sym.SoftmaxOutput(out, name="softmax")
    np.random.seed(seed)
    mx.random.seed(seed)
    mod = mx.mod.Module(net, data_names=("emb", "dense"),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=adapter.provide_data,
             label_shapes=adapter.provide_label,
             for_training=True, inputs_need_grad=True)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    return mod, adapter


def _embed_fit_epoch(mod, adapter):
    import incubator_mxnet_tpu as mx
    mod.fit(adapter, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            batch_end_callback=adapter.make_callback(mod),
            eval_metric="acc")


def run_embedding_schedule(name, quiet=False):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import embedding as mxembed
    from incubator_mxnet_tpu.resilience import ServerLostError
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # fast shard-death diagnosis (prod defaults wait seconds/reconnect)
    os.environ["MXNET_PS_RECONNECT_WAIT"] = "0.1"
    os.environ["MXNET_PS_MAX_RETRIES"] = "2"
    os.environ["MXNET_EMBED_BREAKER_THRESHOLD"] = "2"
    t0 = time.time()
    checks = {}
    rows, dim = 64, 4
    seed = 23

    if name == "train-shard-kill":
        # clean reference: epoch 1, checkpoint, epoch 2 -> final shas
        def fresh(ports):
            table = mxembed.ShardedEmbedding(
                "chaos_wd", rows, dim,
                [("127.0.0.1", p) for p in ports], seed=seed,
                cache_rows=32,
                optimizer=mx.optimizer.SGD(learning_rate=0.1,
                                           momentum=0.0))
            mod, adapter = _embed_fit_model(rows, dim, table, seed=seed)
            return table, mod, adapter

        ports = [_free_port(), _free_port()]
        procs = [_spawn_shard_proc(p) for p in ports]
        try:
            table, mod, adapter = fresh(ports)
            _embed_fit_epoch(mod, adapter)
            ck_table = table.checkpoint_rows()
            ck_args, ck_auxs = mod.get_params()
            ck_args = {k: v.asnumpy().copy() for k, v in ck_args.items()}
            _embed_fit_epoch(mod, adapter)
            ref_table_sha, ref_dense_sha = _table_sha(table), \
                _params_sha(mod)

            # chaos lane: restore epoch-1 state, then SIGKILL shard 1
            # at a seeded batch boundary inside the replayed epoch 2
            table.restore_rows(ck_table)
            mod.set_params({k: mx.nd.array(v)
                            for k, v in ck_args.items()}, ck_auxs,
                           allow_missing=False, force_init=True)
            kill_at = int(np.random.RandomState(seed).randint(1, 4))
            state = {"batches": 0, "err": None}
            push_cb = adapter.make_callback(mod)

            def chaos_cb(param):
                push_cb(param)
                state["batches"] += 1
                if state["batches"] == kill_at:
                    procs[1].kill()          # SIGKILL, mid-traffic
                    procs[1].wait()
            try:
                mod.fit(adapter, num_epoch=1, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1},
                        batch_end_callback=chaos_cb, eval_metric="acc")
            except ServerLostError as e:
                state["err"] = e
            err = state["err"]
            checks["server_lost_structured"] = (
                err is not None and err.server == 1
                and any("chaos_wd" in k for k in err.keys))
            checks["killed_sigkill"] = procs[1].returncode == -9
            # auto-resume: respawn the shard, restore the checkpointed
            # table and dense params, replay the epoch from the
            # checkpoint — bit-identical to the clean reference
            ports[1] = _free_port()
            procs[1] = _spawn_shard_proc(ports[1])
            table.replace_shard(1, "127.0.0.1", ports[1],
                                restore=ck_table)
            table.restore_rows(ck_table)
            mod.set_params({k: mx.nd.array(v)
                            for k, v in ck_args.items()}, ck_auxs,
                           allow_missing=False, force_init=True)
            adapter.reset()      # the aborted epoch left it mid-stream
            _embed_fit_epoch(mod, adapter)
            checks["resumed_table_bit_identical"] = (
                _table_sha(table) == ref_table_sha)
            checks["resumed_dense_bit_identical"] = (
                _params_sha(mod) == ref_dense_sha)
            checks["failover_counted"] = table.stats()["failovers"] == 1
            table.close()
        finally:
            for p in procs:
                p.kill()
                p.communicate()

    elif name == "serve-shard-kill":
        from incubator_mxnet_tpu import io, sym
        from incubator_mxnet_tpu.serving import LocalReplica, ReplicaRouter
        ports = [_free_port(), _free_port()]
        procs = [_spawn_shard_proc(p) for p in ports]
        try:
            table = mxembed.ShardedEmbedding(
                "chaos_serve", rows, dim,
                [("127.0.0.1", p) for p in ports], seed=seed,
                cache_rows=0)        # every lookup exercises the wire
            ck = table.checkpoint_rows()
            np.random.seed(seed)
            mx.random.seed(seed)
            net = sym.FullyConnected(sym.Variable("emb"), num_hidden=3,
                                     name="head")
            net = sym.SoftmaxOutput(net, name="softmax")
            mod = mx.mod.Module(net, data_names=("emb",),
                                label_names=("softmax_label",),
                                context=mx.cpu())
            mod.bind(data_shapes=[io.DataDesc("emb", (2, 2 * dim))],
                     label_shapes=[io.DataDesc("softmax_label", (2,))],
                     for_training=False, grad_req="null")
            mod.init_params(mx.initializer.Xavier())
            args, auxs = mod.get_params()
            reps = [LocalReplica(
                mx.serving.ServedModel(
                    net, args, auxs, data_shapes=[("emb", (1, 2 * dim))],
                    buckets=(1, 2, 4), ctx=mx.cpu(), name="tower"),
                replica_id="r%d" % i) for i in range(2)]
            lock = threading.Lock()
            state = {"done": 0, "ok": 0, "killed": False, "gen": 0}

            def on_shard_lost(err):
                # thread-safe respawn: first caller replaces the shard,
                # racers see the bumped generation and just retry
                with lock:
                    gen = state["gen"]
                    if gen == table.failovers:
                        port = _free_port()
                        procs.append(_spawn_shard_proc(port))
                        table.replace_shard(err.server, "127.0.0.1",
                                            port, restore=ck)
                        state["gen"] = table.failovers
                return True

            rng = np.random.RandomState(seed)
            reqs = rng.randint(0, rows, size=(60, 2, 2))
            kill_after = int(rng.randint(8, 16))
            with ReplicaRouter(reps, health_interval_s=0.2) as router:
                path = mxembed.EmbeddingServingPath(
                    table, router, embed_input="emb",
                    on_shard_lost=on_shard_lost)
                baseline = {}
                for i, ids in enumerate(reqs):
                    baseline[i] = path.predict(
                        ids, timeout_ms=10000)[0].asnumpy()
                n_before = path.requests

                def worker(idx0):
                    for i in range(idx0, len(reqs), 4):
                        got = path.predict(reqs[i],
                                           timeout_ms=10000)[0].asnumpy()
                        with lock:
                            state["done"] += 1
                            if np.allclose(got, baseline[i]):
                                state["ok"] += 1
                        if not state["killed"] and \
                                state["done"] >= kill_after:
                            with lock:
                                if not state["killed"]:
                                    state["killed"] = True
                                    procs[0].kill()   # SIGKILL shard 0
                                    procs[0].wait()
                threads = [threading.Thread(target=worker, args=(k,))
                           for k in range(4)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
            st = path.stats()
            checks["killed_sigkill"] = procs[0].returncode == -9
            checks["zero_lost_admitted"] = (
                state["done"] == len(reqs)
                and st["completed"] == n_before + len(reqs))
            checks["results_match_baseline"] = state["ok"] == len(reqs)
            checks["failover_fired"] = (st["shard_failovers"] >= 1
                                        and table.stats()["failovers"] >= 1)
            table.close()
        finally:
            for p in procs:
                p.kill()
                p.communicate()
    else:
        raise ValueError("unknown embedding schedule %r" % name)

    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {"schedule": name, "seed": seed, "checks": checks,
              "duration_s": round(time.time() - t0, 1),
              "passed": bool(bools) and all(bools)}
    if not quiet:
        print("chaos[embed/%s]: passed=%s checks=%s (%.1fs)" %
              (name, result["passed"], result["checks"],
               result["duration_s"]), file=sys.stderr)
    return result


def run_embedding(as_json=False, out_path=None):
    runs = []
    for name in ("train-shard-kill", "serve-shard-kill"):
        try:
            runs.append(run_embedding_schedule(name, quiet=as_json))
        except Exception as exc:
            runs.append({"schedule": name, "passed": False,
                         "error": repr(exc)})
    artifact = {"schedules": runs,
                "all_passed": all(r["passed"] for r in runs)}
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos embedding: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


# -- train-to-serve loop schedules: the continuous-training hand-off ----------
#
# A REAL trainer process (tools/loop_trainer.py) publishes guardian-
# healthy elastic checkpoints into a shared ModelRegistry while a
# 2-replica remote fleet promotes them through the LoopController's
# canary gate under live traffic.  The failure matrix: a corrupted
# training shard + loss spike (guardian rollback -> registry fence; the
# fleet never serves a fenced or rejected version, zero admitted
# requests lost, zero swap compiles, next clean version inside the
# freshness SLO), a healthy-stamped-but-poisoned publish (the serving-
# side canary rejects it, swaps the canary replica back, stamps the
# version rejected — durable, never retried), and a torn publish (the
# truncated manifest is invisible to the watcher; the incumbent keeps
# serving; a clean re-publish promotes).

def _loop_elastic_ckpt(tmp, name, args, auxs, step, transform=None):
    """Params exported as ONE guardian-healthy elastic checkpoint dir."""
    import incubator_mxnet_tpu as mx
    root = os.path.join(tmp, name)
    arrays = {}
    for k, v in args.items():
        a = v.asnumpy()
        arrays["arg:" + k] = transform(k, a) if transform else a
    for k, v in auxs.items():
        arrays["aux:" + k] = v.asnumpy()
    mgr = mx.checkpoint.CheckpointManager(root, async_snapshots=False)
    mgr.snapshot(arrays=arrays, step=step, epoch=0, nbatch=step,
                 meta={"health": {"status": "healthy"}}, sync=True)
    mgr.close()
    return os.path.join(root, "ckpt-%010d" % step)


def _loop_boot_labels(args, x):
    """The boot model's own argmax on `x` — a holdout on which the
    incumbent scores exactly 1.0, so a same-params candidate ties and a
    head-negated (poisoned) one scores ~0."""
    import numpy as np
    w0 = args["fc0_weight"].asnumpy()
    b0 = args["fc0_bias"].asnumpy()
    wh = args["head_weight"].asnumpy()
    bh = args["head_bias"].asnumpy()
    h = np.tanh(x @ w0.T + b0)
    return (h @ wh.T + bh).argmax(axis=1).astype(np.float32)


def _loop_traffic(router, stop_evt, n_threads=3):
    """Open-ended closed-loop traffic until `stop_evt`; returns
    (threads, ok_counter, errors) — the caller starts and joins."""
    import numpy as np
    x = np.random.default_rng(9).standard_normal((2, 16)).astype(
        np.float32)
    oks, errors = [0], []
    lock = threading.Lock()

    def client():
        while not stop_evt.is_set():
            try:
                f = router.submit({"data": x}, timeout_ms=30000)
                f.result(60)
                with lock:
                    oks[0] += 1
            except Exception as exc:   # a lost request is the FINDING
                errors.append(repr(exc))

    threads = [threading.Thread(target=client,
                                name=f"mx-chaos-loop-client-{i}")
               for i in range(n_threads)]
    return threads, oks, errors


def run_loop_schedule(name, tmp, quiet=False):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import loop as mxloop
    from incubator_mxnet_tpu.checkpoint import manifest as _ck_manifest
    from incubator_mxnet_tpu.resilience import faults as _f
    from incubator_mxnet_tpu.resilience.faults import TornWrite
    t0 = time.time()
    checks = {}
    errs = []
    # the loop schedules certify the canary gate, not eviction timing —
    # a generous liveness deadline keeps a CPU-starved worker (trainer
    # subprocess + fleet sharing one loaded box) from being falsely
    # declared lost mid-canary
    router, reps, (mod, prefix) = _serving_fleet(tmp, n=2,
                                                 health_deadline_s=15.0)
    args, auxs = mod.get_params()
    boot_ck = _loop_elastic_ckpt(tmp, "boot", args, auxs, step=0)
    reg = mxloop.ModelRegistry(os.path.join(tmp, "registry"))

    def publish(ckpt, step):
        return reg.publish(ckpt, step=step,
                           health={"status": "healthy"},
                           watermark={"step": step, "time": time.time()})

    stop = threading.Event()
    threads, oks, errors = _loop_traffic(router, stop)
    try:
        checks["spinup_zero_compiles"] = all(
            r.ready_info.get("compiles") == 0 for r in reps[1:])
        base = [r.stats() for r in reps]
        for t in threads:
            t.start()
        if name == "poisoned-shard-loop":
            # the real loop: trainer subprocess reads a record shard
            # through MXRecordIO with a seeded payload corruption AND an
            # injected loss spike; the guardian rolls back, the
            # publisher fences the disowned window, and the serving
            # side keeps promoting only clean versions
            _f.configure("seed=70")   # driver side: trace only
            sys.path.insert(0, os.path.join(REPO, "tools"))
            import loop_trainer as _lt
            ctl = mxloop.LoopController(
                router, reg, _lt.holdout_batch(), canary_tol=1.0,
                poll_interval_s=0.2, freshness_slo_s=120.0,
                incumbent_checkpoint=boot_ck)
            report_path = os.path.join(tmp, "trainer_report.json")
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=REPO + os.pathsep
                       + os.environ.get("PYTHONPATH", ""),
                       MXNET_FAULTS=("seed=71;"
                                     "io.corrupt_record:corrupt(at=40);"
                                     "loss.spike:error(at=30)"),
                       MXNET_GUARDIAN_INTERVAL="4",
                       MXNET_GUARDIAN_SPIKE_WINDOW="4")
            env.pop("MXNET_FAULTS_LOG", None)
            proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(REPO, "tools", "loop_trainer.py"),
                 "--registry", reg.root,
                 "--ckpt", os.path.join(tmp, "trainer-ck"),
                 "--rec", os.path.join(tmp, "shard.rec"),
                 "--report", report_path, "--write-shard", "96"],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            promoted = []
            rejected = []
            deadline = time.time() + 300
            quiet_polls = 0
            try:
                while time.time() < deadline:
                    try:
                        status = ctl.poll_once()
                    except mxloop.CanaryRejectedError as exc:
                        rejected.append(exc.version)
                        continue
                    if status.get("status") == "promoted":
                        promoted.append(status)
                        quiet_polls = 0
                    elif proc.poll() is not None:
                        quiet_polls += 1
                        if quiet_polls >= 5:
                            break
                    time.sleep(0.25)
            finally:
                try:
                    proc.communicate(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.communicate()
            with open(report_path) as f:
                report = json.load(f)
            st = ctl.stats()
            checks.update(
                trainer_completed=bool(report.get("completed")),
                corrupt_record_detected=(
                    report.get("corrupt_records", 0) >= 1),
                guardian_rolled_back=(
                    (report.get("guardian") or {}).get("rollbacks", 0)
                    >= 1),
                registry_fenced=(len(report.get("fences") or ()) >= 1),
                clean_versions_promoted=(len(promoted) >= 1),
                poisoned_never_served=(
                    not rejected and st["canary_rejections"] == 0
                    and all(not reg.fenced(p["version"])
                            and reg.rejected(p["version"]) is None
                            for p in promoted)),
                freshness_within_slo=(st.get("freshness_slo_met") == 1),
                promoted_versions=[p["version"] for p in promoted],
                fenced_windows=report.get("fences"))
        elif name == "poisoned-publish-canary":
            # a healthy-stamped checkpoint with sabotaged weights lands
            # in the registry (poisoned data slipped past the trainer):
            # the serving-side canary is the LAST line of defense
            _f.configure("seed=72")
            x = np.random.default_rng(7).standard_normal(
                (4, 16)).astype(np.float32)
            labels = _loop_boot_labels(args, x)
            ctl = mxloop.LoopController(
                router, reg, ({"data": x}, labels), canary_tol=0.05,
                poll_interval_s=0.2, freshness_slo_s=120.0,
                incumbent_checkpoint=boot_ck)
            good_ck = _loop_elastic_ckpt(tmp, "good", args, auxs, 1)
            poison_ck = _loop_elastic_ckpt(
                tmp, "poison", args, auxs, 2,
                transform=lambda k, a: -a if k == "head_weight" else a)
            publish(good_ck, 1)
            st1 = ctl.poll_once()
            checks["clean_version_promoted"] = (
                st1.get("status") == "promoted" and st1["version"] == 1)
            publish(poison_ck, 2)
            rejected_exc = None
            try:
                ctl.poll_once()
            except mxloop.CanaryRejectedError as exc:
                rejected_exc = exc
            checks["canary_rejected_structured"] = (
                rejected_exc is not None and rejected_exc.version == 2
                and rejected_exc.canary_score
                < rejected_exc.incumbent_score)
            # the canary replica was swapped BACK: every replica still
            # classifies the holdout exactly like the incumbent
            outs = [r.submit({"data": x}, timeout_ms=30000).result(60)
                    for r in reps]
            checks["fleet_swapped_back"] = all(
                bool((np.asarray(o[0]).argmax(axis=1) == labels).all())
                for o in outs)
            checks["rejection_stamp_durable"] = (
                reg.rejected(2) is not None
                and _ck_manifest.is_rejected(poison_ck)
                and mxloop.ModelRegistry(
                    reg.root).latest()["version"] == 1)
            st2 = ctl.poll_once()
            checks["never_retried"] = (
                st2.get("status") == "idle"
                and ctl.stats()["canary_rejections"] == 1)
        elif name == "torn-publish":
            # the publisher dies mid-commit: the truncated manifest
            # must be invisible, the fleet keeps serving, and a clean
            # re-publish of the same step promotes normally
            x = np.random.default_rng(7).standard_normal(
                (4, 16)).astype(np.float32)
            labels = _loop_boot_labels(args, x)
            ctl = mxloop.LoopController(
                router, reg, ({"data": x}, labels), canary_tol=0.05,
                poll_interval_s=0.2, freshness_slo_s=120.0,
                incumbent_checkpoint=boot_ck)
            good_ck = _loop_elastic_ckpt(tmp, "good", args, auxs, 1)
            v2_ck = _loop_elastic_ckpt(tmp, "v2", args, auxs, 2)
            publish(good_ck, 1)
            checks["clean_version_promoted"] = (
                ctl.poll_once().get("status") == "promoted")
            _f.configure("seed=73;publish.commit:torn(at=1)")
            torn_raised = False
            try:
                publish(v2_ck, 2)
            except TornWrite:
                torn_raised = True
            _f.configure("seed=73")
            torn_path = os.path.join(reg.root, "v-0000000002.json")
            checks["torn_publish_raised"] = torn_raised
            checks["torn_manifest_invisible"] = (
                os.path.exists(torn_path)
                and reg.latest()["version"] == 1
                and ctl.poll_once().get("status") == "idle"
                and reg.stats()["torn_manifests"] == 1)
            out = router.predict({"data": x}, timeout_ms=30000)
            checks["fleet_kept_serving"] = bool(
                (np.asarray(out[0]).argmax(axis=1) == labels).all())
            publish(v2_ck, 2)   # clean re-publish commits atomically
            st2 = ctl.poll_once()
            checks["clean_republish_promoted"] = (
                st2.get("status") == "promoted" and st2["version"] == 2)
        else:
            raise ValueError("unknown loop schedule %r" % name)
        stop.set()
        for t in threads:
            t.join(30)
        after = [r.stats() for r in reps]
        compiles = [
            (s.get("cache") or {}).get("compiles", 0) -
            (b.get("cache") or {}).get("compiles", 0)
            for b, s in zip(base, after)]
        checks.update(
            zero_lost=(oks[0] > 0 and not errors),
            zero_swap_compiles=all(c == 0 for c in compiles),
            requests_served=oks[0])
        errs = errors[:5] if errors else []
    finally:
        stop.set()
        try:
            router.shutdown(drain=False)
        except Exception:
            pass
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass
        _f.clear()
    bools = [v for v in checks.values() if isinstance(v, bool)]
    result = {
        "schedule": name,
        "checks": checks,
        "errors": errs,
        "duration_s": round(time.time() - t0, 1),
        "passed": bool(bools) and all(bools),
    }
    if not quiet:
        print("chaos[loop/%s]: passed=%s checks=%s (%.1fs)" %
              (name, result["passed"], checks, result["duration_s"]),
              file=sys.stderr)
    return result


def run_loop(as_json=False, out_path=None):
    runs = []
    for name in ("poisoned-shard-loop", "poisoned-publish-canary",
                 "torn-publish"):
        # one retry on an ESCAPED exception only: on an oversubscribed
        # box (this suite runs trainer + 2 workers + driver on shared
        # cores) a starved worker can be declared lost mid-schedule —
        # an infra artifact, not the invariant under test.  A schedule
        # that RAN but failed its checks is never retried.
        for attempt in (1, 2):
            tmp = tempfile.mkdtemp(prefix="chaos-loop-%s-" % name)
            try:
                run = run_loop_schedule(name, tmp, quiet=as_json)
            except Exception as exc:
                run = {"schedule": name, "passed": False,
                       "error": repr(exc)}
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            run["attempt"] = attempt
            if run.get("error") is None or attempt == 2:
                break
        runs.append(run)
    artifact = {
        "schedules": runs,
        "all_passed": all(r["passed"] for r in runs),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    if as_json:
        print(json.dumps(artifact))
    else:
        print("chaos loop: %d schedule(s), all_passed=%s -> %s" %
              (len(runs), artifact["all_passed"], out_path))
    return 0 if artifact["all_passed"] else 1


def main(argv=None):
    ap = argparse.ArgumentParser(prog="run_chaos", description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--pod", action="store_true")
    ap.add_argument("--serving", action="store_true")
    ap.add_argument("--fleet", action="store_true")
    ap.add_argument("--train", action="store_true")
    ap.add_argument("--decode", action="store_true")
    ap.add_argument("--embedding", action="store_true")
    ap.add_argument("--loop", action="store_true")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.loop:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_LOOP.json")
        sys.path.insert(0, REPO)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_loop(as_json=args.as_json, out_path=out)
    if args.embedding:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_EMBED.json")
        sys.path.insert(0, REPO)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_embedding(as_json=args.as_json, out_path=out)
    if args.decode:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_DECODE.json")
        sys.path.insert(0, REPO)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_decode(as_json=args.as_json, out_path=out)
    if args.fleet:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_FLEET.json")
        sys.path.insert(0, REPO)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_fleet(as_json=args.as_json, out_path=out)
    if args.train:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_TRAIN.json")
        sys.path.insert(0, REPO)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return run_train(as_json=args.as_json, out_path=out)
    if args.serving:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_SERVING.json")
        sys.path.insert(0, REPO)
        return run_serving(as_json=args.as_json, out_path=out)
    if args.pod:
        out = args.out if args.out is not None \
            else os.path.join(REPO, "CHAOS_POD.json")
        return run_pod(as_json=args.as_json, out_path=out)
    if args.out is None:
        args.out = os.path.join(REPO, "CHAOS_REPORT.json")
    tests = QUICK_TESTS if args.quick else FULL_TESTS

    runs = [run_schedule(name, spec, tests, quiet=args.as_json)
            for name, spec in SCHEDULES.items()]
    artifact = {
        "quick": args.quick,
        "tests": tests,
        "schedules": runs,
        "total_faults": sum(r["faults"] for r in runs),
        "total_retries": sum(r["retries"] for r in runs),
        "all_passed": all(r["rc"] == 0 for r in runs),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
    if args.as_json:
        slim = dict(artifact)
        for r in slim["schedules"]:
            r.pop("tail", None)
        print(json.dumps(slim))
    else:
        print("chaos: %d schedule(s), %d faults fired, %d retries, "
              "all_passed=%s -> %s" %
              (len(runs), artifact["total_faults"],
               artifact["total_retries"], artifact["all_passed"], args.out))
    return 0 if artifact["all_passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
