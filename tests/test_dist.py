"""Multi-process distributed kvstore tests.

The reference exercises dist kvstores by launching real localhost worker
processes against a parameter server (`tests/nightly/dist_sync_kvstore.py:30-60`
via `tools/launch.py`); this does the same with small tensors so it runs in
CI: every worker pushes rank-dependent values and asserts the aggregated
result is identical everywhere.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert nw == int(os.environ["DMLC_NUM_WORKER"]), (rank, nw)
if os.environ.get("MXNET_KVSTORE_COLLECTIVE") == "1":
    assert kv._collective is not None, "collective data plane must engage"
    # gradient bytes must never transit the socket in collective mode
    from incubator_mxnet_tpu.dist import transport
    _orig_send = transport.send_msg
    def _no_push(sock, obj):
        assert not (isinstance(obj, dict) and obj.get("cmd") == "push"), \
            "gradient push escaped to the socket in collective mode"
        return _orig_send(sock, obj)
    transport.send_msg = _no_push

# round-trip 1: plain aggregation (no optimizer -> pull returns the sum)
kv.init("3", nd.zeros((4, 2)))
kv.push("3", nd.ones((4, 2)) * (rank + 1))
out = nd.zeros((4, 2))
kv.pull("3", out=out)
expect = np.full((4, 2), sum(r + 1 for r in range(nw)), "f4")
np.testing.assert_allclose(out.asnumpy(), expect)

# round-trip 2: versioned second round must not mix with round 1
kv.push("3", nd.ones((4, 2)) * 10 * (rank + 1))
out2 = nd.zeros((4, 2))
kv.pull("3", out=out2)
np.testing.assert_allclose(out2.asnumpy(), 10 * expect)

# two pushes before a pull: ps-lite timestamp semantics — each push joins
# its own round, rounds aggregate across all workers in order
kv.push("3", nd.ones((4, 2)) * 100 * (rank + 1))
kv.push("3", nd.ones((4, 2)) * 1000 * (rank + 1))
out3 = nd.zeros((4, 2))
kv.pull("3", out=out3)
np.testing.assert_allclose(out3.asnumpy(), 1000 * expect)

# multi-device push: per-device shards reduce locally before the wire
devs = [mx.cpu(i) for i in range(min(4, len(jax.devices())))]
kv.init("md", nd.zeros((2, 2)))
kv.push("md", [nd.ones((2, 2), ctx=d) for d in devs])
md = nd.zeros((2, 2))
kv.pull("md", out=md)
np.testing.assert_allclose(md.asnumpy(), len(devs) * nw)

# batched multi-key push/pull: the whole key list rides ONE fused
# collective dispatch (bucketed all-reduce), not one per key
if os.environ.get("MXNET_KVSTORE_COLLECTIVE") == "1":
    bkeys = ["b0", "b1", "b2"]
    bshapes = [(3,), (2, 2), (5,)]
    for k, s in zip(bkeys, bshapes):
        kv.init(k, nd.zeros(s))
    before = kv._collective.dispatch_count
    kv.push(bkeys, [nd.ones(s) * (rank + 1) for s in bshapes])
    after = kv._collective.dispatch_count
    assert after == before + 1, ("batched push must issue ONE collective",
                                 before, after)
    bouts = [nd.zeros(s) for s in bshapes]
    kv.pull(bkeys, out=bouts)
    tot = sum(r + 1 for r in range(nw))
    for o in bouts:
        np.testing.assert_allclose(o.asnumpy(), tot)

# server-side optimizer: weight = w0 - lr * sum(grads) each round
kv.init("w", nd.ones((3,)))
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / nw))
for step in range(3):
    kv.push("w", nd.ones((3,)) * (rank + 1))
    w = nd.zeros((3,))
    kv.pull("w", out=w)
    grad_mean = sum(r + 1 for r in range(nw)) / nw
    np.testing.assert_allclose(
        w.asnumpy(), 1.0 - 0.1 * grad_mean * (step + 1), rtol=1e-5)

kv._barrier()
kv.close()
print("worker %d OK" % rank)
"""


@pytest.mark.parametrize("n_workers,collective", [(2, "0"), (4, "0"),
                                                  (2, "1")])
def test_dist_sync_multiprocess(tmp_path, n_workers, collective):
    """collective="0": gradients transit the parameter server (socket data
    plane).  collective="1": gradients all-reduce over the global device
    mesh (XLA collectives; server = control plane) — same observable
    semantics either way."""
    from incubator_mxnet_tpu.dist.server import ParameterServer

    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    server = ParameterServer(num_workers=n_workers).start()
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(server.port),
               DMLC_NUM_WORKER=str(n_workers),
               DMLC_ROLE="worker",
               MXNET_KVSTORE_COLLECTIVE=collective,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script)],
                              env=dict(env, DMLC_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in range(n_workers)]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    server.shutdown()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"
        assert f"worker {r} OK" in out


def test_2bit_wire_codec_roundtrip():
    """pack/unpack identity + the 16x wire-size contract
    (reference gradient_compression.h packs 16 grads per 32-bit word)."""
    from incubator_mxnet_tpu.dist.compression import (pack_2bit, unpack_2bit,
                                                      is_packed)
    rng = np.random.RandomState(0)
    for shape in [(7,), (16,), (5, 9), (128, 3)]:
        thr = 0.5
        g = rng.randn(*shape).astype("f4")
        q = np.where(g >= thr, thr,
                     np.where(g <= -thr, -thr, 0.0)).astype("f4")
        msg = pack_2bit(q, thr)
        assert is_packed(msg)
        n = int(np.prod(shape))
        assert msg["packed2bit"].nbytes == (n + 3) // 4, \
            "wire payload must be ~n/4 bytes (16x smaller than fp32)"
        np.testing.assert_array_equal(unpack_2bit(msg), q)


WORKER_COMPRESS = r"""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.dist import transport
from incubator_mxnet_tpu.dist.compression import is_packed

# spy on the wire: every push frame must carry the packed payload
sent = []
orig = transport.send_msg
def spy(sock, obj):
    if isinstance(obj, dict) and obj.get("cmd") == "push":
        sent.append(obj["value"])
    return orig(sock, obj)
transport.send_msg = spy

os.environ["MXNET_KVSTORE_COLLECTIVE"] = "0"  # this test probes the socket wire
kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
n = 64
kv.init("g", nd.zeros((n,)))
grad = np.linspace(-1, 1, n).astype("f4") * (rank + 1)
kv.push("g", nd.array(grad))
out = nd.zeros((n,))
kv.pull("g", out=out)
# every worker's contribution was quantized to {-.5, 0, +.5} then summed
expect = np.zeros(n, "f4")
for r in range(nw):
    g = np.linspace(-1, 1, n).astype("f4") * (r + 1)
    expect += np.where(g >= .5, .5, np.where(g <= -.5, -.5, 0.)).astype("f4")
np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-6)
assert sent and all(is_packed(v) for v in sent), "gradient bytes left the " \
    "socket dense — compression must pack the wire"
assert all(v["packed2bit"].nbytes == (n + 3) // 4 for v in sent)
kv._barrier()
kv.close()
print("worker %d OK" % rank)
"""


def test_dist_compression_packs_the_wire(tmp_path):
    from incubator_mxnet_tpu.dist.server import ParameterServer

    n_workers = 2
    script = tmp_path / "worker_c.py"
    script.write_text(WORKER_COMPRESS)
    server = ParameterServer(num_workers=n_workers).start()
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(server.port),
               DMLC_NUM_WORKER=str(n_workers),
               DMLC_ROLE="worker",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script)],
                              env=dict(env, DMLC_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in range(n_workers)]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    server.shutdown()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"


def test_launcher(tmp_path):
    """tools/launch.py spawns server+workers and propagates exit codes.
    (Launched workers default to MXNET_KVSTORE_COLLECTIVE=1, so the data
    plane needs multiprocess CPU collectives.)"""
    script = tmp_path / "trivial.py"
    script.write_text(
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import incubator_mxnet_tpu as mx\n"
        "from incubator_mxnet_tpu import nd\n"
        "kv = mx.kv.create('dist_sync')\n"
        "kv.init('0', nd.zeros((2,)))\n"
        "kv.push('0', nd.ones((2,)))\n"
        "o = nd.zeros((2,))\n"
        "kv.pull('0', out=o)\n"
        "assert o.asnumpy()[0] == kv.num_workers\n"
        "kv.close()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    rc = subprocess.call(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        env=env, timeout=240)
    assert rc == 0


def test_async_push_applies_immediately():
    """dist_async: a push applies without waiting for the other worker
    (two in-process clients; only rank 0 pushes)."""
    import threading

    from incubator_mxnet_tpu.dist.server import ParameterServer
    from incubator_mxnet_tpu.dist.kvstore_dist import KVStoreDist
    from incubator_mxnet_tpu import nd

    server = ParameterServer(num_workers=2).start()
    old = {k: os.environ.get(k) for k in
           ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_RANK")}
    os.environ.update(DMLC_PS_ROOT_URI="127.0.0.1",
                      DMLC_PS_ROOT_PORT=str(server.port), DMLC_RANK="0")
    try:
        kv0 = KVStoreDist("dist_async")
        os.environ["DMLC_RANK"] = "1"
        kv1 = KVStoreDist("dist_async")
        # init barriers across all workers: run rank 1's from a thread
        t = threading.Thread(target=kv1.init, args=("k", nd.zeros((2,))))
        t.start()
        kv0.init("k", nd.zeros((2,)))
        t.join(timeout=60)
        assert not t.is_alive()
        kv0.push("k", nd.ones((2,)))   # rank 1 never pushes
        out = nd.zeros((2,))
        kv0.pull("k", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)
        kv0.close()
        kv1.close()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        server.shutdown()


WORKER_COLLECTIVE_COMPRESS = r"""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.dist import kvstore_dist

os.environ["MXNET_KVSTORE_COLLECTIVE"] = "1"
kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert kv._collective is not None

# spy on the collective payload dtype: compressed gradients must ride the
# interconnect at bf16 (half of fp32) — the collective-mode reading of the
# reference's wire compression (gradient_compression.h)
payload_dtypes = []
orig_many = kv._collective.allreduce_many
orig_one = kv._collective.allreduce
def spy_many(arrs):
    payload_dtypes.extend(str(a.dtype) for a in arrs)
    return orig_many(arrs)
def spy_one(a):
    payload_dtypes.append(str(a.dtype))
    return orig_one(a)
kv._collective.allreduce_many = spy_many
kv._collective.allreduce = spy_one

kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
n = 64
kv.init("g", nd.zeros((n,)))
payload_dtypes.clear()           # init broadcast stays full width
grad = np.linspace(-1, 1, n).astype("f4") * (rank + 1)
kv.push("g", nd.array(grad))
out = nd.zeros((n,))
kv.pull("g", out=out)
expect = np.zeros(n, "f4")
for r in range(nw):
    g = np.linspace(-1, 1, n).astype("f4") * (r + 1)
    expect += np.where(g >= .5, .5, np.where(g <= -.5, -.5, 0.)).astype("f4")
np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-2, atol=1e-3)
assert payload_dtypes and all(d == "bfloat16" for d in payload_dtypes), \
    payload_dtypes
kv._barrier()
kv.close()
print("worker %d OK" % rank)
"""


def test_dist_collective_compression_halves_payload(tmp_path):
    """Collective mode + 2-bit compression: gradients quantize with error
    feedback device-side and the global all-reduce payload is bf16."""
    from incubator_mxnet_tpu.dist.server import ParameterServer

    n_workers = 2
    script = tmp_path / "worker_cc.py"
    script.write_text(WORKER_COLLECTIVE_COMPRESS)
    server = ParameterServer(num_workers=n_workers).start()
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(server.port),
               DMLC_NUM_WORKER=str(n_workers),
               DMLC_ROLE="worker",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script)],
                              env=dict(env, DMLC_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in range(n_workers)]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    server.shutdown()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"


SHARDED_WORKER = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert kv._num_servers == 2, kv._num_servers
assert len(kv._chans) == 2

# small key: lands whole on ONE hashed server
kv.init("tiny", nd.zeros((3,)))
kv.push("tiny", nd.ones((3,)) * (rank + 1))
out = nd.zeros((3,))
kv.pull("tiny", out=out)
tot = sum(r + 1 for r in range(nw))
np.testing.assert_allclose(out.asnumpy(), tot)

# big key: over MXNET_KVSTORE_BIGARRAY_BOUND -> flat-split, one
# contiguous range per server, reassembled on pull
big = np.arange(40, dtype="f4").reshape(5, 8)
kv.init("big", nd.array(big * 0))
kv.push("big", nd.array(big * (rank + 1)))
bout = nd.zeros((5, 8))
kv.pull("big", out=bout)
np.testing.assert_allclose(bout.asnumpy(), big * tot)

# server-side optimizer applies per range: weight = w0 - lr*mean over rounds
kv.init("w", nd.ones((30,)))
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / nw))
for step in range(2):
    kv.push("w", nd.ones((30,)) * (rank + 1))
    w = nd.zeros((30,))
    kv.pull("w", out=w)
    gm = tot / nw
    np.testing.assert_allclose(w.asnumpy(), 1.0 - 0.1 * gm * (step + 1),
                               rtol=1e-5)

kv._barrier()
kv.close()
print("worker %d OK" % rank)
"""


def test_dist_sync_sharded_servers(tmp_path):
    """Key-range sharding over TWO parameter servers (reference
    kvstore_dist.h:44 + MXNET_KVSTORE_BIGARRAY_BOUND splitting,
    docs/faq/distributed_training.md:50-53): big arrays flat-split one
    range per server; small keys hash to one; server-side optimizer runs
    per range."""
    from incubator_mxnet_tpu.dist.server import (ParameterServer,
                                                 register_with_root)

    n_workers = 2
    script = tmp_path / "worker.py"
    script.write_text(SHARDED_WORKER)
    root = ParameterServer(num_workers=n_workers, num_servers=2).start()
    second = ParameterServer(num_workers=n_workers, num_servers=2,
                             port=0).start()
    register_with_root("127.0.0.1", root.port, 1, "127.0.0.1", second.port)
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(root.port),
               DMLC_NUM_WORKER=str(n_workers),
               DMLC_NUM_SERVER="2",
               DMLC_ROLE="worker",
               MXNET_KVSTORE_COLLECTIVE="0",
               MXNET_KVSTORE_BIGARRAY_BOUND="16",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script)],
                              env=dict(env, DMLC_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in range(n_workers)]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    root.shutdown()
    second.shutdown()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"
        assert f"worker {r} OK" in out
    # both servers actually held key ranges of the big arrays
    assert "big" in root._state.store and "big" in second._state.store
    assert root._state.store["big"].size + \
        second._state.store["big"].size == 40


THREE_SERVER_WORKER = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert kv._num_servers == 3, kv._num_servers
assert len(kv._chans) == 3

# uneven key ranges: 40 elements over 3 servers -> bounds [0,13,26,40],
# slice sizes 13/13/14 — every boundary crossed inside one array
big = np.arange(40, dtype="f4").reshape(8, 5)
shards = kv._shards("big", 40)
sizes = [sl.stop - sl.start for _, sl in shards]
assert sizes == [13, 13, 14], sizes
assert [srv for srv, _ in shards] == [0, 1, 2]
kv.init("big", nd.array(big * 0))
kv.push("big", nd.array(big * (rank + 1)))
out = nd.zeros((8, 5))
kv.pull("big", out=out)
tot = sum(r + 1 for r in range(nw))
np.testing.assert_allclose(out.asnumpy(), big * tot)

# several small keys: hashed placement must stay within the server set
# and every round trip reassembles exactly
for i, shape in enumerate([(3,), (2, 2), (7,), (5,)]):
    k = "k%d" % i
    kv.init(k, nd.zeros(shape))
    kv.push(k, nd.ones(shape) * (rank + 1) * (i + 1))
    o = nd.zeros(shape)
    kv.pull(k, out=o)
    np.testing.assert_allclose(o.asnumpy(), tot * (i + 1))

# server-side optimizer over uneven ranges + state pull-back through the
# control channel (the checkpoint plane's dist resume path)
kv.init("w", nd.ones((40,)))
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                                  rescale_grad=1.0 / nw))
kv.push("w", nd.ones((40,)) * (rank + 1))
w = nd.zeros((40,))
kv.pull("w", out=w)
gm = tot / nw
np.testing.assert_allclose(w.asnumpy(), 1.0 - 0.1 * gm, rtol=1e-5)
blob = kv.get_optimizer_states(dump_optimizer=True)
import pickle
per_server = pickle.loads(blob)["dist_server_states"]
assert set(per_server) == {0, 1, 2}
# every server holds the momentum slots for exactly ITS range of "w"
sizes = []
for srv, s in sorted(per_server.items()):
    states = pickle.loads(s)
    states = states[0] if isinstance(states, tuple) else states
    mom = states["w"]
    sizes.append(int(mom.size))
assert sorted(sizes) == [13, 13, 14], sizes
# restore round-trips cleanly (rank 0 writes back, everyone barriers)
kv.set_optimizer_states(blob)

kv._barrier()
kv.close()
print("worker %d OK" % rank)
"""


def test_dist_sync_three_servers_uneven_ranges(tmp_path):
    """num_servers=3 with UNEVEN key ranges (40 elements -> 13/13/14), a
    big-array split crossing every server boundary, and server-side
    optimizer state pulled back through the control channel — the dist
    layout the elastic checkpoint resume path depends on."""
    from incubator_mxnet_tpu.dist.server import (ParameterServer,
                                                 register_with_root)

    n_workers = 2
    script = tmp_path / "worker3.py"
    script.write_text(THREE_SERVER_WORKER)
    root = ParameterServer(num_workers=n_workers, num_servers=3).start()
    secondaries = []
    for sid in (1, 2):
        srv = ParameterServer(num_workers=n_workers, num_servers=3,
                              port=0).start()
        register_with_root("127.0.0.1", root.port, sid, "127.0.0.1",
                           srv.port)
        secondaries.append(srv)
    env = dict(os.environ,
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(root.port),
               DMLC_NUM_WORKER=str(n_workers),
               DMLC_NUM_SERVER="3",
               DMLC_ROLE="worker",
               MXNET_KVSTORE_COLLECTIVE="0",
               MXNET_KVSTORE_BIGARRAY_BOUND="16",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, str(script)],
                              env=dict(env, DMLC_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for r in range(n_workers)]
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    root.shutdown()
    for srv in secondaries:
        srv.shutdown()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"
        assert f"worker {r} OK" in out
    # all three servers held a range of the big keys
    for key in ("big", "w"):
        sizes = sorted(s._state.store[key].size
                       for s in [root] + secondaries)
        assert sizes == [13, 13, 14], (key, sizes)


def test_dist_killed_server_surfaces_clean_error():
    """A killed secondary server must surface as a structured
    ServerLostError naming the server AND the keys it owned, not a raw
    socket traceback: run the secondary as a real subprocess and SIGKILL
    it mid-training."""
    from incubator_mxnet_tpu.resilience import ServerLostError
    from incubator_mxnet_tpu.dist.server import ParameterServer
    from incubator_mxnet_tpu.dist.kvstore_dist import KVStoreDist
    from incubator_mxnet_tpu import nd

    root = ParameterServer(num_workers=1, num_servers=2).start()
    env = dict(os.environ, DMLC_SERVER_ID="1",
               DMLC_PS_ROOT_URI="127.0.0.1",
               DMLC_PS_ROOT_PORT=str(root.port),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.dist.server"], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    old = {k: os.environ.get(k) for k in
           ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_RANK",
            "DMLC_NUM_WORKER", "DMLC_NUM_SERVER", "MXNET_KVSTORE_COLLECTIVE",
            "MXNET_KVSTORE_BIGARRAY_BOUND")}
    os.environ.update(DMLC_PS_ROOT_URI="127.0.0.1",
                      DMLC_PS_ROOT_PORT=str(root.port), DMLC_RANK="0",
                      DMLC_NUM_WORKER="1", DMLC_NUM_SERVER="2",
                      MXNET_KVSTORE_COLLECTIVE="0",
                      MXNET_KVSTORE_BIGARRAY_BOUND="16")
    try:
        kv = KVStoreDist("dist_sync")
        kv.init("w", nd.ones((30,)))
        kv.push("w", nd.ones((30,)))
        out = nd.zeros((30,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), 1.0)

        proc.kill()
        proc.wait(timeout=30)
        with pytest.raises(ServerLostError, match="parameter server 1 .* "
                                                  "is lost") as err:
            kv.push("w", nd.ones((30,)))
            kv.pull("w", out=out)
        assert err.value.server == 1
        assert "w" in err.value.keys
        kv.close()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if proc.poll() is None:
            proc.kill()
        root.shutdown()


def test_server_profiler_commands(tmp_path):
    """profiler.set_config/set_state/dump(profile_process='server') drive
    the parameter server's profiler over the control channel (reference
    set_kvstore_handle + MXKVStoreSendCommmandToServers)."""
    from incubator_mxnet_tpu.dist.server import ParameterServer
    from incubator_mxnet_tpu.dist.transport import Channel

    server = ParameterServer(num_workers=1).start()
    chan = Channel("127.0.0.1", server.port)
    try:
        out = str(tmp_path / "server_prof.json")
        r = chan.request({"cmd": "profiler", "action": "set_config",
                          "config": {"filename": out,
                                     "aggregate_stats": True}})
        assert r.get("ok"), r
        r = chan.request({"cmd": "profiler", "action": "dump"})
        assert r.get("ok"), r
        assert os.path.exists(out)
        r = chan.request({"cmd": "profiler", "action": "bogus"})
        assert "error" in r
    finally:
        chan.request({"cmd": "stop"})
        chan.close()
        server.shutdown()
        # the in-process test server shares this process's profiler
        # module: restore the global config for later tests
        from incubator_mxnet_tpu import profiler as _p
        _p.set_config(filename="profile.json", aggregate_stats=False)
        _p.set_kvstore_handle(None)
