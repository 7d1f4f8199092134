"""The refusals that keep a CPU from passing for the chip.

`chip_smoke.py` fails without a TPU (and only `--cpu-dry-run`, chosen by
name, runs it here) and so does `benchmark/run.py`; `mx.tpu()` is a promise
of an accelerator; a device error in a selected fused step is raised, not
replaced by another path; the compile cache is placed by the environment
variable when there is one; the README names no file that is gone.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import io, nd
from incubator_mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, drop=()):
    e = dict(os.environ, **(env or {}))
    for k in drop:
        e.pop(k, None)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_a_chip():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform is 'cpu', need tpu" in r.stderr
    # it says what it found, and prints no result
    assert "platform cpu" in r.stdout
    assert '"ok"' not in r.stdout


def test_chip_smoke_cpu_dry_run_is_stamped():
    r = _run(["chip_smoke.py", "--cpu-dry-run", "--model", "mlp"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the driver's contract: exactly these keys
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    # everything else the run found is on the summary line before it
    tag = "[smoke] summary: "
    assert lines[-2].startswith(tag)
    summary = json.loads(lines[-2][len(tag):])
    assert summary["dry_run"] is True and summary["claim"] is None
    assert set(summary["stages"]) == {"train", "serve"}


def test_benchmark_refuses_the_cpu():
    r = _run(["benchmark/run.py", "--workload", "lstm_ptb_train",
              "--seed", "1", "--seconds", "1"],
             env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2
    assert "needs a TPU chip" in r.stderr
    assert r.stdout.strip() == ""                # no result line


def test_readme_names_only_files_that_exist():
    """A `tools/x.py`, a bare `x.py` and a root `X.json` that README.md
    names in backticks is in the tree: the path as written, a bare name at
    the root, under tools/ or in the package, an artifact at the root or
    among those .gitignore says a run leaves behind."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        spans = re.findall(r"`([^`\n]+)`", f.read())
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored = set(f.read().split())
    here = set(os.listdir(REPO)) | ignored
    for root in ("tools", "incubator_mxnet_tpu"):
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, root)):
            here.update(files)
    named = set()
    for span in spans:
        named.update(re.findall(
            r"(?<![\w/.<*{-])(tools/\w+\.py|\w+\.py|[A-Z][A-Z0-9_]*\.json)"
            r"(?![\w.])", span))
    assert "tools/mxlint.py" in named and "BENCHMARK.json" in named
    missing = sorted(n for n in named if not (
        os.path.exists(os.path.join(REPO, n)) if "/" in n else n in here))
    assert not missing, f"README.md names files that are gone: {missing}"


def test_tpu_context_needs_an_accelerator_unless_cpu_was_named():
    """JAX that merely CAME UP on the CPU (libtpu found no chip) is not a
    platform chosen by name: mx.tpu() must raise there."""
    code = ("import jax, incubator_mxnet_tpu as mx\n"
            "if jax.default_backend() != 'cpu':\n"
            "    print('HAS_ACCELERATOR')\n"
            "else:\n"
            "    print(mx.cpu().jax_device.platform)\n"
            "    try:\n"
            "        mx.tpu().jax_device\n"
            "    except mx.base.MXNetError as e:\n"
            "        print('RAISED', e)\n")
    r = _run(["-c", code], drop=("JAX_PLATFORMS",))
    assert r.returncode == 0, r.stderr[-2000:]
    if "HAS_ACCELERATOR" not in r.stdout:
        assert "RAISED" in r.stdout and "JAX found none" in r.stdout


def test_accelerator_device_id_does_not_wrap():
    assert mx.tpu(7).jax_device.id == 7          # the 8-device CPU mesh
    with pytest.raises(MXNetError, match=r"tpu\(99\) does not exist"):
        mx.tpu(99).jax_device
    with pytest.raises(MXNetError, match=r"gpu\(8\) does not exist"):
        mx.gpu(8).jax_device


def _fit_ready_module():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = io.DataBatch([nd.array(np.ones((4, 6), "f4"))],
                         [nd.array(np.zeros(4, "f4"))])
    return mod, batch


def test_device_error_in_selected_fused_step_is_raised(monkeypatch):
    import jax

    def refuse(*args):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: injected by the test")

    def two_good_steps():
        mx.random.seed(3)
        mod, batch = _fit_ready_module()
        metric = mx.metric.create("acc")
        mod.fit_step(batch, metric)
        mod.fit_step(batch, metric)   # steady: its write-back is deferred
        return mod, batch, metric

    ref, _, _ = two_good_steps()
    want = ref.get_params()[0]["fc1_weight"].asnumpy()

    mod, batch, metric = two_good_steps()
    unfused = []
    monkeypatch.setattr(mod, "forward_backward",
                        lambda b: unfused.append(b))
    monkeypatch.setattr(mod._fused_step, "_jit", refuse)
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED") as ei:
        mod.fit_step(batch, metric)
    assert isinstance(ei.value.__cause__, jax.errors.JaxRuntimeError)
    assert not unfused, "the step must not be replaced by another path"
    assert mod._optimizer.num_update == 2, "the failed step must not count"
    # the pending results of the good steps survive the failure
    np.testing.assert_array_equal(
        mod.get_params()[0]["fc1_weight"].asnumpy(), want)


def test_mesh_error_while_tracing_the_pod_step_is_raised(monkeypatch):
    """The pod `shard_map` wrap runs inside the framework trace.  A mesh or
    sharding error there is the selected step failing, not a step that
    "cannot trace": it is raised, and nothing runs in its place."""
    import jax

    monkeypatch.setenv("MXNET_POD_SPMD", "1")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.tpu(i) for i in range(8)])
    mod.bind(data_shapes=[("data", (16, 6))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert mod._fused_step._pod_axis is not None, "pod path must be selected"
    batch = io.DataBatch([nd.array(np.ones((16, 6), "f4"))],
                         [nd.array(np.zeros(16, "f4"))])

    def refuse(*args, **kwargs):
        raise ValueError("Mesh for all inputs should be equal: injected")

    unfused = []
    monkeypatch.setattr(mod, "forward_backward", lambda b: unfused.append(b))
    monkeypatch.setattr(jax, "shard_map", refuse)
    with pytest.raises(MXNetError, match="failed to trace.*Mesh for all") \
            as ei:
        mod.fit_step(batch, mx.metric.create("acc"))
    assert isinstance(ei.value.__cause__, ValueError)
    assert not unfused, "the step must not be replaced by another path"
    assert mod._optimizer.num_update == 0, "the failed step must not count"


def test_untraceable_optimizer_still_selects_the_unfused_path():
    """Selection from what the code can observe stays: an optimizer whose
    update cannot trace was never eligible for the fused step."""

    @mx.optimizer.register
    class HostRngSgd(mx.optimizer.Optimizer):
        def create_state(self, index, weight):
            return None

        def update(self, index, weight, grad, state):
            self._update_count(index)
            noise = mx.random.next_key()   # host RNG: blocked under trace
            del noise
            weight -= self._get_lr(index) * grad

    try:
        mod, batch = _fit_ready_module()
        mod.init_optimizer(optimizer=HostRngSgd(learning_rate=0.1),
                           force_init=True)
        w0 = mod.get_params()[0]["fc1_weight"].asnumpy()
        mod.fit_step(batch, mx.metric.create("acc"))
        assert mod._fused_step.broken
        assert not np.array_equal(
            mod.get_params()[0]["fc1_weight"].asnumpy(), w0)
    finally:
        del mx.optimizer.Optimizer.opt_registry["hostrngsgd"]


@pytest.mark.parametrize("placed", ["/x", None])
def test_compile_cache_is_placed_from_outside(placed):
    code = ("import jax, incubator_mxnet_tpu as mx\n"
            "(mx.nd.ones((2,)) + 1).asnumpy()\n"
            "print('DIR=' + str(jax.config.jax_compilation_cache_dir))\n")
    if placed:
        r = _run(["-c", code], env={"JAX_COMPILATION_CACHE_DIR": placed})
        want = placed
    else:
        r = _run(["-c", code], drop=("JAX_COMPILATION_CACHE_DIR",))
        want = os.path.join(REPO, ".jax_cache")
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR={want}" in r.stdout.splitlines()


def test_one_call_site_sets_the_compile_cache_dir():
    setters = []
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs if x not in ("tests", "chiprun_out")
                   and not x.startswith(".")]
        for f in files:
            path = os.path.join(d, f)
            if f.endswith(".py") and \
                    '"jax_compilation_cache_dir"' in open(path).read():
                setters.append(os.path.relpath(path, REPO))
    assert setters == ["incubator_mxnet_tpu/compile/__init__.py"]
