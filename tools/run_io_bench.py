#!/usr/bin/env python
"""Data-plane bench & CI gate (BENCH_IO.json).

Measures the production io tier (io_plane.py h2d staging ring +
per-host sharded readers + uint8-on-the-wire) and gates it:

1. **h2d probe** — host memcpy bandwidth (the physical ceiling), the
   BLOCKING ``device_put`` baseline (what the pre-ring loop paid), and
   the PIPELINED staging-ring rate (transfers on the ``mx-io-h2d`` thread, the
   consumer pops device-resident batches).
2. **real vs synthetic** — the same convnet (uint8 NHWC in, in-graph
   `ImageNormalize` head) trained from an in-memory iterator vs the
   full RecordIO decode pipeline; real-data steady img/s must be
   ≥ 0.98x synthetic (the pipeline hides behind compute).
3. **zero steady recompiles** — the unified program cache's compile
   counter must not move across the measurement window with the ring
   enabled (the ring's staged batches keep the dispatch signature
   fixed).
4. **tsan sweep** — a throwaway subprocess drives the ring + decode
   pool + a mini fit under ``MXNET_TSAN=1``; the dump must hold zero
   findings (the new ``mx-io-*`` threads are race/lock-order clean).

Gates (BENCH_IO.json `gates`):
  pipelined_h2d_10x_baseline   pipelined ≥ 10 × 13.8 MB/s
  pipelined_within_10x_memcpy  pipelined × 10 ≥ memcpy probe
  real_ge_098x_synthetic       real img/s ≥ 0.98 × synthetic img/s
  zero_steady_recompiles       no compiles inside the steady window
  tsan_clean                   zero sanitizer findings

Exit code 0 iff every gate passes.  ``--quick`` shrinks the model and
windows for the run_tpu_parity `io` stage.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the fixed blocking-h2d figure the 10x gate was first set against
BASELINE_BLOCKING_MBPS = 13.8

MEAN = (123.68, 116.78, 103.94)
STD = (58.4, 57.1, 57.4)


from bench_io import h2d_probe  # noqa: E402  (the shared probe)


def _convnet(dtype="float32"):
    """uint8-NHWC-in convnet with the in-graph normalize head — the
    uint8-on-the-wire shape both lanes train."""
    import incubator_mxnet_tpu as mx
    data = mx.sym.Variable("data")
    x = mx.sym.ImageNormalize(data, mean=MEAN, std=STD,
                              input_layout="NHWC", output_layout="NCHW",
                              dtype=dtype)
    x = mx.sym.Convolution(x, num_filter=16, kernel=(3, 3), pad=(1, 1),
                           name="conv0")
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    x = mx.sym.Convolution(x, num_filter=32, kernel=(3, 3), pad=(1, 1),
                           name="conv1")
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.Flatten(x)
    x = mx.sym.FullyConnected(x, num_hidden=16, name="fc0")
    return mx.sym.SoftmaxOutput(x, name="softmax")


class _Probe:
    """Batch callback: steady img/s over [warm, warm+steps) plus the
    program-cache compile counter at the window edges."""

    def __init__(self, warm, steps, batch):
        self.warm, self.steps, self.batch = warm, steps, batch
        self.t0 = None
        self.img_s = None
        self.compiles = None

    @staticmethod
    def _compile_count():
        from incubator_mxnet_tpu import compile as _compile
        try:
            return int(_compile.stats()["counters"]["compiles"])
        except Exception:
            return -1

    def __call__(self, param):
        if param.nbatch == self.warm:
            param.eval_metric.get()     # sync the window edge
            self.t0 = time.perf_counter()
            self._c0 = self._compile_count()
        elif param.nbatch == self.warm + self.steps:
            param.eval_metric.get()
            dt = time.perf_counter() - self.t0
            self.img_s = self.batch * self.steps / dt
            self.compiles = self._compile_count() - self._c0


def _fit(mod_sym, it, batch, warm, steps):
    import incubator_mxnet_tpu as mx
    mx.random.seed(0)
    mod = mx.mod.Module(mod_sym, context=mx.cpu(),
                        label_names=("softmax_label",))
    probe = _Probe(warm, steps, batch)
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            eval_metric="acc",
            initializer=mx.initializer.Xavier(),
            batch_end_callback=probe, kvstore=None)
    assert probe.img_s is not None, "probe window missed (too few batches)"
    return probe


def train_lanes(batch, image, warm, steps):
    """Synthetic (in-memory uint8 batches) vs real (RecordIO decode
    pipeline) img/s on the identical model + signature."""
    import incubator_mxnet_tpu as mx
    from bench_io import build_corpus
    n = batch * (warm + steps + 9)   # one block past the window, no tail
    rng = np.random.RandomState(0)
    sym = _convnet()

    data = rng.randint(0, 255, (n, image, image, 3)).astype(np.uint8)
    labels = rng.randint(0, 16, n).astype("f4")
    synth_it = mx.io.NDArrayIter(data, labels, batch_size=batch,
                                 label_name="softmax_label")
    synth = _fit(sym, synth_it, batch, warm, steps)

    d = tempfile.mkdtemp(prefix="bench_io_")
    rec = os.path.join(d, "corpus.rec")
    build_corpus(rec, n=n, size=image + 8)
    real_it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, image, image), batch_size=batch,
        rand_crop=True, rand_mirror=True,
        mean_r=MEAN[0], mean_g=MEAN[1], mean_b=MEAN[2],
        std_r=STD[0], std_g=STD[1], std_b=STD[2],
        preprocess_threads=4, label_width=1, device_augment="auto")
    real = _fit(sym, real_it, batch, warm, steps)
    real_it.close()

    from incubator_mxnet_tpu import io_plane
    io_stats = io_plane.stats()
    return {
        "synthetic_img_s": round(synth.img_s, 2),
        "real_img_s": round(real.img_s, 2),
        "real_vs_synthetic": round(real.img_s / synth.img_s, 4),
        "steady_recompiles": {"synthetic": synth.compiles,
                              "real": real.compiles},
        "ring": {k: round(v, 4) if isinstance(v, float) else v
                 for k, v in io_stats.items()},
    }


_TSAN_CHILD = """
import numpy as np
import incubator_mxnet_tpu as mx
rng = np.random.RandomState(0)
n, b = 64, 8
it = mx.io.NDArrayIter(rng.randn(n, 12).astype('f4'),
                       rng.randint(0, 4, n).astype('f4'), batch_size=b)
data = mx.sym.Variable('data')
x = mx.sym.FullyConnected(data, num_hidden=16, name='fc0')
x = mx.sym.Activation(x, act_type='relu')
x = mx.sym.FullyConnected(x, num_hidden=4, name='fc1')
sym = mx.sym.SoftmaxOutput(x, name='softmax')
mod = mx.mod.Module(sym, context=mx.cpu())
mod.fit(it, num_epoch=2, optimizer='sgd', eval_metric='acc',
        initializer=mx.initializer.Xavier(), kvstore=None)
"""


def tsan_sweep():
    """Drive the ring + a mini fit in a throwaway process under
    MXNET_TSAN=1; zero findings in the dump = clean."""
    log = os.path.join(tempfile.mkdtemp(prefix="io_tsan_"), "tsan.json")
    env = dict(os.environ, MXNET_TSAN="1", MXNET_TSAN_LOG=log,
               JAX_PLATFORMS="cpu", MXNET_IO_RING="1")
    proc = subprocess.run([sys.executable, "-c", _TSAN_CHILD], cwd=REPO,
                          capture_output=True, text=True, timeout=600,
                          env=env)
    out = {"rc": proc.returncode}
    try:
        with open(log) as f:
            dumps = [json.loads(ln) for ln in f.read().splitlines()
                     if ln.strip()]
        found = [fi for dmp in dumps for fi in dmp.get("findings", [])]
        out["findings"] = len(found)
        out["detail"] = [
            {k: fi.get(k) for k in ("code", "severity", "location")}
            for fi in found][:20]
    except Exception as exc:
        out["findings"] = None
        out["dump_error"] = repr(exc)
    if proc.returncode != 0:
        out["tail"] = proc.stderr.strip()[-500:]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small model + short windows (CI stage)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_IO.json"))
    args = ap.parse_args()

    if args.quick:
        batch, image, warm, steps = 16, 48, 8, 24
        probe_batch, probe_image = 32, 128
    else:
        batch, image, warm, steps = 32, 64, 8, 48
        probe_batch, probe_image = 64, 224

    t0 = time.time()
    result = {"quick": bool(args.quick),
              "baseline_blocking_MBps": BASELINE_BLOCKING_MBPS}
    result["h2d"] = h2d_probe(probe_batch, probe_image)
    result["train"] = train_lanes(batch, image, warm, steps)
    result["tsan"] = tsan_sweep()

    h2d = result["h2d"]
    tr = result["train"]
    gates = {
        "pipelined_h2d_10x_baseline":
            h2d["pipelined_MBps"] >= 10 * BASELINE_BLOCKING_MBPS,
        "pipelined_within_10x_memcpy":
            h2d["pipelined_MBps"] * 10 >= h2d["memcpy_MBps"],
        "real_ge_098x_synthetic": tr["real_vs_synthetic"] >= 0.98,
        "zero_steady_recompiles":
            tr["steady_recompiles"]["synthetic"] == 0 and
            tr["steady_recompiles"]["real"] == 0,
        "tsan_clean": result["tsan"].get("rc") == 0 and
            result["tsan"].get("findings") == 0,
    }
    result["gates"] = gates
    result["passed"] = all(gates.values())
    result["duration_s"] = round(time.time() - t0, 1)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    if args.json:
        print(json.dumps(result))
    else:
        print(json.dumps(result, indent=1))
    print("artifact:", args.out, file=sys.stderr)
    return 0 if result["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
