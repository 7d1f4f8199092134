"""The gated delta rule's Pallas kernels on the REAL chip, at the published
widths and the size the benchmark times (2 sequences of 4,096 positions, 16
key and 32 value heads of 128, bfloat16 q, k, v, float32 g and beta):
against the scan driver of the same block algebra on the same chip, the
output and all five gradients.

Both drivers round the operands of their products to bfloat16 (the kernel
by its own cast, the scan by XLA's `Precision.DEFAULT`) and sum in float32;
the inverse's chain is bf16x3 in the kernel and `HIGHEST` in the scan, and
both are rounded to bfloat16 again where the inverse is an operand.  So the
two agree in nearly every entry, and a gap is one or two bfloat16 roundings
of a single entry of o, dq, dk or dv (themselves bfloat16) that fell the
other way.  Measured on the v5e (PR 31; largest difference over largest
entry, over the seeds of this file and of the builder's timing script): o
4.4e-5 to 2.9e-3, dq 0 to 2.3e-3, dk 1.7e-8 to 2.0e-3, dv 1.6e-4 to 1.3e-3,
dg 7.6e-7 to 1.1e-4, dbeta 8.9e-7 to 1.4e-4.  The limits leave two
roundings of the largest bfloat16 entry (2 x 2^-8) and ten times the
largest float32 reading.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.ops import delta_rule

B, T, HK, HV, D = 2, 4096, 16, 32, 128
NAMES = ("q", "k", "v", "g", "beta")
TOL = {"o": 8e-3, "q": 8e-3, "k": 8e-3, "v": 8e-3, "g": 1.5e-3, "beta": 1.5e-3}


def _inputs(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k = (jax.random.normal(ks[i], (B, T, HK, D)).astype(jnp.bfloat16)
            for i in (0, 1))
    v, ct = (jax.random.normal(ks[i], (B, T, HV, D)).astype(jnp.bfloat16)
             for i in (2, 3))
    a_log = jnp.log(jax.random.uniform(ks[4], (HV,), jnp.float32, 0.001, 16))
    g = -jnp.exp(a_log) * jax.nn.softplus(
        jax.random.normal(ks[5], (B, T, HV), jnp.float32) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (B, T, HV), jnp.float32))
    return (q, k, v, g, beta), ct


def _value_and_grad(ct):
    def loss(*args):
        o = delta_rule.gated_delta_rule(*args)
        return jnp.sum(o.astype(jnp.float32) * ct.astype(jnp.float32)), o
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)


def _gap(got, want):
    got, want = (np.asarray(x.astype(jnp.float32), np.float64)
                 for x in (got, want))
    assert np.all(np.isfinite(got))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("seed", [0, 1])
def test_kernel_against_the_scan_driver(seed, monkeypatch):
    args, ct = _inputs(seed)
    kernel, scan = (obs.counter("ops.delta_rule.lowered." + d)
                    for d in ("kernel", "scan"))
    before = kernel.value, scan.value
    (_, o), grads = jax.jit(_value_and_grad(ct))(*args)
    assert kernel.value > before[0] and scan.value == before[1]
    # the same entry point, told that it is not on a TPU while it traces
    before = kernel.value, scan.value
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    traced = jax.jit(_value_and_grad(ct)).lower(*args)
    monkeypatch.undo()
    assert scan.value > before[1] and kernel.value == before[0]
    assert "tpu_custom_call" not in traced.as_text()
    (_, want_o), want = traced.compile()(*args)
    gaps = {"o": _gap(o, want_o)}
    gaps.update({n: _gap(x, w) for n, x, w in zip(NAMES, grads, want)})
    print("gaps, kernel against scan:", gaps)
    assert all(gaps[n] <= TOL[n] for n in gaps), gaps
    for x, given in zip(grads, args):
        assert x.dtype == given.dtype and x.shape == given.shape


def test_value_and_grad_holds_the_kernels_and_no_scan():
    args, ct = _inputs(0)
    jaxpr = str(jax.make_jaxpr(_value_and_grad(ct))(*args))
    assert jaxpr.count("pallas_call") == 2, jaxpr.count("pallas_call")
    assert "scan[" not in jaxpr and "while[" not in jaxpr
    hlo = jax.jit(_value_and_grad(ct)).lower(*args).as_text()
    assert hlo.count("tpu_custom_call") == 2
