"""The routed experts' Pallas kernels on the REAL chip, at the published
widths and the sizes the benchmark times (`qwen3`: 8,192 tokens of hidden
size 2,048, 16 of 512 experts of intermediate size 512 held, 10 experts a
token, the softmax router; `lfm2`: 16,384 tokens, 8 of 64 experts of
intermediate size 1,536 held, 4 a token, the sigmoid router with its
selection bias, the backward kernel in two tiles of 768; bfloat16): against
the XLA driver of the same block algebra over the same
plan on the same chip, the output and all five gradients.

Both drivers take bfloat16 operands and sum in float32, round the
pre-activations g and u, the hidden rows and the output rows to bfloat16 at
the same places, and share the plan, the gathers, the combine and the
routing's transpose; they differ in the order of the float32 sums inside a
product (Mosaic's tiles against XLA's) and in where a weight gradient is
summed over an expert's blocks (float32 in VMEM against a segment sum of
float32 slabs).  So they agree in nearly every entry, and a gap is one or
two bfloat16 roundings of a single entry that fell the other way.  The
limits leave two roundings of the largest bfloat16 entry (2 x 2^-8); the
router's gradient sums 8,192 tokens in float32 and gets the same.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.ops import experts

C = 2048
# tokens, intermediate, experts, held, a token, capacity, router
# parameters, and by how much four held experts' router rows are scaled
SIZES = {"qwen3": (8192, 512, 512, 16, 10, 5120, {}, 1.3),
         "lfm2": (16384, 1536, 64, 8, 4, 16384,
                  {"scoring": "sigmoid", "norm_eps": 1e-6}, 1.5)}
NAMES = ("x", "router", "gate", "up", "down")
TOL = 8e-3


def _inputs(seed, size="qwen3"):
    N, INTER, NUM, HELD = SIZES[size][:4]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def draw(key, *shape, scale=1.0):
        return (scale * jax.random.normal(key, shape)).astype(jnp.bfloat16)
    router = draw(ks[1], NUM, C, scale=0.02)
    # an uneven load, as the cell's Zipf ids make it: four of the experts
    # held are drawn two to three times as often (their rows scaled)
    router = router.at[:4].multiply(SIZES[size][7])
    return (draw(ks[0], 2, N // 2, C), router,
            draw(ks[2], HELD, INTER, C, scale=0.02),
            draw(ks[3], HELD, INTER, C, scale=0.02),
            draw(ks[4], HELD, C, INTER, scale=0.02)), \
        draw(ks[5], 2, N // 2, C)


def _value_and_grad(ct, size="qwen3"):
    NUM, _, TOPK, _, router = SIZES[size][2:7]
    if router:      # the selection bias: a fixed buffer, no gradient
        router = dict(router, bias=0.01 * jax.random.normal(
            jax.random.PRNGKey(5), (NUM,)))

    def loss(*args):
        out, counts, dropped = experts.routed_experts(*args, NUM, TOPK, 0,
                                                      **router)
        return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32)), \
            (out, counts, dropped)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)


def _gap(got, want):
    got, want = (np.asarray(x.astype(jnp.float32), np.float64)
                 for x in (got, want))
    assert np.all(np.isfinite(got))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("seed,size", [(0, "qwen3"), (1, "qwen3"),
                                       (0, "lfm2"), (1, "lfm2")])
def test_kernel_against_the_xla_driver(seed, size, monkeypatch):
    args, ct = _inputs(seed, size)
    _value_and_grad = functools.partial(globals()["_value_and_grad"],
                                        size=size)
    kernel, xla = (obs.counter("ops.experts.lowered." + d)
                   for d in ("kernel", "xla"))
    before = kernel.value, xla.value
    (_, (out, counts, dropped)), grads = jax.jit(_value_and_grad(ct))(*args)
    assert kernel.value > before[0] and xla.value == before[1]
    # the same entry point, told that it is not on a TPU while it traces
    before = kernel.value, xla.value
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    traced = jax.jit(_value_and_grad(ct)).lower(*args)
    monkeypatch.undo()
    assert xla.value > before[1] and kernel.value == before[0]
    assert "tpu_custom_call" not in traced.as_text()
    (_, (want_out, want_counts, _)), want = traced.compile()(*args)
    # the grouped form, every assignment in a row, an uneven load
    assert int(dropped) == 0 and int(counts.sum()) <= SIZES[size][5]
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(counts.max()) > 2 * int(counts.min())
    gaps = {"out": _gap(out, want_out)}
    gaps.update({n: _gap(x, w) for n, x, w in zip(NAMES, grads, want)})
    print("gaps, kernel against XLA:", gaps)
    assert all(gap <= TOL for gap in gaps.values()), gaps
    for x, given in zip(grads, args):
        assert x.dtype == given.dtype and x.shape == given.shape


def test_value_and_grad_holds_the_kernels_and_no_third_forward():
    args, ct = _inputs(0)
    jaxpr = str(jax.make_jaxpr(_value_and_grad(ct))(*args))
    # the product and its combine, each pass
    assert jaxpr.count("pallas_call") == 4, jaxpr.count("pallas_call")
    hlo = jax.jit(_value_and_grad(ct)).lower(*args).as_text()
    assert hlo.count("tpu_custom_call") == 4
