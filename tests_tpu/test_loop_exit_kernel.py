"""The looped LM's heads and exit objective (`LoopExitOutput`) as Pallas
kernels (ops/loop_exit.py) on the REAL chip, at the shapes of
`ouro_loop_train_hostfed` (4 recurrent steps of 2 x 4,096 rows, states of
2,048, 49,152 ids, bfloat16): the last step's probabilities, the exit
counts and the four gradients against XLA's form of the same operator
(`loop_exit.driver` steered to "xla") on the same chip.

Both sides round the same values to bfloat16 (the probabilities, dz, dh,
dW): they differ by the order of float32 sums and by exp(z - lse) against
XLA's softmax, so an entry may round to the neighbouring bfloat16.  Each
bfloat16 output is held to two roundings of its largest entry (2 x 2^-8);
the exit counts, made of the gates alone by the same code on both sides,
to float32's rounding.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.ops import loop_exit, registry

STEPS, ROWS, C, V = 4, 8192, 2048, 49152
ROUNDINGS = 2 * 2.0 ** -8


def _inputs(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (STEPS, ROWS, C)).astype(jnp.bfloat16)
    w = (0.02 * jax.random.normal(ks[1], (V, C))).astype(jnp.bfloat16)
    gw = (0.02 * jax.random.normal(ks[2], (1, C))).astype(jnp.bfloat16)
    gb = jnp.zeros((1,), jnp.bfloat16)
    y = jax.random.randint(ks[3], (ROWS,), 0, V).astype(jnp.float32)
    return (h, w, gw, gb), y


def _step():
    op = registry.get("LoopExitOutput")
    params = op.canonicalize_params({"beta": 0.1})
    params["_train"] = True

    def step(h, w, gw, gb, y, stats):
        (out, new), vjp = jax.vjp(
            lambda *a: op.fn(dict(params), *a, y, stats), h, w, gw, gb)
        return out, new, vjp((jnp.zeros_like(out), jnp.zeros_like(new)))
    return jax.jit(step)


def _gap(got, want):
    got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (got, want))
    assert np.all(np.isfinite(got))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _ms(fn, args, calls=5):
    jax.block_until_ready(fn(*args))
    tic = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - tic) / calls * 1e3


def test_kernels_against_xlas_form(monkeypatch):
    args, y = _inputs(41)
    stats = jnp.zeros((STEPS + 3,), jnp.float32)
    kernel, xla = (obs.counter("ops.loop_exit.lowered." + d)
                   for d in ("kernel", "xla"))
    before = kernel.value, xla.value
    run = _step()
    text = run.lower(*args, y, stats).compile().as_text()
    # the losses and the probabilities forward, one call a step and a
    # superblock of rows backward
    tiles = loop_exit._tiles(ROWS, C, V, 2)
    assert text.count("tpu_custom_call") == \
        2 + STEPS * ROWS // tiles.superblock
    assert kernel.value > before[0] and xla.value == before[1]
    got = run(*args, y, stats)
    ms = _ms(run, (*args, y, stats))

    monkeypatch.setattr(loop_exit, "driver", lambda *a: ("xla", None))
    xla_run = _step()
    want = xla_run(*args, y, stats)
    xla_ms = _ms(xla_run, (*args, y, stats))
    assert xla.value > before[1]

    out, new, grads = got
    want_out, want_new, want_grads = want
    assert out.dtype == jnp.bfloat16 and out.shape == (ROWS, V)
    gaps = {"probs": _gap(out, want_out), "exit_stats": _gap(new, want_new)}
    for name, g, w, given in zip(("dh", "dw", "dgw", "dgb"), grads,
                                 want_grads, args):
        assert g.dtype == given.dtype and g.shape == given.shape
        gaps[name] = _gap(g, w)
    print("gaps to XLA's form", gaps, "ms a step: kernels", ms, "XLA", xla_ms)
    for name in ("probs", "dh", "dw", "dgw", "dgb"):
        assert gaps[name] <= ROUNDINGS, (name, gaps)
    assert gaps["exit_stats"] <= 1e-6, gaps


@pytest.mark.parametrize("rows,vocab", [(1024, 512), (512, 512)])
def test_forward_kernels_alone(rows, vocab):
    """The losses and log-sum-exp of every step and the last step's
    probabilities, at two tilings: the same to float32's rounding."""
    (h, w, _, _), y = _inputs(7)
    yi = y.astype(jnp.int32)
    probs, loss, lse = loop_exit.forward(h, w, yi, rows=rows, vocab=vocab)
    z = jnp.einsum("nc,vc->nv", h[-1], w, preferred_element_type=jnp.float32)
    want_lse = jax.nn.logsumexp(z, axis=-1)
    assert _gap(lse[-1], want_lse) < 1e-5
    assert _gap(loss[-1], want_lse - jnp.take_along_axis(
        z, yi[:, None], axis=-1)[:, 0]) < 1e-5
    assert _gap(probs, jax.nn.softmax(z, axis=-1).astype(jnp.bfloat16)) <= \
        ROUNDINGS
