"""The looped LM's heads and exit objective (`LoopExitOutput`) as Pallas
kernels (ops/loop_exit.py): the kernels' arithmetic interpreted on the CPU
against XLA's form of the same operator, in float32 and bfloat16, at tiny
shapes and several tilings (one and several row blocks, vocabulary tiles
and superblocks); `loop_exit.driver`'s choice from platform and shapes
with its counters.  tests/test_ouro.py compiles the kernels for the described v5e at
the cell's size; tests_tpu/test_loop_exit_kernel.py runs them on the chip.

Tolerances.  In float32 the two forms differ in the order of their float32
sums only (tiles of the vocabulary, exp(z - lse) against a softmax): 2e-5
of the largest entry for values, 1e-4 for gradients (tests/test_ouro.py's).
In bfloat16 both round the same float32 values to bfloat16 (the
probabilities, dz, dh, dW), so an entry may round to its neighbour: two
roundings of the largest entry, 2 x 2^-8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import loop_exit, registry

T, ROWS, C, V = 3, 256, 128, 384
ROUNDINGS = 2 * 2.0 ** -8
# (vocabulary tile, forward rows, backward rows, superblock)
TILINGS = {
    "from_the_shapes": None,
    "every_block_small": loop_exit.Tiles(128, 128, 128, 128),
    "two_blocks_a_superblock": loop_exit.Tiles(128, 256, 128, 256),
}


def _inputs(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (T, ROWS, C))
    w = jax.random.normal(ks[1], (V, C)) * 0.3
    gw = jax.random.normal(ks[2], (1, C)) * 0.3
    gb = jax.random.normal(ks[3], (1,))
    y = jax.random.randint(ks[4], (ROWS,), 0, V).astype(jnp.float32)
    return tuple(a.astype(dtype) for a in (h, w, gw, gb)), y


def _step(h, w, gw, gb, y):
    """The training call: (probabilities, exit_stats after, the gradients
    of states, head weight, gate weight and gate bias)."""
    op = registry.get("LoopExitOutput")
    params = op.canonicalize_params({"beta": 0.1})
    params["_train"] = True
    stats = jnp.arange(T + 3, dtype=jnp.float32)
    (out, new), vjp = jax.vjp(
        lambda *a: op.fn(dict(params), *a, y, stats), h, w, gw, gb)
    return out, new - stats, vjp((jnp.ones_like(out), jnp.zeros_like(new)))


def _gap(got, want):
    got, want = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                 for a in (got, want))
    assert got.shape == want.shape and np.all(np.isfinite(got))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def _counts():
    return tuple(mx.obs.counter("ops.loop_exit.lowered." + d).value
                 for d in ("kernel", "xla"))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tiling", sorted(TILINGS))
def test_kernels_interpreted_against_xlas_form(monkeypatch, dtype, tiling):
    """The probabilities, the exit counts and the four gradients, both
    forms of the operator; and the kernels' step losses and log-sum-exp
    against the logits written out."""
    args, y = _inputs(jnp.dtype(dtype))
    want = _step(*args, y)
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    tiles = TILINGS[tiling]
    if tiles is not None:
        monkeypatch.setattr(loop_exit, "_tiles", lambda *a: tiles)
    before = _counts()
    got = _step(*args, y)
    assert _counts() == (before[0] + 1, before[1])
    value_tol, grad_tol = (2e-5, 1e-4) if dtype == "float32" else \
        (ROUNDINGS, ROUNDINGS)
    assert got[0].dtype == args[0].dtype
    assert _gap(got[0], want[0]) <= value_tol
    assert _gap(got[1], want[1]) <= 1e-6
    for name, g, w, given in zip(("dh", "dw", "dgw", "dgb"), got[2],
                                 want[2], args):
        assert g.dtype == given.dtype and g.shape == given.shape, name
        assert _gap(g, w) <= grad_tol, (name, _gap(g, w))

    h, w = args[:2]
    tiles = loop_exit._tiles(ROWS, C, V, h.dtype.itemsize)
    probs, loss, lse = loop_exit.forward(
        h, w, y.astype(jnp.int32), rows=tiles.rows, vocab=tiles.vocab,
        interpret=True)
    z = jnp.einsum("trc,vc->trv", h, w, preferred_element_type=jnp.float32)
    want_lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(
        z, jnp.broadcast_to(y.astype(jnp.int32)[None, :, None],
                            (T, ROWS, 1)), axis=-1)[..., 0]
    assert _gap(lse, want_lse) <= 2e-5
    assert _gap(loss, want_lse - picked) <= 2e-5
    assert _gap(probs, jax.nn.softmax(z[-1], axis=-1)) <= value_tol


def test_no_float32_logits_outside_the_kernels(interpreted):
    """Read from the jaxpr of the value and the gradient, outside the
    kernels' bodies: no float32 value of a step's (rows, V) logits, where
    XLA's form holds one (the reading can see)."""
    args, y = _inputs(jnp.bfloat16)

    def f32_logits(jaxpr):
        return [v.aval for eqn in jaxpr.eqns for v in eqn.outvars
                if getattr(v.aval, "dtype", None) == jnp.float32 and
                getattr(v.aval, "size", 0) >= ROWS * V] + \
            [a for eqn in jaxpr.eqns if eqn.primitive.name != "pallas_call"
             for p in eqn.params.values()
             for sub in (p if isinstance(p, (tuple, list)) else (p,))
             if hasattr(getattr(sub, "jaxpr", sub), "eqns")
             for a in f32_logits(getattr(sub, "jaxpr", sub))]
    kernels = jax.make_jaxpr(lambda *a: _step(*a, y))(*args).jaxpr
    assert "pallas_call" in str(kernels)
    assert f32_logits(kernels) == []
    with pytest.MonkeyPatch.context() as m:
        m.delenv("MXNET_FLASH_INTERPRET")
        xla = jax.make_jaxpr(lambda *a: _step(*a, y))(*args).jaxpr
    assert f32_logits(xla)


def test_backward_traces_its_kernel_twice(interpreted, monkeypatch):
    """The backward's calls, one a step and a superblock of rows (here
    3 x 3), share two traces of the kernel: the first call's, which starts
    dW, and the others'.  A trace is set-up time."""
    traced = []
    kernel = loop_exit._grads_kernel

    def counting(*refs, **kw):
        traced.append(kw["first"])
        return kernel(*refs, **kw)
    monkeypatch.setattr(loop_exit, "_grads_kernel", counting)
    monkeypatch.setattr(loop_exit, "_tiles",
                        lambda *a: loop_exit.Tiles(128, 128, 128, 128))
    rows = 384          # a shape no other test traces: no cached trace
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    h = jax.random.normal(ks[0], (T, rows, C))
    w = jax.random.normal(ks[1], (V, C))
    y = jnp.arange(rows, dtype=jnp.float32) % V
    jaxpr = str(jax.make_jaxpr(lambda *a: _step(*a, y))(
        h, w, jnp.ones((1, C)), jnp.zeros((1,))))
    assert jaxpr.count("name=_grads_call") == T * 3
    assert sorted(traced) == [False, True]


@pytest.mark.parametrize("why,shape", [
    ("a vocabulary off the lanes", (256, 128, 400)),
    ("rows off the lanes", (250, 128, 384)),
    ("states off the lanes", (256, 96, 384)),
])
def test_shapes_the_kernels_do_not_hold_take_xlas_form(interpreted, why,
                                                      shape):
    """No masked tail: a vocabulary, rows or states that are not whole lane
    tiles take XLA's form, counted `.xla`."""
    rows, c, v = shape
    assert loop_exit._tiles(rows, c, v, 4) is None
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(ks[0], (T, rows, c))
    w = jax.random.normal(ks[1], (v, c))
    y = jnp.arange(rows, dtype=jnp.float32) % v
    before = _counts()
    jaxpr = str(jax.make_jaxpr(lambda *a: _step(*a, y))(
        h, w, jnp.ones((1, c)), jnp.zeros((1,))))
    assert _counts() == (before[0], before[1] + 1)
    assert "pallas_call" not in jaxpr


@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", False, "xla"), ("cpu", True, "interpret"),
    ("tpu", False, "kernel"), ("gpu", False, "xla")])
def test_driver_follows_platform_and_shapes(monkeypatch, backend, interpret,
                                            want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret:
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    # `ouro_loop_train_hostfed`'s shapes, and the tests'
    assert loop_exit.driver(8192, 2048, 49152, 2)[0] == want
    assert loop_exit.driver(ROWS, C, V, 4)[0] == want
    assert loop_exit.driver(8192, 2048, 49150, 2) == ("xla", None)
    if want != "xla":
        assert loop_exit.driver(ROWS, C, V, 4)[1] == \
            loop_exit._tiles(ROWS, C, V, 4)


def test_tiles_follow_the_shapes():
    """The widest vocabulary tile that divides V; a forward grid step of
    1,024 rows; backward 512 rows beside the largest superblock of float32
    dh that `VMEM_BYTES` holds (at the cell's size, with tiles of 1,024
    ids, 2,048 rows: 4,096 would ask for 101 MiB)."""
    assert loop_exit._tiles(8192, 2048, 49152, 2) == \
        loop_exit.Tiles(1024, 1024, 512, 2048)
    assert loop_exit._backward_bytes(512, 1024, 4096, 2048, 2) > \
        loop_exit.VMEM_BYTES
    assert loop_exit._tiles(8192, 2048, 49152 + 384, 2) == \
        loop_exit.Tiles(128, 1024, 512, 8192)
    assert loop_exit._tiles(ROWS, C, V, 4) == \
        loop_exit.Tiles(128, 256, 256, 256)
