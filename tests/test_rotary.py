"""`RotaryEmbedding` as one lane-aligned Pallas pass each way
(ops/rotary.py): the kernel interpreted on the CPU against XLA's form of
the same rotation and against the formula written out in float64, forward
and backward, at every configuration's head layout at a short length, in
float32 and bfloat16; lanes from `rotary_dim` on pass bit for bit, inf and
NaN among them; the written backward (the rotation by -theta) against
`jax.vjp` of XLA's form; the driver's choice from the shapes and its
counters; XLA's form after a per-head norm (`after_norm`).  tests_tpu/test_operator_tpu.py runs the kernel on the chip.

Tolerances.  Both forms compute x1 cos - x2 sin and x2 cos + x1 sin in
float32 from cos and sin of the same float32 angles and round once, so in
float32 they differ by no more than a product contracted into a sum
(1e-6 of the largest entry), and in bfloat16 an entry may round to its
neighbour (one rounding of the largest entry, 2^-8).  Against the float64
formula, the float32 angles of positions below 64 are off by up to
64 x 2^-24 = 3.8e-6 radians: `ANGLES` = 1e-5 of the largest entry more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import registry, rotary as R

BASE = 1e4
ANGLES = 1e-5
# (batch, time, heads, head size, rotary_dim, copies) of each configuration
LAYOUTS = {
    "mla_q_192_pe_64_first": (2, 64, 4, 192, 64, 1),     # kanana2_30b_a3b
    "mla_key_one_head_of_64": (2, 64, 1, 64, 64, 1),     # kanana2's k_pe
    "whole_128": (2, 64, 4, 128, 128, 1),                # ouro_2_6b
    "partial_64_of_256": (2, 64, 2, 256, 64, 1),         # qwen3_next
    "whole_64": (2, 64, 4, 64, 64, 1),                   # lfm2_24b_a2b
    "whole_128_copies_2": (2, 64, 4, 128, 128, 2),       # sdar_30b_a3b_chat
}
DTYPES = ["float32", "bfloat16"]


def _x(layout, dtype, seed=0):
    b, t, h, d = LAYOUTS[layout][:4]
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, h, d),
                             jnp.float32).astype(dtype)


def _formula(x, rd, copies, sign=1):
    """The rotation by sign x theta written out in float64."""
    x = np.asarray(jnp.asarray(x, jnp.float32), np.float64)
    t, half = x.shape[1], rd // 2
    pos = np.arange(t) % (t // copies)
    angle = pos[:, None] * BASE ** (-np.arange(half) * 2.0 / rd)[None, :]
    cos, sin = np.cos(angle)[None, :, None], sign * np.sin(angle)[None, :,
                                                                  None]
    x1, x2 = x[..., :half], x[..., half:rd]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., rd:]], axis=-1)


def _gap(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    return np.abs(got - want).max() / np.abs(want).max()


def _tol(dtype):
    return 1e-6 if dtype == "float32" else 2.0 ** -8


def _counts():
    return tuple(mx.obs.counter("ops.rotary.lowered." + d).value
                 for d in ("kernel", "xla"))


def _op(rd, copies):
    op = registry.get("RotaryEmbedding")
    params = op.canonicalize_params({"rotary_dim": rd, "base": BASE,
                                     "copies": copies})
    return lambda x: op.fn(dict(params), x)


def _old(rd, copies):
    """XLA's form, differentiated by JAX: the operator as it was."""
    return lambda x: R.xla_form(x, rd, BASE, copies)


def _vjp(fn, x, g):
    return jax.jit(lambda a, c: jax.vjp(fn, a)[1](c)[0])(x, g)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_operator_against_xlas_form_and_the_formula(interpreted, layout,
                                                    dtype):
    """The registered operator, forward and backward, the kernel
    interpreted where the driver takes it: against XLA's form and its
    `jax.vjp`, and against the float64 formula at theta and at -theta."""
    _, _, _, _, rd, copies = LAYOUTS[layout]
    x, g = _x(layout, dtype), _x(layout, dtype, seed=1)
    op, tol = _op(rd, copies), _tol(dtype)
    before = _counts()
    got = jax.jit(op)(x)
    kernel = R.driver(x.shape, rd, copies, x.dtype.itemsize)[0] != "xla"
    assert _counts() == (before[0] + kernel, before[1] + (not kernel))
    assert got.dtype == x.dtype and got.shape == x.shape
    assert _gap(got, jax.jit(_old(rd, copies))(x)) <= tol
    assert _gap(got, _formula(x, rd, copies)) <= tol + ANGLES

    dx = _vjp(op, x, g)
    assert dx.dtype == x.dtype and dx.shape == x.shape
    assert _gap(dx, _vjp(_old(rd, copies), x, g)) <= tol
    assert _gap(dx, _formula(g, rd, copies, sign=-1)) <= tol + ANGLES


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_written_backward_is_the_old_forms_vjp(layout, dtype):
    """XLA's form under the custom VJP (the CPU's driver): forward, the
    form itself; backward, the rotation by -theta against `jax.vjp` of the
    forward form, whose transpose is the same products and sums (on the
    CPU a product may be contracted into a sum in one program and not in
    the other: one float32 rounding)."""
    _, _, _, _, rd, copies = LAYOUTS[layout]
    x, g = _x(layout, dtype), _x(layout, dtype, seed=1)
    assert R.driver(x.shape, rd, copies, x.dtype.itemsize)[0] == "xla"
    op = _op(rd, copies)
    np.testing.assert_array_equal(np.asarray(jax.jit(op)(x), np.float32),
                                  np.asarray(jax.jit(_old(rd, copies))(x),
                                             np.float32))
    assert _gap(_vjp(op, x, g), _vjp(_old(rd, copies), x, g)) <= _tol(dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["mla_q_192_pe_64_first",
                                    "partial_64_of_256"])
def test_lanes_past_rotary_dim_pass_bit_for_bit(layout, dtype):
    """inf, -inf, NaN, the largest finite value and the smallest subnormal
    in the lanes the rotation does not turn come out as they went in, both
    ways, and the turned lanes stay finite: no lane is multiplied by 0."""
    b, t, h, d, rd, copies = LAYOUTS[layout]
    dt = jnp.dtype(dtype)
    special = np.array([np.inf, -np.inf, np.nan, jnp.finfo(dt).max,
                        jnp.finfo(dt).smallest_subnormal, -0.0],
                       np.float32)
    x = np.array(_x(layout, dtype), np.float32)
    x[..., rd:] = np.resize(special, x[..., rd:].shape)
    x = jnp.asarray(x, dt)
    how = ("interpret", R._plan(h, d, rd),
           R._rows(t // copies, h * d, dt.itemsize))
    bits = {2: np.uint16, 4: np.uint32}[dt.itemsize]
    for sign in (1, -1):
        out = R._turn(x, rd, BASE, copies, how, sign)
        np.testing.assert_array_equal(
            np.asarray(out[..., rd:]).view(bits),
            np.asarray(x[..., rd:]).view(bits))
        assert np.all(np.isfinite(np.asarray(out[..., :rd], np.float32)))


@pytest.mark.parametrize("rows", [16, 32])
def test_row_blocks_follow_the_period(interpreted, monkeypatch, rows):
    """Smaller row blocks than the shapes give (several blocks of the
    table a sequence, and copies of the sequence within a batch row): the
    same rotation, forward and backward."""
    monkeypatch.setattr(R, "_rows", lambda *a: rows)
    layout = "whole_128_copies_2"
    _, _, _, _, rd, copies = LAYOUTS[layout]
    x, g = _x(layout, "float32"), _x(layout, "float32", seed=1)
    op = _op(rd, copies)
    assert _gap(jax.jit(op)(x), _formula(x, rd, copies)) <= ANGLES
    assert _gap(_vjp(op, x, g), _formula(g, rd, copies, sign=-1)) <= ANGLES


def test_plan_from_the_shapes():
    """Windows of whole lane tiles around each head's turned entries, the
    rest passed through; None where a row is not whole lane tiles."""
    p = R._plan(4, 192, 64)
    assert [(w.start, w.width, w.offsets) for w in p.windows] == [
        (0, 128, (0,)), (128, 128, (64,)), (384, 128, (0,)),
        (512, 128, (64,))]
    assert p.through == ((256, 128), (640, 128))
    assert R._plan(4, 64, 64).windows[0].offsets == (0, 64)
    assert R._plan(2, 256, 256).windows == (R.Window(0, 256, (0,), 0),
                                            R.Window(256, 256, (0,), 0))
    assert R._plan(1, 64, 64) is None
    assert R._rows(8192, 6144, 2) == 256 and R._rows(24, 128, 2) is None


def test_driver_and_counters(interpreted):
    """The kernel where a row of heads is whole lane tiles and the period
    whole row blocks, XLA's form elsewhere; one count a traced call."""
    assert R.driver((2, 64, 4, 192), 64, 1, 2)[0] == "interpret"
    assert R.driver((2, 64, 1, 64), 64, 1, 2)[0] == "xla"
    assert R.driver((2, 48, 4, 128), 128, 2, 2)[0] == "xla"
    before = _counts()
    jax.jit(_op(64, 1))(jnp.zeros((2, 64, 4, 192), jnp.bfloat16))
    jax.jit(_op(64, 1))(jnp.zeros((2, 64, 1, 64), jnp.bfloat16))
    assert _counts() == (before[0] + 1, before[1] + 1)


@pytest.mark.parametrize("qk_norm", [True, False])
def test_after_a_per_head_norm_xlas_form_runs(interpreted, qk_norm):
    """The attention mixer marks its rotations `after_norm` where per-head
    q / k norms feed them; such a rotation is XLA's form differentiated by
    JAX (the operator as it was) even where the kernel could run."""
    from incubator_mxnet_tpu.llm.qwen3_next import (GatedAttentionMixer,
                                                    Qwen3NextConfig)
    cfg = Qwen3NextConfig(hidden_size=64, num_attention_heads=2,
                          num_key_value_heads=1, head_dim=128)
    out = GatedAttentionMixer(cfg, gate=False, qk_norm=qk_norm,
                              prefix="attn_")(mx.sym.Variable("data"))
    ropes = [n for n in out._topo() if not n.is_variable and
             n.op.name == "RotaryEmbedding"]
    assert len(ropes) == 2
    assert all(bool(registry.get("RotaryEmbedding").canonicalize_params(
        n.attrs)["after_norm"]) == qk_norm for n in ropes)
    layout = "whole_128"
    _, _, _, _, rd, copies = LAYOUTS[layout]
    x, g = _x(layout, "bfloat16"), _x(layout, "bfloat16", seed=1)
    op = registry.get("RotaryEmbedding")
    params = op.canonicalize_params({"rotary_dim": rd, "base": BASE,
                                     "after_norm": True})
    before = _counts()
    got = jax.jit(lambda a: op.fn(dict(params), a))(x)
    assert _counts() == (before[0], before[1] + 1)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(
        jax.jit(_old(rd, copies))(x), np.float32))
    np.testing.assert_array_equal(
        np.asarray(_vjp(lambda a: op.fn(dict(params), a), x, g), np.float32),
        np.asarray(_vjp(_old(rd, copies), x, g), np.float32))


# ---------------------------------------------------------------------------
# Compiled HERE for the chip the benchmark runs on (a TPU v5e that is
# described, not attached): what Mosaic would refuse on the chip it refuses
# here, at no chip time; nothing runs.  The topology is described inside a
# fixture, never at import.
# ---------------------------------------------------------------------------

# (batch, time, heads, head size, rotary_dim, copies) at the cells' sizes
CELLS = {
    "kanana2_q": (2, 8192, 32, 192, 64, 1),
    "ouro_q": (2, 4096, 16, 128, 128, 1),
    "sdar_k": (2, 8192, 4, 128, 128, 2),
    "qwen3_next_q": (2, 4096, 16, 256, 64, 1),
    "lfm2_q": (2, 8192, 32, 64, 64, 1),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_kernels_compile_for_the_v5e(one_chip, uncached, cell):
    """Both kernels at a cell's shape, with the rows a grid step the shapes
    give, through the operator's custom VJP."""
    b, t, h, d, rd, copies = CELLS[cell]
    how = ("kernel", R._plan(h, d, rd), R._rows(t // copies, h * d, 2))
    assert how[1] is not None and how[2] is not None

    def value_and_grad(x, g):
        y, vjp = jax.vjp(lambda a: R._rotate(a, rd, 1e6, copies, how), x)
        return y, vjp(g)[0]
    x = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(value_and_grad).lower(x, x).compile().as_text()
    assert "rotary_embedding_fwd" in text and "rotary_embedding_bwd" in text


def _producers(text):
    """{instruction: (opcode, [operands])} of a compiled program's text."""
    import re
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(([^)]*)"
                         r"\)", text, re.M):
        out[m.group(1)] = (m.group(2), re.findall(r"%([\w.\-]+)",
                                                  m.group(3)))
    return out


def _source(ops, name):
    """`name` followed back through bitcasts and tuple elements."""
    while ops[name][0] in ("bitcast", "get-tuple-element"):
        name = ops[name][1][0]
    return name


def test_no_relayout_between_projection_rotation_and_attention(
        one_chip, uncached, monkeypatch):
    """kanana2's q at a quarter of the cell's length, projected, turned and
    attended, forward and backward, compiled for the v5e with both
    operators' kernels: the rotation's heads-major output IS the flash
    kernels' q (bitcasts between), and the rotation's backward reads the
    flash backward kernel's dq as it is; the projection's output and its
    gradient's input are the rotation's rows."""
    from incubator_mxnet_tpu.ops import attention, flash_attention
    monkeypatch.setattr(flash_attention, "pallas_mode", lambda: (True, False))
    b, t, h, d, dv, rd, c = 2, 2048, 32, 192, 128, 64, 2048

    def loss(x, wq, wk, wv):
        q = R.rotary((x @ wq).reshape(b, t, h, d), rd, 1e6)
        k = (x @ wk).reshape(b, t, h, d)
        v = (x @ wv).reshape(b, t, h, dv)
        return jnp.sum(attention.grouped_query_attention(q, k, v)
                       .astype(jnp.float32))
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((b, t, c), (c, h * d), (c, h * d), (c, h * dv))]
    ops = _producers(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args)
                     .compile().as_text())
    (fwd,) = [n for n in ops if n.startswith("rotary_embedding_fwd")]
    (bwd,) = [n for n in ops if n.startswith("rotary_embedding_bwd")]
    (attn_fwd,) = [n for n in ops if n.startswith("flash_attention_fwd")]
    (attn_bwd,) = [n for n in ops if n.startswith("flash_attention_bwd")]
    # the kernels' operands: scalar prefetch first where they have it, then
    # q; cos, sin and then the data for the rotation
    assert fwd in [_source(ops, o) for o in ops[attn_fwd][1]]
    assert fwd in [_source(ops, o) for o in ops[attn_bwd][1]]
    assert _source(ops, ops[bwd][1][2]) == attn_bwd
    assert ops[_source(ops, ops[fwd][1][2])][0] == "fusion"   # the product
