"""`RotaryEmbedding` (ops/lm_ops.py) as one lane-aligned pass forward and one
backward: a Pallas rotation under the operator's own custom VJP.

The rotation is linear.  Entry i of a head's first `rotary_dim` (rd) entries
pairs with entry i + rd / 2:

    y1 = x1 cos - x2 sin,    y2 = x2 cos + x1 sin,    the rest passes,

so y = R(theta) x, and since R(theta)^T = R(-theta) the backward pass is the
same rotation with sin negated.  The custom VJP keeps no residual.

The forward kernel reads the data as rows (B T, H D), the layout of the
projection that feeds the operator, and hands the heads over major-most,
(B, H, T, D), the layout `BlockwiseAttention`'s kernels read q and k in;
the backward kernel reads the attention's dq / dk in that layout and writes
rows for the projection's backward.  The operator's own transposes cancel
the attention's (`_kernel_layout`, `_from_rows`), so no relayout stands
between the projection, the rotation and the attention either way.  A
grid step holds `rows` rows of one sequence.  Within a row, each head's
rotated entries lie in a WINDOW of whole lane tiles (`_plan`; two heads of
64, or a pair of 192-wide heads' first 64 entries, each take one), which
the kernel turns with two lane rolls (`pltpu.roll`) by +-rd/2 and a select
by lane index, in float32, rounded once to the input's type; lane tiles
that hold no rotated entry, and the lanes of a window outside its heads'
first rd, are selected through bit for bit (never multiplied by 0: an inf
would become a NaN).  The rows pass through VMEM scratch on the way to or
from the heads.  cos and sin come from a float32 table a position, built
by the same jnp expressions as XLA's form, laid out as the windows need
them (the first half's sin negated): (period, table lanes), read at row
block i mod (period / rows), so that a time axis of `copies` copies of
one sequence (block diffusion) turns each copy alike.

Where a per-head normalisation feeds the rotation (`after_norm`: the q / k
norms of `llm/qwen3_next.GatedAttentionMixer`), XLA fuses the two both
ways, and a kernel between them costs the norm's backward a float32
relayout of the data: XLA's form runs there, differentiated by JAX.

Blocks and VMEM come from the shapes (`_plan`, `_rows`).  Off the TPU,
and where a row is not whole lane tiles (a key of one 64-wide head) or the
positions' period is not whole row blocks, the operator keeps XLA's form
under the same custom VJP, whose backward is then the same rotation by
-theta; ``MXNET_FLASH_INTERPRET=1`` runs the kernel interpreted (the CPU
tests).  Which a traced call took is counted: `ops.rotary.lowered.kernel`
/ `.xla`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
I32 = np.int32
LANES = 128
ROW_BLOCKS = (1024, 512, 256, 128, 64, 32, 16)
BLOCK_BYTES = 4 << 20   # a block of rows, in the input's type
VMEM_BYTES = 48 << 20


class Window(NamedTuple):
    """Lanes [start, start + width) of a row, whole lane tiles, that hold
    the rotated entries of heads beginning at `offsets` within it; their
    cos and sin are lanes [table, table + width) of the table."""
    start: int
    width: int
    offsets: tuple
    table: int


class Plan(NamedTuple):
    """The windows of a row, the runs of lane tiles that pass through
    untouched ((start, width) each) and the table's patterns ((offsets,
    width) each, laid side by side in that order)."""
    windows: tuple
    through: tuple
    patterns: tuple


def _plan(heads, d, rd):
    """The kernel's `Plan` for rows of `heads` heads of `d` entries turned
    on their first `rd`, or None where a row is not whole lane tiles."""
    if heads * d % LANES:
        return None
    spans = []                  # [first tile, last tile + 1, offsets]
    for h in range(heads):
        lo, hi = h * d, h * d + rd
        first, last = lo // LANES, -(-hi // LANES)
        if spans and first < spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], last)
            spans[-1][2].append(lo - spans[-1][0] * LANES)
        else:
            spans.append([first, last, [lo - first * LANES]])
    patterns, windows = [], []
    for first, last, offsets in spans:
        key = (tuple(offsets), (last - first) * LANES)
        if key not in patterns:
            patterns.append(key)
        table = sum(w for _, w in patterns[:patterns.index(key)])
        windows.append(Window(first * LANES, key[1], key[0], table))
    through, at = [], 0
    for w in windows + [Window(heads * d, 0, (), 0)]:
        if w.start > at:
            through.append((at, w.start - at))
        at = w.start + w.width
    return Plan(tuple(windows), tuple(through), tuple(patterns))


def _rows(period, lanes, item):
    """The rows a grid step holds: the most that divide the positions'
    period with a block within `BLOCK_BYTES`, or None."""
    fits = [r for r in ROW_BLOCKS if period % r == 0]
    small = [r for r in fits if r * lanes * item <= BLOCK_BYTES]
    return (small or fits[-1:] or [None])[0]


def driver(shape, rd, copies, item):
    """("kernel", "interpret" or "xla", the `Plan`, rows a grid step) for a
    call on data of `shape` (B, T, H, D), from the platform and the
    shapes."""
    from .flash_attention import pallas_mode
    use, interpret = pallas_mode()
    _, t, heads, d = shape
    plan = _plan(heads, d, rd) if use else None
    rows = _rows(t // copies, heads * d, item) if plan is not None else None
    if rows is None:
        return "xla", None, None
    return ("interpret" if interpret else "kernel"), plan, rows


def count(which):
    from .. import obs
    obs.counter("ops.rotary.lowered." +
                ("xla" if which == "xla" else "kernel")).inc()


def _inv_freq(rd, base):
    return 1.0 / (base ** (jnp.arange(rd // 2, dtype=F32) * 2.0 / rd))


def _positions(t, copies):
    """Positions 0 .. t - 1 repeating every t / copies, float32."""
    position = jnp.arange(t, dtype=F32)
    if copies != 1:
        position = jnp.tile(position[:t // copies], copies)
    return position


def xla_form(x, rd, base, copies, sign=1):
    """XLA's form of the rotation by sign x theta on x (B, T, H, D)."""
    t, half = x.shape[1], rd // 2
    angle = _positions(t, copies)[:, None] * _inv_freq(rd, base)[None, :]
    cos, sin = (a[None, :, None, :] for a in (jnp.cos(angle),
                                                jnp.sin(angle)))
    xf = x.astype(F32)
    x1, x2, rest = xf[..., :half], xf[..., half:rd], xf[..., rd:]
    if sign > 0:
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    else:
        turned = [x1 * cos + x2 * sin, x2 * cos - x1 * sin]
    return jnp.concatenate(turned + [rest], axis=-1).astype(x.dtype)


def _table(plan, period, rd, base):
    """(cos, sin) (period, table lanes) float32: each pattern's lanes, the
    first half of every head's rotated entries with sin negated; angle 0
    (cos 1, sin 0) on the lanes a window passes through.  One elementwise
    pass from the positions and a row of frequencies."""
    inv_freq, half = _inv_freq(rd, base), rd // 2
    freq, sign = [], []
    for offsets, width in plan.patterns:
        at = 0
        for o in offsets:
            freq += [jnp.zeros(o - at, F32), inv_freq, inv_freq]
            sign += [np.ones(o - at), -np.ones(half), np.ones(half)]
            at = o + rd
        freq.append(jnp.zeros(width - at, F32))
        sign.append(np.ones(width - at))
    angle = _positions(period, 1)[:, None] * jnp.concatenate(freq)[None, :]
    return jnp.cos(angle), \
        jnp.sin(angle) * np.concatenate(sign).astype(np.float32)


def _turn_rows(cos_ref, sin_ref, x_ref, o_ref, *, plan, rd, sign):
    """o = R(sign theta) x on a block of (rows, H D) refs."""
    from jax.experimental.pallas import tpu as pltpu
    half = rd // 2
    for start, width in plan.through:
        o_ref[:, start:start + width] = x_ref[:, start:start + width]
    for w in plan.windows:
        x = x_ref[:, w.start:w.start + w.width]
        xf = x.astype(F32)
        ahead = pltpu.roll(xf, I32(w.width - half), 1)  # entry l + rd / 2
        # one head's rd entries fill the window: l + rd/2 and l - rd/2 are
        # the same lane modulo the window, and every lane turns
        whole = w.offsets == (0,) and w.width == rd
        if whole:
            partner = ahead
        else:
            lane = lax.broadcasted_iota(jnp.int32, (1, w.width), 1)

            def among(lo, hi):
                return functools.reduce(jnp.logical_or, [
                    (lane >= I32(o + lo)) & (lane < I32(o + hi))
                    for o in w.offsets])
            behind = pltpu.roll(xf, I32(half), 1)     # entry l - rd / 2
            partner = jnp.where(among(0, half), ahead, behind)
        c = cos_ref[:, w.table:w.table + w.width]
        s = sin_ref[:, w.table:w.table + w.width]
        turned = (xf * c + partner * s if sign > 0 else
                  xf * c - partner * s).astype(x.dtype)
        o_ref[:, w.start:w.start + w.width] = turned if whole else \
            jnp.where(among(0, rd), turned, x)


def _forward_kernel(cos_ref, sin_ref, x_ref, o_ref, rows_ref, *, plan, rd,
                    d):
    """Rows (block, H D) in, turned by theta, out as the block's heads
    (1, H, block, D)."""
    _turn_rows(cos_ref, sin_ref, x_ref, rows_ref, plan=plan, rd=rd, sign=1)
    for h in range(o_ref.shape[1]):
        o_ref[0, h] = rows_ref[:, h * d:(h + 1) * d]


def _backward_kernel(cos_ref, sin_ref, g_ref, o_ref, rows_ref, *, plan, rd,
                     d):
    """The block's heads (1, H, block, D) in, as rows, turned by -theta,
    out as rows (block, H D)."""
    for h in range(g_ref.shape[1]):
        rows_ref[:, h * d:(h + 1) * d] = g_ref[0, h]
    _turn_rows(cos_ref, sin_ref, rows_ref, o_ref, plan=plan, rd=rd, sign=-1)


def _call(kernel, x, b, t, heads, d, *, rd, base, copies, plan, rows,
          interpret, to_heads):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    lanes, period = heads * d, t // copies
    cos, sin = _table(plan, period, rd, base)
    blocks, per_row, zero = I32(period // rows), I32(t // rows), I32(0)
    table = pl.BlockSpec((rows, cos.shape[1]),
                         lambda i: (lax.rem(i, blocks), zero))
    flat = pl.BlockSpec((rows, lanes), lambda i: (i, zero))
    by_head = pl.BlockSpec((1, heads, rows, d), lambda i: (
        lax.div(i, per_row), zero, lax.rem(i, per_row), zero))
    return pl.pallas_call(
        functools.partial(kernel, plan=plan, rd=rd, d=d),
        grid=(b * t // rows,),
        in_specs=[table, table, by_head if not to_heads else flat],
        out_specs=by_head if to_heads else flat,
        out_shape=jax.ShapeDtypeStruct(
            (b, heads, t, d) if to_heads else (b * t, lanes), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, lanes), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="rotary_embedding_" + ("fwd" if to_heads else "bwd"),
    )(cos, sin, x)


_STATIC = ("rd", "base", "copies", "plan", "rows", "interpret")


# Jitted, so that a program that traces the operator again (the primal, each
# fit's shape inference, the scanned body's recomputation) finds it traced.
@functools.partial(jax.jit, static_argnames=_STATIC)
def forward(x, **kw):
    """x (B, T, H, D) turned by theta, as (B, H, T, D): one Pallas pass
    over its (B T, H D) rows, `rows` a grid step."""
    b, t, heads, d = x.shape
    return _call(_forward_kernel, x.reshape(b * t, heads * d), b, t, heads,
                 d, to_heads=True, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def backward(g, **kw):
    """g (B, H, T, D) turned by -theta, as (B, T, H, D): one Pallas pass."""
    b, heads, t, d = g.shape
    return _call(_backward_kernel, g, b, t, heads, d, to_heads=False,
                 **kw).reshape(b, t, heads, d)


def _turn(x, rd, base, copies, how, sign):
    """x (B, T, H, D) turned by sign x theta.  The kernels hand the heads
    over major-most, (B, H, T, D), the layout `BlockwiseAttention`'s
    kernels read q and k in: the transposes here and the attention's
    cancel, and XLA moves no data between the two operators."""
    which, plan, rows = how
    if which == "xla":
        return xla_form(x, rd, base, copies, sign)
    kw = dict(rd=rd, base=base, copies=copies, plan=plan, rows=rows,
              interpret=which == "interpret")
    if sign > 0:
        return forward(x, **kw).transpose(0, 2, 1, 3)
    return backward(x.transpose(0, 2, 1, 3), **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rotate(x, rd, base, copies, how):
    return _turn(x, rd, base, copies, how, 1)


def _rotate_fwd(x, rd, base, copies, how):
    return _turn(x, rd, base, copies, how, 1), None


def _rotate_bwd(rd, base, copies, how, _, g):
    return (_turn(g, rd, base, copies, how, -1),)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotary(x, rotary_dim, base, copies=1, after_norm=False):
    """x (B, T, H, D): positions 0..T-1 rotate the first `rotary_dim`
    entries of every head, entry i paired with entry i + rotary_dim / 2.
    `copies`: the time axis holds that many copies of one sequence, each at
    positions 0..T/copies - 1 (the positions' period is T / copies).  The
    backward pass is the rotation by -theta.  `driver` picks what runs, and
    `ops.rotary.lowered.kernel` / `.xla` count its choice a traced call.
    `after_norm`: x is a per-head normalisation's output, with which XLA
    fuses its own form both ways: that form, differentiated by JAX."""
    if after_norm:
        count("xla")
        return xla_form(x, rotary_dim, base, copies)
    how = driver(x.shape, rotary_dim, copies, x.dtype.itemsize)
    count(how[0])
    return _rotate(x, int(rotary_dim), float(base), int(copies), how)
