"""The gated delta rule: the linear-attention recurrence of Gated DeltaNet
(Yang, Kautz, Hatamizadeh 2024, arXiv:2412.06464), per value head with a
float32 state S of (key size, value size), S_0 = 0:

    S' = alpha_t S_{t-1},  u_t = beta_t (v_t - S'^T k_t),
    S_t = S' + k_t u_t^T,  o_t = S_t^T q_t,      alpha_t = exp(g_t)

on q and k brought to unit length (q also scaled by key size ** -0.5),
computed in chunks of `chunk_size` positions (the WY form of the paper's
section 3.3).  The algebra is stated once, on values, for a block of whole
chunks of one head: `_block_fwd` (state in; the outputs and the state out;
inside, matrix products and the inverse of a unit lower-triangular matrix,
taken as the finite product (I + M)(I + M^2)(I + M^4)... of its nilpotent
part) and `_block_bwd`, its transpose written by hand (the inverse's own
derivative folds into two products that the pass needs anyway).  Two
drivers sweep them over a (batch, value head):

* the KERNEL (compiled, on ``tpu``, where the shapes tile: key and value
  size multiples of 128, a chunk of a multiple of 8): each pass is ONE
  Pallas kernel whose grid walks (batch, value head, positions), the last
  in order, with the float32 state (backward: its gradient) resident in
  VMEM.  A value head reads its key head's q and k through the index map;
  two chunks of 64 make one block as wide as the matrix unit;
* the SCAN (anywhere else, and what tier-1 on the CPU runs): one `lax.scan`
  over the chunks for each pass, all heads at once, a chunk a block.

Both run under one custom VJP.  The backward pass keeps the inputs and, per
chunk, the state that entered it and the block's inverse (written by the
forward sweep when a gradient is asked for, and named `registry.scan_kept`
with the output: a re-materialised scanned layer stacks the three), and
makes everything else again in its one reverse sweep.  Products take their
operands as XLA's
`Precision.DEFAULT` takes float32 on the backend at hand (on the TPU:
rounded to bfloat16, summed in float32), the inverse's chain as `HIGHEST`
(in the kernel: bf16x3); state, decays and every sum are float32.  Which
driver a traced call took is counted: `ops.delta_rule.lowered.kernel` /
`.scan`.  `GatedDeltaGates` turns the mixer's two small projections into
the float32 `g` and `beta` the recurrence takes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register, REQUIRED, scan_kept
from ..base import MXNetError

F32 = jnp.float32
LANES = 128     # the kernel tiles key and value size by this
SUBLANES = 8    # ... and the chunk by this
SCALARS = 8     # rows of the per-position scalars: [cumulative g, beta]
EPS = 1e-6      # under the root of a head's length


def _unit(x, scale):
    """Rows of x at length `scale`: (y, 1 / the rows' lengths)."""
    inv = lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + EPS)
    return x * (inv * scale), inv


def _unit_bwd(y, inv, scale, dy):
    """The transpose of `_unit` at dy, from its outputs."""
    along = jnp.sum(y * dy, axis=-1, keepdims=True)
    return inv * (scale * dy - y * (along / scale))


# ---------------------------------------------------------------------------
# How a chunk's products take their operands
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


class _Products:
    """The matrix products of a chunk.  `operand` None leaves float32
    operands to `Precision.DEFAULT` and gives the inverse's chain
    `HIGHEST` (the scan driver; on the CPU both are true float32
    products).  `operand` bfloat16 is the compiled kernel's statement of
    the same on the TPU, where XLA's DEFAULT rounds float32 operands to
    bfloat16 and accumulates in float32: operands are cast once, the
    chain splits each operand in a high and a low bfloat16 half and sums
    the three products that matter (bf16x3)."""

    def __init__(self, operand=None):
        self.operand = operand

    def _dot(self, a, b, dims, precision=None):
        return lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=F32)

    def _mm(self, a, b, dims):
        if self.operand is not None:
            a, b = a.astype(self.operand), b.astype(self.operand)
        return self._dot(a, b, dims)

    def nn(self, a, b):
        return self._mm(a, b, _NN)

    def nt(self, a, b):
        return self._mm(a, b, _NT)

    def tn(self, a, b):
        return self._mm(a, b, _TN)

    def chain(self, a, b):
        if self.operand is None:
            return self._dot(a, b, _NN, lax.Precision.HIGHEST)
        a_hi, b_hi = a.astype(self.operand), b.astype(self.operand)
        a_lo = (a - a_hi.astype(F32)).astype(self.operand)
        b_lo = (b - b_hi.astype(F32)).astype(self.operand)
        return self._dot(a_hi, b_hi, _NN) + (self._dot(a_hi, b_lo, _NN) +
                                             self._dot(a_lo, b_hi, _NN))


def _unit_lower_inverse(mm, strict_lower, c):
    """(I + L)^-1 for L strictly lower-triangular in diagonal blocks of (C,
    C) and zero elsewhere: with M = -L, M^C = 0 and sum_k M^k = prod_j (I +
    M^(2^j)) while 2^j < C.  A step squares the power and multiplies the
    product so far by it: two products against ONE right-hand side, made
    as one of twice the rows."""
    m = -strict_lower
    n = m.shape[-1]
    steps = max(0, math.ceil(math.log2(c)) - 1)
    inv, power = _eye(n) + m, m
    if steps:
        power = mm.chain(m, m)
    for _ in range(steps - 1):
        both = mm.chain(jnp.concatenate([power, inv], axis=0), power)
        power, inv = both[:n], inv + both[n:]
    return inv + mm.chain(inv, power) if steps else inv


def _iotas(c):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _eye(c):
    row, col = _iotas(c)
    return jnp.where(row == col, F32(1), F32(0))   # no float64 under x64


# ---------------------------------------------------------------------------
# A block of whole chunks of one head, on values
# ---------------------------------------------------------------------------
#
# The matrices a chunk makes of its own positions alone (the decays, q k^T,
# the triangular inverse) are made for a block of m chunks at once, as the
# diagonal blocks of (m C, m C) matrices whose other entries are zero: the
# kernel takes m C = 128, the width of the matrix unit, so that two chunks
# of 64 cost the products of one.  What the state enters goes one chunk
# after another over row slices (C, m C) of those matrices.  m = 1 is the
# plain chunk (the scan driver).

def _local(mm, q, k, gc, gc_row, beta, c):
    """{lower, strict: the masks; decay; kk = k k^T; p = q k^T decay (the
    output's); l (the inverse's strictly lower part)}, each (m C, m C) and
    zero where two positions are not of one chunk.  q, k (m C, key size)
    at their lengths (`_unit`); gc the chunks' cumulative g as a column (m
    C, 1) and as a row (1, m C); beta (m C, 1).  All float32."""
    n = q.shape[0]
    row, col = _iotas(n)
    lower, strict = row >= col, row > col
    for j in range(1, n // c):     # of one chunk: on one side of every border
        same = jnp.logical_not(jnp.logical_xor(row >= j * c, col >= j * c))
        lower, strict = lower & same, strict & same
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc - gc_row, 0.0)), 0.0)
    qk_kk = mm.nt(jnp.concatenate([q, k], axis=0), k)     # (2 m C, m C)
    kk = qk_kk[n:]
    return dict(lower=lower, strict=strict, decay=decay, kk=kk,
                p=qk_kk[:n] * decay,
                l=jnp.where(strict, beta * kk * decay, 0.0))


def _embed(x, j, m):
    """Chunk j's rows (C, ...) among the block's (m C, ...), zeros around."""
    if m == 1:
        return x
    return jnp.concatenate([x if i == j else jnp.zeros_like(x)
                            for i in range(m)], axis=0)


def _with_state(mm, s, tinv, rows, j, q, k, v, gc, gc_row, beta):
    """What both passes make of chunk j of a block (its rows `rows`) and the
    state `s` (key size, value size) that enters it."""
    c, n = rows.stop - rows.start, gc_row.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    last = jnp.sum(jnp.where(lane == rows.stop - 1, gc_row, 0.0), axis=1,
                   keepdims=True)                         # (1, 1)
    eg, ek = jnp.exp(gc[rows]), jnp.exp(last - gc[rows])
    # over the state: lanes first, then sublanes (Mosaic does not spread a
    # single element both ways at once)
    a = jnp.broadcast_to(jnp.broadcast_to(jnp.exp(last), (1, s.shape[1])),
                         s.shape)
    qs_ks = mm.nn(jnp.concatenate([q[rows], k[rows]], axis=0), s)
    qs, ks = qs_ks[:c], qs_ks[c:]
    core = v[rows] - eg * ks
    v_new = mm.nn(tinv[rows], _embed(beta[rows] * core, j, n // c))  # u - w S
    return eg, ek, a, qs, ks, core, v_new


def _block_fwd(mm, s, q, k, v, gc, gc_row, beta, c):
    """(o (m C, value size), state out, the states that entered the m
    chunks, the block's inverse (m C, m C))."""
    m = q.shape[0] // c
    (q, _), (k, _) = _unit(q, q.shape[1] ** -0.5), _unit(k, 1.0)
    x = _local(mm, q, k, gc, gc_row, beta, c)
    tinv = _unit_lower_inverse(mm, x["l"], c)
    out, entered = [], []
    for j in range(m):
        rows = slice(j * c, (j + 1) * c)
        eg, ek, a, qs, _, _, v_new = _with_state(
            mm, s, tinv, rows, j, q, k, v, gc, gc_row, beta)
        out.append(eg * qs + mm.nn(x["p"][rows], _embed(v_new, j, m)))
        entered.append(s)
        s = a * s + mm.tn(ek * k[rows], v_new)
    return jnp.concatenate(out, axis=0), s, entered, tinv


def _block_bwd(mm, entered, tinv, q, k, v, gc, gc_row, beta, do, ds, c):
    """The transpose of `_block_fwd` at (o, state out) = (do, ds): (dq, dk,
    dv, dgc as a column, a further part of dgc as a row, dbeta, the
    gradient of the state that entered the block).  With T = (I + L)^-1
    and v_new = T rhs, dL = -T^T dT T^T = -(T^T dv_new) v_new^T: the
    inverse's derivative costs no product of its own."""
    m = q.shape[0] // c
    scale = q.shape[1] ** -0.5
    (q, q_inv), (k, k_inv) = _unit(q, scale), _unit(k, 1.0)
    x = _local(mm, q, k, gc, gc_row, beta, c)
    dq, dk, dv, dgc, dbeta = ([None] * m for _ in range(5))
    dk_block = dgc_row = 0.0
    for j in reversed(range(m)):
        rows = slice(j * c, (j + 1) * c)
        s, qj, kj, bj, do_j = entered[j], q[rows], k[rows], beta[rows], \
            do[rows]
        decay = x["decay"][rows]                          # (C, m C)
        eg, ek, a, qs, ks, core, v_new = _with_state(
            mm, s, tinv, rows, j, q, k, v, gc, gc_row, beta)
        dv_new = mm.tn(x["p"][rows], do_j)[rows] + mm.nn(ek * kj, ds)
        drhs = mm.tn(tinv[rows], dv_new)[rows]
        via_s = bj * eg * drhs
        dp_dl = mm.nt(jnp.concatenate([do_j, -drhs], axis=0),
                      _embed(v_new, j, m))                # (2C, m C)
        dp = jnp.where(x["lower"][rows], dp_dl[:c], 0.0)
        dl = jnp.where(x["strict"][rows], dp_dl[c:], 0.0)
        dq_dk = mm.nt(jnp.concatenate([eg * do_j, -via_s], axis=0), s)
        dkd = mm.nt(v_new, ds)                            # (C, key size)
        da_dx = jnp.concatenate([dp * decay, bj * dl * decay], axis=0)
        via_k = mm.nn(da_dx, k)                           # (2C, key size)
        dk_block = dk_block + mm.tn(da_dx, jnp.concatenate([qj, kj], axis=0))
        dq[j] = dq_dk[:c] + via_k[:c]
        dk[j] = dq_dk[c:] + via_k[c:] + ek * dkd
        dv[j] = bj * drhs
        dbeta[j] = jnp.sum(drhs * core, axis=1, keepdims=True) + \
            jnp.sum(dl * decay * x["kk"][rows], axis=1, keepdims=True)
        e = dl * x["l"][rows] + dp * x["p"][rows]         # through the decays
        dek_ek = ek * jnp.sum(kj * dkd, axis=1, keepdims=True)
        at_last = jnp.sum(a * s * ds, keepdims=True) + \
            jnp.sum(dek_ek, keepdims=True)
        at = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        dgc[j] = jnp.sum(e, axis=1, keepdims=True) - dek_ek + \
            eg * jnp.sum(do_j * qs - bj * drhs * ks, axis=1, keepdims=True) + \
            jnp.where(at == c - 1, at_last, 0.0)
        dgc_row = dgc_row - jnp.sum(e, axis=0, keepdims=True)
        ds = a * ds + mm.tn(jnp.concatenate([eg * qj, kj], axis=0),
                            jnp.concatenate([do_j, -via_s], axis=0))
    join = functools.partial(jnp.concatenate, axis=0)
    return (_unit_bwd(q, q_inv, scale, join(dq)),
            _unit_bwd(k, k_inv, 1.0, join(dk) + dk_block),
            join(dv), join(dgc), dgc_row, join(dbeta), ds)


# ---------------------------------------------------------------------------
# The scan driver: all heads at once, one chunk after another
# ---------------------------------------------------------------------------

def _over_heads(fn):
    return jax.vmap(jax.vmap(fn))       # (batch, value heads)


def _chunks(x, c):
    """(B, T, H, ...) -> (n, B, H, C, ...)."""
    b, t = x.shape[:2]
    x = x.reshape((b, t // c, c) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)


def _unchunk(x):
    """(n, B, H, C, ...) -> (B, T, H, ...)."""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 2), 1, 3)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _scan_inputs(q, k, v, gc, beta, c):
    r = v.shape[2] // q.shape[2]
    if r != 1:
        q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))
    qc, kc, vc = (_chunks(x.astype(F32), c) for x in (q, k, v))
    gcc, bc = _chunks(gc, c), _chunks(beta, c)            # (n, B, H, C)
    return qc, kc, vc, gcc[..., None], gcc[..., None, :], bc[..., None]


def _scan_forward(q, k, v, gc, beta, c, save):
    xs = _scan_inputs(q, k, v, gc, beta, c)
    mm = _Products()

    def step(s, x):
        o, s_new, _, tinv = _over_heads(
            functools.partial(_block_fwd, mm, c=c))(s, *x)
        return s_new, (o, s, tinv) if save else (o,)
    b, hv, dk, dv = v.shape[0], v.shape[2], q.shape[3], v.shape[3]
    _, ys = lax.scan(step, jnp.zeros((b, hv, dk, dv), F32), xs)
    return (_unchunk(ys[0]).astype(v.dtype),) + tuple(ys[1:])


def _scan_backward(q, k, v, gc, beta, c, states, tinvs, do):
    xs = _scan_inputs(q, k, v, gc, beta, c)
    mm = _Products()

    def chunk(s, tinv, *rest):
        return _block_bwd(mm, [s], tinv, *rest, c=c)

    def step(ds, x):
        out = _over_heads(chunk)(*x, ds)
        return out[-1], out[:-1]
    ds0 = jnp.zeros(states.shape[1:], F32)
    _, (dq, dk, dv, dgc, dgc_row, dbeta) = lax.scan(
        step, ds0, (states, tinvs) + xs + (_chunks(do.astype(F32), c),),
        reverse=True)
    dgc = dgc[..., 0] + dgc_row[..., 0, :]
    return (_unchunk(dq), _unchunk(dk), _unchunk(dv).astype(v.dtype),
            _unchunk(dgc), _unchunk(dbeta[..., 0]))


# ---------------------------------------------------------------------------
# The kernel driver: one Pallas kernel a pass
# ---------------------------------------------------------------------------

def _column(cols, lane):
    """Column `lane` of a (positions, SCALARS) value as (positions, 1)."""
    lanes = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.sum(jnp.where(lanes == lane, cols, 0.0), axis=1, keepdims=True)


def _kernel_blocks(n, c):
    """(chunks a block, blocks a grid step) for `n` chunks of `c`: a block
    as wide as the matrix unit where the chunks fill it evenly, and two
    blocks a grid step where they divide (the step's own cost is a third
    of a microsecond, a chunk's about one; four blocks a step are 4%
    faster still and cost twice the seconds of tracing at set-up)."""
    m = max(LANES // c, 1)
    if LANES % c or n % m:
        m = 1
    return m, 2 - (n // m) % 2


def _block_inputs(q_ref, k_ref, v_ref, rows_ref, at, i):
    """(q, k, v, gc, gc_row, beta) of block i of a grid step, float32: the
    scalars come as rows and are turned into columns here."""
    given = rows_ref[0, 0, i]                     # (SCALARS, m C)
    cols = given.T
    return (q_ref[0, at, :].astype(F32), k_ref[0, at, :].astype(F32),
            v_ref[0, at, :].astype(F32), _column(cols, 0), given[:1],
            _column(cols, 1))


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest,
                c, m, per, mm, save):
    from jax.experimental import pallas as pl
    s_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, F32)

    s = s_scr[...]
    for i in range(per):
        at = pl.ds(i * m * c, m * c)
        o, s, entered, tinv = _block_fwd(
            mm, s, *_block_inputs(q_ref, k_ref, v_ref, rows_ref, at, i), c)
        o_ref[0, at, :] = o.astype(o_ref.dtype)
        if save:
            for j in range(m):
                rest[0][0, 0, i * m + j] = entered[j]
            rest[1][0, 0, i] = tinv
    s_scr[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, do_ref, s_ref, t_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, ds_scr, *, c, m, per, mm):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, F32)

    ds = ds_scr[...]
    lanes = lax.broadcasted_iota(jnp.int32, (m * c, SCALARS), 1)
    sublanes = lax.broadcasted_iota(jnp.int32, (SCALARS, m * c), 0)
    for i in reversed(range(per)):
        at = pl.ds(i * m * c, m * c)
        dq, dk, dv, dgc, dgc_row, dbeta, ds = _block_bwd(
            mm, [s_ref[0, 0, i * m + j] for j in range(m)], t_ref[0, 0, i],
            *_block_inputs(q_ref, k_ref, v_ref, rows_ref, at, i),
            do_ref[0, at, :].astype(F32), ds, c)
        dq_ref[0, at, :] = dq
        dk_ref[0, at, :] = dk
        dv_ref[0, at, :] = dv.astype(dv_ref.dtype)
        # rows [dgc, dbeta, the part of dgc that comes as a row]
        dcols = jnp.where(lanes == 0, dgc, jnp.where(lanes == 1, dbeta, 0.0))
        drows_ref[0, 0, i] = dcols.T + jnp.where(sublanes == 2, dgc_row, 0.0)
    ds_scr[...] = ds


def _kernel_layout(q, k, v, gc, beta, width):
    """The kernel's arguments: q, k, v packed (B, T, heads x size) as the
    operator gets them, a head a block of lanes; the per-position scalars
    as rows [cumulative g, beta, 0...] of (B, Hv, blocks, SCALARS,
    positions of a block), which the kernel turns into columns itself."""
    b, t, hv = gc.shape
    rows = jnp.stack([jnp.moveaxis(x, 2, 1).reshape(b, hv, t // width, width)
                      for x in (gc, beta)], axis=3)
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, SCALARS - 2), (0, 0)))
    return (q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
            rows)


def _kernel_specs(q, v, c, m, per, reverse):
    from jax.experimental import pallas as pl
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    width, rows = m * c, per * m * c        # of a block, of a grid step
    steps = t // rows

    def at(j):
        return steps - 1 - j if reverse else j
    # int32 throughout: under jax_enable_x64 a Python 0 or a `//` of a
    # traced index is 64 bits wide, which Mosaic cannot lower
    zero = np.int32(0)
    spec = {
        "key": pl.BlockSpec(
            (1, rows, dk),
            lambda i, h, j: (i, at(j), lax.div(h, np.int32(r)))),
        "dkey": pl.BlockSpec((1, rows, dk), lambda i, h, j: (i, at(j), h)),
        "value": pl.BlockSpec((1, rows, dv), lambda i, h, j: (i, at(j), h)),
        "rows": pl.BlockSpec((1, 1, per, SCALARS, width),
                             lambda i, h, j: (i, h, at(j), zero, zero)),
        "state": pl.BlockSpec((1, 1, per * m, dk, dv),
                              lambda i, h, j: (i, h, at(j), zero, zero)),
        "inverse": pl.BlockSpec((1, 1, per, width, width),
                                lambda i, h, j: (i, h, at(j), zero, zero)),
    }
    return spec, (b, hv, steps)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kernel_products(interpret):
    # interpreted on the CPU the products are what DEFAULT gives there
    return _Products(None if interpret else jnp.bfloat16)


# Jitted, so that a program that traces the operator again (the primal, the
# recomputed forward, each fit's shape inference) finds the kernels' bodies
# traced: each is a second of Python at four blocks a grid step.
@functools.partial(jax.jit, static_argnames=("c", "save", "interpret"))
def _kernel_forward(q, k, v, gc, beta, c, save, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    n = t // c
    m, per = _kernel_blocks(n, c)
    spec, grid = _kernel_specs(q, v, c, m, per, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, t, hv * dv), v.dtype)]
    out_specs = [spec["value"]]
    if save:
        out_shape += [jax.ShapeDtypeStruct((b, hv, n, dk, dv), F32),
                      jax.ShapeDtypeStruct((b, hv, n // m, m * c, m * c), F32)]
        out_specs += [spec["state"], spec["inverse"]]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, m=m, per=per, save=save,
                          mm=_kernel_products(interpret)),
        grid=grid,
        in_specs=[spec["key"], spec["key"], spec["value"], spec["rows"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gated_delta_rule_fwd",
    )(*_kernel_layout(q, k, v, gc, beta, m * c))
    return (out[0].reshape(b, t, hv, dv),) + tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _kernel_backward(q, k, v, gc, beta, c, states, tinvs, do,
                     interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    n = t // c
    m, per = _kernel_blocks(n, c)
    spec, grid = _kernel_specs(q, v, c, m, per, reverse=True)
    dq, dk_, dv_, drows = pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, m=m, per=per,
                          mm=_kernel_products(interpret)),
        grid=grid,
        in_specs=[spec["key"], spec["key"], spec["value"], spec["rows"],
                  spec["value"], spec["state"], spec["inverse"]],
        out_specs=[spec["dkey"], spec["dkey"], spec["value"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, hv * dk), F32),
                   jax.ShapeDtypeStruct((b, t, hv * dk), F32),
                   jax.ShapeDtypeStruct((b, t, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, hv, n // m, SCALARS, m * c), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="gated_delta_rule_bwd",
    )(*_kernel_layout(q, k, v, gc, beta, m * c), do.reshape(b, t, hv * dv),
      states, tinvs)

    def per_position(x):        # (B, Hv, blocks, m C) -> (B, T, Hv)
        return jnp.moveaxis(x.reshape(b, hv, t), 1, 2)
    return (dq.reshape(b, t, hv, dk), dk_.reshape(b, t, hv, dk),
            dv_.reshape(b, t, hv, dv),
            per_position(drows[:, :, :, 0] + drows[:, :, :, 2]),
            per_position(drows[:, :, :, 1]))


# ---------------------------------------------------------------------------
# One custom VJP over both drivers
# ---------------------------------------------------------------------------

def _tiles(dk, dv, chunk_size):
    """Whether the kernel can tile these shapes."""
    return dk % LANES == 0 and dv % LANES == 0 and chunk_size % SUBLANES == 0


def _driver(dk, dv, chunk_size, interpret):
    """"kernel", "interpret" or "scan" for a call: the compiled kernel on
    ``tpu`` where the shapes tile, the scan anywhere else.  Interpret mode
    is never chosen for the caller."""
    if interpret:
        if not _tiles(dk, dv, chunk_size):
            raise MXNetError(
                "gated_delta_rule: the kernel tiles key and value sizes of "
                "multiples of %d and chunks of multiples of %d, not %d, %d "
                "and %d" % (LANES, SUBLANES, dk, dv, chunk_size))
        return "interpret"
    if jax.default_backend() == "tpu" and _tiles(dk, dv, chunk_size):
        return "kernel"
    return "scan"


def _count(driver):
    from .. import obs
    obs.counter("ops.delta_rule.lowered." +
                ("scan" if driver == "scan" else "kernel")).inc()


def _forward(driver, q, k, v, gc, beta, c, save):
    if driver == "scan":
        return _scan_forward(q, k, v, gc, beta, c, save)
    return _kernel_forward(q, k, v, gc, beta, c, save,
                           interpret=driver == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _sweep(q, k, v, g, beta, c, driver):
    """q, k (B, T, Hk, Dk) as given (the blocks bring every head to its
    length); v (B, T, Hv, Dv); g, beta (B, T, Hv) float32; T a multiple of
    the chunk `c`."""
    return _forward(driver, q, k, v, _chunk_cumsum(g, c), beta, c, False)[0]


def _chunk_cumsum(g, c):
    b, t, hv = g.shape
    return jnp.cumsum(g.reshape(b, t // c, c, hv), axis=2).reshape(b, t, hv)


def _sweep_fwd(q, k, v, g, beta, c, driver):
    # what the sweep made is named (`registry.scan_kept`): a
    # re-materialised scanned layer stacks these three and computes the
    # sweep's inputs again, not the sweep
    o, states, tinvs = (scan_kept(x) for x in _forward(
        driver, q, k, v, _chunk_cumsum(g, c), beta, c, True))
    return o, (q, k, v, g, beta, states, tinvs)


def _sweep_bwd(c, driver, kept, do):
    q, k, v, g, beta, states, tinvs = kept
    gc = _chunk_cumsum(g, c)
    if driver == "scan":
        dq, dk, dv, dgc, dbeta = _scan_backward(
            q, k, v, gc, beta, c, states, tinvs, do)
    else:
        dq, dk, dv, dgc, dbeta = _kernel_backward(
            q, k, v, gc, beta, c, states, tinvs, do,
            interpret=driver == "interpret")
    b, t, hk, dk_size = q.shape
    hv = v.shape[2]
    if hv != hk:    # the value heads of a key head, each a block of lanes
        dq, dk = (x.reshape(b, t, hk, -1) for x in (dq, dk))
        dq, dk = (sum(x[..., i * dk_size:(i + 1) * dk_size]
                      for i in range(hv // hk)) for x in (dq, dk))
    dq, dk = dq.astype(q.dtype), dk.astype(k.dtype)
    # gc is the cumulative sum of g inside each chunk
    dg = jnp.flip(jnp.cumsum(jnp.flip(
        dgc.reshape(b, t // c, c, hv), 2), axis=2), 2).reshape(b, t, hv)
    return dq, dk, dv, dg, dbeta


_sweep.defvjp(_sweep_fwd, _sweep_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk_size=64, interpret=False):
    """q, k (B, T, Hk, Dk); v (B, T, Hv, Dv); g, beta (B, T, Hv) with Hv a
    multiple of Hk (key head h serves value heads h*r .. h*r + r - 1).
    Returns o (B, T, Hv, Dv) in v's type; the state and every sum in
    float32.  `interpret` is for tests: the kernel, interpreted, on any
    backend."""
    t, dk = q.shape[1], q.shape[3]
    c = int(chunk_size)
    driver = _driver(dk, v.shape[3], c, interpret)
    _count(driver)
    g, beta = g.astype(F32), beta.astype(F32)
    pad = (-t) % c
    if pad:     # g = 0 and beta = 0: the state passes through unchanged
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    return _sweep(q, k, v, g, beta, c, driver)[:, :t]


def _delta_flops(params, in_avals, out_avals):
    """The recurrence's three key-size x value-size products per value
    head and position: S'^T k, k u^T, S^T q."""
    q, v = in_avals[0], in_avals[2]
    dk = int(q.shape[-1]) // int(params["num_heads"])
    return 6.0 * int(v.shape[0]) * int(v.shape[1]) * int(v.shape[2]) * dk


@register("GatedDeltaRule", nin=5,
          params={"num_heads": REQUIRED, "num_v_heads": REQUIRED,
                  "chunk_size": 64},
          input_names=["query", "key", "value", "g", "beta"],
          cost_meta={"flops": _delta_flops}, scan_remat=True)
def _gated_delta_rule(params, q, k, v, g, beta):
    """The gated delta rule on packed (batch, time, channels) activations:
    `num_heads` key heads in query and key, `num_v_heads` value heads in
    value, one g = log(alpha) and one beta per value head and position.
    Every query and key head is normalised to unit length first (the query
    also scaled by key size ** -0.5).

    On ``tpu``, with key and value sizes of multiples of 128 and a chunk of
    a multiple of 8, each pass is one Pallas kernel that keeps a head's
    state in VMEM; anywhere else a `lax.scan` over the chunks runs the same
    chunk algebra (ops/delta_rule.py).  The backward pass is written, not
    derived: it keeps the inputs and, per chunk, the entering state and the
    chunk's inverse.  Registered `scan_remat`: a scanned layer body
    computes its activations again in the backward pass, but not the
    forward sweep, whose outputs (o, the states, the inverses: 470 MB a
    layer at `qwen3_next_80b_a3b`'s shapes) the forward rule names
    `registry.scan_kept`."""
    hk, hv = int(params["num_heads"]), int(params["num_v_heads"])
    b, t = q.shape[0], q.shape[1]
    if q.shape[-1] % hk or v.shape[-1] % hv or hv % hk or \
            k.shape != q.shape or g.shape != (b, t, hv) or \
            beta.shape != (b, t, hv):
        raise MXNetError(
            "GatedDeltaRule: query/key %s, value %s, g %s, beta %s do not "
            "fit num_heads %d and num_v_heads %d"
            % (tuple(q.shape), tuple(v.shape), tuple(g.shape),
               tuple(beta.shape), hk, hv))
    out = gated_delta_rule(
        q.reshape(b, t, hk, -1), k.reshape(b, t, hk, -1),
        v.reshape(b, t, hv, -1), g, beta,
        chunk_size=int(params["chunk_size"]))
    return out.reshape(b, t, -1)


@register("GatedDeltaGates", nin=4, nout=2,
          input_names=["a", "b", "a_log", "dt_bias"])
def _gated_delta_gates(params, a, b, a_log, dt_bias):
    """(g, beta) in float32 from the mixer's projections a, b of (batch,
    time, value heads): g = -exp(A_log) * softplus(a + dt_bias), beta =
    sigmoid(b)."""
    a, b, a_log, dt_bias = (x.astype(jnp.float32)
                            for x in (a, b, a_log, dt_bias))
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias), jax.nn.sigmoid(b)
