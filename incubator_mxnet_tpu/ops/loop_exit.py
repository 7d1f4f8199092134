"""The heads and the exit objective of `LoopExitOutput` (ops/loss_output.py)
as Pallas kernels: T recurrent steps' logits z_t = h_t W^T over a
vocabulary of V, walked in tiles with each tile's logits in VMEM, so that
no float32 (rows, V) value reaches HBM, forward or backward.

Three kernels, under the operator's own custom VJP:

* `loop_exit_losses` (grid: step t, a block of rows, a tile of the
  vocabulary): the tile's logits TRANSPOSED (vocabulary down, rows across,
  so the rows' statistics are lane-dense rows), a running maximum and sum
  and the label's logit in float32 scratch; out come each step's
  log-sum-exp and loss L_t = lse_t - z_t[label], (T, rows) float32.
* `loop_exit_probs` (grid: a block of rows, a tile): the last step's
  probabilities exp(z - lse) in the input's type, from its log-sum-exp:
  the last step's product made a second time, so that the probabilities
  are rounded once, from float32, as XLA's softmax rounds them.
* `loop_exit_grads` (grid: a tile, a block of rows; one call a step and a
  superblock of rows): the tile's logits made again, transposed,
  dz = d_loss (exp(z - lse) - one_hot(label)) in float32 and rounded to the
  input's type, then dh += dz W into a float32 sum of the superblock's rows
  that stays in VMEM over the vocabulary, and dW += dz^T h into the tile's
  float32 block of the weight's gradient, which stays in VMEM over the rows
  and passes from one call to the next through HBM (the calls alias it);
  the step and the superblock are scalar-prefetched data, so that every
  call after the first shares one trace of the kernel.
  The exit gate's part of dh, d_gate w_g, is added where a row block's sum
  is written.  Three products a step, none of them a fourth time.

Tiles come from the shapes (`_tiles`): rows, states and vocabulary in
whole lane tiles, a grid step's residents within `VMEM_BYTES`.  Anywhere
else, and off the TPU, the operator keeps XLA's form, one step's float32
logits at a time; ``MXNET_FLASH_INTERPRET=1`` runs the kernels interpreted
(the CPU tests).  Which a traced call took is counted:
`ops.loop_exit.lowered.kernel` / `.xla`.
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
# float32, not a Python float: under jax_enable_x64 that is 64 bits wide in
# a kernel, which Mosaic cannot lower
_NEG = np.float32(-1e30)

_NT = (((1,), (1,)), ((), ()))      # (m, c) x (n, c) -> (m, n)
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))      # (k, m) x (k, n) -> (m, n)

VMEM_BYTES = 96 << 20   # what a grid step may ask of the v5e's 128 MiB
ROW_TILES = (1024, 512, 256, 128)
VOCAB_TILES = (1024, 512, 256, 128)


class Tiles(NamedTuple):
    """The vocabulary's tile, the rows a forward grid step holds and a
    backward one, and the rows whose float32 dh stays in VMEM over the
    vocabulary in one backward call (a multiple of `grad_rows`)."""
    vocab: int
    rows: int
    grad_rows: int
    superblock: int


def _forward_bytes(rows, vocab, c, item):
    """VMEM of a forward grid step: the row block and the weight's tile,
    double-buffered, and four float32 tiles of logits (z, its exponentials,
    the label's compare, the probabilities' output)."""
    return 2 * (rows + vocab) * c * item + 4 * 4 * rows * vocab


def _backward_bytes(rows, vocab, superblock, c, item):
    """VMEM of a backward grid step: the superblock's float32 dh; the row
    block, the weight's tile and a row block of dh out, double-buffered;
    the tile's float32 dW in and out, double-buffered; the float32 tiles
    of logits and dz; the two products' float32 results."""
    return 4 * superblock * c + 2 * (2 * rows + vocab) * c * item + \
        16 * vocab * c + (4 * 4 + item) * rows * vocab + 4 * (rows + vocab) * c


def _tiles(rows, c, v, item):
    """The kernels' `Tiles` at these shapes, or None where they do not hold
    them: rows, state width and vocabulary in whole lane tiles, and a grid
    step within `VMEM_BYTES`.  The widest row blocks and vocabulary tiles
    that divide the shapes, the largest superblock that fits beside them."""
    if rows % LANES or c % LANES or v % LANES:
        return None
    vocab = next(t for t in VOCAB_TILES if v % t == 0)
    fwd = [r for r in ROW_TILES if rows % r == 0 and
           _forward_bytes(r, vocab, c, item) <= VMEM_BYTES]
    # the backward's row blocks one step narrower: its grid step holds
    # the superblock's sums and five float32 tiles besides
    grads = [(r, rows // n) for r in ROW_TILES[1:] if rows % r == 0
             for n in range(1, rows // r + 1)
             if (rows // n) % r == 0 and rows % n == 0 and
             _backward_bytes(r, vocab, rows // n, c, item) <= VMEM_BYTES]
    if not fwd or not grads:
        return None
    return Tiles(vocab, fwd[0], *grads[0])


def driver(rows, c, v, item):
    """("kernel", "interpret" or "xla", the kernels' `Tiles` or None) for a
    call, from the platform and the shapes (`item`: bytes an entry of the
    states takes)."""
    from .flash_attention import pallas_mode
    use, interpret = pallas_mode()
    tiles = _tiles(rows, c, v, item) if use else None
    if tiles is None:
        return "xla", None
    return ("interpret" if interpret else "kernel"), tiles


def count(which):
    from .. import obs
    obs.counter("ops.loop_exit.lowered." +
                ("xla" if which == "xla" else "kernel")).inc()


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, F32)


# ---------------------------------------------------------------------------
# Forward: every step's log-sum-exp and loss, then the last step's
# probabilities
# ---------------------------------------------------------------------------

def _losses_kernel(y_ref, h_ref, w_ref, lse_ref, loss_ref, m_scr, l_scr,
                   pick_scr, *, vocab):
    from jax.experimental import pallas as pl
    j = pl.program_id(2)

    @pl.when(j == I32(0))
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        pick_scr[...] = jnp.zeros(pick_scr.shape, F32)

    zt = lax.dot_general(w_ref[...], h_ref[...], _NT,
                         preferred_element_type=F32)          # (vocab, rows)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(zt, axis=0, keepdims=True))
    l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(zt - m_new), axis=0, keepdims=True)
    m_scr[...] = m_new
    ids = j * I32(vocab) + lax.broadcasted_iota(jnp.int32, zt.shape, 0)
    pick_scr[...] += jnp.sum(jnp.where(ids == y_ref[...], zt, F32(0)),
                             axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(2) - I32(1))
    def _():
        lse = m_scr[...] + jnp.log(l_scr[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - pick_scr[...]


def _probs_kernel(lse_ref, h_ref, w_ref, p_ref):
    z = lax.dot_general(h_ref[...], w_ref[...], _NT,
                        preferred_element_type=F32)           # (rows, vocab)
    p_ref[...] = jnp.exp(z - lse_ref[...]).astype(p_ref.dtype)


# Jitted, so that a program that traces the operator again (the primal, each
# fit's shape inference) finds the kernels traced.
@functools.partial(jax.jit, static_argnames=("rows", "vocab", "interpret"))
def forward(h, w, y, *, rows, vocab, interpret=False):
    """h (T, N, C), w (V, C), y (N,) int32: (the last step's probabilities
    (N, V) in h's type, every step's loss (T, N) and log-sum-exp (T, N)
    float32), in blocks of `rows` rows and tiles of `vocab` ids."""
    from jax.experimental import pallas as pl
    steps, n, c = h.shape
    v = w.shape[0]
    zero = I32(0)
    stat = pl.BlockSpec((None, 1, rows), lambda t, i, j: (t, zero, i))
    lse, loss = pl.pallas_call(
        functools.partial(_losses_kernel, vocab=vocab),
        grid=(steps, n // rows, v // vocab),
        in_specs=[pl.BlockSpec((1, rows), lambda t, i, j: (zero, i)),
                  pl.BlockSpec((None, rows, c), lambda t, i, j: (t, i, zero)),
                  pl.BlockSpec((vocab, c), lambda t, i, j: (j, zero))],
        out_specs=[stat, stat],
        out_shape=[jax.ShapeDtypeStruct((steps, 1, n), F32)] * 2,
        scratch_shapes=[_vmem((1, rows))] * 3,
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="loop_exit_losses",
    )(y.reshape(1, n), h, w)
    lse, loss = lse[:, 0], loss[:, 0]
    last = I32(steps - 1)
    probs = pl.pallas_call(
        _probs_kernel,
        grid=(n // rows, v // vocab),
        in_specs=[pl.BlockSpec((rows, 1), lambda i, j: (i, zero)),
                  pl.BlockSpec((None, rows, c), lambda i, j: (last, i, zero)),
                  pl.BlockSpec((vocab, c), lambda i, j: (j, zero))],
        out_specs=pl.BlockSpec((rows, vocab), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), h.dtype),
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret, name="loop_exit_probs",
    )(lse[-1].reshape(n, 1), h, w)
    return probs, loss, lse


# ---------------------------------------------------------------------------
# Backward: dh and dW, one call a step and a superblock of rows
# ---------------------------------------------------------------------------

def _grads_kernel(at_ref, y_ref, lse_ref, dl_ref, dg_ref, gw_ref, h_ref, w_ref,
                  *refs, vocab, rows, first):
    from jax.experimental import pallas as pl
    del at_ref                          # read by the index maps alone
    dw_in = None if first else refs[0]
    dh_ref, dw_ref, acc = refs[-3:]
    j, i = pl.program_id(0), pl.program_id(1)
    h, w = h_ref[...], w_ref[...]
    zt = lax.dot_general(w, h, _NT, preferred_element_type=F32)  # (vocab, rows)
    ids = j * I32(vocab) + lax.broadcasted_iota(jnp.int32, zt.shape, 0)
    p = jnp.exp(zt - lse_ref[...])
    dzt = (dl_ref[...] * jnp.where(ids == y_ref[...], p - F32(1), p)) \
        .astype(h.dtype)
    own = pl.ds(pl.multiple_of(i * I32(rows), rows), rows)
    part = lax.dot_general(dzt, w, _TN, preferred_element_type=F32)

    @pl.when(j == I32(0))
    def _():
        acc[own, :] = part

    @pl.when(j > I32(0))
    def _():
        acc[own, :] += part

    @pl.when(j == pl.num_programs(0) - I32(1))
    def _():
        dh_ref[...] = (acc[own, :] + dg_ref[...] * gw_ref[...].astype(F32)) \
            .astype(dh_ref.dtype)

    @pl.when(i == I32(0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32) if first else dw_in[...]

    dw_ref[...] += lax.dot_general(dzt, h, _NN, preferred_element_type=F32)


# Jitted, and the step and the superblock given as data (scalar prefetch),
# so that the kernel is traced twice, for the first call and for the others,
# not once a call: a trace is set-up time.
@functools.partial(jax.jit, static_argnames=("rows", "vocab", "superblock",
                                             "interpret"))
def _grads_call(at, h, w, rowwise, gw, dw, *, rows, vocab, superblock,
                interpret):
    """Step t's dh over superblock s of rows ((superblock, C), h's type) and
    the weight's float32 gradient with step t's part of superblock s added
    to `dw` (None: to nothing).  `at` (2,) int32: t and the superblock's
    first row block; `rowwise`: the labels (1, N), log-sum-exp and d_loss
    (T, 1, N), d_gate (T, N, 1)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    v, c = w.shape
    zero = I32(0)
    nj = v // vocab

    def stat(j, i, at):                 # (T, 1, N): step t's row block
        return at[0], zero, at[1] + i

    def block(j, i, at):                # (T, N, ·): step t's row block
        return at[0], at[1] + i, zero
    specs = [pl.BlockSpec((1, rows), lambda j, i, at: (zero, at[1] + i)),
             pl.BlockSpec((None, 1, rows), stat),
             pl.BlockSpec((None, 1, rows), stat),
             pl.BlockSpec((None, rows, 1), block),
             pl.BlockSpec((1, c), lambda j, i, at: (zero, zero)),
             pl.BlockSpec((None, rows, c), block),
             pl.BlockSpec((vocab, c), lambda j, i, at: (j, zero))]
    args = [at] + list(rowwise) + [gw, h, w]
    tile = pl.BlockSpec((vocab, c), lambda j, i, at: (j, zero))
    if dw is not None:
        specs.append(tile)
        args.append(dw)
    # a row block of dh is written once, at the last tile of the
    # vocabulary: until then its output block stays at the superblock's
    # first, which nothing writes before its own last tile
    dh_spec = pl.BlockSpec((rows, c), lambda j, i, at: (
        jnp.where(j == I32(nj - 1), i, zero), zero))
    return pl.pallas_call(
        functools.partial(_grads_kernel, vocab=vocab, rows=rows,
                          first=dw is None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nj, superblock // rows),
            in_specs=specs, out_specs=[dh_spec, tile],
            scratch_shapes=[_vmem((superblock, c))]),
        out_shape=[jax.ShapeDtypeStruct((superblock, c), h.dtype),
                   jax.ShapeDtypeStruct((v, c), F32)],
        input_output_aliases={} if dw is None else {len(args) - 1: 1},
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=interpret, name="loop_exit_grads",
    )(*args)


@functools.partial(jax.jit, static_argnames=("rows", "vocab", "superblock",
                                             "interpret"))
def backward(h, w, y, lse, d_loss, d_gate, gw, *, rows, vocab, superblock,
             interpret=False):
    """The gradients of sum over steps and rows of d_loss_t L_t + d_gate_t
    (w_g h_t) with respect to h (T, N, C), in h's type, and w (V, C),
    float32.  `lse`, `d_loss`, `d_gate` (T, N) float32, `gw` (1, C)."""
    steps, n, _ = h.shape
    rowwise = (y.reshape(1, n), lse[:, None], d_loss[:, None],
               d_gate[:, :, None])
    dw, dh = None, []
    for t in range(steps):
        parts = []
        for s in range(n // superblock):
            at = jnp.array([t, s * superblock // rows], jnp.int32)
            part, dw = _grads_call(
                at, h, w, rowwise, gw, dw, rows=rows, vocab=vocab,
                superblock=superblock, interpret=interpret)
            parts.append(part)
        dh.append(jnp.concatenate(parts) if len(parts) > 1 else parts[0])
    return jnp.stack(dh), dw
