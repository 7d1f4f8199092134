"""``RoutedExperts``: a sparse mixture-of-experts feed-forward layer that is
told which experts it holds (`parallel.ExpertShare`: offset and count out
of `num_experts`).

It routes over ALL `num_experts`, in float32.  The router (`Router`) scores
with a softmax or a sigmoid, CHOOSES the `top_k` largest of score + bias
where a selection bias is given (an input that takes no gradient), WEIGHTS
by the score without the bias, renormalises over the chosen ones with
`norm_topk` (their sum plus `eps`).  The layer computes

    sum over the chosen experts e that are held here of  w_e * E_e(x),
    E(x) = W_down (SiLU(W_gate x) * W_up x)

for the tokens routed to an expert it holds: the part of the layer's result
that this share gives.  What the experts held elsewhere would add is left
out; on one chip nothing stands in for them or for their exchange.

Dispatch is plan-and-group.  The PLAN of a call (`_plan`) is made from the
(tokens, experts held) mask alone, without a sort and without a scatter
over the assignments that go elsewhere: a cumulative sum down each held
expert's column ranks its tokens, every expert's group is padded to whole
blocks of rows (at least one), and a row finds its token by counting the
column's entries below its rank.  The grouped product runs a block of rows
against the weights of the block's expert (`_block_forward`,
`_block_backward`: the algebra, stated once on values), under one of two
drivers:

* the KERNEL (compiled, on ``tpu``, where hidden and intermediate size are
  multiples of 128 and the block is 128 rows): one Pallas kernel a pass
  whose grid walks the row blocks with the block-to-expert map as scalar
  prefetch, so that an expert's weights are read where they lie, once for
  its consecutive blocks, blocks past the live ones are skipped, and the
  weights' gradients are summed per expert in VMEM.  Where an expert's
  whole matrices (in the backward pass beside their gradients and float32
  sums) pass the VMEM a grid step may ask for, the intermediate width is
  cut into tiles (`_tile`, from the shapes): the grid walks the row blocks
  once a tile, every weight is still read once, and the rows' outputs come
  out a float32 part a tile, summed outside;
* XLA (anywhere else, and what tier-1 on the CPU runs): the same block
  algebra as one batched product over the blocks against gathered weights.

The whole operator, routing included, stands under one custom VJP whose
backward pass is written, not derived: it keeps the inputs, the plan, the
router's probabilities and the rows' pre-activations, and runs no forward
product again.  Shapes are static, so the rows are sized for a capacity,
`capacity_factor` (2 unless told) times the mean load; a step whose load
passes it runs the exact dense form over the experts held, slowly (and
derived: at 16,384 tokens and 8 experts of width 1,536 it costs 64 ms a
layer where the grouped form takes 6).  No token is ever dropped.  The
auxiliary states count, on the device, the assignments each expert held got
(`load`), and in `dropped` those left without a row among the rows there
were (counted where the rows are placed, so a fault in the sizing would
show) beside the tokens routed.  Which driver a traced call took is
counted: `ops.experts.lowered.kernel` / `.xla`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register, REQUIRED
from ..base import MXNetError

F32 = jnp.float32
I32 = jnp.int32
LANES = 128     # the kernel tiles hidden and intermediate size by this,
                # and takes blocks of as many rows


class Router(NamedTuple):
    """How the scores of all experts are made and turned into weights."""
    scoring: str = "softmax"    # or "sigmoid"
    norm_topk: bool = True      # weights over the sum of the chosen scores
    eps: float = 0.0            # added to that sum


def _chosen(per_expert, top_e):
    """(N, E) values at the (N, k) chosen experts, by compares: a gather
    over the assignments costs more than the 64 compares an entry."""
    experts = jnp.arange(per_expert.shape[1], dtype=top_e.dtype)
    return jnp.sum(jnp.where(top_e[:, :, None] == experts,
                             per_expert[:, None, :], F32(0)), axis=-1)


def _chosen_total(top_s, router):
    """What `norm_topk` divides by: the chosen scores' sum, plus `eps`."""
    total = jnp.sum(top_s, axis=-1, keepdims=True)
    return total + F32(router.eps) if router.eps else total


def _routing(x2, router_weight, bias, top_k, router):
    """(scores (N, E) float32, the chosen ones (N, k), the weights made of
    those, their experts (N, k) int32).  `bias` (E,) or None moves the
    choice alone."""
    logits = jnp.dot(x2, router_weight.astype(x2.dtype).T,
                     preferred_element_type=F32)
    if router.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    if bias is None:
        top_s, experts = lax.top_k(scores, top_k)
    else:
        experts = lax.top_k(scores + bias.astype(F32), top_k)[1]
        top_s = _chosen(scores, experts)
    weights = top_s
    if router.norm_topk:
        weights = top_s / _chosen_total(top_s, router)
    return scores, top_s, weights, experts


def _routing_bwd(x2, router_weight, scores, top_s, top_e, router, dw):
    """The transpose of `_routing` at weights = dw: (dx2, drouter_weight);
    the bias moves no weight, so it gets nothing.  `top_k` is transposed
    with compares against `top_e`, not a scatter."""
    if router.norm_topk:
        total = _chosen_total(top_s, router)
        dw = (dw - jnp.sum(dw * top_s, axis=-1, keepdims=True) / total) / \
            total
    experts = jnp.arange(scores.shape[1], dtype=top_e.dtype)
    dscores = jnp.sum(jnp.where(top_e[:, :, None] == experts, dw[:, :, None],
                                F32(0)), axis=1)
    if router.scoring == "softmax":
        dlogits = scores * (dscores - jnp.sum(scores * dscores, axis=-1,
                                              keepdims=True))
    else:
        dlogits = dscores * scores * (F32(1) - scores)
    dlogits = dlogits.astype(x2.dtype)
    dx2 = jnp.dot(dlogits, router_weight.astype(x2.dtype),
                  preferred_element_type=F32)
    drouter = lax.dot_general(dlogits, x2, _TN, preferred_element_type=F32)
    return dx2, drouter.astype(router_weight.dtype)


# ---------------------------------------------------------------------------
# The plan of a call
# ---------------------------------------------------------------------------

class Plan(NamedTuple):
    """Integers, and one float32 weight a row.  `rows` rows in blocks of
    `block`; row r of a block of expert e is the (r - start of e's group)-th
    token of e's column, or no token's."""
    counts: jax.Array        # (count,) assignments each expert held got
    cum: jax.Array           # (count, N) tokens of a column up to each one
    block_expert: jax.Array  # (blocks,) non-decreasing
    live: jax.Array          # (1,) blocks that hold a group
    rank: jax.Array          # (blocks, block) a row's rank in its column
    row_token: jax.Array     # (rows,) N where a row holds no token
    row_weight: jax.Array    # (rows,) float32
    dropped: jax.Array       # () assignments left without a row
    # the same rows in the order of their tokens, for the combine: which
    # row stands there, its token and weight as lane-dense rows of a
    # block, and the combine's walk: (block of tokens, block of rows) pairs
    by_token: jax.Array      # (rows,) the row that stands at each place
    token_rows: jax.Array    # (blocks, 1, block) the tokens in that order
    weight_rows: jax.Array   # (blocks, 1, block) float32
    walk_tokens: jax.Array   # (steps,) non-decreasing
    walk_rows: jax.Array     # (steps,)
    walk_live: jax.Array     # (1,) steps that hold a pair


def _plan(top_w, top_e, offset, count, rows, block):
    n = top_e.shape[0]
    blocks = rows // block
    held = offset + jnp.arange(count, dtype=top_e.dtype)
    match = top_e[:, :, None] == held                   # (N, k, count)
    weight = jnp.sum(jnp.where(match, top_w[:, :, None], F32(0)), axis=1)
    cum = jnp.cumsum(jnp.any(match, axis=1).T.astype(I32), axis=1)
    counts = cum[:, -1]
    # every group in whole blocks, an empty one in one: each expert's
    # weight gradient is written by a block of its own
    padded = jnp.maximum((counts + (block - 1)) // block, 1) * block
    ends = jnp.cumsum(padded)
    at = jnp.arange(blocks, dtype=I32)
    block_expert = jnp.minimum(
        jnp.sum((ends // block)[None, :] <= at[:, None], axis=1,
                dtype=I32), count - 1)
    live = jnp.minimum(ends[-1] // block, blocks).astype(I32).reshape(1)
    rank = at[:, None] * block + jnp.arange(block, dtype=I32)[None, :] - \
        (ends - padded)[block_expert][:, None]
    col_cum = cum[block_expert][:, None, :]             # (blocks, 1, N)
    col_w = weight.T[block_expert][:, None, :]
    # the rank-th token of a column stands after as many entries that have
    # counted up to `rank` at most; at it the count reaches rank + 1
    row_token = jnp.sum(col_cum <= rank[:, :, None], axis=-1, dtype=I32)
    row_weight = jnp.sum(jnp.where(col_cum == rank[:, :, None] + 1, col_w,
                                   F32(0)), axis=-1)
    row_token, row_weight = row_token.reshape(rows), row_weight.reshape(rows)
    dropped = jnp.sum(counts) - jnp.sum(row_token < n, dtype=I32)
    return Plan(counts, cum, block_expert, live, rank, row_token,
                row_weight, dropped,
                *_by_token(row_token, row_weight, n, blocks, block))


def _by_token(row_token, row_weight, n, blocks, block):
    """The rows sorted by token (7,168 keys, not the 81,920 assignments),
    and the walk of the combine over them: a block of `block` tokens takes
    the blocks of sorted rows that hold a row of its tokens, at least one,
    so every block of tokens is written."""
    rows = blocks * block
    tokens, by_token, weights = lax.sort(
        (row_token, jnp.arange(rows, dtype=I32), row_weight), num_keys=1)
    groups = -(-n // block)
    edges = jnp.arange(groups + 1, dtype=I32) * block
    before = jnp.sum(tokens[None, :] < edges[:, None], axis=1, dtype=I32)
    lo, hi = before[:-1], before[1:]
    first = jnp.minimum(lo // block, blocks - 1)
    takes = jnp.minimum(jnp.maximum(hi - 1, lo) // block, blocks - 1) - \
        first + 1
    ends = jnp.cumsum(takes)
    step = jnp.arange(groups + blocks, dtype=I32)
    walk_tokens = jnp.minimum(
        jnp.sum(ends[None, :] <= step[:, None], axis=1, dtype=I32),
        groups - 1)
    walk_rows = jnp.minimum(
        first[walk_tokens] + step - (ends - takes)[walk_tokens], blocks - 1)
    return (by_token, tokens.reshape(blocks, 1, block),
            weights.reshape(blocks, 1, block), walk_tokens, walk_rows,
            ends[-1:].astype(I32))


def _rows_of(per_token, plan):
    """(rows, C): every row its token's entry.  A row that holds no token
    takes the last token's: its weight is 0, so nothing of it reaches a
    result or a gradient, and a gather that fills would pass over the rows
    once more."""
    return jnp.take(per_token, plan.row_token, axis=0, mode="clip")


def _rows_to_tokens(plan, per_row):
    """What every row holds (rows,), at its (token, expert held): (N,
    count), 0 where a token is not routed to an expert."""
    blocks, block = plan.rank.shape
    count, n = plan.cum.shape
    col_cum = plan.cum[plan.block_expert][:, None, :]
    at_token = jnp.sum(jnp.where(
        col_cum == plan.rank[:, :, None] + 1,
        per_row.reshape(blocks, block)[:, :, None], F32(0)), axis=1)
    experts = jnp.arange(count, dtype=I32)[:, None, None]
    per_expert = jnp.sum(jnp.where(plan.block_expert[None, :, None] ==
                                   experts, at_token[None], F32(0)), axis=1)
    hit = jnp.diff(plan.cum, axis=1, prepend=0) > 0
    return jnp.where(hit, per_expert, F32(0)).T


# ---------------------------------------------------------------------------
# A block of rows against its expert's weights, on values
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _block_forward(x, gate, up, down, part=None):
    """x (B, C); gate, up (I, C); down (C, I), all of x's type.  (y (B, C),
    the pre-activations g and u (B, I)) in x's type, sums in float32.  With
    I a tile of the intermediate width, y is a part of the sum over the
    tiles and comes in the type `part`."""
    g = _dot(x, gate, _NT).astype(x.dtype)
    u = _dot(x, up, _NT).astype(x.dtype)
    h = jax.nn.silu(g.astype(F32)) * u.astype(F32)
    return _dot(h.astype(x.dtype), down, _NT).astype(part or x.dtype), g, u


def _block_backward(x, dy, g, u, w, gate, up, down, part=None):
    """The transpose of `w * _block_forward(x, ...)[0]` at dy, from the
    kept pre-activations: (dx (B, C) in x's type, dw (B, 1), dgate, dup (I,
    C), ddown (C, I) float32).  w (B, 1) float32.  With I a tile, dx (in
    the type `part`) and dw are parts of the sums over the tiles."""
    g, u = g.astype(F32), u.astype(F32)
    sig = jax.nn.sigmoid(g)
    act = g * sig
    h = act * u
    dh = _dot(dy, down, _NN)                              # (B, I)
    dw = jnp.sum(dh * h, axis=1, keepdims=True)
    dh = dh * w
    dg = (dh * u * (sig * (F32(1) + g * (F32(1) - sig)))).astype(x.dtype)
    du = (dh * act).astype(x.dtype)
    dx = _dot(dg, gate, _NN) + _dot(du, up, _NN)
    dyw = (dy.astype(F32) * w).astype(x.dtype)
    return (dx.astype(part or x.dtype), dw, _dot(dg, x, _TN),
            _dot(du, x, _TN), _dot(dyw, h.astype(x.dtype), _TN))


# ---------------------------------------------------------------------------
# The XLA driver: one batched product over the blocks
# ---------------------------------------------------------------------------

def _xla_forward(xg, gate, up, down, plan, save):
    """(y, g, u) of all rows; what is not kept (`save`) XLA drops itself."""
    blocks, block = plan.rank.shape
    e = plan.block_expert
    y, g, u = jax.vmap(_block_forward)(
        xg.reshape(blocks, block, -1), gate[e], up[e], down[e])
    return tuple(a.reshape(blocks * block, -1) for a in (y, g, u))


def _xla_backward(xg, dy, g, u, gate, up, down, plan):
    blocks, block = plan.rank.shape
    e = plan.block_expert
    dx, dw, dgate, dup, ddown = jax.vmap(_block_backward)(
        *(a.reshape(blocks, block, -1)
          for a in (xg, dy, g, u, plan.row_weight)), gate[e], up[e], down[e])
    dgate, dup, ddown = (
        jax.ops.segment_sum(a, e, num_segments=gate.shape[0],
                            indices_are_sorted=True).astype(gate.dtype)
        for a in (dgate, dup, ddown))
    return dx.reshape(xg.shape), dw.reshape(-1), dgate, dup, ddown


# ---------------------------------------------------------------------------
# The kernel driver: one Pallas kernel a pass
# ---------------------------------------------------------------------------

def _fwd_kernel(expert_ref, live_ref, x_ref, gate_ref, up_ref, down_ref,
                y_ref, *kept, axis):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(axis) < live_ref[0])
    def _():
        y, g, u = _block_forward(x_ref[...], gate_ref[0], up_ref[0],
                                 down_ref[0], y_ref.dtype)
        y_ref[...] = y
        if kept:
            kept[0][...] = g
            kept[1][...] = u


def _run_edges(group_ref, at, live):
    """Whether live step `at` is (the first, the last) of its run of equal
    entries of a prefetched non-decreasing map."""
    here = group_ref[at]
    last_step = np.int32(group_ref.shape[0] - 1)
    first = jnp.logical_or(
        at == 0, group_ref[jnp.maximum(at - 1, np.int32(0))] != here)
    last = jnp.logical_or(
        at == live - 1, group_ref[jnp.minimum(at + 1, last_step)] != here)
    return first, last


def _sum_over_run(first, last, sums):
    """(float32 scratch, this step's part, output block) each: the parts
    of a run summed in the scratch, the output written at its last step."""
    from jax.experimental import pallas as pl

    @pl.when(first)
    def _():
        for acc, part, _ in sums:
            acc[...] = part

    @pl.when(jnp.logical_not(first))
    def _():
        for acc, part, _ in sums:
            acc[...] += part

    @pl.when(last)
    def _():
        for acc, _, out in sums:
            out[...] = acc[...].reshape(out.shape).astype(out.dtype)


def _bwd_kernel(expert_ref, live_ref, x_ref, dy_ref, g_ref, u_ref, w_ref,
                gate_ref, up_ref, down_ref, dx_ref, dw_ref, dgate_ref,
                dup_ref, ddown_ref, gate_acc, up_acc, down_acc, *, axis):
    from jax.experimental import pallas as pl
    at, live = pl.program_id(axis), live_ref[0]

    @pl.when(at < live)
    def _():
        dx, dw, dgate, dup, ddown = _block_backward(
            x_ref[...], dy_ref[...], g_ref[...], u_ref[...], w_ref[...],
            gate_ref[0], up_ref[0], down_ref[0], dx_ref.dtype)
        dx_ref[...] = dx
        dw_ref[...] = dw
        _sum_over_run(*_run_edges(expert_ref, at, live), (
            (gate_acc, dgate, dgate_ref), (up_acc, dup, dup_ref),
            (down_acc, ddown, ddown_ref)))


VMEM_BYTES = 96 << 20   # what a grid step may ask of the v5e's 128 MiB.  The
                        # forward pass holds a tile of an expert's three
                        # matrices, double-buffered; the backward pass those,
                        # their gradients (double-buffered too), the float32
                        # sums of the gradients over an expert's blocks and
                        # the float32 products the sums are made from; both
                        # the row blocks beside them


def _step_bytes(c, tile, item, backward):
    """VMEM a grid step needs for a tile of `tile` of the intermediate
    width, from the shapes: the residents `VMEM_BYTES`' comment lists."""
    matrix, rows = tile * c, LANES * (c + tile)
    if backward:
        # three matrices in, three gradients out (two buffers each), three
        # float32 sums, three float32 products; x, dy, g, u in, dx out, and
        # the float32 dh, dg, du, h and dx of the algebra
        return matrix * (12 * item + 24) + rows * (6 * item + 16)
    return matrix * 6 * item + rows * (4 * item + 12)


def _tile(c, inter, item, backward):
    """The widest tile of the intermediate width (a divisor of it in whole
    lanes) whose grid step fits `VMEM_BYTES`, or None."""
    for tiles in range(1, inter // LANES + 1):
        tile = inter // tiles
        if inter % tiles == 0 and tile % LANES == 0 and \
                _step_bytes(c, tile, item, backward) <= VMEM_BYTES:
            return tile
    return None


def _kernel_specs(plan, c, tile, tiles):
    """Row blocks and the expert's weights of a row block, through the
    prefetched plan: a block past the live ones stays at the last live
    one's, so nothing is fetched or written for it.  An index is a
    function of (tile j, row block i, the two prefetched maps); the grid of
    one tile has no j, as it had none before there were tiles."""
    from jax.experimental import pallas as pl
    block = plan.rank.shape[1]
    # int32 throughout: under jax_enable_x64 a Python 0 in an index map is
    # 64 bits wide, which Mosaic cannot lower
    zero, one = np.int32(0), np.int32(1)

    def at(i, live):
        return jnp.minimum(i, live[0] - one)

    def spec(shape, index):
        if tiles == 1:
            return pl.BlockSpec(shape, lambda i, e, live:
                                index(zero, i, e, live))
        return pl.BlockSpec(shape, index)

    def rows(width):
        return spec((block, width), lambda j, i, e, live: (at(i, live), zero))

    def part(width):
        """A row block of an output that is summed over the tiles."""
        if tiles == 1:
            return rows(width)
        return spec((None, block, width),
                    lambda j, i, e, live: (j, at(i, live), zero))
    return {
        "rows": rows, "part": part,
        "kept": spec((block, tile),
                     lambda j, i, e, live: (at(i, live), j)),
        "gate": spec((1, tile, c),
                     lambda j, i, e, live: (e[at(i, live)], j, zero)),
        "down": spec((1, c, tile),
                     lambda j, i, e, live: (e[at(i, live)], zero, j)),
    }


def _pallas(kernel, plan, tiles, name, interpret, out_shape, **kwargs):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    blocks = plan.rank.shape[0]
    grid = (blocks,) if tiles == 1 else (tiles, blocks)
    return pl.pallas_call(
        functools.partial(kernel, axis=len(grid) - 1), out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, **kwargs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret, name=name)


def _part_shape(rows, width, dtype, tiles):
    """An output summed over the tiles: itself of one, else a float32 part
    a tile."""
    if tiles == 1:
        return jax.ShapeDtypeStruct((rows, width), dtype)
    return jax.ShapeDtypeStruct((tiles, rows, width), F32)


def _sum_parts(a, dtype, tiles):
    return a if tiles == 1 else jnp.sum(a, axis=0).astype(dtype)


# Jitted, so that a program that traces the operator again (the primal, the
# recomputed forward, each fit's shape inference) finds the kernels traced.
@functools.partial(jax.jit, static_argnames=("save", "tile", "interpret"))
def _kernel_forward(xg, gate, up, down, plan, save, tile=None,
                    interpret=False):
    rows, c = xg.shape
    inter = gate.shape[1]
    tile = tile or inter
    tiles = inter // tile
    spec = _kernel_specs(plan, c, tile, tiles)
    shapes = [_part_shape(rows, c, xg.dtype, tiles)]
    out_specs = [spec["part"](c)]
    if save:
        shapes += [jax.ShapeDtypeStruct((rows, inter), xg.dtype)] * 2
        out_specs += [spec["kept"]] * 2
    out = _pallas(
        _fwd_kernel, plan, tiles, "routed_experts_fwd", interpret, shapes,
        in_specs=[spec["rows"](c), spec["gate"], spec["gate"], spec["down"]],
        out_specs=out_specs,
    )(plan.block_expert, plan.live, xg, gate, up, down)
    y = _sum_parts(out[0], xg.dtype, tiles)
    return (y,) + tuple(out[1:]) if save else (y, None, None)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _kernel_backward(xg, dy, g, u, gate, up, down, plan, tile=None,
                     interpret=False):
    from jax.experimental.pallas import tpu as pltpu
    rows, c = xg.shape
    inter = gate.shape[1]
    tile = tile or inter
    tiles = inter // tile
    spec = _kernel_specs(plan, c, tile, tiles)
    shapes = [_part_shape(rows, c, xg.dtype, tiles),
              _part_shape(rows, 1, F32, tiles)] + \
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (gate, up, down)]
    dx, dw, dgate, dup, ddown = _pallas(
        _bwd_kernel, plan, tiles, "routed_experts_bwd", interpret, shapes,
        in_specs=[spec["rows"](c), spec["rows"](c), spec["kept"],
                  spec["kept"], spec["rows"](1), spec["gate"],
                  spec["gate"], spec["down"]],
        out_specs=[spec["part"](c), spec["part"](1), spec["gate"],
                   spec["gate"], spec["down"]],
        scratch_shapes=[pltpu.VMEM((tile, c), F32),
                        pltpu.VMEM((tile, c), F32),
                        pltpu.VMEM((c, tile), F32)],
    )(plan.block_expert, plan.live, xg, dy, g, u,
      plan.row_weight.reshape(rows, 1), gate, up, down)
    return _sum_parts(dx, xg.dtype, tiles), \
        _sum_parts(dw, F32, tiles).reshape(rows), dgate, dup, ddown


def _exact_dot(p, y, terms):
    """p (float32, a weight or 0 an entry) times y with nothing of p
    rounded away: against bfloat16 rows p goes in `terms` bfloat16 parts
    (three hold a float32, one a 0 or 1), each product exact, the sums
    float32; against float32 rows as `HIGHEST`."""
    if y.dtype != jnp.bfloat16:
        return lax.dot_general(p, y.astype(F32), _NN,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=F32)
    out = None
    for _ in range(terms):
        part = p.astype(jnp.bfloat16)
        p = p - part.astype(F32)
        out = _dot(part, y, _NN) if out is None else out + _dot(part, y, _NN)
    return out


def _combine_kernel(group_ref, block_ref, live_ref, y_ref, tokens_ref,
                    weights_ref, out_ref, acc, *, weighted):
    """A block of tokens as the sum of the rows of its tokens, a block of
    sorted rows a step: the rows go through the matrix unit against the
    (token, row) matrix of their weights (of ones)."""
    from jax.experimental import pallas as pl
    at, live = pl.program_id(0), live_ref[0]

    @pl.when(at < live)
    def _():
        block = y_ref.shape[0]
        ids = group_ref[at] * np.int32(block) + lax.broadcasted_iota(
            I32, (block, block), 0)
        here = jnp.where(tokens_ref[0] == ids,
                         weights_ref[0] if weighted else F32(1), F32(0))
        part = _exact_dot(here, y_ref[...], 3 if weighted else 1)
        _sum_over_run(*_run_edges(group_ref, at, live),
                      ((acc, part, out_ref),))


@functools.partial(jax.jit, static_argnames=("tokens", "weighted", "dtype",
                                             "interpret"))
def _kernel_combine(rows, plan, tokens, weighted, dtype, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    block, c = plan.rank.shape[1], rows.shape[1]
    zero, one = np.int32(0), np.int32(1)

    def at(i, live):
        return jnp.minimum(i, live[0] - one)
    lanes = pl.BlockSpec(
        (1, 1, block), lambda i, g, b, live: (b[at(i, live)], zero, zero))
    return pl.pallas_call(
        functools.partial(_combine_kernel, weighted=weighted),
        out_shape=jax.ShapeDtypeStruct((tokens, c), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(plan.walk_tokens.shape[0],),
            in_specs=[pl.BlockSpec((block, c), lambda i, g, b, live:
                                   (b[at(i, live)], zero)), lanes, lanes],
            out_specs=pl.BlockSpec((block, c), lambda i, g, b, live:
                                   (g[at(i, live)], zero)),
            scratch_shapes=[pltpu.VMEM((block, c), F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="routed_experts_combine",
    )(plan.walk_tokens, plan.walk_rows, plan.walk_live,
      jnp.take(rows, plan.by_token, axis=0, mode="clip"), plan.token_rows,
      plan.weight_rows)


# ---------------------------------------------------------------------------
# The dense form, for a load above the capacity
# ---------------------------------------------------------------------------

DENSE_BLOCK = 1024      # tokens the dense form holds at once


def _dense(x2, gate, up, down, top_w, local):
    """Every expert held against every token, weighted by the routing: the
    exact form for any load, `count` times the work.  A block of tokens at
    a time, made again in the backward pass, so that it asks for little
    memory (the program reserves room for every branch, taken or not).
    `local` (N, k): the expert held that an assignment goes to, `count`
    for one that goes elsewhere."""
    n = x2.shape[0]
    experts = jnp.arange(gate.shape[0])

    @jax.checkpoint
    def block(xs):
        x, w, e = xs
        share = jnp.sum(jnp.where(e[None] == experts[:, None, None], w[None],
                                  0.0), axis=-1)            # (count, rows)
        h = jax.nn.silu(jnp.einsum("nc,eic->eni", x, gate)) * \
            jnp.einsum("nc,eic->eni", x, up)
        y = jnp.einsum("eni,eci->enc", h, down,
                       preferred_element_type=jnp.float32)
        return jnp.sum(y * share[..., None], axis=0)

    rows = next(d for d in range(min(DENSE_BLOCK, n), 0, -1) if n % d == 0)
    out = lax.map(block, tuple(a.reshape((n // rows, rows) + a.shape[1:])
                               for a in (x2, top_w, local)))
    return out.reshape(n, x2.shape[1]).astype(x2.dtype)


def capacity(tokens, top_k, num_experts, count, factor=2.0):
    """(capacity in assignments, rows, block) of the grouped form: `factor`
    times the mean load, in blocks of 128 rows (of at most an expert's mean
    group, for the small sizes of the tests); a load above it takes the
    dense form."""
    worst = tokens * min(top_k, count)
    mean = max(1, -(-tokens * top_k * count // num_experts))
    per_expert = 1 << (-(-mean // count) - 1).bit_length()
    block = min(128, max(8, per_expert))
    cap = min(worst, -(-int(factor * mean) // block) * block)
    return cap, cap + count * block, block


# ---------------------------------------------------------------------------
# One custom VJP over routing, both forms and both drivers
# ---------------------------------------------------------------------------

def _combine(driver, rows, plan, tokens, weighted, dtype):
    """(tokens, C) of `dtype`: every token the float32 sum of its rows,
    each times its weight where `weighted`."""
    if driver != "xla":
        return _kernel_combine(rows, plan, tokens, weighted, dtype,
                               interpret=driver == "interpret")
    rows = rows.astype(F32)
    if weighted:
        rows = rows * plan.row_weight[:, None]
    return jnp.zeros((tokens, rows.shape[1]), F32).at[plan.row_token].add(
        rows, mode="drop").astype(dtype)


def _tiles(tokens, c, inter, block, item):
    """The tiles of the intermediate width the kernels take forward and
    backward at these shapes, or None where they cannot tile them: a size
    off the lanes, or a grid step that no tile brings under `VMEM_BYTES`."""
    if tokens % LANES or c % LANES or inter % LANES or block != LANES:
        return None
    tiles = _tile(c, inter, item, False), _tile(c, inter, item, True)
    return None if None in tiles else tiles


def _driver(tokens, c, inter, block, interpret, item=2):
    """"kernel", "interpret" or "xla" for a call: the compiled kernel on
    ``tpu`` where the shapes tile and a grid step fits the VMEM (`item`:
    bytes an activation or weight entry takes), XLA's products anywhere
    else.  Interpret mode is never chosen for the caller."""
    tiles = _tiles(tokens, c, inter, block, item)
    if interpret:
        if tiles is None:
            raise MXNetError(
                "routed_experts: the kernels tile tokens, hidden and "
                "intermediate sizes of multiples of %d in blocks of %d "
                "rows, a tile of an expert's matrices within %d bytes of "
                "VMEM a grid step; not %d, %d and %d in blocks of %d"
                % (LANES, LANES, VMEM_BYTES, tokens, c, inter, block))
        return "interpret"
    if jax.default_backend() == "tpu" and tiles is not None:
        return "kernel"
    return "xla"


def _count(driver):
    from .. import obs
    obs.counter("ops.experts.lowered." +
                ("xla" if driver == "xla" else "kernel")).inc()


@functools.lru_cache(maxsize=None)
def _apply_fn(cap, rows, block, top_k, offset, router, driver, tiles=None):
    """The custom-VJP `(x2, router_weight, gate, up, down[, bias]) -> ((N,
    C), the assignments each expert held got, those left without a row)`.
    Either pass takes the grouped form while the load is within `cap` and
    the dense form above it.  `router` is a `Router`; `tiles` the kernels'
    (forward, backward) tiles of the intermediate width, None for whole
    matrices."""
    products = (_xla_forward, _xla_backward) if driver == "xla" else tuple(
        functools.partial(f, tile=tile, interpret=driver == "interpret")
        for f, tile in zip((_kernel_forward, _kernel_backward),
                           tiles or (None, None)))

    def form(counts):
        return (jnp.sum(counts) > cap).astype(I32)

    def local_of(top_e, count):
        local = top_e - offset
        return jnp.where((local >= 0) & (local < count), local, count)

    def forward(save, x2, router_weight, gate, up, down, bias=None):
        count, inter = gate.shape[0], gate.shape[1]
        scores, top_s, top_w, top_e = _routing(x2, router_weight, bias,
                                               top_k, router)
        plan = _plan(top_w, top_e, offset, count, rows, block)
        weights = tuple(w.astype(x2.dtype) for w in (gate, up, down))

        def grouped(x2, gate, up, down):
            y, g, u = products[0](_rows_of(x2, plan), gate, up, down, plan,
                                  save)
            out = _combine(driver, y, plan, x2.shape[0], True, x2.dtype)
            return (out, g, u) if save else (out,)

        def dense(x2, gate, up, down):
            out = _dense(x2, gate, up, down, top_w, local_of(top_e, count))
            kept = jnp.zeros((rows, inter), x2.dtype)
            return (out, kept, kept) if save else (out,)
        which = form(plan.counts)
        out = lax.switch(which, (grouped, dense), x2, *weights)
        dropped = jnp.where(which == 0, plan.dropped, 0)
        return (out[0], plan.counts, dropped), \
            (scores, top_s, top_w, top_e, plan) + tuple(out[1:])

    @jax.custom_vjp
    def apply(*args):
        return forward(False, *args)[0]

    def fwd(*args):
        out, kept = forward(True, *args)
        return out, (args, kept)

    def bwd(res, cts):
        (x2, router_weight, gate, up, down, *bias), \
            (scores, top_s, top_w, top_e, plan, g, u) = res
        ct = cts[0]
        count = gate.shape[0]
        weights = tuple(w.astype(x2.dtype) for w in (gate, up, down))
        held = offset + jnp.arange(count, dtype=top_e.dtype)

        def grouped(x2, ct, g, u, gate, up, down):
            dxg, dw, dgate, dup, ddown = products[1](
                _rows_of(x2, plan), _rows_of(ct, plan), g, u, gate, up, down,
                plan)
            dx = _combine(driver, dxg, plan, x2.shape[0], False, F32)
            dw = _rows_to_tokens(plan, dw)               # (N, count)
            dtop_w = jnp.sum(jnp.where(top_e[:, :, None] == held,
                                       dw[:, None, :], F32(0)), axis=-1)
            return dx, dgate, dup, ddown, dtop_w

        def dense(x2, ct, g, u, gate, up, down):
            local = local_of(top_e, count)
            dx, dgate, dup, ddown, dtop_w = jax.vjp(
                lambda *f: _dense(*f, local), x2, gate, up, down,
                top_w)[1](ct)
            return dx.astype(F32), dgate, dup, ddown, dtop_w
        dx, dgate, dup, ddown, dtop_w = lax.switch(
            form(plan.counts), (grouped, dense), x2, ct, g, u, *weights)
        dx_router, drouter = _routing_bwd(x2, router_weight, scores, top_s,
                                          top_e, router, dtop_w)
        return ((dx + dx_router).astype(x2.dtype), drouter) + tuple(
            d.astype(w.dtype) for d, w in zip((dgate, dup, ddown),
                                              (gate, up, down))) + \
            tuple(jnp.zeros_like(b) for b in bias)

    apply.defvjp(fwd, bwd)
    return apply


def routed_experts(x, router_weight, gate, up, down, num_experts, top_k,
                   offset, norm_topk=True, interpret=False, bias=None,
                   scoring="softmax", norm_eps=0.0, capacity_factor=2.0):
    """(partial output of x's shape and type, assignments per expert held
    (count,) int32, assignments left without a row, scalar int32).  `bias`
    (num_experts,): the selection bias, which takes no gradient.
    `capacity_factor`: the rows of the grouped form, in mean loads.
    `interpret` is for tests: the kernel, interpreted, on any backend."""
    if scoring not in ("softmax", "sigmoid"):
        raise MXNetError("routed_experts: scoring is 'softmax' or 'sigmoid', "
                         "not %r" % (scoring,))
    count = gate.shape[0]
    x2 = x.reshape(-1, x.shape[-1])
    if not capacity_factor >= 1.0:
        raise MXNetError("routed_experts: capacity_factor is at least 1 "
                         "(the mean load), not %r" % (capacity_factor,))
    cap, rows, block = capacity(x2.shape[0], top_k, num_experts, count,
                                float(capacity_factor))
    shapes = (x2.shape[0], x2.shape[1], gate.shape[1], block)
    driver = _driver(*shapes, interpret, x2.dtype.itemsize)
    tiles = None if driver == "xla" else _tiles(*shapes, x2.dtype.itemsize)
    _count(driver)
    apply = _apply_fn(cap, rows, block, int(top_k), int(offset),
                      Router(scoring, bool(norm_topk), float(norm_eps)),
                      driver, tiles)
    out, counts, dropped = apply(
        x2, router_weight, gate, up, down, *(() if bias is None else (bias,)))
    return out.reshape(x.shape), counts, dropped


def _experts_flops(params, in_avals, out_avals):
    """The router over all experts (a softmax's or a sigmoid's product is
    the same), and the three products of an expert at the expected number
    of assignments to the experts held here."""
    x, gate = in_avals[0], in_avals[2]
    tokens = 1
    for d in x.shape[:-1]:
        tokens *= int(d)
    c, inter = int(x.shape[-1]), int(gate.shape[1])
    num, k = int(params["num_experts"]), int(params["top_k"])
    local = k * int(params["experts_count"]) / num
    return 2.0 * tokens * c * (num + local * 3 * inter)


def _load_counters(deltas):
    """The routing of an epoch, from what every `RoutedExperts` layer of a
    graph has added to `load` and `dropped` (`OpDef.counters`; `params` is
    the node's own: the span says which scoring routed)."""
    loads = np.concatenate([d["load"] for d in deltas])
    lost = [d["dropped"] for d in deltas]
    scoring = sorted({str(d["params"].get("scoring", "softmax"))
                      for d in deltas})
    args = {"layers": len(deltas), "tokens": int(max(d[1] for d in lost)),
            "assigned": int(loads.sum()), "max": float(loads.max()),
            "mean": float(loads.mean()),
            "dropped": int(sum(d[0] for d in lost)),
            "scoring": ",".join(scoring)}
    return {"span": "moe.load", "args": args,
            "counters": {"moe." + k: args[k]
                         for k in ("tokens", "assigned", "dropped")},
            "gauges": {"moe.load_max_over_mean": args["max"] / args["mean"]}
            if args["mean"] else {}}


def _input_names(params):
    return ["data", "router_weight", "gate_weight", "up_weight",
            "down_weight"] + \
        (["select_bias"] if params.get("select_bias") else []) + \
        ["load", "dropped"]


@register("RoutedExperts", nin=-1, mode_dependent=True,
          naux=lambda p: 3 if p.get("select_bias") else 2,
          params={"num_experts": REQUIRED, "top_k": REQUIRED,
                  "experts_offset": 0, "experts_count": REQUIRED,
                  "norm_topk": True, "scoring": "softmax",
                  "select_bias": False, "norm_eps": 0.0,
                  "capacity_factor": 2.0},
          input_names=_input_names,
          cost_meta={"flops": _experts_flops}, scan_remat=True,
          counters=_load_counters)
def _routed_experts(params, x, router_weight, gate, up, down, *states):
    """This share's part of a routed-expert layer (see the module's text).
    data (..., C); router_weight (num_experts, C); gate_weight and
    up_weight (experts_count, I, C); down_weight (experts_count, C, I).
    The router: `scoring` "softmax" or "sigmoid", `norm_topk` with
    `norm_eps` added to the chosen scores' sum; with `select_bias` a
    further input (num_experts,) is added to the scores for the CHOICE alone.  It is an auxiliary state: no gradient
    reaches it and the step hands it back as it was.  Auxiliary states
    added to in every training step: `load` (experts_count,), and `dropped`
    (2,): the assignments left without a row, and the tokens routed.

    Registered `scan_remat`, and it names nothing `registry.scan_kept` yet:
    what its written backward pass keeps (the rows' two pre-activations,
    the plan, the scores) is ~290 MB a layer at `lfm2_24b_a2b`'s shapes,
    0.87 GB over that cell's three scanned layers, which its 14.63 GB of
    15.75 do not have until the block's stacked outputs that nobody reads
    are gone (ROADMAP S3); in a scanned layer its forward runs again."""
    num, k = int(params["num_experts"]), int(params["top_k"])
    offset, count = int(params["experts_offset"]), \
        int(params["experts_count"])
    biased = bool(params.get("select_bias"))
    if len(states) != 2 + biased:
        raise MXNetError("RoutedExperts: %d inputs after down_weight, "
                         "select_bias=%s takes %d"
                         % (len(states), biased, 2 + biased))
    bias = states[0] if biased else None
    load, dropped = states[-2:]
    if router_weight.shape != (num, x.shape[-1]) or k > num or \
            offset < 0 or offset + count > num or \
            gate.shape[0] != count or up.shape != gate.shape or \
            down.shape != (count, x.shape[-1], gate.shape[1]) or \
            (biased and bias.shape != (num,)):
        raise MXNetError(
            "RoutedExperts: router %s, gate %s, up %s, down %s%s do not fit "
            "data %s with num_experts %d, top_k %d and experts held "
            "[%d, %d)" % (tuple(router_weight.shape), tuple(gate.shape),
                          tuple(up.shape), tuple(down.shape),
                          ", select_bias %s" % (tuple(bias.shape),)
                          if biased else "", tuple(x.shape), num, k, offset,
                          offset + count))
    out, counts, lost = routed_experts(
        x, router_weight, gate, up, down, num, k, offset,
        bool(params["norm_topk"]), bias=bias,
        scoring=str(params["scoring"]), norm_eps=float(params["norm_eps"]),
        capacity_factor=float(params["capacity_factor"]))
    if not params.get("_train", False):
        return out
    tokens = x.size // x.shape[-1]
    return (out,) + ((bias,) if biased else ()) + (
        load + counts.astype(load.dtype),
        dropped + jnp.stack([lost, jnp.asarray(tokens, lost.dtype)])
        .astype(dropped.dtype))
