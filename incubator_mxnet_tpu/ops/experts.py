"""``RoutedExperts``: a sparse mixture-of-experts feed-forward layer that is
told which experts it holds (`parallel.ExpertShare`: offset and count out
of `num_experts`).

It routes over ALL `num_experts` (softmax in float32, the `top_k` largest,
weights renormalised over the chosen ones with `norm_topk`), and computes

    sum over the chosen experts e that are held here of  w_e * E_e(x),
    E(x) = W_down (SiLU(W_gate x) * W_up x)

for the tokens routed to an expert it holds: the part of the layer's result
that this share gives.  What the experts held elsewhere would add is left
out; on one chip nothing stands in for them or for their exchange.

Dispatch is sort-and-group: the assignments to experts held here are sorted
by expert, every expert's group padded to whole blocks of rows, and the
grouped matrix product is one batched product over the blocks, each block
against its expert's weights; the combine scatters the weighted rows back.
Shapes are static, so the rows are sized for a capacity, twice the mean
load; a step whose load passes it runs the exact dense form over the experts
held, slowly.  No token is ever dropped.  The auxiliary states count, on the
device, the assignments each expert held got (`load`), and in `dropped` those
whose row fell outside the rows there were (counted where the rows are
placed, so a fault in the sizing would show) beside the tokens routed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register, REQUIRED
from ..base import MXNetError


def route(x2, router_weight, top_k, norm_topk=True):
    """(weights (N, k) float32, experts (N, k) int32) of every token."""
    logits = jnp.dot(x2, router_weight.astype(x2.dtype).T,
                     preferred_element_type=jnp.float32)
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def _expert(x, gate, up, down):
    h = jax.nn.silu(jnp.matmul(x, jnp.swapaxes(gate, -1, -2))) * \
        jnp.matmul(x, jnp.swapaxes(up, -1, -2))
    return jnp.matmul(h, jnp.swapaxes(down, -1, -2))


def _grouped(rows, block, x2, gate, up, down, top_w, order, sorted_e, counts):
    """(result, assignments left without a row) of sort-and-group over
    `rows` rows in blocks of `block`: exact while the padded groups fit,
    which the caller's look at the load guarantees."""
    n, k = top_w.shape
    count = gate.shape[0]
    held = sorted_e < count
    e = jnp.minimum(sorted_e, count - 1)
    padded = (counts + block - 1) // block * block
    ends = jnp.cumsum(padded)
    rank = jnp.arange(n * k, dtype=jnp.int32) - (jnp.cumsum(counts) -
                                                 counts)[e]
    dest = jnp.where(held, (ends - padded)[e] + rank, rows)
    row_token = jnp.full((rows,), n, jnp.int32).at[dest].set(
        order // k, mode="drop")
    row_weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        top_w.reshape(-1)[order], mode="drop")
    block_expert = jnp.minimum(jnp.searchsorted(
        ends // block, jnp.arange(rows // block), side="right"), count - 1)
    xg = jnp.take(x2, row_token, axis=0, mode="fill", fill_value=0)
    y = _expert(xg.reshape(rows // block, block, -1), gate[block_expert],
                up[block_expert], down[block_expert])
    y = y.reshape(rows, -1).astype(jnp.float32) * row_weight[:, None]
    out = jnp.zeros((n, x2.shape[1]), jnp.float32).at[row_token].add(
        y, mode="drop")
    return out, jnp.sum(held & (dest >= rows), dtype=jnp.int32)


DENSE_BLOCK = 1024      # tokens the dense form holds at once


def _dense(x2, gate, up, down, top_w, order, sorted_e, counts):
    """Every expert held against every token, weighted by the routing: the
    exact form for any load, `count` times the work.  A block of tokens at
    a time, made again in the backward pass, so that it asks for little
    memory (the program reserves room for every branch, taken or not)."""
    n, k = top_w.shape
    local = jnp.zeros((n * k,), jnp.int32).at[order].set(sorted_e) \
        .reshape(n, k)
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
    return out.reshape(n, x2.shape[1]), jnp.zeros((), jnp.int32)


def capacity(tokens, top_k, num_experts, count):
    """(capacity in assignments, rows, block) of the grouped form: twice the
    mean load, in blocks of 128 rows (of at most an expert's mean group, for
    the small sizes of the tests); a load above it takes the dense form."""
    worst = tokens * min(top_k, count)
    mean = max(1, -(-tokens * top_k * count // num_experts))
    per_expert = 1 << (-(-mean // count) - 1).bit_length()
    block = min(128, max(8, per_expert))
    cap = min(worst, -(-2 * mean // block) * block)
    return cap, cap + count * block, block


@functools.lru_cache(maxsize=None)
def _apply_fn(cap, rows, block):
    """The custom-VJP `(x2, gate, up, down, top_w, order, sorted_e, counts)
    -> ((N, C) float32, assignments left without a row)`: the forward pass
    takes the grouped form while the load is within `cap` and the dense form
    above it, the backward pass differentiates the form the load selects
    again, so that only the inputs are kept between the two."""
    branches = [functools.partial(_grouped, rows, block), _dense]

    def form(counts):
        return (jnp.sum(counts) > cap).astype(jnp.int32)

    @jax.custom_vjp
    def apply(*args):
        return lax.switch(form(args[-1]), branches, *args)

    def fwd(*args):
        return apply(*args), args

    def bwd(args, ct):
        floats, ints = args[:5], args[5:]

        def grads(branch):
            def run(*operands):
                *fl, cot = operands[:6]
                return jax.vjp(lambda *f: branch(*f, *operands[6:])[0],
                               *fl)[1](cot)
            return run
        out = lax.switch(form(ints[-1]), [grads(b) for b in branches],
                         *floats, ct[0], *ints)
        return tuple(out) + tuple(
            np.zeros(i.shape, jax.dtypes.float0) for i in ints)

    apply.defvjp(fwd, bwd)
    return apply


def routed_experts(x, router_weight, gate, up, down, num_experts, top_k,
                   offset, norm_topk=True):
    """(partial output of x's shape and type, assignments per expert held
    (count,) int32, assignments left without a row, scalar int32)."""
    count = gate.shape[0]
    x2 = x.reshape(-1, x.shape[-1])
    n = x2.shape[0]
    top_w, top_e = route(x2, router_weight, top_k, norm_topk)
    local = top_e - offset
    local = jnp.where((local >= 0) & (local < count), local, count) \
        .reshape(-1).astype(jnp.int32)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sorted_e = local[order]
    counts = jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0,
                     dtype=jnp.int32)
    apply = _apply_fn(*capacity(n, top_k, num_experts, count))
    weights = [w.astype(x.dtype) for w in (gate, up, down)]
    out, dropped = apply(x2, *weights, top_w, order, sorted_e, counts)
    return out.astype(x.dtype).reshape(x.shape), counts, dropped


def _experts_flops(params, in_avals, out_avals):
    """The router over all experts, and the three products of an expert at
    the expected number of assignments to the experts held here."""
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
    graph has added to `load` and `dropped` (`OpDef.counters`)."""
    loads = np.concatenate([d["load"] for d in deltas])
    lost = [d["dropped"] for d in deltas]
    args = {"layers": len(deltas), "tokens": int(max(d[1] for d in lost)),
            "assigned": int(loads.sum()), "max": float(loads.max()),
            "mean": float(loads.mean()),
            "dropped": int(sum(d[0] for d in lost))}
    return {"span": "moe.load", "args": args,
            "counters": {"moe." + k: args[k]
                         for k in ("tokens", "assigned", "dropped")},
            "gauges": {"moe.load_max_over_mean": args["max"] / args["mean"]}
            if args["mean"] else {}}


@register("RoutedExperts", nin=7, naux=2, mode_dependent=True,
          params={"num_experts": REQUIRED, "top_k": REQUIRED,
                  "experts_offset": 0, "experts_count": REQUIRED,
                  "norm_topk": True},
          input_names=["data", "router_weight", "gate_weight", "up_weight",
                       "down_weight", "load", "dropped"],
          cost_meta={"flops": _experts_flops}, scan_remat=True,
          counters=_load_counters)
def _routed_experts(params, x, router_weight, gate, up, down, load, dropped):
    """This share's part of a routed-expert layer (see the module's text).
    data (..., C); router_weight (num_experts, C); gate_weight and
    up_weight (experts_count, I, C); down_weight (experts_count, C, I).
    Auxiliary states, added to in every training step: `load`
    (experts_count,), and `dropped` (2,): the assignments left without a
    row, and the tokens routed."""
    num, k = int(params["num_experts"]), int(params["top_k"])
    offset, count = int(params["experts_offset"]), \
        int(params["experts_count"])
    if router_weight.shape != (num, x.shape[-1]) or k > num or \
            offset < 0 or offset + count > num or \
            gate.shape[0] != count or up.shape != gate.shape or \
            down.shape != (count, x.shape[-1], gate.shape[1]):
        raise MXNetError(
            "RoutedExperts: router %s, gate %s, up %s, down %s do not fit "
            "data %s with num_experts %d, top_k %d and experts held "
            "[%d, %d)" % (tuple(router_weight.shape), tuple(gate.shape),
                          tuple(up.shape), tuple(down.shape),
                          tuple(x.shape), num, k, offset, offset + count))
    out, counts, lost = routed_experts(
        x, router_weight, gate, up, down, num, k, offset,
        bool(params["norm_topk"]))
    if not params.get("_train", False):
        return out
    tokens = x.size // x.shape[-1]
    return out, load + counts.astype(load.dtype), \
        dropped + jnp.stack([lost, jnp.asarray(tokens, lost.dtype)]) \
        .astype(dropped.dtype)
