"""The comparison that decides `correct` for a training cell.

The program's side is read in set-up, from the first block of K steps that
`Module.fit` drives through the window's own compiled program (the program
exposes its state only at block boundaries): each step's loss, and per leaf
the norm of the parameters' change, of the optimizer's momentum (the
gradients as the optimizer got them, where it keeps any) and of the change of
the auxiliary statistics.  The reference's side is computed once the window
has closed, from the same seed: the same weights, the same K batches.

Numbers (a cell's benchmark/limits/<cell>.json says which are held to a
limit; the others are printed, not judged):
  loss_gap      worst step j of |M_j - M_j_ref| / M_j_ref, M_j the mean loss
                over steps 0..j: the running mean the program's metric
                reports, not a difference of two float32 sums
  loss0_gap     the first step's loss alone: the forward pass, before any
                update can amplify a rounding
  out0_gap      the first step's outputs, row by row (where the reference
                has `outputs`): the root mean square, over all rows and
                classes, of log p - log p_ref.  A mean loss averages the
                rows' errors away; this does not
  out0_cls_gap  the same gaps averaged over the rows first, then the root
                mean square over the classes: what all rows have in common.
                Rounding of activations differs from row to row and
                averages out; weights held in too few bits, or other
                weights than the reference's, shift every row alike
  dw_gap        worst leaf of | ||dw|| - ||dw_ref|| | / max(||dw_ref||, median)
  dw_wide_gap   the same over the leaves of WIDE_LEAF elements or more
                (matrices and filters; see below)
  dw_med_gap    the median leaf of the same
  mom_*, aux_*  worst and median leaf for the momentum (where momentum is
                kept) and the auxiliary statistics (where there are any)
A gap is between the two norms of a leaf, not the norm of a difference, and
is measured against the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose accumulated gradient in the reference is
under a thousandth of the median leaf's (a convolution bias in front of a
BatchNorm) move by round-off alone and are left out of dw_* and mom_*.
The norm of a leaf of a few hundred elements (a BatchNorm scale or shift)
follows a few numbers, and where training amplifies a rounding (PERF.md
section 4) it swings by tens of percent between two float32 runs; the norm
of a wide leaf averages over thousands and does not.  `dw_wide_gap` is the
worst-leaf number a cell can hold where `dw_gap` sits on that floor.
"""
import functools
import math

import numpy as np

DEAD_LEAF = 1e-3      # of the median leaf's gradient norm
WIDE_LEAF = 4096      # elements


def program_key(seed):
    """The PRNG key every side derives weights from; any whole number up to
    a little over 2**31 and beyond."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _norms_program():
    """The one jitted `{leaf: ||a - b||}` (made once: a fresh closure per
    call would be traced and compiled again every time)."""
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            (a[k].astype(jnp.float32) - b[k].astype(jnp.float32))
            if b is not None else a[k].astype(jnp.float32))))
            for k in a}
    return jax.jit(norms)


def leaf_norms(after, before=None):
    """{leaf: ||after - before||} (or ||after||) as floats, one device call."""
    import jax
    if before is not None:
        before = {k: jax.device_put(before[k], after[k].sharding)
                  for k in after}
    out = _norms_program()(after, before)
    return {k: float(v) for k, v in jax.device_get(out).items()}


def run_reference(ref, cfg, key, pool, k, numerics="float32", fault=None):
    """Follow the first K steps with the plain reference (or, for the
    control and the planted faults, with the reference in a lower precision
    or broken) and return the same readings as the program's side gives."""
    import jax
    import jax.numpy as jnp
    params, aux = jax.jit(lambda kk: ref.init_params(kk, cfg))(key)
    p0, a0 = params, aux
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    rows = None
    if fault == "half_batch":
        rows = slice(0, pool[0][0].shape[0] // 2)
    step = jax.jit(lambda p, m, a, d, lab: ref.train_step(
        p, m, a, d, lab, cfg, numerics, rows))
    out0 = None
    if hasattr(ref, "outputs"):
        data = jnp.asarray(pool[0][0])
        if rows is not None:        # the half that is used, seen twice
            data = jnp.concatenate([data[rows], data[rows]])
        out0 = np.asarray(jax.jit(lambda p, a, d: ref.outputs(
            p, a, d, cfg, numerics))(params, aux, data))
    losses, gsum = [], None
    for j in range(k):
        data, label = pool[j % len(pool)]
        data, label = jnp.asarray(data), jnp.asarray(label)
        if fault == "state_unchanged":
            _, _, _, loss = step(params, mom, aux, data, label)
        else:
            prev = params
            params, mom, aux, loss = step(params, mom, aux, data, label)
            moved = leaf_norms(params, prev)
            gsum = moved if gsum is None else \
                {n: gsum[n] + v for n, v in moved.items()}
        losses.append(float(loss))
    out = {"loss": losses, "dw": leaf_norms(params, p0),
           "step_sum": gsum or {n: 0.0 for n in params},
           "size": {n: int(v.size) for n, v in params.items()}}
    if out0 is not None:
        out["out0"] = out0
    if cfg["optimizer"]["momentum"]:
        out["mom"] = leaf_norms(mom)
    if aux:
        out["aux"] = leaf_norms(aux, a0)
    return out


def _leaf_gaps(prog, ref, skip=()):
    """{worst: (gap, leaf), median: (gap, "median leaf")} over the leaves."""
    names = [n for n in ref if n not in skip]
    med = float(np.median([ref[n] for n in names])) if names else 0.0
    gaps = {}
    for n in names:
        denom = max(ref[n], med)
        gap = abs(prog[n] - ref[n]) / denom if denom > 0 else \
            (0.0 if prog[n] == 0 else math.inf)
        gaps[n] = gap if gap == gap else math.inf     # NaN is the worst
    if not gaps:
        return (0.0, None), (0.0, None)
    at = max(gaps, key=gaps.get)
    return (gaps[at], at), (float(np.median(list(gaps.values()))),
                            "median leaf")


def _log_gaps(p, q, floor=1e-30):
    """(rms over all entries, rms over the classes of the mean over the
    rows) of log p - log q; inf where the program handed out nothing,
    another shape, or a NaN."""
    if p is None or np.shape(p) != np.shape(q):
        return math.inf, math.inf
    gap = np.log(np.maximum(np.asarray(p, np.float64), floor)) - \
        np.log(np.maximum(np.asarray(q, np.float64), floor))
    both = (float(np.sqrt(np.mean(np.square(gap)))),
            float(np.sqrt(np.mean(np.square(gap.mean(axis=0))))))
    return tuple(v if v == v else math.inf for v in both)


def numbers(prog, ref):
    """{name: (value, where)} of every number this pair of readings allows."""
    if len(prog["loss"]) != len(ref["loss"]):
        out = {"loss_gap": (math.inf, "steps missing"),
               "loss0_gap": (math.inf, "steps missing")}
    else:
        steps = np.arange(1, len(ref["loss"]) + 1)
        mp, mr = np.cumsum(prog["loss"]) / steps, np.cumsum(ref["loss"]) / steps
        gaps = [g if g == g else math.inf for g in np.abs(mp - mr) / np.abs(mr)]
        out = {"loss_gap": (float(max(gaps)), f"step {int(np.argmax(gaps))}"),
               "loss0_gap": (float(gaps[0]), "step 0")}
    if "out0" in ref:
        rms, common = _log_gaps(prog.get("out0"), ref["out0"])
        out["out0_gap"] = (rms, "rms over rows and classes")
        out["out0_cls_gap"] = (common, "rms over classes of the rows' mean")
    move = ref["step_sum"]
    med = float(np.median(list(move.values())))
    dead = {n for n, v in move.items() if v < DEAD_LEAF * med}
    narrow = {n for n, v in ref["size"].items() if v < WIDE_LEAF}
    for kind, skip in (("dw", dead), ("mom", dead), ("aux", ())):
        if kind in ref:
            out[kind + "_gap"], out[kind + "_med_gap"] = _leaf_gaps(
                prog[kind], ref[kind], skip)
    out["dw_wide_gap"] = _leaf_gaps(prog["dw"], ref["dw"], dead | narrow)[0]
    return out


def judge(nums, limits):
    """(correct, {name: {"value", "limit"}}): every number that has a limit
    must be at or under it; a number without one is printed, not judged."""
    compared, ok = {}, True
    for name, (value, where) in nums.items():
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit, "at": where}
        if limit is not None and not value <= limit:
            ok = False
    missing = [n for n in limits if n not in nums]
    if missing:
        ok = False
        for n in missing:
            compared[n] = {"value": None, "limit": limits[n], "at": "absent"}
    return ok, compared
