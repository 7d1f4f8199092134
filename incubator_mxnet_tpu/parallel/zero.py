"""ZeRO-style sharded optimizer state over the data axis.

The TPU mapping of the reference's *sharded parameter server*
(`src/kvstore/kvstore_dist_server.h:155` — each server owns a key range and
updates it; workers push grads, pull fresh weights): here every dp rank IS
one "server" owning 1/N of every parameter, the push is a
`psum_scatter` (reduce-scatter riding ICI), the server-side update runs on
the owned shard with 1/N-sized optimizer state, and the pull is an
`all_gather`.  This is ZeRO stage 1+2 (sharded states + sharded gradient
reduction); parameters stay replicated between steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["zero_init_state", "zero_update", "zero_train_step",
           "adam_shard_update", "sgd_shard_update"]


def _shard_size(size, n):
    return -(-size // n)  # ceil: shards are padded to equal size


def zero_init_state(params, n_shards, state_fn):
    """Global optimizer-state arrays for a ZeRO run.

    Every leaf's state is 1-D of global size n*ceil(size/n), sharded
    P(axis) so each rank materializes exactly its 1/N slice (lay it out
    with `jax.device_put` on a NamedSharding, or let `zero_train_step`'s
    in_spec place it).  state_fn(global_shape, dtype) -> state pytree for
    one leaf, e.g. lambda s, d: (jnp.zeros(s, d), jnp.zeros(s, d)) for
    (m, v).
    """
    def per_leaf(p):
        k = _shard_size(p.size, n_shards)
        return state_fn((n_shards * k,), p.dtype)
    return jax.tree_util.tree_map(per_leaf, params)


def zero_update(params, grads, state, update_fn, axis_name="dp"):
    """One sharded optimizer step inside shard_map.

    update_fn(p_shard, g_shard, s) -> (new_p_shard, new_s); all 1-D shards.
    grads are LOCAL per-rank gradients — the reduce-scatter here replaces
    the dp all-reduce, so callers must NOT pre-psum them.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)

    def per_leaf(p, g, s):
        size = p.size
        k = _shard_size(size, n)
        pad = k * n - size
        gflat = jnp.pad(g.reshape(-1), (0, pad))
        # mean-reduce-scatter: each rank receives the summed k-slice it owns
        gshard = jax.lax.psum_scatter(gflat.reshape(n, k), axis_name,
                                      scatter_dimension=0, tiled=False) / n
        pshard = jax.lax.dynamic_slice(jnp.pad(p.reshape(-1), (0, pad)),
                                       (idx * k,), (k,))
        new_pshard, new_s = update_fn(pshard, gshard, s)
        full = jax.lax.all_gather(new_pshard, axis_name, tiled=True)
        return full[:size].reshape(p.shape), new_s

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_s = treedef.flatten_up_to(state)
    new = [per_leaf(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_p = jax.tree_util.tree_unflatten(treedef, [a for a, _ in new])
    new_s = jax.tree_util.tree_unflatten(treedef, [b for _, b in new])
    return new_p, new_s


def zero_train_step(loss_fn, update_fn, mesh, axis_name="dp", donate=True):
    """Fused DP train step with ZeRO-sharded optimizer state.

    Like `data_parallel.data_parallel_step` but the gradient exchange is a
    reduce-scatter and the optimizer state lives sharded: per-device state
    memory is 1/N of the replicated version.

    Returns step(params, opt_state, batch) -> (params, opt_state, loss);
    params and batch as in the dp step; opt_state leaves are the local
    1/N shards (out_spec P(axis_name) on the leading dim).
    """
    from jax.sharding import PartitionSpec as P

    def spmd_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis_name)
        new_params, new_state = zero_update(params, grads, opt_state,
                                            update_fn, axis_name)
        return new_params, new_state, loss

    step = jax.shard_map(
        spmd_step, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name), P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def sgd_shard_update(momentum=0.9, lr=0.01, wd=0.0):
    def update(p, g, s):
        m = s[0] if isinstance(s, (tuple, list)) else s
        m2 = momentum * m - lr * (g + wd * p)
        return p + m2, (m2,) if isinstance(s, (tuple, list)) else m2
    return update


def adam_shard_update(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8):
    """Adam on a parameter shard; state s = (m, v, t), t a (1,) step count."""
    def update(p, g, s):
        m, v, t = s
        t = t + 1
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        mhat = m / (1 - beta1 ** t[0])
        vhat = v / (1 - beta2 ** t[0])
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v, t)
    return update