"""Data-parallel SPMD train step.

The TPU-native replacement for the reference's data-parallel machinery
(`DataParallelExecutorGroup` batch slicing + kvstore push/pull reduce,
`executor_group.py:281-310` + `comm.h`): ONE jit-compiled SPMD program per
step — forward, backward, gradient psum over the `dp` axis, and optimizer
update all fused by XLA, with the all-reduce riding the ICI mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def replicate(tree, mesh):
    """Place a pytree replicated over the whole mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def unreplicate(tree):
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, x.devices().pop())
                                  if hasattr(x, "devices") else x, tree)


def data_parallel_step(loss_fn, optimizer_update, mesh, axis_name="dp",
                      donate=True):
    """Build a fused DP train step.

    loss_fn(params, batch) -> scalar loss (per-shard mean)
    optimizer_update(params, grads, opt_state, lr) -> (new_params, new_opt_state)

    Returns step(params, opt_state, batch, lr) -> (params, opt_state, loss):
    params/opt_state replicated; batch sharded on axis 0 over `axis_name`.
    """
    def spmd_step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # gradient all-reduce over the data axis (kvstore push+pull fused)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, axis_name), grads)
        loss = jax.lax.pmean(loss, axis_name)
        new_params, new_opt = optimizer_update(params, grads, opt_state, lr)
        return new_params, new_opt, loss

    batch_spec = P(axis_name)
    rep = P()
    step = jax.shard_map(spmd_step, mesh=mesh,
                         in_specs=(rep, rep, batch_spec, rep),
                         out_specs=(rep, rep, rep), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def sgd_tree_update(momentum=0.9, wd=0.0):
    """Simple fused SGD for pytrees (used by the dp step builder)."""
    def update(params, grads, opt_state, lr):
        def upd(p, g, m):
            m2 = momentum * m - lr * (g + wd * p)
            return p + m2, m2
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(opt_state)
        new = [upd(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
        new_p = jax.tree_util.tree_unflatten(treedef, [a for a, _ in new])
        new_m = jax.tree_util.tree_unflatten(treedef, [b for _, b in new])
        return new_p, new_m
    return update
