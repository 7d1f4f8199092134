"""Default Pallas-backed subgraph backend: FullyConnected(+bias)+ReLU.

The fused kernel runs the matmul on the MXU with the bias add and ReLU
applied in VMEM before the tile is written back — the epilogue fusion XLA
usually does on its own, expressed by hand to prove the escape hatch
works end-to-end (graph partition -> custom kernel inside the jitted
program -> custom VJP for training).  On ``tpu`` the kernel compiles or
the call fails; elsewhere the op is the plain XLA expression, and
``MXNET_FLASH_INTERPRET=1`` runs the kernel interpreted for the CPU tests
(`ops.flash_attention.pallas_mode`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops import registry as _reg
from ..ops.flash_attention import pallas_mode
from .subgraph_property import SubgraphProperty, register_subgraph_property
from .partition import external_inputs


def _fc_relu_pallas(x, w, b, interpret=False):
    """relu(x @ w.T + b) via one Pallas kernel."""
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = w.shape[0]

    def kernel(x_ref, w_ref, b_ref, o_ref):
        acc = jnp.dot(x_ref[:], w_ref[:].T,
                      preferred_element_type=jnp.float32)
        o_ref[:] = jnp.maximum(acc + b_ref[:], 0.0).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, w, b)


def _fc_relu(x, w, b):
    use, interpret = pallas_mode()
    if use:
        return _fc_relu_pallas(x, w, b, interpret=interpret)
    return jnp.maximum(x @ w.T + b, 0.0).astype(x.dtype)


@functools.lru_cache(maxsize=1)
def _fused_fc_relu_fn():
    @jax.custom_vjp
    def fused(x, w, b):
        return _fc_relu(x, w, b)

    def fwd(x, w, b):
        y = fused(x, w, b)
        return y, (x, w, y)

    def bwd(res, g):
        x, w, y = res
        g = jnp.where(y > 0, g, 0.0)
        return g @ w, g.T @ x, jnp.sum(g, axis=0)

    fused.defvjp(fwd, bwd)
    return fused


def _compute(params, x, w, b):
    x2 = x.reshape(x.shape[0], -1) if params["flatten"] and x.ndim > 2 else x
    return _fused_fc_relu_fn()(x2, w, b)


_OP = _reg.OpDef(
    "_sg_pallas_fc_relu", _compute, nin=3,
    params={"num_hidden": _reg.REQUIRED, "flatten": True},
    input_names=["data", "weight", "bias"],
    doc="Fused FC+ReLU Pallas kernel (subgraph backend TPU_PALLAS)")
_reg.register_opdef(_OP)


class PallasFCReluProperty(SubgraphProperty):
    """Matches Activation(relu)(FullyConnected(data, w, b)) chains."""

    name = "TPU_PALLAS"

    def match_chain(self, node, get_input):
        if node.is_variable or node.op.name != "Activation":
            return None
        if node.attrs.get("act_type") != "relu":
            return None
        prod = get_input(node)
        if prod is None or prod.is_variable:
            return None
        if prod.op.name != "FullyConnected":
            return None
        if prod.attrs.get("no_bias"):
            return None                      # kernel variant expects bias
        if not prod.attrs.get("flatten", True):
            # flatten=False admits N-D inputs the 2-D kernel can't take;
            # leave those to XLA
            return None
        return [prod, node]

    def create_fused_op(self, nodes):
        fc = nodes[0]
        params = {"num_hidden": fc.attrs["num_hidden"],
                  "flatten": fc.attrs.get("flatten", True)}
        return _OP, params, external_inputs(nodes)


register_subgraph_property(PallasFCReluProperty())
