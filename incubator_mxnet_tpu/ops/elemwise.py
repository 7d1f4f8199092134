"""Elementwise operators: unary, binary (broadcast + same-shape), scalar, logic.

Covers the reference families in `src/operator/tensor/`:
`elemwise_unary_op_basic.cc`, `elemwise_unary_op_trig.cc`,
`elemwise_binary_broadcast_op_{basic,extended,logic}.cc`,
`elemwise_binary_op_basic.cc`, `elemwise_binary_scalar_op_*.cc`.

Every op is one jax-traceable function; XLA fuses chains of these into single
TPU kernels (the mshadow expression-template fusion equivalent, done by the
compiler instead of C++ templates).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

# ---------------------------------------------------------------------------
# Unary
# ---------------------------------------------------------------------------

_UNARY = {
    # name: (fn, aliases)
    "abs": (jnp.abs, ("_abs",)),
    "sign": (jnp.sign, ()),
    "rint": (jnp.rint, ()),
    "round": (jnp.round, ()),
    "ceil": (jnp.ceil, ()),
    "floor": (jnp.floor, ()),
    "trunc": (jnp.trunc, ()),
    "fix": (jnp.trunc, ()),
    "square": (jnp.square, ()),
    "sqrt": (jnp.sqrt, ()),
    "rsqrt": (lambda x: jax.lax.rsqrt(x), ()),
    "cbrt": (jnp.cbrt, ()),
    "rcbrt": (lambda x: 1.0 / jnp.cbrt(x), ()),
    "exp": (jnp.exp, ()),
    "log": (jnp.log, ()),
    "log10": (jnp.log10, ()),
    "log2": (jnp.log2, ()),
    "log1p": (jnp.log1p, ()),
    "expm1": (jnp.expm1, ()),
    "sin": (jnp.sin, ()),
    "cos": (jnp.cos, ()),
    "tan": (jnp.tan, ()),
    "arcsin": (jnp.arcsin, ()),
    "arccos": (jnp.arccos, ()),
    "arctan": (jnp.arctan, ()),
    "sinh": (jnp.sinh, ()),
    "cosh": (jnp.cosh, ()),
    "tanh": (jnp.tanh, ()),
    "arcsinh": (jnp.arcsinh, ()),
    "arccosh": (jnp.arccosh, ()),
    "arctanh": (jnp.arctanh, ()),
    "degrees": (jnp.degrees, ()),
    "radians": (jnp.radians, ()),
    "sigmoid": (jax.nn.sigmoid, ()),
    "softsign": (jax.nn.soft_sign, ()),
    "relu": (jax.nn.relu, ()),
    "reciprocal": (lambda x: 1.0 / x, ()),
    "erf": (jax.scipy.special.erf, ()),
    "erfinv": (jax.scipy.special.erfinv, ()),
    "gammaln": (jax.scipy.special.gammaln, ()),
    "logical_not": (lambda x: (x == 0).astype(x.dtype), ()),
    "negative": (jnp.negative, ("_np_negative",)),
}


def _make_unary(f):
    def fn(params, x):
        return f(x)
    return fn


for _name, (_f, _aliases) in _UNARY.items():
    register(_name, nin=1, aliases=_aliases)(_make_unary(_f))


@register("gamma")
def _gamma(params, x):
    """tgamma (reference `elemwise_unary_op_basic.cc` gamma)."""
    return jax.scipy.special.gamma(x)


@register("_copy", aliases=("identity",))
def _copy(params, x):
    return x + 0 if jnp.issubdtype(x.dtype, jnp.number) else jnp.array(x)


@register("BlockGrad", aliases=("stop_gradient", "block_grad"), stop_grad=True)
def _block_grad(params, x):
    """Reference `src/operator/tensor/elemwise_unary_op_basic.cc` BlockGrad."""
    return jax.lax.stop_gradient(x)


@register("make_loss", aliases=("MakeLoss_simple",))
def _make_loss(params, x):
    return x


@register("zeros_like")
def _zeros_like(params, x):
    return jnp.zeros_like(x)


@register("ones_like")
def _ones_like(params, x):
    return jnp.ones_like(x)


@register("clip", params={"a_min": None, "a_max": None})
def _clip(params, x):
    """Reference `src/operator/tensor/matrix_op.cc` clip."""
    return jnp.clip(x, params["a_min"], params["a_max"])


# ---------------------------------------------------------------------------
# Binary with broadcasting (reference broadcast_* family) and same-shape
# elemwise_* family.  jnp broadcasts by numpy rules which subsume mshadow's.
# ---------------------------------------------------------------------------

def _cmp(f):
    def g(x, y):
        return f(x, y).astype(jnp.result_type(x, y))
    return g


_BINARY = {
    "broadcast_add": (jnp.add, ("broadcast_plus", "elemwise_add", "_add", "_plus", "_Plus")),
    "broadcast_sub": (jnp.subtract, ("broadcast_minus", "elemwise_sub", "_sub", "_minus", "_Minus")),
    "broadcast_mul": (jnp.multiply, ("elemwise_mul", "_mul", "_Mul")),
    "broadcast_div": (jnp.divide, ("elemwise_div", "_div", "_Div")),
    "broadcast_mod": (jnp.mod, ("_mod",)),
    "broadcast_power": (jnp.power, ("_power", "_Power", "pow")),
    "broadcast_maximum": (jnp.maximum, ("_maximum",)),
    "broadcast_minimum": (jnp.minimum, ("_minimum",)),
    "broadcast_hypot": (jnp.hypot, ("_hypot",)),
    "broadcast_equal": (_cmp(jnp.equal), ("_equal",)),
    "broadcast_not_equal": (_cmp(jnp.not_equal), ("_not_equal",)),
    "broadcast_greater": (_cmp(jnp.greater), ("_greater",)),
    "broadcast_greater_equal": (_cmp(jnp.greater_equal), ("_greater_equal",)),
    "broadcast_lesser": (_cmp(jnp.less), ("_lesser",)),
    "broadcast_lesser_equal": (_cmp(jnp.less_equal), ("_lesser_equal",)),
    "broadcast_logical_and": (_cmp(jnp.logical_and), ("_logical_and",)),
    "broadcast_logical_or": (_cmp(jnp.logical_or), ("_logical_or",)),
    "broadcast_logical_xor": (_cmp(jnp.logical_xor), ("_logical_xor",)),
}


def _make_binary(f):
    def fn(params, x, y):
        return f(x, y)
    return fn


for _name, (_f, _aliases) in _BINARY.items():
    register(_name, nin=2, aliases=_aliases)(_make_binary(_f))


@register("smooth_l1", nin=1, params={"scalar": 1.0})
def _smooth_l1(params, x):
    """Reference `elemwise_binary_scalar_op_extended.cc` smooth_l1."""
    s2 = float(params["scalar"]) ** 2
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / s2, 0.5 * s2 * jnp.square(x), ax - 0.5 / s2)


# ---------------------------------------------------------------------------
# Scalar ops (reference elemwise_binary_scalar_op_*.cc) — scalar is a static
# attr in the reference; we keep it static too so the jit cache keys on it.
# ---------------------------------------------------------------------------

_SCALAR = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: jnp.mod(x, s),
    "_rmod_scalar": lambda x, s: jnp.mod(s, x),
    "_power_scalar": lambda x, s: jnp.power(x, s),
    "_rpower_scalar": lambda x, s: jnp.power(s, x),
    "_maximum_scalar": lambda x, s: jnp.maximum(x, s),
    "_minimum_scalar": lambda x, s: jnp.minimum(x, s),
    "_hypot_scalar": lambda x, s: jnp.hypot(x, jnp.asarray(s, x.dtype)),
    "_equal_scalar": lambda x, s: (x == s).astype(x.dtype),
    "_not_equal_scalar": lambda x, s: (x != s).astype(x.dtype),
    "_greater_scalar": lambda x, s: (x > s).astype(x.dtype),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(x.dtype),
    "_lesser_scalar": lambda x, s: (x < s).astype(x.dtype),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(x.dtype),
    "_logical_and_scalar": lambda x, s: jnp.logical_and(x, s).astype(x.dtype),
    "_logical_or_scalar": lambda x, s: jnp.logical_or(x, s).astype(x.dtype),
    "_logical_xor_scalar": lambda x, s: jnp.logical_xor(x, s).astype(x.dtype),
}


def _make_scalar(f):
    def fn(params, x):
        return f(x, params["scalar"])
    return fn


from .registry import REQUIRED  # noqa: E402

for _name, _f in _SCALAR.items():
    register(_name, nin=1, params={"scalar": REQUIRED})(_make_scalar(_f))
