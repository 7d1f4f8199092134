"""Weight initializers (reference `python/mxnet/initializer.py`)."""
from __future__ import annotations

import json
import math
import re

import numpy as np

from .base import MXNetError
from .ndarray.ndarray import NDArray
from . import random as _random

_INIT_REGISTRY = {}


class InitDesc(str):
    """Name + attrs descriptor (reference `initializer.py InitDesc`)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


def register(klass):
    name = klass.__name__.lower()
    _INIT_REGISTRY[name] = klass
    # reference registers plural aliases for Zero/One
    if name == "zero":
        _INIT_REGISTRY["zeros"] = klass
    if name == "one":
        _INIT_REGISTRY["ones"] = klass
    return klass


class Initializer:
    """Base initializer; callable on (InitDesc, NDArray)
    (reference `initializer.py:Initializer`)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        self._print_func = print_func
        return self

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, str):
            raise TypeError("desc must be string or InitDesc")
        init = getattr(desc, "attrs", {}).get("__init__", "")
        if init:
            create(init)._init_weight(desc, arr)
            return
        name = str(desc)
        if name.endswith("weight"):
            self._init_weight(name, arr)
        elif name.endswith("bias"):
            self._init_bias(name, arr)
        elif name.endswith("gamma"):
            self._init_gamma(name, arr)
        elif name.endswith("beta"):
            self._init_beta(name, arr)
        elif name.endswith("moving_mean") or name.endswith("running_mean"):
            self._init_zero(name, arr)
        elif name.endswith("moving_var") or name.endswith("running_var"):
            self._init_one(name, arr)
        elif name.endswith("moving_inv_var") or name.endswith("moving_avg"):
            self._init_zero(name, arr)
        elif name.endswith("min") or name.endswith("max"):
            self._init_zero(name, arr)
        elif name.endswith("parameters"):
            # FusedRNNCell's flat 1-D parameter block: honor the concrete
            # initializer when it can handle a vector (Zero/Constant/
            # Uniform); fan-in schemes like Xavier cannot, so fall back to
            # small uniform (the reference's FusedRNN default)
            try:
                self._init_weight(name, arr)
            except Exception:
                self._set(arr, _random.host_rng().uniform(-0.07, 0.07, arr.shape))
        else:
            self._init_default(name, arr)

    def _set(self, arr, np_values):
        from . import engine as _engine
        vals = np.asarray(np_values).astype(np.dtype(arr.dtype), copy=False)
        if _engine.bulk_active():
            # host-stage; the engine flush batches the device transfer
            arr._data = vals
            _engine.stage(arr)
            return
        import jax.numpy as jnp
        arr._data = jnp.asarray(vals)

    def _init_zero(self, _, arr):
        self._set(arr, np.zeros(arr.shape))

    def _init_one(self, _, arr):
        self._set(arr, np.ones(arr.shape))

    def _init_bias(self, _, arr):
        self._set(arr, np.zeros(arr.shape))

    def _init_gamma(self, _, arr):
        self._set(arr, np.ones(arr.shape))

    def _init_beta(self, _, arr):
        self._set(arr, np.zeros(arr.shape))

    def _init_weight(self, name, arr):
        raise NotImplementedError("Must override it")

    def _init_default(self, name, arr):
        raise ValueError(
            f"Unknown initialization pattern for {name}. Default initialization "
            "is now limited to \"weight\", \"bias\", \"gamma\" and \"beta\". "
            "Please use mx.sym.Variable(init=mx.init.*) to set initialization "
            "pattern")


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        self._set(arr, np.zeros(arr.shape))


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        self._set(arr, np.ones(arr.shape))


@register
class Constant(Initializer):
    def __init__(self, value=0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        if isinstance(self.value, NDArray):
            self._set(arr, self.value.asnumpy())
        else:
            self._set(arr, np.full(arr.shape, self.value))


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        self._set(arr, _random.host_rng().uniform(-self.scale, self.scale, arr.shape))


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        self._set(arr, _random.host_rng().normal(0, self.sigma, arr.shape))


@register
class LogUniform(Initializer):
    """log of uniform(low, high): the A_log of a gated delta-rule mixer,
    whose decay rate exp(A_log) the family draws uniformly in (0, 16)."""

    def __init__(self, low=1e-3, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def _init_weight(self, _, arr):
        self._set(arr, np.log(_random.host_rng().uniform(
            self.low, self.high, arr.shape)))


@register
class Xavier(Initializer):
    """Reference `initializer.py Xavier` (:728 area)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise ValueError(f"Xavier initializer cannot be applied to vector "
                             f"{name}. It requires at least 2D.")
        if len(shape) > 2:
            hw_scale = np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        factor = 1.0
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise ValueError("Incorrect factor type")
        scale = np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            self._set(arr, _random.host_rng().uniform(-scale, scale, shape))
        elif self.rnd_type == "gaussian":
            self._set(arr, _random.host_rng().normal(0, scale, shape))
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = _random.host_rng().uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = _random.host_rng().normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else v
        self._set(arr, (self.scale * res).reshape(arr.shape))


@register
class Bilinear(Initializer):
    """Bilinear upsampling kernel (reference initializer.py Bilinear)."""

    def _init_weight(self, _, arr):
        weight = np.zeros(int(np.prod(arr.shape)), dtype="float32")
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight.reshape(shape))


@register
class LSTMBias(Initializer):
    """Forget-gate bias init (reference initializer.py LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = np.zeros(arr.shape, dtype="float32")
        num_hidden = arr.shape[0] // 4
        b[num_hidden:2 * num_hidden] = self.forget_bias  # i,f,g,o gate order
        self._set(arr, b)

    def _init_bias(self, name, arr):
        self._init_weight(name, arr)


class Load:
    """Init from saved dict, falling back to default_init
    (reference initializer.py Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {k[4:] if k.startswith(("arg:", "aux:")) else k: v
                      for k, v in param.items()}
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            src = self.param[name]
            if src.shape != arr.shape:
                raise ValueError(f"Parameter {name} cannot be initialized from "
                                 "loading. Shape mismatch, "
                                 f"target {arr.shape} vs loaded {src.shape}")
            arr._data = src._data.astype(arr.dtype)
        else:
            if self.default_init is None:
                raise ValueError(f"Cannot Initialize {name}. Not found in "
                                 "loaded param and no default Initializer is "
                                 "provided.")
            self.default_init(name, arr)


class Mixed:
    """Pattern-dispatched initializers (reference initializer.py Mixed)."""

    def __init__(self, patterns, initializers):
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers mismatch")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise ValueError(f"Parameter name {name} did not match any pattern")


def create(init, **kwargs):
    if isinstance(init, Initializer):
        return init
    if callable(init):
        return init
    if isinstance(init, str):
        if init.startswith("["):
            name, args = json.loads(init)
            return _INIT_REGISTRY[name.lower()](**args)
        if init.lower() not in _INIT_REGISTRY:
            raise MXNetError(f"Unknown initializer {init}")
        return _INIT_REGISTRY[init.lower()](**kwargs)
    raise MXNetError(f"Cannot create initializer from {init!r}")
