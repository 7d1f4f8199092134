"""Test utilities (reference `python/mxnet/test_utils.py`).

Carries the reference's operator-test backbone: `check_numeric_gradient`
(finite differences vs registered gradients, reference :790),
`check_symbolic_forward`/`backward` (:923), `assert_almost_equal` (:470),
and `check_consistency` (:1204) — the cross-backend parity harness the TPU
build uses to compare tpu vs cpu executions of the same symbol.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray, array
from . import ndarray as nd

_default_ctx = [None]


def default_context():
    """Reference `test_utils.py:53 default_context`."""
    return _default_ctx[0] or current_context()


def set_default_context(ctx):
    _default_ctx[0] = ctx


def default_dtype():
    return np.float32


def rand_shape_2d(dim0=10, dim1=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None, **kwargs):
    ctx = ctx or default_context()
    arr = np.random.uniform(-1, 1, shape).astype(dtype or "float32")
    if stype == "default":
        return array(arr, ctx=ctx, dtype=dtype)
    from .ndarray import sparse
    if density is not None:
        mask = np.random.rand(*shape) < density
        arr = arr * mask
    return sparse.cast_storage(array(arr, ctx=ctx), stype)


def same(a, b):
    return np.array_equal(a, b)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    rtol = 1e-5 if rtol is None else rtol
    atol = 1e-20 if atol is None else atol
    return np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Reference `test_utils.py:470 assert_almost_equal`."""
    if isinstance(a, NDArray):
        a = a.asnumpy()
    if isinstance(b, NDArray):
        b = b.asnumpy()
    rtol = 1e-5 if rtol is None else rtol
    atol = 1e-20 if atol is None else atol
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                               equal_nan=equal_nan,
                               err_msg=f"{names[0]} vs {names[1]}")


def _parse_location(sym, location, ctx, dtype=np.float32):
    if isinstance(location, dict):
        sorted_locations = [location[name] for name in sym.list_arguments()
                            if name in location]
        location = {k: array(v, ctx=ctx, dtype=getattr(v, "dtype", dtype))
                    if not isinstance(v, NDArray) else v
                    for k, v in location.items()}
        return location
    location = {k: array(v, ctx=ctx, dtype=getattr(v, "dtype", dtype))
                if not isinstance(v, NDArray) else v
                for k, v in zip(sym.list_arguments(), location)}
    return location


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Finite-difference gradients of sum(outputs) w.r.t. each argument."""
    approx_grads = {}
    for name, arr in location.items():
        base = arr.asnumpy().astype("float64")
        grad = np.zeros_like(base)
        it = np.nditer(base, flags=["multi_index"])
        while not it.finished:
            idx = it.multi_index
            orig = base[idx]
            for sign in (1, -1):
                base[idx] = orig + sign * eps
                executor.arg_dict[name]._data = \
                    executor.arg_dict[name]._data * 0 + base.astype(
                        np.asarray(executor.arg_dict[name].asnumpy()).dtype)
                outs = executor.forward(is_train=use_forward_train)
                val = sum(float(o.asnumpy().astype("float64").sum())
                          for o in outs)
                if sign == 1:
                    fplus = val
                else:
                    fminus = val
            base[idx] = orig
            grad[idx] = (fplus - fminus) / (2 * eps)
            it.iternext()
        executor.arg_dict[name]._data = executor.arg_dict[name]._data * 0 + \
            base.astype(np.asarray(executor.arg_dict[name].asnumpy()).dtype)
        approx_grads[name] = grad
    return approx_grads


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None, grad_stype_dict=None,
                           dtype=np.float64):
    """Reference `test_utils.py:790 check_numeric_gradient`: compare the
    registered (vjp) gradient against central finite differences."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx, dtype)
    if grad_nodes is None:
        grad_nodes = [name for name in sym.list_arguments()
                      if name in location]
    shapes = {k: v.shape for k, v in location.items()}
    ex = sym.simple_bind(ctx=ctx, grad_req={
        name: ("write" if name in grad_nodes else "null")
        for name in sym.list_arguments()}, **shapes)
    for k, v in location.items():
        ex.arg_dict[k]._data = ex.arg_dict[k]._data * 0 + v._data.astype(
            ex.arg_dict[k].dtype)
    if aux_states:
        for k, v in aux_states.items():
            ex.aux_dict[k]._data = ex.aux_dict[k]._data * 0 + (
                v._data if isinstance(v, NDArray) else np.asarray(v))
    ex.forward(is_train=use_forward_train)
    ex.backward()
    analytic = {name: ex.grad_dict[name].asnumpy() for name in grad_nodes}
    approx = numeric_grad(ex, {k: location[k] for k in grad_nodes},
                          eps=numeric_eps,
                          use_forward_train=use_forward_train)
    for name in grad_nodes:
        assert_almost_equal(analytic[name], approx[name], rtol=rtol,
                            atol=atol if atol is not None else 1e-4,
                            names=(f"analytic_{name}", f"numeric_{name}"))


def check_symbolic_forward(sym, location, expected, rtol=1e-5, atol=None,
                           aux_states=None, ctx=None, dtype=np.float32,
                           equal_nan=False):
    """Reference `test_utils.py:923 check_symbolic_forward`."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx, dtype)
    shapes = {k: v.shape for k, v in location.items()}
    ex = sym.simple_bind(ctx=ctx, grad_req="null", **shapes)
    for k, v in location.items():
        ex.arg_dict[k]._data = ex.arg_dict[k]._data * 0 + v._data.astype(
            ex.arg_dict[k].dtype)
    if aux_states:
        for k, v in aux_states.items():
            src = v._data if isinstance(v, NDArray) else np.asarray(v)
            ex.aux_dict[k]._data = ex.aux_dict[k]._data * 0 + src
    outputs = ex.forward(is_train=False)
    for out, exp in zip(outputs, expected):
        assert_almost_equal(out.asnumpy(), exp, rtol=rtol,
                            atol=atol if atol is not None else 1e-20,
                            equal_nan=equal_nan)
    return [o.asnumpy() for o in outputs]


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None, grad_stypes=None, equal_nan=False,
                            dtype=np.float32):
    """Reference `test_utils.py check_symbolic_backward`."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx, dtype)
    shapes = {k: v.shape for k, v in location.items()}
    if isinstance(expected, (list, tuple)):
        expected = dict(zip(sym.list_arguments(), expected))
    ex = sym.simple_bind(ctx=ctx, grad_req=grad_req, **shapes)
    for k, v in location.items():
        ex.arg_dict[k]._data = ex.arg_dict[k]._data * 0 + v._data.astype(
            ex.arg_dict[k].dtype)
    ex.forward(is_train=True)
    if out_grads is not None and not isinstance(out_grads, (list, tuple)):
        out_grads = [out_grads]
    if out_grads is not None:
        out_grads = [array(g, ctx=ctx) if not isinstance(g, NDArray) else g
                     for g in out_grads]
    ex.backward(out_grads)
    grads = {name: ex.grad_dict[name].asnumpy() for name in expected
             if ex.grad_dict.get(name) is not None}
    for name, exp in expected.items():
        if name in grads:
            assert_almost_equal(grads[name], exp, rtol=rtol,
                                atol=atol if atol is not None else 1e-20,
                                names=(f"grad_{name}", "expected"))
    return grads


def check_consistency(sym, ctx_list, scale=1.0, dtype=None,
                      grad_req="write", arg_params=None, aux_params=None,
                      tol=None, raise_on_err=True, ground_truth=None,
                      equal_nan=False, use_uniform=False):
    """Reference `test_utils.py:1204 check_consistency`: run one symbol on
    several (ctx, dtype) configurations, compare outputs and gradients.  This
    is THE TPU-vs-CPU parity harness."""
    if tol is None:
        tol = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
               np.dtype(np.float64): 1e-5, np.dtype(np.uint8): 0,
               np.dtype(np.int32): 0, np.dtype(np.int64): 0}
    elif isinstance(tol, float):
        tol = {np.dtype(t): tol for t in (np.float16, np.float32, np.float64,
                                          np.uint8, np.int32, np.int64)}

    assert len(ctx_list) > 1
    if isinstance(sym, (list, tuple)):
        sym_list = list(sym)
    else:
        sym_list = [sym] * len(ctx_list)

    output_data = []
    grad_datas = []
    arg_names = sym_list[0].list_arguments()

    # generate shared random inputs from the first config's shapes
    shapes = {k: v for k, v in ctx_list[0].items() if k != "ctx" and
              not k.endswith("type_dict")}
    np.random.seed(0)
    base_inputs = {}

    for config, s in zip(ctx_list, sym_list):
        ctx = config["ctx"]
        cshapes = {k: v for k, v in config.items() if k != "ctx" and
                   not k.endswith("type_dict")}
        type_dict = config.get("type_dict", {})
        ex = s.simple_bind(ctx=ctx, grad_req=grad_req, type_dict=type_dict,
                           **cshapes)
        for name in arg_names:
            if name not in base_inputs:
                base_inputs[name] = np.random.normal(
                    size=ex.arg_dict[name].shape, scale=scale)
            src = base_inputs[name]
            ex.arg_dict[name]._data = ex.arg_dict[name]._data * 0 + \
                src.astype(ex.arg_dict[name].dtype)
        if arg_params:
            for k, v in arg_params.items():
                ex.arg_dict[k]._data = ex.arg_dict[k]._data * 0 + \
                    np.asarray(v).astype(ex.arg_dict[k].dtype)
        if aux_params:
            for k, v in aux_params.items():
                ex.aux_dict[k]._data = ex.aux_dict[k]._data * 0 + \
                    np.asarray(v).astype(ex.aux_dict[k].dtype)
        outs = ex.forward(is_train=grad_req != "null")
        if grad_req != "null":
            ex.backward()
            grad_datas.append({name: ex.grad_dict[name].asnumpy()
                               for name in arg_names
                               if ex.grad_dict.get(name) is not None})
        output_data.append([o.asnumpy() for o in outs])

    # compare everything against the most precise config (last one by
    # convention in the reference: fp64 cpu last)
    gt_idx = len(output_data) - 1
    max_dtype = max((np.dtype(o.dtype) for o in output_data[gt_idx]),
                    key=lambda d: d.itemsize)
    for i, outs in enumerate(output_data):
        if i == gt_idx:
            continue
        this_tol = max(tol.get(np.dtype(outs[0].dtype), 1e-3),
                       tol.get(max_dtype, 1e-5))
        for o, gt in zip(outs, output_data[gt_idx]):
            assert_almost_equal(o.astype("float64"), gt.astype("float64"),
                                rtol=this_tol, atol=this_tol,
                                equal_nan=equal_nan)
    if grad_req != "null":
        for i, grads in enumerate(grad_datas):
            if i == gt_idx:
                continue
            for name in grads:
                this_tol = max(tol.get(np.dtype(grads[name].dtype), 1e-3),
                               tol.get(max_dtype, 1e-5))
                assert_almost_equal(grads[name].astype("float64"),
                                    grad_datas[gt_idx][name].astype("float64"),
                                    rtol=this_tol, atol=this_tol,
                                    names=(f"grad_{name}_{i}", "ground_truth"),
                                    equal_nan=equal_nan)
    return output_data


def get_mnist_like(num=1000, seed=0):
    """Synthetic MNIST-like dataset (deterministic) for e2e train tests —
    replaces the reference's downloaded MNIST in this zero-egress env."""
    rng = np.random.RandomState(seed)
    # 10 class prototypes + noise: linearly separable enough for LeNet/MLP
    protos = rng.rand(10, 1, 28, 28).astype("f4")
    labels = rng.randint(0, 10, num)
    imgs = protos[labels] + 0.1 * rng.rand(num, 1, 28, 28).astype("f4")
    return imgs.astype("f4"), labels.astype("f4")


def list_gpus():
    from .context import num_gpus
    return list(range(num_gpus()))
