"""CPU-vs-TPU parity battery over the ENTIRE operator registry.

Reference pattern: `tests/python/gpu/test_operator_gpu.py` imports the
whole CPU operator suite and reruns it under the GPU context.  Here the
registry itself is the source of truth: every distinct operator is either

  * exercised through `check_consistency` (outputs AND gradients compared
    between mx.cpu() and mx.tpu() with per-dtype tolerances), via an
    auto-generated generic case or an entry in CASES, or
  * listed in SKIP with the triage reason,

and a completeness guard fails the suite if a newly-registered operator is
neither — new ops must be triaged into the parity lane.

Matmul-bearing ops run under `jax.default_matmul_precision("highest")`:
the MXU's default bf16 ingestion is a documented precision envelope tested
separately (`test_operator_tpu.py`), not a parity bug.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import sym as S
from incubator_mxnet_tpu.ops import registry as _reg
from incubator_mxnet_tpu.test_utils import check_consistency


def _case(shapes, grad_req="write", tol=None, data_scale=1.0, **params):
    return {"shapes": shapes, "grad_req": grad_req, "tol": tol,
            "scale": data_scale, "params": params}


V = (3, 4)          # generic vector-ish input
M = (4, 4)          # square matrix (linalg)
IMG = (2, 3, 8, 8)  # NCHW image
SEQ = (5, 3, 6)     # TNC sequence

# -- explicit cases for ops the generic profile can't drive -----------------
CASES = {
    # heads / NN layers
    "Activation": _case({"data": V}, act_type="relu"),
    "Cast": _case({"data": V}, dtype="float64"),
    "Embedding": _case({"data": None}, grad_req="null"),  # built below
    "LRN": _case({"data": IMG}, nsize=3),
    "Pad": _case({"data": IMG}, mode="constant",
                 pad_width=(0, 0, 0, 0, 1, 1, 2, 2)),
    "SliceChannel": _case({"data": (4, 6)}, num_outputs=2),
    "GridGenerator": _case({"data": (2, 6)}, transform_type="affine",
                           target_shape=(8, 8)),
    "ROIPooling": _case({"data": IMG, "rois": (2, 5)}, grad_req="null",
                        pooled_size=(2, 2), spatial_scale=1.0),
    "_contrib_ROIAlign": _case({"data": IMG, "rois": (2, 5)},
                               grad_req="null", pooled_size=(2, 2),
                               spatial_scale=1.0),
    "Convolution": _case({"data": IMG}, kernel=(3, 3), num_filter=4,
                         pad=(1, 1)),
    "Deconvolution": _case({"data": IMG}, kernel=(3, 3), num_filter=4),
    "FullyConnected": _case({"data": (4, 6)}, num_hidden=5),
    "Concat": _case({"arg0": V, "arg1": V}, num_args=2, dim=1),
    "add_n": _case({"arg0": V, "arg1": V}, num_args=2),
    "stack": _case({"arg0": V, "arg1": V}, num_args=2),
    "LeakyReLU": _case({"data": V}, act_type="leaky"),
    "UpSampling": _case({"arg0": IMG}, num_args=1, scale=2,
                        sample_type="nearest"),
    "Crop": _case({"arg0": IMG}, num_args=1, h_w=(5, 5)),
    "SequenceLast": _case({"data": SEQ}),
    "SequenceMask": _case({"data": SEQ}),
    "SequenceReverse": _case({"data": SEQ}),
    "ctc_loss": _case({"data": (6, 2, 5), "label": (2, 3)},
                      grad_req="null"),
    "BatchNorm": _case({"data": IMG}, grad_req="null",
                       use_global_stats=True, fix_gamma=False),
    "_contrib_SyncBatchNorm": _case({"data": IMG}, grad_req="null",
                                    use_global_stats=True, fix_gamma=False,
                                    key="bn0"),
    "cast_storage": _case({"data": V}, stype="default"),
    # built by name in _run_case (structured inputs / subgraph attrs)
    "khatri_rao": _case({"data": None}),
    "_histogram": _case({"data": None}),
    "_ravel_multi_index": _case({"data": None}),
    "_unravel_index": _case({"data": None}),
    "_contrib_count_sketch": _case({"data": None}),
    "_foreach": _case({"data": None}),
    "_while_loop": _case({"data": None}),
    "_cond": _case({"data": None}),
    "_contrib_DeformableConvolution": _case(
        {"data": IMG, "offset": (2, 18, 6, 6)}, kernel=(3, 3),
        num_filter=4, tol=5e-3),
    "_contrib_DeformablePSROIPooling": _case(
        {"data": (2, 8, 8, 8), "rois": (2, 5), "trans": (2, 2, 2, 2)},
        grad_req="null", spatial_scale=1.0, output_dim=2, group_size=2,
        pooled_size=2, part_size=2, sample_per_part=2, trans_std=0.1),
    "LayerNorm": _case({"data": (4, 6)}),
    "BlockwiseAttention": _case({"query": (2, 8, 8), "key": (2, 8, 8),
                                 "value": (2, 8, 8)}, num_heads=2),
    "topk": _case({"data": (4, 6)}, grad_req="null", k=2),
    # the hybrid LM's operators (PR 30), at sizes no kernel tiles: the
    # chip takes the same XLA drivers the CPU takes
    "RMSNorm": _case({"data": (4, 6), "gamma": (6,)}),
    "RotaryEmbedding": _case({"data": (2, 5, 3, 8)}, rotary_dim=4),
    "CausalConv1D": _case({"data": (2, 9, 6), "weight": (6, 4)}, kernel=4),
    "GatedDeltaRule": _case({"data": None}),    # built below: g < 0
    # 2 of 8 experts a token, experts 2-5 held: the routing is a choice,
    # so the weights are small enough that no two probabilities of a
    # token come within a rounding of each other
    "RoutedExperts": _case(
        {"data": (2, 8, 16), "router_weight": (8, 16),
         "gate_weight": (4, 12, 16), "up_weight": (4, 12, 16),
         "down_weight": (4, 16, 12), "load": (4,), "dropped": (2,)},
        data_scale=0.5, num_experts=8, top_k=2, experts_offset=2,
        experts_count=4),
    # scalar-op family: one representative shape, scalar=2.5
    **{n: _case({"data": V}, scalar=2.5) for n in (
        "_div_scalar", "_maximum_scalar", "_minimum_scalar",
        "_minus_scalar", "_mul_scalar", "_plus_scalar", "_rdiv_scalar",
        "_rminus_scalar")},
    **{n: _case({"data": V}, grad_req="null", scalar=2.5) for n in (
        "_equal_scalar", "_greater_equal_scalar", "_greater_scalar",
        "_lesser_equal_scalar", "_lesser_scalar", "_logical_and_scalar",
        "_logical_or_scalar", "_logical_xor_scalar",
        "_not_equal_scalar")},
    "_mod_scalar": _case({"data": V}, grad_req="null", scalar=2.5),
    "_rmod_scalar": _case({"data": V}, grad_req="null", scalar=2.5),
    "_hypot_scalar": _case({"data": V}, scalar=2.5),
    "_power_scalar": _case({"data": V}, grad_req="null", scalar=2.0),
    "_rpower_scalar": _case({"data": V}, grad_req="null", scalar=2.0),
    # shape/index manipulation
    "broadcast_to": _case({"data": (1, 4)}, shape=(3, 4)),
    "Reshape": _case({"data": V}, shape=(4, 3)),
    "_contrib_MultiBoxPrior": _case({"data": IMG}, grad_req="null",
                                    sizes=(0.5, 0.25), ratios=(1.0, 2.0)),
    "_contrib_BilinearResize2D": _case({"data": IMG}, height=4, width=4),
    "expand_dims": _case({"data": V}, axis=1),
    "one_hot": _case({"data": None}, grad_req="null"),  # built below
    "repeat": _case({"data": V}, repeats=2),
    "reverse": _case({"data": V}, axis=0),
    "tile": _case({"data": V}, reps=(2, 1)),
    "slice": _case({"data": V}, begin=(0, 1), end=(2, 3)),
    "slice_axis": _case({"data": V}, axis=1, begin=0, end=2),
    "depth_to_space": _case({"data": (1, 4, 3, 3)}, block_size=2),
    "space_to_depth": _case({"data": (1, 1, 4, 4)}, block_size=2),
    "_eye": _case({}, grad_req="null", N=4),
    "_full": _case({}, grad_req="null", shape=(2, 3), value=1.5),
    "_linspace": _case({}, grad_req="null", start=0.0, stop=1.0, num=7),
    "_contrib_interleaved_matmul_selfatt_qk": _case(
        {"queries_keys_values": (4, 2, 18)}, heads=2),
    "_contrib_interleaved_matmul_selfatt_valatt": _case(
        {"queries_keys_values": (4, 2, 18), "attention": (4, 4, 4)},
        heads=2),
}

# -- triaged exclusions ------------------------------------------------------
SKIP = {
    # int8 lane: covered by tests/test_quantization.py end-to-end; the
    # int domain makes gradient parity meaningless
    "_contrib_quantize": "int8 lane; covered in test_quantization.py",
    "_contrib_quantize_v2": "int8 lane",
    "_contrib_requantize": "int8 lane",
    "_contrib_quantized_conv": "int8 lane",
    "_contrib_quantized_fully_connected": "int8 lane",
    "_contrib_quantized_pooling": "int8 lane",
    "_sg_pallas_fc_relu": "subgraph-internal fused op; tested in "
                          "test_subgraph.py",
    "_index": "indexing helper with data-dependent shapes (host-side)",
    "scatter_nd": "integer index inputs; covered in test_ndarray.py",
    "_contrib_bipartite_matching": "host-side greedy matching; covered in "
                                   "test_image_detection.py",
    "_contrib_MultiBoxTarget": "detection target assembly; covered in "
                               "test_image_detection.py",
    "_contrib_MultiBoxDetection": "nms/decode pipeline needing structured "
                                  "(cls_prob, loc_pred, anchor) inputs; "
                                  "covered in test_image_detection.py",
    "linalg_syevd": "eigenvector sign/ordering is backend-defined; "
                    "reconstruction-based checks live in test_operator.py",
    "linalg_gelqf": "LQ factor signs are backend-defined; reconstruction "
                    "checks live in test_operator.py",
    # RNG family: same threefry key chain on both devices, but the op
    # consumes the GLOBAL key singleton — covered by seeded-moments tests
    # in tests/test_operator.py; cross-device parity is by construction
    # (counter-based threefry is device-independent)
    **{n: "rng op; counter-based threefry is device-independent by design"
       for n in ("Dropout", "RNN", "_random_exponential", "_random_gamma",
                 "_random_generalized_negative_binomial",
                 "_random_negative_binomial", "_random_normal",
                 "_random_poisson", "_random_randint", "_random_uniform",
                 "_sample_gamma", "_sample_multinomial", "_sample_normal",
                 "_sample_uniform", "_shuffle")},
}

# generic ops that need a domain/shape tweak
TWEAKS = {
    "log": dict(use_abs=True), "log10": dict(use_abs=True),
    "log2": dict(use_abs=True), "sqrt": dict(use_abs=True),
    "rsqrt": dict(use_abs=True), "log1p": dict(use_abs=True),
    "cbrt": dict(use_abs=True), "rcbrt": dict(use_abs=True),
    "reciprocal": dict(use_abs=True),
    "gamma": dict(use_abs=True), "gammaln": dict(use_abs=True),
    "arccosh": dict(shift=2.0),
    "erfinv": dict(scale=0.3),
    "InstanceNorm": dict(shapes={"data": IMG}),
    "_contrib_AdaptiveAvgPooling2D": dict(shapes={"data": IMG},
                                          params={"output_size": (2, 2)}),
    "broadcast_power": dict(use_abs=True),
    "arcsin": dict(scale=0.3), "arccos": dict(scale=0.3),
    "arctanh": dict(scale=0.3),
    "Pooling": dict(shapes={"data": IMG}),
    "Pooling_v1": dict(shapes={"data": IMG}),
    "BilinearSampler": dict(shapes={"data": IMG, "grid": (2, 2, 8, 8)},
                            scale=0.5),
    "SpatialTransformer": dict(shapes={"data": IMG, "loc": (2, 6)},
                               params={"transform_type": "affine",
                                       "sampler_type": "bilinear",
                                       "target_shape": (8, 8)}),
    "Correlation": dict(shapes={"data1": IMG, "data2": IMG},
                        grad_req="null"),
    "batch_dot": dict(shapes={"lhs": (2, 3, 4), "rhs": (2, 4, 5)}),
    "dot": dict(shapes={"lhs": (3, 4), "rhs": (4, 5)}),
    "linalg_gemm": dict(shapes={"A": M, "B": M, "C": M}),
    "linalg_gemm2": dict(shapes={"A": M, "B": M}),
    "linalg_potrf": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "linalg_potri": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "linalg_trsm": dict(shapes={"A": M, "B": M}, spd=True,
                        grad_req="null"),
    "linalg_trmm": dict(shapes={"A": M, "B": M}, spd=True,
                        grad_req="null"),
    "linalg_sumlogdiag": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "linalg_syrk": dict(shapes={"A": M}, grad_req="null"),
    "linalg_slogdet": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "linalg_extractdiag": dict(shapes={"A": M}),
    "linalg_makediag": dict(shapes={"A": (4,)}),
    "linalg_extracttrian": dict(shapes={"A": M}),
    "linalg_maketrian": dict(shapes={"A": (10,)}),
    "linalg_inverse": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "linalg_det": dict(shapes={"A": M}, spd=True, grad_req="null"),
    "SVMOutput": dict(shapes={"data": (4, 5), "label": (4,)},
                      grad_req="null"),
    "SoftmaxOutput": dict(shapes={"data": (4, 5),
                                  "softmax_label": (4,)},
                          grad_req="null"),
}


def _distinct_ops():
    seen = {}
    for name in _reg.list_ops():
        op = _reg.get(name)
        seen.setdefault(op.name, op)
    return seen


def _strict_matmul():
    import jax
    return jax.default_matmul_precision("highest")


def _generic_names():
    from incubator_mxnet_tpu.ops.registry import REQUIRED
    out = []
    for n, op in sorted(_distinct_ops().items()):
        if n in CASES or n in SKIP:
            continue
        if n.startswith("_grad_of_") or n.startswith("_cached_op"):
            # derived ops materialize lazily while earlier tests run
            # (create_graph gradients; hybridize() CachedOp wrappers);
            # they are internal wrappers of already-triaged base ops and
            # user graphs, not public surface
            continue
        req = [k for k, v in op.params.items() if v is REQUIRED]
        if op.needs_rng or op.nin < 0 or req:
            out.append((n, "unhandled"))
        else:
            out.append((n, "generic"))
    return out


def test_registry_fully_triaged():
    """Every registered op is a case, a generic, or a documented skip."""
    unhandled = [n for n, kind in _generic_names() if kind == "unhandled"]
    assert not unhandled, (
        "ops neither cased nor skipped (triage them into CASES or SKIP): "
        f"{unhandled}")


def _run_case(name):
    op = _reg.get(name)
    case = CASES.get(name)
    tweak = TWEAKS.get(name, {})
    grad_req = (case or {}).get("grad_req", tweak.get("grad_req", "write"))
    tol = (case or {}).get("tol") or 1e-3
    params = dict((case or {}).get("params", tweak.get("params", {})))

    if name == "khatri_rao":
        s = S.khatri_rao(S.Variable("a"), S.Variable("b"))
        ctxs = [{"ctx": mx.cpu(), "a": (2, 3), "b": (4, 3)},
                {"ctx": mx.tpu(), "a": (2, 3), "b": (4, 3)}]
        check_consistency(s, ctxs, grad_req="write")
        return
    if name == "_histogram":
        s = S.Group(list(S.histogram(S.Variable("data"), bin_cnt=5,
                                     range=(-2, 2))))
        ctxs = [{"ctx": mx.cpu(), "data": (40,)},
                {"ctx": mx.tpu(), "data": (40,)}]
        check_consistency(s, ctxs, grad_req="null")
        return
    if name in ("_ravel_multi_index", "_unravel_index"):
        if name == "_unravel_index":
            s = S.unravel_index(S.Variable("data"), shape=(3, 4))
            idx = np.random.randint(0, 12, (6,)).astype("f4")
            shapes = {"data": (6,)}
        else:
            s = S.ravel_multi_index(S.Variable("data"), shape=(3, 4))
            idx = np.stack([np.random.randint(0, 3, 6),
                            np.random.randint(0, 4, 6)]).astype("f4")
            shapes = {"data": (2, 6)}
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"data": idx})
        return
    if name == "_contrib_count_sketch":
        s = S.contrib.count_sketch(S.Variable("data"), S.Variable("h"),
                                   S.Variable("s"), out_dim=5)
        h = np.random.randint(0, 5, (8,)).astype("f4")
        sg = np.random.choice([-1.0, 1.0], 8).astype("f4")
        shapes = {"data": (3, 8), "h": (8,), "s": (8,)}
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"h": h, "s": sg})
        return
    if name == "_foreach":
        w = S.Variable("w")
        outs, st = S.contrib.foreach(
            lambda x, st_: (S.broadcast_mul(x, w) + st_,
                            S.broadcast_mul(x, w) + st_),
            S.Variable("data"), S.Variable("init"))
        s = S.Group([outs, st])
        shapes = {"data": (5, 4), "init": (4,), "w": (4,)}
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        check_consistency(s, ctxs, grad_req="write")
        return
    if name == "_while_loop":
        outs, fin = S.contrib.while_loop(
            cond=lambda i, acc: i < 4,
            func=lambda i, acc: ([acc + i], [i + 1, acc + i]),
            loop_vars=[S.Variable("i0"), S.Variable("acc0")],
            max_iterations=6)
        s = S.Group(list(outs) + list(fin))
        shapes = {"i0": (1,), "acc0": (3,)}
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"i0": np.zeros(1, "f4")})
        return
    if name == "_cond":
        a = S.Variable("a")
        b = S.Variable("b")
        s = S.contrib.cond(S.sum(a) < 1.0,
                           lambda: (a + 5) * (b + 5),
                           lambda: (a - 5) * (b - 5))
        shapes = {"a": (3,), "b": (3,)}
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        check_consistency(s, ctxs, grad_req="null")
        return
    if name == "GatedDeltaRule":
        s = S.GatedDeltaRule(*(S.Variable(n) for n in (
            "query", "key", "value", "g", "beta")), num_heads=2,
            num_v_heads=4, chunk_size=8)
        shapes = {"query": (2, 20, 16), "key": (2, 20, 16),
                  "value": (2, 20, 24), "g": (2, 20, 4), "beta": (2, 20, 4)}
        rng = np.random.RandomState(0)
        ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
        with _strict_matmul():
            check_consistency(s, ctxs, grad_req="write", arg_params={
                "g": -np.abs(rng.normal(size=shapes["g"])) - 0.05,
                "beta": 1 / (1 + np.exp(-rng.normal(size=shapes["beta"])))})
        return
    if name == "Embedding":
        data = S.Variable("data")
        s = S.Embedding(data, input_dim=10, output_dim=4, name="emb")
        idx = np.random.randint(0, 10, (6,)).astype("f4")
        ctxs = [{"ctx": mx.cpu(), "data": (6,)},
                {"ctx": mx.tpu(), "data": (6,)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"data": idx})
        return
    if name == "one_hot":
        data = S.Variable("data")
        s = S.one_hot(data, depth=5)
        idx = np.random.randint(0, 5, (6,)).astype("f4")
        ctxs = [{"ctx": mx.cpu(), "data": (6,)},
                {"ctx": mx.tpu(), "data": (6,)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"data": idx})
        return
    if name == "pick":
        s = S.pick(S.Variable("data"), S.Variable("index"))
        idx = np.random.randint(0, 5, (6,)).astype("f4")
        ctxs = [{"ctx": mx.cpu(), "data": (6, 5), "index": (6,)},
                {"ctx": mx.tpu(), "data": (6, 5), "index": (6,)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"index": idx})
        return
    if name == "batch_take":
        s = S.batch_take(S.Variable("data"), S.Variable("indices"))
        idx = np.random.randint(0, 5, (6,)).astype("f4")
        ctxs = [{"ctx": mx.cpu(), "data": (6, 5), "indices": (6,)},
                {"ctx": mx.tpu(), "data": (6, 5), "indices": (6,)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"indices": idx})
        return
    if name == "_contrib_box_iou":
        s = getattr(S, "_internal")._contrib_box_iou(
            S.Variable("lhs"), S.Variable("rhs"))
        rng = np.random.RandomState(0)
        mk = lambda n: np.sort(rng.rand(n, 2, 2), axis=1) \
            .reshape(n, 4).astype("f4")  # valid (xmin, ymin, xmax, ymax)
        ctxs = [{"ctx": mx.cpu(), "lhs": (3, 4), "rhs": (5, 4)},
                {"ctx": mx.tpu(), "lhs": (3, 4), "rhs": (5, 4)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"lhs": mk(3), "rhs": mk(5)})
        return
    if name == "_contrib_index_copy":
        s = getattr(S, "_internal")._contrib_index_copy(
            S.Variable("data"), S.Variable("index"), S.Variable("new"))
        idx = np.array([0, 2], "f4")
        ctxs = [{"ctx": mx.cpu(), "data": (4, 3), "index": (2,),
                 "new": (2, 3)},
                {"ctx": mx.tpu(), "data": (4, 3), "index": (2,),
                 "new": (2, 3)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"index": idx})
        return
    if name == "gather_nd":
        s = S.gather_nd(S.Variable("data"), S.Variable("indices"))
        idx = np.random.randint(0, 4, (2, 5)).astype("f4")
        ctxs = [{"ctx": mx.cpu(), "data": (4, 4), "indices": (2, 5)},
                {"ctx": mx.tpu(), "data": (4, 4), "indices": (2, 5)}]
        check_consistency(s, ctxs, grad_req="null",
                          arg_params={"indices": idx})
        return

    if case is not None:
        shapes = dict(case["shapes"])
    else:
        shapes = dict(tweak.get("shapes") or {})
        if not shapes:
            nin = op.num_inputs({})
            in_names = op.list_input_names(params) or \
                [f"arg{i}" for i in range(nin)]
            shapes = {in_names[i] if i else
                      ("data" if in_names[0] in (None, "data") else
                       in_names[0]): V for i in range(max(nin, 0))}

    scale = (case or {}).get("scale", tweak.get("scale", 1.0))
    spd = tweak.get("spd", False)
    shift = tweak.get("shift", 0.0)
    use_abs = tweak.get("use_abs", False)

    # build the symbol: one Variable per input slot
    in_names = op.list_input_names(params) or list(shapes)
    vars_ = [S.Variable(n) for n in (in_names if in_names else list(shapes))]
    fn = getattr(S, name, None) or getattr(S._internal, name)
    if op.nin == 0 or not shapes:
        s = fn(**params)
        check_consistency(s, [{"ctx": mx.cpu()}, {"ctx": mx.tpu()}],
                          grad_req="null", tol=tol)
        return
    s = fn(*vars_, **params)

    arg_params = None
    if spd:
        a = np.random.normal(size=M)
        spd_mat = a @ a.T + 4 * np.eye(M[0])
        arg_params = {list(shapes)[0]: spd_mat}
        for extra in list(shapes)[1:]:
            arg_params[extra] = np.random.normal(size=shapes[extra])
    elif use_abs or shift:
        arg_params = {k: np.abs(np.random.normal(scale=scale, size=v)) +
                      shift + (0.1 if use_abs else 0.0)
                      for k, v in shapes.items()}

    ctxs = [dict(shapes, ctx=mx.cpu()), dict(shapes, ctx=mx.tpu())]
    with _strict_matmul():
        check_consistency(s, ctxs, grad_req=grad_req, tol=tol, scale=scale,
                          arg_params=arg_params)


ALL_NAMES = sorted(set(list(_distinct_ops())) - set(SKIP))

# optional sharding for slow single-chip runs: MXNET_PARITY_SHARD="i/n"
import os as _os
_shard = _os.environ.get("MXNET_PARITY_SHARD")
if _shard:
    _i, _n = (int(x) for x in _shard.split("/"))
    ALL_NAMES = ALL_NAMES[_i::_n]


@pytest.mark.parametrize("name", ALL_NAMES)
def test_op_parity(name):
    _run_case(name)
