"""How the `resnet50_v1` configuration is composed in the program under
test, and how its parameter names map onto the plain reference's leaves.
The composition is `chip_smoke.py`'s: the gluon model-zoo `resnet50_v1`
under `SoftmaxOutput`."""
import re


def build_symbol(mx, cfg):
    from incubator_mxnet_tpu import sym
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import (
        ResNetV1, BottleneckV1)
    widths = [64] + [w[1] for w in cfg["widths"]]
    net = ResNetV1(BottleneckV1, list(cfg["layers"]), widths,
                   classes=cfg["classes"])
    out = net(sym.Variable("data"))
    return sym.SoftmaxOutput(out, name="softmax")


def input_descs(cfg, batch):
    """(data shape, label shape) of one batch."""
    s = cfg["image_size"]
    return (batch, cfg["in_channels"], s, s), (batch,)


def _pairs(cfg, prefix):
    """(reference leaf, program name) for every parameter and statistic."""
    def unit(ref, conv, bn, bias):
        out = [(ref + ".w", f"{conv}_weight"), (ref + ".g", f"{bn}_gamma"),
               (ref + ".beta", f"{bn}_beta"),
               (ref + ".mean", f"{bn}_running_mean"),
               (ref + ".var", f"{bn}_running_var")]
        if bias:
            out.append((ref + ".b", f"{conv}_bias"))
        return out

    pairs = unit("stem", prefix + "conv2d0", prefix + "batchnorm0", False)
    for si, n in enumerate(cfg["layers"]):
        sp = f"{prefix}stage{si + 1}_"
        c = 0
        for bi in range(n):
            ref = f"s{si + 1}.b{bi}"
            for j, (part, bias) in enumerate(
                    [(".c1", True), (".c2", False), (".c3", True)] +
                    ([(".ds", False)] if bi == 0 else [])):
                pairs += unit(ref + part, f"{sp}conv2d{c + j}",
                              f"{sp}batchnorm{c + j}", bias)
            c += 4 if bi == 0 else 3
    return pairs + [("fc.w", prefix + "dense0_weight"),
                    ("fc.b", prefix + "dense0_bias")]


def _prefix(names):
    """The block prefix the model zoo gave this instance (`resnetv1_0_`)."""
    for n in names:
        m = re.match(r"(.*?)(stage\d+_)?(conv2d|batchnorm|dense)\d+_", n)
        if m:
            return m.group(1)
    raise ValueError(f"no ResNet parameter among {list(names)[:3]}...")


def to_program(leaves, cfg, names):
    """Reference leaves -> {program name: array} for the names given."""
    ref_of = {prog: ref for ref, prog in _pairs(cfg, _prefix(names))}
    return {n: leaves[ref_of[n]] for n in names if n in ref_of}


def from_program(arrays, cfg):
    """{program name: array} -> {reference leaf: array}."""
    prog_of = dict(_pairs(cfg, _prefix(list(arrays))))
    return {ref: arrays[prog] for ref, prog in prog_of.items()
            if prog in arrays}


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one image, from the shapes."""
    s = cfg["image_size"]
    total = flops.Count()
    h = s // 2
    total.conv(cfg["in_channels"], 64, 7, h, h, first=True)
    h = h // 2
    cin = 64
    for si, (n, (mid, cout)) in enumerate(zip(cfg["layers"], cfg["widths"])):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            ho = h // stride
            total.conv(cin if bi == 0 else cout, mid, 1, ho, ho)
            total.conv(mid, mid, 3, ho, ho)
            total.conv(mid, cout, 1, ho, ho)
            if bi == 0:
                total.conv(cin, cout, 1, ho, ho)
            h = ho
        cin = cout
    total.dense(cin, cfg["classes"])
    return total
