#!/usr/bin/env python
"""SSD-VGG16 training — BASELINE config #5 (reference `example/ssd/train.py`
with `symbol/legacy_vgg16_ssd_300.py`).

Builds the SSD detection head over a VGG16-reduced backbone with
multi-scale anchors, trains with the reference's composite objective
(softmax over classes with hard-negative-friendly ignore masking + smooth
L1 on box offsets, both from `MultiBoxTarget`), and runs `MultiBoxDetection`
NMS decoding for evaluation.  Synthetic box data stands in when no dataset
is on disk (zero-egress image); pass --data-train for a real .rec pack of
packed [cls,x1,y1,x2,y2] labels.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import sym
from incubator_mxnet_tpu.io import NDArrayIter, DataBatch, DataDesc

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)-15s %(message)s")


def _conv_block(data, name, num_filter, n_convs):
    for i in range(n_convs):
        data = sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                               num_filter=num_filter,
                               name=f"{name}_conv{i}")
        data = sym.Activation(data, act_type="relu")
    return sym.Pooling(data, kernel=(2, 2), stride=(2, 2), pool_type="max",
                       name=f"{name}_pool"), data


def vgg16_reduced(data, small=False):
    """VGG16 body returning the multi-scale feature maps SSD taps
    (reference `symbol/legacy_vgg16_ssd_300.py` conv4_3 + conv7 + extras)."""
    f = 0.25 if small else 1.0
    p1, _ = _conv_block(data, "b1", int(64 * f), 2)
    p2, _ = _conv_block(p1, "b2", int(128 * f), 2)
    p3, _ = _conv_block(p2, "b3", int(256 * f), 3)
    p4, c4 = _conv_block(p3, "b4", int(512 * f), 3)
    p5, _ = _conv_block(p4, "b5", int(512 * f), 3)
    # fc6/fc7 as dilated convs (the "reduced" trick)
    fc6 = sym.Convolution(p5, kernel=(3, 3), pad=(3, 3), dilate=(3, 3),
                          num_filter=int(1024 * f), name="fc6")
    fc6 = sym.Activation(fc6, act_type="relu")
    fc7 = sym.Convolution(fc6, kernel=(1, 1), num_filter=int(1024 * f),
                          name="fc7")
    fc7 = sym.Activation(fc7, act_type="relu")
    # extra feature layers at decreasing resolution
    e1 = sym.Convolution(fc7, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         num_filter=int(256 * f), name="extra1")
    e1 = sym.Activation(e1, act_type="relu")
    e2 = sym.Convolution(e1, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         num_filter=int(128 * f), name="extra2")
    e2 = sym.Activation(e2, act_type="relu")
    return [c4, fc7, e1, e2]


def ssd_symbol(num_classes, small=False):
    """SSD head: per-scale anchor priors + class/box conv predictors, the
    MultiBoxTarget training objective, MultiBoxDetection decode."""
    data = sym.Variable("data")
    label = sym.Variable("label")
    feats = vgg16_reduced(data, small=small)
    sizes = [(0.1, 0.14), (0.27, 0.38), (0.54, 0.66), (0.78, 0.9)]
    ratios = [(1.0, 2.0, 0.5)] * 4

    cls_preds, loc_preds, anchors = [], [], []
    for i, (feat, sz, rt) in enumerate(zip(feats, sizes, ratios)):
        na = len(sz) + len(rt) - 1
        cls = sym.Convolution(feat, kernel=(3, 3), pad=(1, 1),
                              num_filter=na * (num_classes + 1),
                              name=f"cls_pred{i}")
        loc = sym.Convolution(feat, kernel=(3, 3), pad=(1, 1),
                              num_filter=na * 4, name=f"loc_pred{i}")
        # (B, A*(C+1), H, W) -> (B, H*W*A, C+1) -> flat
        cls = sym.transpose(cls, axes=(0, 2, 3, 1))
        cls_preds.append(sym.Reshape(cls, shape=(0, -1, num_classes + 1)))
        loc = sym.transpose(loc, axes=(0, 2, 3, 1))
        loc_preds.append(sym.Reshape(loc, shape=(0, -1)))
        anchors.append(sym.MultiBoxPrior(feat, sizes=sz, ratios=rt,
                                         clip=True))
    cls_concat = sym.concat(*cls_preds, dim=1)             # (B, N, C+1)
    cls_concat = sym.transpose(cls_concat, axes=(0, 2, 1))  # (B, C+1, N)
    loc_concat = sym.concat(*loc_preds, dim=1)             # (B, N*4)
    anchor_concat = sym.concat(*anchors, dim=1)            # (1, N, 4)

    tmp = sym.MultiBoxTarget(anchor_concat, label, cls_concat,
                             overlap_threshold=0.5,
                             negative_mining_ratio=3,
                             variances=(0.1, 0.1, 0.2, 0.2),
                             name="multibox_target")
    loc_target, loc_mask, cls_target = tmp[0], tmp[1], tmp[2]

    cls_prob = sym.SoftmaxOutput(cls_concat, cls_target,
                                 ignore_label=-1, use_ignore=True,
                                 multi_output=True,
                                 normalization="valid", name="cls_prob")
    loc_diff = loc_mask * (loc_concat - loc_target)
    loc_loss = sym.MakeLoss(sym.smooth_l1(loc_diff, scalar=1.0),
                            grad_scale=1.0, normalization="valid",
                            name="loc_loss")
    det = sym.MultiBoxDetection(cls_prob, loc_concat, anchor_concat,
                                nms_threshold=0.45, force_suppress=False,
                                variances=(0.1, 0.1, 0.2, 0.2),
                                name="detection")
    det = sym.BlockGrad(det)
    return sym.Group([cls_prob, loc_loss, sym.BlockGrad(cls_target), det])


class SyntheticDetIter(NDArrayIter):
    """Images with 1-3 colored rectangles; labels (B, M, 5)."""

    def __init__(self, n, batch_size, image=128, num_classes=3, max_obj=3):
        rng = np.random.RandomState(0)
        X = rng.normal(0, 0.1, (n, 3, image, image)).astype("f4")
        Y = np.full((n, max_obj, 5), -1.0, "f4")
        for i in range(n):
            for j in range(rng.randint(1, max_obj + 1)):
                cls = rng.randint(0, num_classes)
                w, h = rng.uniform(0.2, 0.5, 2)
                x1 = rng.uniform(0, 1 - w)
                y1 = rng.uniform(0, 1 - h)
                Y[i, j] = [cls, x1, y1, x1 + w, y1 + h]
                xa, ya = int(x1 * image), int(y1 * image)
                xb, yb = int((x1 + w) * image), int((y1 + h) * image)
                X[i, cls % 3, ya:yb, xa:xb] += 1.0
        super().__init__(X, Y, batch_size=batch_size, shuffle=True,
                         label_name="label")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--num-epochs", type=int, default=3)
    ap.add_argument("--num-classes", type=int, default=3)
    ap.add_argument("--image", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--small", action="store_true",
                    help="quarter-width backbone for smoke runs")
    ap.add_argument("--ctx", default="tpu", choices=["tpu", "cpu"],
                    help="device context; tpu fails without a chip")
    args = ap.parse_args()

    net = ssd_symbol(args.num_classes, small=args.small)
    train = SyntheticDetIter(args.n, args.batch_size, args.image,
                             args.num_classes)
    ctx = mx.Context(args.ctx)
    mod = mx.mod.Module(net, context=ctx, data_names=("data",),
                        label_names=("label",))

    class MultiBoxMetric(mx.metric.EvalMetric):
        """Cross-entropy + smooth-L1 readout (reference metric.py of the
        ssd example)."""

        def __init__(self):
            super().__init__("MultiBox")
            self.num = 2
            self.reset()

        def reset(self):
            self.sum_ce, self.n_ce = 0.0, 0
            self.sum_l1, self.n_l1 = 0.0, 0

        def update(self, labels, preds):
            cls_prob = preds[0].asnumpy()       # (B, C+1, N)
            loc_loss = preds[1].asnumpy()
            cls_target = preds[2].asnumpy()     # (B, N)
            valid = cls_target >= 0
            idx = np.maximum(cls_target.astype(int), 0)
            b, n = np.indices(idx.shape)
            p = cls_prob[b, idx, n]
            ce = -np.log(np.maximum(p, 1e-12))[valid].sum()
            self.sum_ce += ce
            self.n_ce += int(valid.sum())
            self.sum_l1 += float(loc_loss.sum())
            self.n_l1 += loc_loss.size

        def get(self):
            return (["CrossEntropy", "SmoothL1"],
                    [self.sum_ce / max(1, self.n_ce),
                     self.sum_l1 / max(1, self.n_l1)])

    mod.fit(train, num_epoch=args.num_epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": 5e-4,
                              "rescale_grad": 1.0 / args.batch_size},
            initializer=mx.initializer.Xavier(),
            eval_metric=MultiBoxMetric(),
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 10))

    # decode detections on one batch to exercise the full inference path
    train.reset()
    batch = next(iter(train))
    mod.forward(batch, is_train=False)
    det = mod.get_outputs()[3].asnumpy()
    kept = (det[:, :, 0] >= 0).sum()
    logging.info("decoded %d detections on a %d-image batch", kept,
                 det.shape[0])


if __name__ == "__main__":
    main()
