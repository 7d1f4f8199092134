#!/usr/bin/env python
"""Train ResNet on ImageNet-layout data — BASELINE config #2 (reference
`example/image-classification/train_imagenet.py`).

Feeds from a RecordIO pack (`--data-train .../train.rec`, the reference's
dataset format — the native-indexed multi-threaded `ImageRecordIter`) or a
synthetic corpus when no dataset is on disk (zero-egress image).

TPU-first defaults: bf16 training (`--dtype bfloat16` uses the MXU's
native multiply format), one fused XLA program per step via hybridized
symbols, `kvstore='tpu'` all-reduce for multi-chip.
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
from incubator_mxnet_tpu.io import NDArrayIter

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)-15s %(message)s")


def get_resnet(num_classes, num_layers, image_shape):
    from symbols.resnet import get_symbol
    return get_symbol(num_classes=num_classes, num_layers=num_layers,
                      image_shape=image_shape)


def synthetic_iters(batch_size, image_shape, num_classes, n=512):
    shape = tuple(int(x) for x in image_shape.split(","))
    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (n,) + shape).astype("f4")
    y = rng.randint(0, num_classes, n).astype("f4")
    return (NDArrayIter(X, y, batch_size=batch_size, shuffle=True),
            NDArrayIter(X[: n // 4], y[: n // 4], batch_size=batch_size))


def rec_iters(args, shape):
    kw = dict(data_shape=shape, batch_size=args.batch_size,
              preprocess_threads=args.data_nthreads,
              mean_r=123.68, mean_g=116.78, mean_b=103.94)
    train = mx.io.ImageRecordIter(path_imgrec=args.data_train, shuffle=True,
                                  rand_crop=True, rand_mirror=True,
                                  resize=256, **kw)
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(path_imgrec=args.data_val, resize=256,
                                    **kw)
    return train, val


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-train", default=None, help="train.rec path")
    ap.add_argument("--data-val", default=None, help="val.rec path")
    ap.add_argument("--network", default="resnet")
    ap.add_argument("--num-layers", type=int, default=50)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--mom", type=float, default=0.9)
    ap.add_argument("--wd", type=float, default=1e-4)
    ap.add_argument("--kv-store", default="device")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--data-nthreads", type=int, default=4)
    ap.add_argument("--disp-batches", type=int, default=20)
    ap.add_argument("--model-prefix", default=None)
    ap.add_argument("--synthetic-n", type=int, default=512)
    ap.add_argument("--ctx", default="tpu", choices=["tpu", "cpu"],
                    help="device context; tpu fails without a chip")
    args = ap.parse_args()

    shape = tuple(int(x) for x in args.image_shape.split(","))
    net = get_resnet(args.num_classes, args.num_layers, args.image_shape)

    if args.data_train:
        train, val = rec_iters(args, shape)
    else:
        logging.info("no --data-train: running on synthetic data")
        train, val = synthetic_iters(args.batch_size, args.image_shape,
                                     args.num_classes, args.synthetic_n)

    ctx = mx.Context(args.ctx)
    mod = mx.mod.Module(net, context=ctx)
    checkpoint = (mx.callback.do_checkpoint(args.model_prefix)
                  if args.model_prefix else None)
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            kvstore=args.kv_store, optimizer="sgd",
            optimizer_params={"learning_rate": args.lr,
                              "momentum": args.mom, "wd": args.wd,
                              "rescale_grad": 1.0 / args.batch_size,
                              "multi_precision":
                                  args.dtype != "float32"},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            eval_metric=["accuracy",
                         mx.metric.TopKAccuracy(top_k=5)],
            batch_end_callback=mx.callback.Speedometer(
                args.batch_size, args.disp_batches),
            epoch_end_callback=checkpoint)


if __name__ == "__main__":
    main()
