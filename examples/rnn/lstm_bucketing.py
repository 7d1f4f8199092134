#!/usr/bin/env python
"""LSTM language model with bucketing (BASELINE config 4; reference
`example/rnn/bucketing/lstm_bucketing.py`).

Variable-length sentences are grouped into length buckets;
BucketingModule compiles ONE XLA program per bucket — the TPU answer to
dynamic sequence lengths (static shapes per program, shared parameters).

With no corpus on disk (this image has zero egress), a synthetic
power-law corpus stands in for Sherlock Holmes/PTB; pass --data to train
on a real tokenized text file (one sentence per line).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)-15s %(message)s")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import rnn


def synthetic_corpus(n_sentences, vocab_size, rng):
    """Power-law token stream with sentence lengths in [8, 60]."""
    probs = 1.0 / np.arange(1, vocab_size + 1)
    probs /= probs.sum()
    out = []
    for _ in range(n_sentences):
        length = int(rng.randint(8, 60))
        out.append(rng.choice(vocab_size, size=length, p=probs).tolist())
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None, help="tokenized corpus file")
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-hidden", type=int, default=200)
    ap.add_argument("--num-embed", type=int, default=200)
    ap.add_argument("--num-epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--mom", type=float, default=0.0)
    ap.add_argument("--wd", type=float, default=1e-5)
    ap.add_argument("--kv-store", default="device")
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[10, 20, 30, 40, 50, 60])
    ap.add_argument("--vocab-size", type=int, default=1000)
    ap.add_argument("--n-sentences", type=int, default=2000)
    ap.add_argument("--fused", action="store_true",
                    help="use FusedRNNCell (one lax.scan per bucket)")
    ap.add_argument("--ctx", default="tpu", choices=["tpu", "cpu"],
                    help="device context; tpu fails without a chip")
    args = ap.parse_args()

    rng = np.random.RandomState(0)
    if args.data:
        with open(args.data) as f:
            sentences = [line.split() for line in f if line.strip()]
        coded, vocab = rnn.encode_sentences(sentences)
        vocab_size = len(vocab)
    else:
        coded = synthetic_corpus(args.n_sentences, args.vocab_size, rng)
        vocab_size = args.vocab_size

    train_iter = rnn.BucketSentenceIter(coded, args.batch_size,
                                        buckets=args.buckets,
                                        invalid_label=0)

    if args.fused:
        stack = rnn.FusedRNNCell(args.num_hidden,
                                 num_layers=args.num_layers, mode="lstm")
    else:
        stack = rnn.SequentialRNNCell()
        for i in range(args.num_layers):
            stack.add(rnn.LSTMCell(args.num_hidden, prefix=f"lstm_l{i}_"))

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=vocab_size,
                                 output_dim=args.num_embed, name="embed")
        stack.reset()
        outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab_size,
                                     name="pred")
        lab = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, lab, name="softmax")
        return pred, ("data",), ("softmax_label",)

    ctx = mx.Context(args.ctx)
    model = mx.mod.BucketingModule(
        sym_gen=sym_gen,
        default_bucket_key=train_iter.default_bucket_key,
        context=ctx)
    model.fit(
        train_data=train_iter,
        eval_metric=mx.metric.Perplexity(0),
        kvstore=args.kv_store,
        optimizer=args.optimizer,
        optimizer_params={"learning_rate": args.lr, "momentum": args.mom,
                          "wd": args.wd,
                          "rescale_grad": 1.0 / args.batch_size},
        initializer=mx.initializer.Xavier(factor_type="in", magnitude=2.34),
        num_epoch=args.num_epochs,
        batch_end_callback=mx.callback.Speedometer(args.batch_size, 50))


if __name__ == "__main__":
    main()
