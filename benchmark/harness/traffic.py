"""The one general generator of training traffic.  A traffic mix is a JSON
file of parameters under benchmark/traffic/; nothing here knows a mix or a
configuration by name.

    feed             "host": numpy batches, handed to `Module.fit` as they
                     are and so through the program's input ring
    batch_per_chip   rows per chip
    pool_batches     distinct batches made from the seed in set-up; the feed
                     cycles through them (no RNG and no decode in the window)
    data.draw        "uniform":     floats in [low, high) of the
                                    configuration's input shape; labels
                                    uniform over its classes.  Batch j of the
                                    pool is batch 0 rolled by 7j pixels, so
                                    all rows differ at the cost of one draw.
                     "zipf_stream": one token stream with 1/rank**exponent
                                    frequencies over the vocabulary, cut into
                                    `batch` parallel streams PTB-fashion;
                                    batch j is columns [j*T, (j+1)*T) and its
                                    label the same columns shifted by one
    data.dtype       the type the program is BOUND with for the data input
                     (host batches are float32 whatever this says; the
                     program's ring casts them)
    tiny             the same keys at a size the CPU can hold, for the
                     tests under benchmark/tests/ only
"""
import time

import numpy as np


def make_pool(traffic, cfg, adapter, batch, seed):
    """[(data, label)] float32 numpy batches: the same seed gives the same
    pool, whoever asks (the program's feed, then the reference)."""
    rng = np.random.default_rng(int(seed))
    n = int(traffic["pool_batches"])
    spec = traffic["data"]
    data_shape, label_shape = adapter.input_descs(cfg, batch)
    if spec["draw"] == "uniform":
        base = rng.random(data_shape, dtype=np.float32)
        span = float(spec.get("high", 1.0)) - float(spec.get("low", 0.0))
        base = base * span + float(spec.get("low", 0.0))
        pool = []
        for j in range(n):
            data = base if j == 0 else np.roll(base, 7 * j, axis=-1)
            label = rng.integers(0, cfg["classes"], label_shape)
            pool.append((data, label.astype(np.float32)))
        return pool
    if spec["draw"] == "zipf_stream":
        steps = data_shape[1]
        vocab = cfg["vocab_size"]
        p = 1.0 / np.arange(1, vocab + 1) ** float(spec.get("exponent", 1.0))
        stream = rng.choice(vocab, size=batch * (n * steps + 1),
                            p=p / p.sum()).reshape(batch, n * steps + 1)
        return [(stream[:, j * steps:(j + 1) * steps].astype(np.float32),
                 stream[:, j * steps + 1:(j + 1) * steps + 1]
                 .astype(np.float32)) for j in range(n)]
    raise ValueError(f"unknown data.draw {spec['draw']!r}")


def make_feed(mx, traffic, cfg, adapter, batch, pool, k):
    """The `DataIter` that `Module.fit` is given, in set-up and in the
    window alike.  It ends only at a boundary of K batches, so no partial
    block (another program) is ever asked for."""
    import jax
    from incubator_mxnet_tpu import io
    bind_dtype = np.dtype(traffic["data"].get("dtype", "float32"))
    data_shape, label_shape = adapter.input_descs(cfg, batch)
    descs = ([io.DataDesc("data", data_shape, dtype=bind_dtype)],
             [io.DataDesc("softmax_label", label_shape, dtype=np.float32)])
    if traffic["feed"] != "host":
        raise ValueError(f"unknown feed {traffic['feed']!r}")
    batches = [io.DataBatch(data=[d], label=[lab], pad=0,
                            provide_data=descs[0], provide_label=descs[1])
               for d, lab in pool]

    class Feed(io.DataIter):
        provide_data = property(lambda self: descs[0])
        provide_label = property(lambda self: descs[1])

        def __init__(self):
            super().__init__(batch_size=batch)
            self.blocks = None      # stop after this many blocks, or
            self.deadline = None    # at the first boundary past this time
            self.served = 0

        def arm(self, blocks=None, seconds=None):
            self.blocks = blocks
            self.deadline = None if seconds is None else \
                time.perf_counter() + float(seconds)
            self.served = 0

        def reset(self):
            pass                    # one epoch per fit call

        def next(self):
            with jax.profiler.TraceAnnotation("bench.feed"):
                return self._next()

        def _next(self):
            if self.served % k == 0:
                done = self.served // k
                if (self.blocks is not None and done >= self.blocks) or \
                        (self.deadline is not None and done >= 1 and
                         time.perf_counter() >= self.deadline):
                    raise StopIteration
            b = batches[self.served % len(batches)]
            self.served += 1
            return b

    return Feed()
