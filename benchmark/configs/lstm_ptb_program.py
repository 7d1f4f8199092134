"""How the `lstm_ptb` configuration is composed in the program under test
(`examples/rnn/lstm_bucketing.py --fused`, one bucket of `seq_len`), and how
the program's flat cuDNN-layout parameter vector maps onto the plain
reference's per-layer leaves."""
import numpy as np


def build_symbol(mx, cfg):
    from incubator_mxnet_tpu import rnn, sym
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Embedding(data, input_dim=cfg["vocab_size"],
                          output_dim=cfg["embed_size"], name="embed")
    stack = rnn.FusedRNNCell(cfg["hidden_size"],
                             num_layers=cfg["num_layers"], mode="lstm",
                             prefix="lstm_")
    outputs, _ = stack.unroll(cfg["seq_len"], inputs=embed,
                              merge_outputs=True)
    pred = sym.Reshape(outputs, shape=(-1, cfg["hidden_size"]))
    pred = sym.FullyConnected(pred, num_hidden=cfg["vocab_size"], name="pred")
    return sym.SoftmaxOutput(pred, sym.Reshape(label, shape=(-1,)),
                             name="softmax")


def input_descs(cfg, batch):
    return (batch, cfg["seq_len"]), (batch, cfg["seq_len"])


def fixed_params(cfg, batch, arg_names):
    """Arguments the module holds but does not train: the initial hidden
    and cell state, which the program makes variables and which stay zero
    (`fixed_param_names`), as the paper's and the example's runs start every
    batch from zero state."""
    shape = (cfg["num_layers"], batch, cfg["hidden_size"])
    return {n: np.zeros(shape, np.float32) for n in arg_names
            if "begin_state" in n}


def _layout(cfg):
    """[(reference leaf, offset, shape)] of the flat vector: all weight
    matrices layer by layer (W_x then W_h), then all biases (b_x, b_h)."""
    h, e = cfg["hidden_size"], cfg["embed_size"]
    out, off = [], 0
    for layer in range(cfg["num_layers"]):
        nin = e if layer == 0 else h
        for leaf, shape in ((f"l{layer}.wx", (4 * h, nin)),
                            (f"l{layer}.wh", (4 * h, h))):
            out.append((leaf, off, shape))
            off += int(np.prod(shape))
    for layer in range(cfg["num_layers"]):
        for leaf in (f"l{layer}.bx", f"l{layer}.bh"):
            out.append((leaf, off, (4 * h,)))
            off += 4 * h
    return out, off


_DIRECT = (("embed.w", "embed_weight"), ("pred.w", "pred_weight"),
           ("pred.b", "pred_bias"))


def to_program(leaves, cfg, names):
    import jax.numpy as jnp
    out = {prog: leaves[ref] for ref, prog in _DIRECT if prog in names}
    flat = next((n for n in names if n.endswith("parameters")), None)
    if flat is not None:
        out[flat] = jnp.concatenate(
            [leaves[leaf].reshape(-1) for leaf, _, _ in _layout(cfg)[0]])
    return out


def from_program(arrays, cfg):
    out = {ref: arrays[prog] for ref, prog in _DIRECT if prog in arrays}
    flat = next((n for n in arrays if n.endswith("parameters")), None)
    if flat is not None:
        vec = arrays[flat]
        for leaf, off, shape in _layout(cfg)[0]:
            out[leaf] = vec[off: off + int(np.prod(shape))].reshape(shape)
    return out


def flops_per_sample(cfg, flops):
    """Model FLOPs of forward + backward for one token."""
    h, e = cfg["hidden_size"], cfg["embed_size"]
    total = flops.Count()
    for layer in range(cfg["num_layers"]):
        total.dense(e if layer == 0 else h, 4 * h)
        total.dense(h, 4 * h)
    total.dense(h, cfg["vocab_size"])
    return total
