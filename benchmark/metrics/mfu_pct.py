"""The whole step's share of the chips' peak: model FLOPs of forward +
backward per sample (from the shapes, recomputation not counted) times the
window's rate, over chips times the published bf16 peak."""


def read(ctx):
    if ctx["platform"] != "tpu":
        return None
    return 100.0 * ctx["train_flops_per_sample"] * ctx["rate"] / \
        (ctx["chips"] * ctx["peaks"]["flops_bf16"])
