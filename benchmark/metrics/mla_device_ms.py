"""Device time per step of every instruction whose node lies in a
multi-head latent attention mixer (the nodes `llm/kanana2.py` names
``mla_*``: the four projections, the latent's norm, the rotary embeddings,
the shared key's broadcast and the attention, forward, backward and
recomputation together), joined with the trace's per-instruction sums of
the busiest chip as loop_device_ms.py joins a loop's.  On a program whose
map names no such node there is nothing to read."""


def _mla_ns(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("steps"):
        return None
    import incubator_mxnet_tpu as mx
    op_scopes = getattr(mx.compile, "op_scopes", None)
    if op_scopes is None:
        return None
    by_op = max(tr["per_chip"], key=lambda c: c["busy_ns"])["by_op"]
    # the train block's map: the program whose instructions carry most of
    # the window's device time
    labels = {p["label"] for p in ctx.get("programs", ())} | {None}
    best, covered = None, 0.0
    for label in sorted(labels, key=str):
        scopes = op_scopes(label)
        ns = sum(t for n, t in by_op.items() if n in scopes)
        if ns > covered:
            best, covered = scopes, ns
    if best is None:
        return None
    return sum(t for n, t in by_op.items()
               if ((best.get(n) or {}).get("node") or "").startswith("mla_"))


def read(ctx):
    ns = _mla_ns(ctx)
    return ns / ctx["steps"] / 1e6 if ns else None
