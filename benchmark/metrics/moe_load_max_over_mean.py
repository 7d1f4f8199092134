"""How uneven the routing was over the window: the largest over the mean of
the assignments the experts held received, all routed layers together, from
the program's `moe.load` span (the counters `RoutedExperts` keeps on the
device, read where `fit` syncs at the epoch's end)."""


def read(ctx):
    loads = [s["args"] for s in ctx["spans"] if s["name"] == "moe.load"]
    if not loads or not loads[-1].get("mean"):
        return None
    return loads[-1]["max"] / loads[-1]["mean"]
