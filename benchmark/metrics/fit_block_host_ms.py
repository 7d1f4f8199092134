"""Host time per K-step block in the fit loop: the mean duration of the
program's `fit.step_block` spans of the window (the dispatch returns before
the device finishes, so this is host work, not the wait for the device)."""


def read(ctx):
    durs = [s["dur"] for s in ctx["spans"] if s["name"] == "fit.step_block"]
    return sum(durs) / len(durs) / 1e3 if durs else None
