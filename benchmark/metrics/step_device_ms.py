"""Device time of the train program per step: the trace's `XLA Modules`
events of the program that took most device time, summed, over the steps of
the window.  On several chips: the mean over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["steps"]:
        return None
    per_chip = []
    for chip in tr["per_chip"]:
        mods = chip["modules"]
        if not mods:
            return None
        per_chip.append(max(sum(d) for d in mods.values()))
    return sum(per_chip) / len(per_chip) / ctx["steps"] / 1e6
