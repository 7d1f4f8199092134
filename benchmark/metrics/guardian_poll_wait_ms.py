"""How long the fit loop stood still for the device per block before it
could prepare the next one: the summed `wait_us` of the program's
`fit.guardian` spans of the window (the time inside the poll's blocking
`device_get` of the block just dispatched), over the blocks."""


def read(ctx):
    spans = ctx["spans"]
    blocks = sum(1 for s in spans if s["name"] == "fit.step_block")
    waits = [s["args"]["wait_us"] for s in spans
             if s["name"] == "fit.guardian" and "wait_us" in s["args"]]
    if not blocks or not waits:
        return None
    return sum(waits) / blocks / 1e3
