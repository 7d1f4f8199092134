"""1 - the union of device-op intervals over the traced window; on several
chips the busiest chip's."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_ns_max"] / tr["window_ns"])
