"""The share of the score square's tiles that the attention kernels never
ran: `ops.attention.tiles.skipped` over `.run` + `.masked` + `.skipped`,
the program's counters of its lowered kernel calls (forward and backward,
in tiles of block_q rows by 128 keys, from the tile walk's own statement).
0 where XLA's form was lowered and no kernel; nothing on a program without
the counters."""


def read(ctx):
    import incubator_mxnet_tpu as mx

    def count(name):
        return float(mx.obs.counter("ops.attention." + name).value)
    run, masked, skipped = (count("tiles." + n)
                            for n in ("run", "masked", "skipped"))
    total = run + masked + skipped
    if not total:
        return 0.0 if count("lowered.xla") and not count("lowered.kernel") \
            else None
    return 100.0 * skipped / total
