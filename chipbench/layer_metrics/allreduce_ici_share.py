"""``native_allreduce_busbw_GBps`` over the chip's published interconnect
rate (1,600 Gbit/s = 200 GB/s a chip, all links together).  On the 2x2 host
there is no wrap-around: each chip has two neighbours, so not every link
the published figure counts is wired, and 100 % is not reachable here."""


def read(ctx):
    busbw = ctx["reader"]("native_allreduce_busbw_GBps").read(ctx)
    if busbw is None:
        return None
    return 100.0 * busbw * 1e9 / ctx["peaks"]["ici_bytes_per_s"]
