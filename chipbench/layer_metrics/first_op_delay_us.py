"""From the hand-over to the runtime until a chip works: the median over
the window's calls of (start of the call's first device operation,
earliest over the devices) - (start of its span ``mpx.launch``).  The one
reader here that crosses from the program's clock to the trace's, by the
session's constant (``call_path_spans``)."""

import statistics


def read(ctx):
    spans = ctx["reader"]("call_path_spans")
    path = spans.matched(ctx)
    if path is None:
        return None
    firsts = spans.first_op_starts(ctx, path)
    if any(first is None for first in firsts):
        return None
    return statistics.median(
        first - (c["launch"]["start_ns"] - path["constant_ns"])
        for first, c in zip(firsts, path["calls"])) * 1e-3
