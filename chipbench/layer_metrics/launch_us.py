"""Host time inside jax's compiled call until it returns: the median
duration of the span ``mpx.launch`` over the window's calls of a pinned
program."""

import statistics


def read(ctx):
    path = ctx["reader"]("call_path_spans").matched(ctx)
    if path is None:
        return None
    return statistics.median(
        c["launch"]["end_ns"] - c["launch"]["start_ns"]
        for c in path["calls"]) * 1e-3
