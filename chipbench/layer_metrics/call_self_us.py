"""Host time the library itself adds to a call of a pinned program: the
median over the window's calls of the span ``mpx.call`` less its child
``mpx.launch`` — the world stamp, the call count, the donation notes and
the analysis test of ``PinnedProgram.__call__``."""

import statistics


def read(ctx):
    path = ctx["reader"]("call_path_spans").matched(ctx)
    if path is None:
        return None
    return statistics.median(
        (c["call"]["end_ns"] - c["call"]["start_ns"])
        - (c["launch"]["end_ns"] - c["launch"]["start_ns"])
        for c in path["calls"]) * 1e-3
