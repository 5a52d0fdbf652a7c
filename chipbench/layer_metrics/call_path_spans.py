"""The program's own host spans of the call path, matched to the driver's
spans of the same calls.  No metric of its own: the readers of
``call_self_us``, ``launch_us`` and ``first_op_delay_us`` build on it.

The program records a span ``mpx.call`` round every call of a pinned
program, with one child ``mpx.launch`` round jax's compiled call
(``mpi4jax_tpu/utils/profiling.py``), while a profiler session runs.  They
reach a reader in-process, through ``profiling.spans()``, with
``time.time_ns()`` timestamps; the trace file counts from the session's
start, so the two clocks differ by one constant per session.

**The shared clock.**  The k-th ``mpx.call`` is the first thing inside the
driver's k-th ``dispatch_`` span, so the constant is the median over the
calls of (``mpx.call`` start - ``dispatch_`` start).  It holds where every
call's difference lies within ``SCATTER_NS`` of it and every ``mpx.call``,
moved by it, lies inside its ``dispatch_`` span.  The median puts half the
calls a share of the scatter *before* their ``dispatch_`` start, so "inside"
allows the same ``SCATTER_NS``; a call matched to another call's span is
off by a whole call, 12 ms or more.  Where it does not hold, where the
count of ``mpx.call`` spans is not the driver's count of calls, where the
program has no spans (a parent commit) or the trace no device plane (no
chip), :func:`matched` returns ``None`` and every reader leaves its metric
out.  What it found goes to standard error once a run, beside the
harness's own lines.
"""

import statistics
import sys
from bisect import bisect_left

SCATTER_NS = 50_000


def program_spans():
    """The records of the newest profiler session, or none where the
    program has no such buffer."""
    try:
        from mpi4jax_tpu.utils.profiling import spans
    except ImportError:
        return []
    return spans()


def driver_calls(counters):
    """How many calls of a pinned program the driver made in the window."""
    calls = counters.get("calls")
    if calls:
        return sum(calls.values())
    return counters.get("legs")


def _match(ctx):
    trace = ctx["trace"]
    records = program_spans()
    n = driver_calls(ctx["counters"])
    if not records or not n or not trace["devices"]:
        return None
    calls = sorted((r for r in records if r["name"] == "mpx.call"),
                   key=lambda r: r["start_ns"])
    dispatches = ctx["reduce"].spans_named(trace, "dispatch_")
    waits = ctx["reduce"].spans_named(trace, "wait_")
    if not len(calls) == len(dispatches) == len(waits) == n:
        return None
    launches = {}
    for r in records:
        if r["name"] == "mpx.launch":
            launches.setdefault(r["parent"], []).append(r)
    if any(len(launches.get(c["id"], ())) != 1 for c in calls):
        return None
    diffs = [c["start_ns"] - d[0] for c, d in zip(calls, dispatches)]
    constant = int(statistics.median(diffs))
    scatter = max(abs(x - constant) for x in diffs)
    inside = all(d[0] - SCATTER_NS <= c["start_ns"] - constant
                 and c["end_ns"] - constant <= d[1] + SCATTER_NS
                 for c, d in zip(calls, dispatches))
    print(f"chipbench: call path: {n} calls, clock constant {constant} ns, "
          f"scatter {scatter} ns, inside their dispatch spans: {inside}",
          file=sys.stderr)
    if scatter > SCATTER_NS or not inside:
        return None
    return {"constant_ns": constant, "scatter_ns": scatter, "calls": [
        {"call": c, "launch": launches[c["id"]][0], "dispatch": d, "wait": w}
        for c, d, w in zip(calls, dispatches, waits)]}


def matched(ctx):
    """``{"constant_ns", "scatter_ns", "calls": [{"call", "launch",
    "dispatch", "wait"}]}`` in order of the calls, or ``None``; worked out
    once a run and kept in ``ctx``."""
    if "call_path" not in ctx:
        ctx["call_path"] = _match(ctx)
    return ctx["call_path"]


def first_op_starts(ctx, path):
    """Per call, the start of its first device operation, earliest over
    the devices, on the trace's clock; ``None`` for a call in which no
    device ran an operation."""
    starts = [[op[1] for op in d["ops"]]  # sorted by start
              for d in ctx["trace"]["devices"].values()]
    out = []
    for call in path["calls"]:
        lo, hi = call["dispatch"][0], call["wait"][1]
        first = None
        for dev in starts:
            i = bisect_left(dev, lo)
            if i < len(dev) and dev[i] < hi and (first is None
                                                 or dev[i] < first):
                first = dev[i]
        out.append(first)
    return out
