"""Host time the library itself adds to a call of an un-pinned ``mpx.spmd``
region: the median over the traced window's calls of the span
``mpx.region_call`` less its child ``mpx.launch`` — the flag stamp and the
probe of the region's program cache (``parallel/region.py``:
``program_for``), beside jax's own compiled call.

The spans reach the reader in-process (``call_path_spans.program_spans``).
Checked against a counter at the same boundary: nothing is reported unless
the count of ``mpx.region_call`` spans equals the driver's count of region
calls in the window (``counters.calls``) and each has exactly one launch.
"""

import statistics


def read(ctx):
    spans = ctx["reader"]("call_path_spans")
    records = spans.program_spans()
    calls = [r for r in records if r["name"] == "mpx.region_call"]
    if not calls or len(calls) != spans.driver_calls(ctx["counters"]):
        return None
    launches = {}
    for r in records:
        if r["name"] == "mpx.launch":
            launches.setdefault(r["parent"], []).append(r)
    if any(len(launches.get(c["id"], ())) != 1 for c in calls):
        return None
    return statistics.median(
        (c["end_ns"] - c["start_ns"])
        - (launches[c["id"]][0]["end_ns"] - launches[c["id"]][0]["start_ns"])
        for c in calls) * 1e-3
