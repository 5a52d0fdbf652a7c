"""Share of the device's busy time inside the large-set calls that is spent
in collective operations, and not in the copies, slices, updates and
rescales around them."""

COLLECTIVES = tuple(
    base + suffix
    for base in ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                 "collective-permute")
    for suffix in ("", "-start", "-done"))


def large_spans(ctx):
    return [span for p in ctx["traffic"]["programs"] if p["set"] == "large"
            for span in ctx["reduce"].call_spans(ctx["trace"], p["name"])]


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    spans = large_spans(ctx)
    busy_s = red.busy_within(trace, spans)
    if not spans or busy_s <= 0:
        return None
    ns = sum(n for _d, _k, n in red.ops_within(trace, spans, COLLECTIVES))
    return 100.0 * ns * 1e-9 / len(trace["devices"]) / busy_s
