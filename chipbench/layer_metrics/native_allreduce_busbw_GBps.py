"""Bus bandwidth of XLA's own all-reduce over the links: the bus bytes of
the 1 GiB allreduce program's collectives over the device time of its
``all-reduce`` operations, per device, averaged over the devices."""

KINDS = ("all-reduce", "all-reduce-start", "all-reduce-done")
PROGRAM = "large_allreduce_1GiB"


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    spec = next((p for p in ctx["traffic"]["programs"]
                 if p["name"] == PROGRAM), None)
    calls = ctx["counters"].get("calls", {}).get(PROGRAM, 0)
    if spec is None or not calls:
        return None
    per_dev = red.op_ns_by_device(trace, red.call_spans(trace, PROGRAM),
                                  KINDS)
    if not per_dev:
        return None
    bus = calls * spec["chain"] * ctx["work"].bus_bytes(
        spec["op"], spec["block_elems"], ctx["chips"])
    return sum(bus / ns for ns in per_dev.values()) / len(per_dev)  # B/ns
