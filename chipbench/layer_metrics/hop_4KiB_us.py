"""Device time per hop of the 4 KiB ``shift(1)`` program: the time of its
``collective-permute`` operations over the hops it made (chain length x
calls), per device, averaged over the devices."""

KINDS = ("collective-permute", "collective-permute-start",
         "collective-permute-done")
PROGRAM = "small_sendrecv_4KiB"


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
    hops = calls * spec["chain"]
    return sum(ns * 1e-3 / hops for ns in per_dev.values()) / len(per_dev)
