"""Share of the device's busy time inside the solver's legs that is spent in
``copy`` operations, and not in the kernel.

A leg is one program: the Euler step, then a ``fori_loop`` whose carry is
the six fields of the state.  Whatever the loop's form costs outside the
kernel shows here as whole-field copies: the loop's carry copied back each
iteration, and the changes of layout at the leg's entry and exit.  Self
times, so the ``while`` that encloses the loop's operations is not counted
twice; the busy time is the union of the device's intervals inside the
same spans.
"""

COPY_KINDS = ("copy",)


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    legs = red.call_spans(trace, "leg")
    busy_s = red.busy_within(trace, legs)
    if busy_s <= 0:  # no device, or no leg
        return None
    ns = sum(n for _dev, _kind, n in red.ops_within(trace, legs, COPY_KINDS))
    return 100.0 * ns * 1e-9 / len(trace["devices"]) / busy_s
