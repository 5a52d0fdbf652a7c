"""Share of the device's busy time inside the solver's legs that goes into
keeping the widened frame, and not into the kernel.

A leg of the wide-halo path builds its frame once (``concatenate``), calls
the kernel once per chunk of steps with the frame's margin bands refreshed
before each call (``dynamic-update-slice``), carries the frame through the
loop (``copy`` where XLA cannot hand the kernel's result to the carry in
place) and crops once.  The self times of those three kinds of operation
inside the legs, over the busy time inside the same spans.

The trace is first held against the program's own count of what a leg is
made of: the driver's counter ``traced_custom_calls_a_leg`` (the legs'
custom-call events per leg, by instruction: ``drivers/solver_runner.py``)
against its counter ``leg_plan`` (the program's ``leg_plan()``).  The steady
wide-halo kernels (``sw_wide_x<steps>``) must have run ``chunk_calls +
single_step_calls`` times a leg and the Euler-step kernel (``_euler``)
``euler_calls`` times.  Where they disagree, where the driver gives no plan
or no count (a tree without ``leg_plan``, a trace with no device or no
leg), or no frame is built (a leg of the whole-step kernel), nothing is
reported.
"""

FRAME_KINDS = ("copy", "dynamic-update-slice", "concatenate")
KERNEL_NAME = "sw_wide_x"


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    plan = ctx["counters"].get("leg_plan")
    calls = ctx["counters"].get("traced_custom_calls_a_leg")
    legs = red.call_spans(trace, "leg")
    busy_s = red.busy_within(trace, legs)
    if not plan or not calls or not plan.get("frames_built") or busy_s <= 0:
        return None
    kernels = [(name, n) for name, n in calls.items()
               if name.startswith(KERNEL_NAME)]
    euler = sum(n for name, n in kernels if "_euler" in name)
    if (sum(n for _name, n in kernels) - euler, euler) != (
            plan["chunk_calls"] + plan["single_step_calls"],
            plan["euler_calls"]):
        return None
    ns = sum(n for _dev, _kind, n in red.ops_within(trace, legs, FRAME_KINDS))
    return 100.0 * ns * 1e-9 / len(trace["devices"]) / busy_s
