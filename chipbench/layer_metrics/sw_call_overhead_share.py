"""Share of the device's busy time inside the solver's runs that is not the
wide-halo kernels': what driving the solver call by call pays once a call.

A run of the documented host loop (``drivers/solver_loop.py``) is 45 region
calls where a leg is one, and every call of the wide-halo path changes the
six fields' layout at its entry and exit, builds the widened frame, refreshes
its bands between kernel calls and crops it.  1 - (self time of the
``sw_wide_x*`` custom calls that start inside the runs' spans) / (busy time
inside the same spans).

The trace is first held against the program's own count of what a run is
made of: the driver's counter ``traced_custom_calls_a_run`` (the runs'
custom-call events a run, by instruction) against its counter ``run_plan``
(the program's ``run_plan()``): the Euler-step kernel once a first-step
call, the steady kernels ``chunk_calls + single_step_calls`` times a
multistep call.  Where they disagree, where the driver gives no plan or no
count (a tree without ``run_plan``, a trace with no device or no run),
nothing is reported.
"""

KERNEL_NAME = "sw_wide_x"


def planned_kernel_calls(plan):
    """``(steady, euler)`` kernel calls a run, from ``run_plan``'s dict."""
    per_program = [(1, plan["first_step"]),
                   (plan["calls"] - 1, plan["multistep"])]
    steady = sum(n * (p["chunk_calls"] + p["single_step_calls"])
                 for n, p in per_program)
    return steady, sum(n * p["euler_calls"] for n, p in per_program)


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    plan = ctx["counters"].get("run_plan")
    calls = ctx["counters"].get("traced_custom_calls_a_run")
    runs = red.call_spans(trace, "leg")
    busy_s = red.busy_within(trace, runs)
    if not plan or not calls or busy_s <= 0:
        return None
    kernels = [(name, n) for name, n in calls.items()
               if name.startswith(KERNEL_NAME)]
    euler = sum(n for name, n in kernels if "_euler" in name)
    if (sum(n for _name, n in kernels) - euler,
            euler) != planned_kernel_calls(plan):
        return None
    ns = sum(n for _dev, name, n in red.events_within(trace, runs)
             if red.op_kind(name) == "custom-call"
             and name.lstrip("%").startswith(KERNEL_NAME))
    return 100.0 * (1.0 - ns * 1e-9 / len(trace["devices"]) / busy_s)
