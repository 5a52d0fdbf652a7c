"""Share of its roofline that the solver's steady whole-step Pallas kernel
reaches, per model step.

Least time of one step: the bytes an unfused step cannot avoid (the six
float32 state fields read once and written once;
``work.solver_step_bytes``) over the chip's peak HBM rate.  That is divided
by the kernel's device time per step: the mean time of one call from the
trace over the steps one call advances (the driver's counter
``steps_per_kernel_call``).  Per step, so that the share moves with
``steps_per_s_per_chip`` whatever number of steps a call fuses.

Which kernel: a Pallas kernel is a custom call to Mosaic.  A leg holds two,
the Euler-step kernel once and the loop's kernel once per chunk of steps;
each is one HLO instruction, so its events carry one name.  The one read
here is the instruction with the most events, the loop's; the Euler-step
kernel (one step of 71) and any other custom call are left out, not
averaged in.

The kernel is bound by HBM bandwidth in this accounting: a few dozen flops
per cell per step on the vector unit against 48 bytes moved, far under the
chip's ridge.  The count leaves out the margin rows a block re-reads and
every intermediate.  A kernel that fuses s steps moves the fields once per
s steps, so its own floor is s times lower than the one counted here: the
share would pass 100 % only for a fused kernel faster than one unfused
step's traffic allows (PERF.md section 7).
"""

from collections import defaultdict

KERNEL_KINDS = ("custom-call",)


def loop_kernel_calls(ctx):
    """Self times (ns) of the calls of the custom-call instruction that ran
    most often inside the legs."""
    red, trace = ctx["reduce"], ctx["trace"]
    by_instruction = defaultdict(list)
    for _dev, name, ns in red.events_within(trace,
                                            red.call_spans(trace, "leg")):
        if red.op_kind(name) in KERNEL_KINDS:
            by_instruction[name.partition(" = ")[0]].append(ns)
    return max(by_instruction.values(), key=len, default=[])


def read(ctx):
    cfg = ctx["config"]
    calls = loop_kernel_calls(ctx)
    steps_per_call = ctx["counters"].get("steps_per_kernel_call")
    if not calls or not steps_per_call:
        return None
    per_step_s = sum(calls) * 1e-9 / len(calls) / steps_per_call
    least_s = ctx["work"].solver_step_bytes(cfg["nx"], cfg["ny"]) / \
        ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / per_step_s
