"""The training cell's kernels as the device trace shows them.  No metric
of its own: ``train_step_mfu``, ``mla_attention_roofline``,
``moe_grouped_mm_roofline`` and ``moe_held_load_max_over_mean`` build on it.

The driver (``drivers/train_step.py``) counts, in a traced run, the custom
calls inside the steps' spans by HLO instruction name less XLA's numbering
(``traced_kernel_calls_a_step``), beside the program's own count of the
kernel calls a step makes (``train_plan``).  Every reader reports nothing
unless the two agree for every kernel the plan names and the trace holds no
other call of the attention or expert kernels: a step that ran another
program than the plan describes is not read.
"""

from chipbench.drivers.train_step import instruction

ATTENTION = ("mla_attention_fwd", "mla_attention_bwd_dq",
             "mla_attention_bwd_dkv")
GROUPED = ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw")


def plan_holds(ctx) -> bool:
    counters = ctx["counters"]
    plan = counters.get("train_plan")
    traced = counters.get("traced_kernel_calls_a_step")
    if not plan or traced is None or not ctx["trace"]["devices"]:
        return False
    ours = {n for n in traced if n.startswith(("mla_attention", "moe_gmm"))}
    return ours == set(plan) and all(traced[n] == plan[n] for n in plan)


def kernel_seconds(ctx, names) -> float:
    """Device seconds of the named kernels' calls inside the steps' spans,
    averaged over the devices."""
    red, trace = ctx["reduce"], ctx["trace"]
    ns = sum(n for _dev, name, n in red.events_within(
                 trace, red.call_spans(trace, "step"))
             if red.op_kind(name) == "custom-call"
             and instruction(name) in names)
    return ns * 1e-9 / len(trace["devices"])
