"""The hybrid training cell's kernels as the device trace shows them.  No
metric of its own: ``hybrid_train_step_mfu``, ``gqa_attention_roofline``,
``short_conv_roofline`` and ``hybrid_grouped_mm_roofline`` build on it.

As ``train_kernels`` does for the MLA cell: every reader reports nothing
unless the trace's kernel calls a step (``traced_kernel_calls_a_step``)
agree with the program's plan (``train_plan``) for every kernel the plan
names, and the trace holds no other call of the attention, convolution or
expert kernels.  ``kernel_seconds`` is ``train_kernels``'s.
"""

from chipbench.layer_metrics.train_kernels import kernel_seconds  # noqa: F401

ATTENTION = ("gqa_attention_fwd", "gqa_attention_bwd_dq",
             "gqa_attention_bwd_dkv")
CONV = ("short_conv_fwd", "short_conv_bwd")
GROUPED = ("moe_gmm", "moe_gmm_dx", "moe_gmm_dw")


def plan_holds(ctx) -> bool:
    counters = ctx["counters"]
    plan = counters.get("train_plan")
    traced = counters.get("traced_kernel_calls_a_step")
    if not plan or traced is None or not ctx["trace"]["devices"]:
        return False
    ours = {n for n in traced
            if n.startswith(("gqa_attention", "short_conv", "moe_gmm"))}
    return ours == set(plan) and all(traced[n] == plan[n] for n in plan)
