"""Share of its roofline that grouped-query attention reaches: the causal
attention FLOPs of the traced steps, forward and backward counted once
(``train_work_hybrid.attention_flops_per_step``, as
``mla_attention_roofline`` counts them), at the chip's bfloat16 peak, over
the device time of ``gqa_attention_fwd``, ``_bwd_dq`` and ``_bwd_dkv``
inside the steps.  Nothing where the trace's kernel calls differ from the
plan (``hybrid_train_kernels``)."""

from chipbench import train_work_hybrid


def read(ctx):
    kernels = ctx["reader"]("hybrid_train_kernels")
    if not kernels.plan_holds(ctx):
        return None
    counters = ctx["counters"]
    seconds = kernels.kernel_seconds(ctx, kernels.ATTENTION)
    if not seconds:
        return None
    flops = train_work_hybrid.attention_flops_per_step(
        ctx["config"], counters["tokens_per_step"]) * counters["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / seconds
