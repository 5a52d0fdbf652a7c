"""Share of its roofline that latent attention reaches: the causal
attention FLOPs of the traced steps, forward and backward counted once
(``train_work.attention_flops_per_step``), at the chip's bfloat16 peak,
over the device time of the attention kernels (``mla_attention_fwd``, its
rematerialised call in the backward, ``_bwd_dq``, ``_bwd_dkv``) inside the
steps.  Compute-bound in this accounting: each kernel call reads its
operands once per query or key tile.  Nothing where the trace's kernel
calls differ from the plan (``train_kernels``)."""

from chipbench import train_work


def read(ctx):
    kernels = ctx["reader"]("train_kernels")
    if not kernels.plan_holds(ctx):
        return None
    counters = ctx["counters"]
    seconds = kernels.kernel_seconds(ctx, kernels.ATTENTION)
    if not seconds:
        return None
    flops = train_work.attention_flops_per_step(
        ctx["config"], counters["tokens_per_step"]) * counters["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / seconds
