"""Share of their roofline that the hybrid cell's held experts' grouped
products reach (expert width 1536): for every traced step and MoE layer,
the least time of its products for the rows routed to held experts (the
layer's held loads summed: ``train_work_hybrid.grouped_mm_floor``, the
larger of the FLOP floor and the byte floor), summed, over the device time
of ``moe_gmm``, ``moe_gmm_dx`` and ``moe_gmm_dw`` inside the steps.
Nothing where the trace's kernel calls differ from the plan
(``hybrid_train_kernels``)."""

from chipbench import train_work_hybrid


def read(ctx):
    kernels = ctx["reader"]("hybrid_train_kernels")
    if not kernels.plan_holds(ctx):
        return None
    counters, peaks = ctx["counters"], ctx["peaks"]
    loads = counters.get("held_loads") or []
    if len(loads) != counters["steps"]:
        return None
    seconds = kernels.kernel_seconds(ctx, kernels.GROUPED)
    if not seconds:
        return None
    least = sum(train_work_hybrid.grouped_mm_floor(
        ctx["config"], sum(layer), peaks["bf16_flops_per_s"],
        peaks["hbm_bytes_per_s"]) for step in loads for layer in step)
    return 100.0 * least / seconds
