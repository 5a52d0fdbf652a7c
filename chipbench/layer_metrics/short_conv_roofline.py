"""Share of its roofline that the gated short convolution reaches: the
least bytes its kernels move in the traced steps (each call of
``short_conv_fwd`` and ``short_conv_bwd`` as often as the plan says,
``train_work_hybrid.short_conv_bytes``) at the chip's HBM bandwidth, over
the device time of those kernels inside the steps.  The kernels are bound
by memory: a few elementwise FLOPs a byte.  Nothing where the trace's
kernel calls differ from the plan (``hybrid_train_kernels``)."""

from chipbench import train_work_hybrid


def read(ctx):
    kernels = ctx["reader"]("hybrid_train_kernels")
    if not kernels.plan_holds(ctx):
        return None
    counters = ctx["counters"]
    seconds = kernels.kernel_seconds(ctx, kernels.CONV)
    if not seconds:
        return None
    per_call = train_work_hybrid.short_conv_bytes(
        ctx["config"], counters["tokens_per_step"])
    moved = sum(per_call[name] * counters["train_plan"][name]
                for name in kernels.CONV) * counters["steps"]
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / seconds
