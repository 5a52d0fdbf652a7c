"""Model FLOP utilisation of the hybrid cell's whole training step: the
model FLOPs a step needs (``train_work_hybrid.model_flops_per_step``: 6 x
the matmul weights a token meets x the tokens, the routed experts at their
expected share, plus causal attention counted once; recomputation not
counted) x the traced steps, over the traced window's wall, over the
chip's bfloat16 peak.  Nothing where the trace's kernel calls differ from
the plan (``hybrid_train_kernels``)."""

from chipbench import train_work_hybrid


def read(ctx):
    if not ctx["reader"]("hybrid_train_kernels").plan_holds(ctx):
        return None
    counters = ctx["counters"]
    flops = train_work_hybrid.model_flops_per_step(
        ctx["config"], counters["tokens_per_step"]) * counters["steps"]
    return 100.0 * flops / ctx["trace"]["window_s"] / \
        ctx["peaks"]["bf16_flops_per_s"] / ctx["chips"]
