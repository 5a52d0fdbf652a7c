"""How unevenly routing loads the experts the hybrid cell's chip holds:
for every traced step and MoE layer, the most tokens a held expert took
over the mean of the held experts (the driver's counter ``held_loads``),
averaged over the steps and layers; 1 is even.  Layers whose held experts
took no token are left out.  Nothing where the trace's kernel calls differ
from the plan (``hybrid_train_kernels``)."""


def read(ctx):
    if not ctx["reader"]("hybrid_train_kernels").plan_holds(ctx):
        return None
    ratios = [max(layer) * len(layer) / sum(layer)
              for step in ctx["counters"].get("held_loads") or []
              for layer in step if sum(layer)]
    return sum(ratios) / len(ratios) if ratios else None
