"""``call_self_us`` over the legs of the solver's traced window."""


def read(ctx):
    return ctx["reader"]("call_self_us").read(ctx)
