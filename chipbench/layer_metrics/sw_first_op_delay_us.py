"""``first_op_delay_us`` over the legs of the solver's traced window."""


def read(ctx):
    return ctx["reader"]("first_op_delay_us").read(ctx)
