"""Programs XLA compiled in this process during set-up: the kept builds
(``mpx.pin``, ``mpx.build``) that ended before the window's first call with
``origin == "compiled"``.  0 in a warm process; a fetch from jax's
persistent cache or the package's disk tier is not a compile."""


def read(ctx):
    builds = ctx["reader"]("setup_builds").before_window(ctx)
    if builds is None:
        return None
    return sum(b["attrs"].get("origin") == "compiled" for b in builds)
