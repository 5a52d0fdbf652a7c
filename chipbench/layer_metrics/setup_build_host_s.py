"""Seconds of set-up the host itself spent building: the sum over the builds
before the window's first call of (duration - ``fetch_s`` - ``compile_s``)
— tracing, lowering, cache keys and the library's own work, the part a
Python frame under a region inflates."""


def read(ctx):
    spans = ctx["reader"]("setup_builds")
    builds = spans.before_window(ctx)
    if builds is None:
        return None
    return sum(spans.host_s(b) for b in builds)
