"""Seconds of set-up spent fetching executables: the sum over the builds
before the window's first call of ``fetch_s`` — jax's own
``cache_retrieval_time_sec`` (with the ``backend_compile_duration`` jax
closes round it) and the package's disk-tier load."""


def read(ctx):
    builds = ctx["reader"]("setup_builds").before_window(ctx)
    if builds is None:
        return None
    return sum(b["attrs"].get("fetch_s", 0.0) for b in builds)
