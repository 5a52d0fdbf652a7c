"""Programs pinned (``mpx.cache_stats()["aot"]``: pins and compiles) plus
programs jax compiled or fetched from its cache, inside the window.  0 is
the only good value."""


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
