"""Where JAX's persistent compilation cache lives for this checkout.

Every entry point that compiles for a device (``chip_smoke.py``,
``examples/shallow_water.py``, ``examples/serving/serve.py``,
``benchmarks/micro.py``) calls :func:`ensure_compile_cache` before its
first compile, so a cold process pays XLA/Mosaic compilation once per
program and every later process deserializes it.

The directory is part of nothing the program decides:

- ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it on its own; this
  module sets no directory in code and returns the environment's value;
- unset — one fixed path inside the checkout, :data:`DEFAULT_CACHE_DIR`
  (git-ignored).  Never a temp, pid or time-derived path: a directory
  that moves between runs never hits.

This is JAX's own cache (keyed by HLO + compile options + backend), not
the package's opt-in serialized-executable tier
(``MPI4JAX_TPU_COMPILE_CACHE_DIR``, aot/diskcache.py), which stays off
unless that variable is set.
"""

import os

__all__ = ["DEFAULT_CACHE_DIR", "ensure_compile_cache"]

# <checkout>/.jax_compile_cache — the package sits one level below the
# checkout root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def ensure_compile_cache() -> str:
    """Make sure JAX's persistent compilation cache is on; returns the
    directory in use.  Call before the first compile of the process."""
    import jax

    # cache every program, not only the slow ones: the eager per-op
    # programs each compile in well under JAX's 1 s default floor, and a
    # cold process runs dozens of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
