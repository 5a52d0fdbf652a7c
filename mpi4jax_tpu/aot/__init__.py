"""AOT program pinning + the persistent compiled-program cache.

The last big single-host throughput lever the ROADMAP names (open item
4): once a program is fixed, the hot loop should execute a pinned
artifact with donated buffers and zero per-call key computation, and
identical SPMD programs should never be re-lowered on every rank of a
multi-host cold start.

- ``mpx.compile(fn, *abstract_args, comm=..., donate_argnums=...,
  unroll=N)`` -> :class:`PinnedProgram` (pinning.py; ``unroll=N`` pins
  a device-resident megastep — parallel/megastep.py);
- ``mpx.aot.compile_step(fn, unroll=N)`` — the elastic adapter: pinned
  (mega)step functions that ``mpx.elastic.run`` re-pins across epoch
  changes;
- ``MPI4JAX_TPU_COMPILE_CACHE_DIR`` — the persistent tier (diskcache.py
  + serialization.py), also consulted by ``mpx.spmd``'s program cache
  on miss, pre-populated fleet-wide by the cache-warming CLI
  (``python -m mpi4jax_tpu.aot warm manifest.json``, warm.py);
- staleness (invalidation.py): :class:`StaleProgramError` (MPX129) when
  a pinned program is called after a config-stamp or elastic-epoch
  change.

docs/aot.md is the full story (pinning model, cache layout,
invalidation rules, the multi-host cold-start recipe, flag table).
"""

from .invalidation import StaleProgramError, WorldStamp  # noqa: F401
from . import diskcache, keys, warm  # noqa: F401
from .pinning import (  # noqa: F401
    ElasticStep,
    PinnedProgram,
    compile,
    compile_step,
    through_disk_cache,
)
from .pinning import reset_stats as _reset_pin_stats
from .pinning import stats as _pin_stats


def stats() -> dict:
    """The persistent tier of ``mpx.cache_stats()``: the AOT pin/call
    counters plus the disk-cache counters and on-disk footprint."""
    return {"aot": _pin_stats(), "disk_cache": diskcache.stats()}


def reset_stats() -> None:
    """Zero the process-local AOT and disk-cache counters (called by
    ``mpx.clear_caches``; on-disk artifacts are untouched)."""
    _reset_pin_stats()
    diskcache.reset_stats()


__all__ = [
    "compile",
    "compile_step",
    "PinnedProgram",
    "ElasticStep",
    "StaleProgramError",
    "WorldStamp",
    "through_disk_cache",
    "stats",
    "reset_stats",
]
