"""mpi4jax_tpu — MPI-style communication primitives, TPU-native.

A brand-new framework with the capabilities of mpi4jax (reference:
Silv3S/mpi4jax): the reference's 12 MPI communication primitives (plus
``reduce_scatter``, which it lacks) usable inside ``jax.jit``, with
explicit token-chaining *and* implicit ordering, and autodiff (JVP +
transpose) through the communication — re-designed for TPU:

- every primitive lowers to **native XLA collective HLO** (AllReduce,
  AllGather, AllToAll, CollectivePermute) scheduled over ICI/DCN — no libmpi,
  no custom calls, no Cython bridge (replaces ref mpi4jax/_src/xla_bridge/*);
- processes are replaced by the **SPMD device mesh**: a ``Comm`` is a set of
  mesh axes, a rank is a device coordinate, and one traced program serves all
  ranks (replaces ref's ``mpirun`` + per-process programs);
- launched with plain ``python`` — multi-host pods via
  ``init_distributed()`` (replaces ref _src/__init__.py:1-3 MPI_Init).

Public API parity with ref mpi4jax/__init__.py:9-41 (12 ops + capability
probes), plus the mesh/comm/region surface that replaces mpi4py.
"""

from .ops import (  # noqa: F401
    BAND,
    BOR,
    BXOR,
    LAND,
    LOR,
    LXOR,
    MAX,
    MIN,
    PROD,
    SUM,
    AsyncHandle,
    Op,
    P2PHandle,
    Status,
    Token,
    allgather,
    allreduce,
    allreduce_start,
    allreduce_wait,
    alltoall,
    alltoall_start,
    alltoall_wait,
    barrier,
    bcast,
    cache_stats,
    clear_caches,
    create_token,
    gather,
    overlap,
    p2p_wait,
    recv,
    recv_start,
    reduce,
    reduce_scatter,
    reduce_scatter_start,
    reduce_scatter_wait,
    scan,
    scatter,
    send,
    send_start,
    sendrecv,
    set_fusion_mode,
    varying,
)
from .parallel import (  # noqa: F401
    Comm,
    PipelineProgram,
    get_default_comm,
    get_default_mesh,
    init_distributed,
    make_world_mesh,
    moe,
    pipeline,
    run,
    set_default_mesh,
    shard_global,
    shift,
    spmd,
)
from .utils import (  # noqa: F401
    flush,
    has_cuda_support,
    has_sycl_support,
    has_tpu_support,
)
from .resilience import (  # noqa: F401
    RankFailure,
    ShardStore,
    elastic,
    install_preemption_handler,
    request_drain,
    set_check_numerics,
    set_fault_spec,
    set_watchdog_timeout,
)
from .analysis import (  # noqa: F401
    AnalysisError,
    Finding,
    Report,
    analyze,
    set_analyze_mode,
)
from . import aot  # noqa: F401
from .aot import (  # noqa: F401
    PinnedProgram,
    StaleProgramError,
    compile,
)
from . import telemetry  # noqa: F401
from .telemetry import set_telemetry_mode  # noqa: F401
# the serving runtime (docs/serving.md): continuous batching under a
# p99 latency SLO on the pinned megastep decode path
from . import serving  # noqa: F401
# wire compression + error feedback for the DCN leg (docs/compression.md)
from . import compress  # noqa: F401
# the tuning layer (docs/autotune.md): mpx.autotune() measures, the
# config layer serves (default < tuning < env).  NOTE this rebinds the
# package attribute `mpi4jax_tpu.autotune` to the FUNCTION — the
# callable is the public API; the subpackage stays reachable through
# the path-based forms only (`python -m mpi4jax_tpu.autotune`,
# `from mpi4jax_tpu.autotune import ...`), never via attribute access
from .autotune import TuningFile, autotune  # noqa: F401
from .utils.config import active_tuning, load_tuning  # noqa: F401
from .utils.profiling import ProfileSummary, profile_ops  # noqa: F401

# JAX version advisory at import (ref mpi4jax/_src/__init__.py:6-8).
from .utils.jax_compat import check_jax_version as _check_jax_version

_check_jax_version()
del _check_jax_version

# Exit-time flush: keep the reference's guarantee that pending async
# communication completes before interpreter teardown
# (ref mpi4jax/_src/__init__.py:13-17).
import atexit as _atexit

_atexit.register(flush)
del _atexit

__all__ = [
    # ops (ref mpi4jax/__init__.py:26-41)
    "allgather",
    "allreduce",
    "alltoall",
    "barrier",
    "bcast",
    "gather",
    "recv",
    "reduce",
    "reduce_scatter",
    "scan",
    "scatter",
    "send",
    "sendrecv",
    "has_cuda_support",
    "has_sycl_support",
    "has_tpu_support",
    # reductions
    "Op",
    "SUM",
    "PROD",
    "MIN",
    "MAX",
    "LAND",
    "LOR",
    "LXOR",
    "BAND",
    "BOR",
    "BXOR",
    # tokens / status
    "Token",
    "create_token",
    "varying",
    "Status",
    # runtime
    "Comm",
    "get_default_comm",
    "get_default_mesh",
    "set_default_mesh",
    "make_world_mesh",
    "init_distributed",
    "spmd",
    "run",
    "shard_global",
    "shift",
    "flush",
    "clear_caches",
    "cache_stats",
    "profile_ops",
    "ProfileSummary",
    # throughput layer: fusion + async overlap (docs/overlap.md)
    "allreduce_start",
    "allreduce_wait",
    "alltoall_start",
    "alltoall_wait",
    "reduce_scatter_start",
    "reduce_scatter_wait",
    "send_start",
    "recv_start",
    "p2p_wait",
    "AsyncHandle",
    "P2PHandle",
    "overlap",
    "set_fusion_mode",
    # pipeline-parallel schedule compiler (docs/pipeline.md)
    "pipeline",
    "PipelineProgram",
    # expert-parallel MoE helper (docs/moe.md)
    "moe",
    # AOT pinning + persistent compile cache (docs/aot.md)
    "aot",
    "compile",
    "PinnedProgram",
    "StaleProgramError",
    # runtime telemetry (docs/observability.md)
    "telemetry",
    "set_telemetry_mode",
    # serving runtime (docs/serving.md)
    "serving",
    # wire compression + error feedback (docs/compression.md)
    "compress",
    # resilience (docs/resilience.md)
    "set_watchdog_timeout",
    "set_fault_spec",
    "set_check_numerics",
    # elastic recovery (docs/resilience.md "Elastic recovery")
    "elastic",
    "RankFailure",
    "ShardStore",
    "request_drain",
    "install_preemption_handler",
    # trace-time collective verifier (docs/analysis.md)
    "analyze",
    "Report",
    "Finding",
    "AnalysisError",
    "set_analyze_mode",
]

# Version comes from git tags via setuptools-scm at build time
# (pyproject.toml [tool.setuptools_scm]); installed packages answer through
# their metadata.  A source checkout on sys.path that was never installed
# has no metadata — fall back to the scm-style local version.
try:
    from importlib.metadata import PackageNotFoundError, version as _version

    __version__ = _version("mpi4jax_tpu")
except PackageNotFoundError:  # uninstalled source tree
    __version__ = "0.0.0+unknown"
del PackageNotFoundError, _version
