"""Parallel runtime: communicators, meshes, SPMD regions, routing specs.

TPU-native replacement for the reference's MPI runtime layer
(ref: mpi4jax/_src/comm.py, the mpirun launch model, and the
communicator-handle plumbing in _src/utils.py:80-96).
"""

from .comm import Comm  # noqa: F401
from . import moe  # noqa: F401  (expert-parallel MoE helper, docs/moe.md)
from .mesh import (  # noqa: F401
    DEFAULT_AXIS,
    get_default_mesh,
    init_distributed,
    make_world_mesh,
    set_default_mesh,
    shrink_world_mesh,
)
from .pipeline import (  # noqa: F401
    PipelineProgram,
    pipeline,
)
from .rankspec import (  # noqa: F401
    invert_pairs,
    normalize_dest,
    normalize_source,
    shift,
)
from .region import (  # noqa: F401
    current_context,
    get_default_comm,
    in_parallel_region,
    resolve_comm,
    run,
    shard_global,
    spmd,
)
