"""Byte arithmetic of the benchmark: what a step or a collective has to
move at the least, computed from shapes alone.

These functions are the yardstick's: a later PR that changes the program
cannot change what counts as the work.  Nothing here imports the program.
"""

F32_BYTES = 4

# nccl-tests PERFORMANCE.md: bus bandwidth = algorithm bandwidth x factor,
# with k the number of ranks.  "size" is the larger per-rank buffer: the
# message for allreduce and sendrecv, the whole receive buffer for
# allgather, the whole send buffer for reduce_scatter and alltoall.
_BUS_FACTORS = {
    "allreduce": lambda k: 2.0 * (k - 1) / k,
    "allgather": lambda k: (k - 1) / k,
    "reduce_scatter": lambda k: (k - 1) / k,
    "alltoall": lambda k: (k - 1) / k,
    "sendrecv": lambda k: 1.0,
}

# ops whose per-rank buffer is k blocks of ``block_elems`` elements
_K_BLOCKS = ("allgather", "reduce_scatter", "alltoall")


def bus_factor(op: str, k: int) -> float:
    """Bus bytes per byte of per-rank buffer for one ``op`` over ``k``
    ranks (nccl-tests convention)."""
    return _BUS_FACTORS[op](k)


def buffer_bytes(op: str, block_elems: int, k: int,
                 itemsize: int = F32_BYTES) -> int:
    """The per-rank buffer nccl-tests calls "size", in bytes, for a
    program whose per-rank block holds ``block_elems`` elements."""
    blocks = k if op in _K_BLOCKS else 1
    return blocks * block_elems * itemsize


def bus_bytes(op: str, block_elems: int, k: int,
              itemsize: int = F32_BYTES) -> float:
    """Bus bytes one collective moves per rank."""
    return bus_factor(op, k) * buffer_bytes(op, block_elems, k, itemsize)


def solver_field_bytes(nx: int, ny: int, itemsize: int = F32_BYTES) -> int:
    """Bytes of one field of the solver's state on one chip: the interior
    plus the one-cell border on each side."""
    return (nx + 2) * (ny + 2) * itemsize


def solver_step_bytes(nx: int, ny: int, itemsize: int = F32_BYTES) -> int:
    """The least one unfused model step can move through HBM: each of the
    six state fields (h, u, v and their three tendencies) read once and
    written once.  Margins and intermediates add to the real traffic and
    are not counted.  A kernel that fuses s steps moves the fields once
    per s steps: against this count its share of a roofline is per step,
    and could pass 100 % only if it ran faster than an unfused step's
    traffic allows."""
    return 12 * solver_field_bytes(nx, ny, itemsize)
